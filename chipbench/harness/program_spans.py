"""The program's own spans (``repro.training.spans``), read after a run in
the run's process, which holds one training session. A program without the
recorder gives ``None`` everywhere, and the readers then report nothing."""
from __future__ import annotations

from typing import Optional

EPOCH_SPAN = "peacock.train.ring_epoch"


def recorder():
    """The program's span recorder, or ``None`` if it has none."""
    try:
        from repro.training import spans
    except ImportError:
        return None
    return spans.recorder()


def last_span_s(name: str) -> Optional[float]:
    """Seconds of the newest span ``name`` the process recorded."""
    rec = recorder()
    kept = rec.recent(name) if rec is not None else []
    return kept[-1].duration if kept else None


def window_spans(name: str, epochs: int) -> Optional[int]:
    """Spans ``name`` recorded in the run's last ``epochs`` epochs (the
    window's), by the epoch the program tags each span with."""
    rec = recorder()
    if rec is None or epochs <= 0:
        return None
    ids = sorted({s.epoch for s in rec.recent(EPOCH_SPAN)})[-epochs:]
    return sum(1 for s in rec.recent(name) if s.epoch in ids)


def device_s_per_span(run, module: str, span: str) -> Optional[float]:
    """Device seconds of the program ``module``'s runs in the traced window
    over the spans ``span`` in the window's epochs."""
    t, c = run.get("trace"), run["counters"]
    if t is None or not c.get("epochs"):
        return None
    dev = sum(s for m, s in t["by_module"].items() if module in m)
    n = window_spans(span, int(c["epochs"]))
    return dev / n if dev > 0 and n else None
