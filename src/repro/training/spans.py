"""Host spans of the training path.

``span(name, **attrs)`` times a block on the host clock and opens a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace shows
the block on its host plane, on the same clock as the device's operations.
Spans record into one process-wide :class:`Recorder`, as the profiler itself
is process-wide. There is no switch: with the profiler off an annotation
records nothing, and a traced run differs from an untraced one only in the
profiler being on.

The recorder keeps the newest ``max_spans`` spans (name, start, end, parent,
the epoch and segment they fell in, attributes) and, for every name, running
totals: calls, seconds and self seconds (a span's time less its direct
children's). ``totals(since=mark)`` gives what was recorded after an earlier
``totals()``: a session's own share (``Trainer.bench_record``).

    with spans.span("peacock.train.ring_epoch") as sp:
        ...
    epoch_s = sp.duration

Span names carry the ``peacock.`` prefix; PERF.md lists every span next to
what reads it.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Any, Deque, Dict, Iterator, List, Optional

now = time.perf_counter          # the recorder's clock


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float = 0.0
    parent: Optional[str] = None
    epoch: Optional[int] = None     # the epoch / segment the span fell in
    segment: int = 0
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    child_s: float = 0.0            # time of direct children

    @property
    def duration(self) -> float:
        return self.end - self.start

    @property
    def self_s(self) -> float:
        return self.duration - self.child_s


class Recorder:
    """Bounded in-memory spans and per-name totals."""

    def __init__(self, max_spans: int = 4096):
        self._lock = threading.Lock()
        self._local = threading.local()     # per-thread stack of open spans
        self.spans: Deque[Span] = collections.deque(maxlen=max_spans)
        self._totals: Dict[str, List[float]] = {}  # name → [n, s, self s]
        self.epoch: Optional[int] = None    # set by the trainer
        self.segment = 0

    def at_epoch(self, epoch: int, segment: int = 0) -> None:
        """Spans opened from now on fall in ``(epoch, segment)``."""
        self.epoch, self.segment = epoch, segment

    @contextlib.contextmanager
    def span(self, name: str, **attrs) -> Iterator[Span]:
        from jax.profiler import TraceAnnotation

        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        sp = Span(name, 0.0, parent=stack[-1].name if stack else None,
                  epoch=self.epoch, segment=self.segment, attrs=attrs)
        stack.append(sp)
        try:
            with TraceAnnotation(name):
                sp.start = now()
                try:
                    yield sp
                finally:
                    sp.end = now()
        finally:
            stack.pop()
            if stack:
                stack[-1].child_s += sp.duration
            with self._lock:
                self.spans.append(sp)
                tot = self._totals.setdefault(name, [0, 0.0, 0.0])
                tot[0] += 1
                tot[1] += sp.duration
                tot[2] += sp.self_s

    def recent(self, name: Optional[str] = None) -> List[Span]:
        """The kept spans, oldest first (those named ``name`` if given)."""
        with self._lock:
            return [s for s in self.spans if name is None or s.name == name]

    def totals(self, since: Optional[Dict[str, Dict[str, float]]] = None
               ) -> Dict[str, Dict[str, float]]:
        """Per-name calls, seconds and self seconds, as plain numbers; with
        ``since`` (an earlier ``totals()``), only what was recorded after
        it."""
        since = since or {}
        out = {}
        with self._lock:
            for name, (n, s, self_s) in self._totals.items():
                was = since.get(name, {"n": 0, "total_s": 0.0, "self_s": 0.0})
                if n > was["n"]:
                    out[name] = {"n": int(n - was["n"]),
                                 "total_s": s - was["total_s"],
                                 "self_s": self_s - was["self_s"]}
        return out


_RECORDER = Recorder()


def recorder() -> Recorder:
    """The process's recorder."""
    return _RECORDER


def span(name: str, **attrs):
    return _RECORDER.span(name, **attrs)


def at_epoch(epoch: int, segment: int = 0) -> None:
    _RECORDER.at_epoch(epoch, segment)
