"""Share of the traced training window in which a collective runs on a chip
and no other operation does (%), averaged over the chips: the ring's stack
exchange (collective-permutes) and the Ψ sum that the epoch program does
not hide behind sampling. From the profiler's trace (``trace.reduce``)."""


def read(run):
    t = run.get("trace")
    return None if t is None else 100.0 * t["exposed_collective_share"]
