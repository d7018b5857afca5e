"""Share of the traced training window in which no operation runs on the
chip (%), from the profiler's trace: 1 − union of device operations / the
window. A traced run's window is one epoch (table rebuild, epoch program,
α step), whose device events alone run to about a million."""


def read(run):
    t = run.get("trace")
    return None if t is None else 100.0 * t["idle_share"]
