"""Share of the device's busy time in the traced epoch spent building the
alias sampler's proposal tables (%): the operations of the table-build
programs (the Walker builds of the word tables and of the α table) in the
profiler's trace, over the union of all the epoch's device operations."""

BUILD_MODULE = "build_alias"


def read(run):
    t = run.get("trace")
    if t is None or t["busy_s"] <= 0:
        return None
    build = sum(s for m, s in t["by_module"].items() if BUILD_MODULE in m)
    return 100.0 * build / t["busy_s"] if build > 0 else None
