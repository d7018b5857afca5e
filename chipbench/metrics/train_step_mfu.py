"""The traced epoch's share of the chip's peak (%): the least time one
alias epoch needs at the peaks (required bytes of ``sampler_epoch_bytes``,
its word-table rebuild included, and the probes' operations, whichever
bound is larger) over the device's busy time in the trace. A traced run's
window is one epoch: the table build, the epoch program and the α step."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import work  # noqa: E402


def read(run):
    c, t = run["counters"], run.get("trace")
    if t is None or not c.get("epochs") or t["busy_s"] <= 0:
        return None
    least, _ = work.least_time(c["epoch_flops"], c["epoch_bytes"],
                               run["device_kind"])
    return 100.0 * least * c["epochs"] / t["busy_s"]
