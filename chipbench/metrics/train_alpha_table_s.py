"""Device seconds per build of the alias sampler's α table: the device time
of the ``build_alias_alpha`` program's runs in the traced window over the
builds the program recorded in the window's epochs (its
``peacock.train.tables.alpha`` spans)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import program_spans  # noqa: E402


def read(run):
    return program_spans.device_s_per_span(run, "build_alias_alpha",
                                           "peacock.train.tables.alpha")
