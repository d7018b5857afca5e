"""Host seconds of the trainer's source set-up: the program's
``peacock.train.setup.source`` span, which resolves the corpus source and
shards the corpus onto the ring (``shard_corpus``), once per run (the
newest such span)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import program_spans  # noqa: E402


def read(run):
    return program_spans.last_span_s("peacock.train.setup.source")
