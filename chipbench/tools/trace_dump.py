#!/usr/bin/env python3
"""Print the layout of a profiler trace taken on this machine's chip: the
planes, their lines, a few events of each, and where the device's events
lie against a host span. Run once before trusting ``harness/trace.py``
with a new device or JAX version.

    python3 chipbench/tools/trace_dump.py
"""
from __future__ import annotations

import glob
import os
import shutil
import tempfile


def main():
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    d = tempfile.mkdtemp(prefix="chipbench-dump-")
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum(axis=0))
    x = jnp.ones((2048, 2048), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(5):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs),
                  "span", (min(e.start_ns for e in evs), max(e.end_ns for e in evs)) if evs else None)
            for e in evs[:4]:
                print("    EV", repr(e.name), e.start_ns, e.duration_ns,
                      {k: v for k, v in dict(e.stats).items()
                       if k in ("hlo_module", "hlo_op", "long_name")})
    shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    main()
