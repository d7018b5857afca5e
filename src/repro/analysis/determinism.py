"""Determinism auditor: static jaxpr checks behind the bitwise kill→resume
contract.

The repo's recovery guarantee (DESIGN.md §4) is *bitwise*: a killed session
resumed from its checkpoint replays the exact same z stream. Three trace-time
properties carry that guarantee, and all three are checkable statically by
walking the epoch function's jaxpr — no devices, no state:

* **No float-dtype ``scatter-add``.** Count updates must ride int
  accumulators: integer scatter-adds commute bitwise under any reduction
  order, while f32 scatter-adds depend on the order XLA happens to pick for
  colliding indices (and that order is not stable across topologies or
  compiler versions). ``phi``/``psi``/``theta`` are int32 by design; a
  float-ified accumulator is exactly the silent violation that surfaces as
  a non-reproducing resume three hours in.

* **No ``jax.random`` primitives inside epoch bodies.** The samplers draw
  randomness from ``core/prng`` counter hashing keyed on (seed, token uid)
  — stateless, order-free, and stable under resharding. A ``threefry``
  split threaded through a scan carry would make the draw stream depend on
  iteration order and ring layout.

* **No host callbacks in jitted paths.** ``pure_callback``/``io_callback``
  escape the compiled computation; their effects are unordered with respect
  to the replayed trace (and they silently serialize the pipeline).

``audit(fn, *args)`` traces abstractly (ShapeDtypeStructs are fine) and
returns findings; ``audit_jaxpr`` walks an already-made jaxpr. Primitives
are matched by name with the same sub-jaxpr recursion as
``repro.dist.analysis`` (scan / while / cond / pjit / shard_map / remat /
custom_* all descended).
"""
from __future__ import annotations

from typing import Any, List

import numpy as np

from repro.analysis.report import Finding, error
from repro.dist.analysis import _as_jaxpr, _sub_jaxprs

# scatter variants whose collision order XLA does not pin; -add/-mul are the
# accumulating forms the bitwise contract cares about (plain scatter with
# unique indices is fine)
_SCATTER_ACCUM_PRIMS = {"scatter-add", "scatter-mul", "scatter-min",
                        "scatter-max"}

# jax.random machinery (both the raw threefry path and typed-key prims)
_RNG_PRIMS = {"threefry2x32", "random_seed", "random_bits", "random_wrap",
              "random_unwrap", "random_fold_in", "random_split",
              "random_gamma"}

# host round-trips inside jitted code
_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "callback"}


def _is_float(aval: Any) -> bool:
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and np.issubdtype(dtype, np.floating)


def _shape_of(var: Any) -> str:
    aval = getattr(var, "aval", None)
    if aval is None or not hasattr(aval, "shape"):
        return "?"
    return f"{getattr(aval.dtype, 'name', aval.dtype)}{list(aval.shape)}"


def _walk(jaxpr: Any, path: str, findings: List[Finding]) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _SCATTER_ACCUM_PRIMS:
            operand = eqn.invars[0]
            if _is_float(getattr(operand, "aval", None)):
                findings.append(error(
                    "determinism.float-scatter-add",
                    f"float-dtype {name} on {_shape_of(operand)} — "
                    "accumulation order is unspecified for colliding "
                    "indices, which breaks the bitwise kill→resume "
                    "contract; keep count accumulators int32 (phi/psi/"
                    "theta) and cast at the read site instead",
                    location=path or "<jaxpr>",
                    primitive=name, operand=_shape_of(operand)))
        elif name in _RNG_PRIMS:
            findings.append(error(
                "determinism.jax-random",
                f"jax.random primitive '{name}' inside the epoch body — "
                "sampler randomness must come from core/prng counter "
                "hashing keyed on (seed, token uid); key-threading makes "
                "the draw stream depend on iteration order and layout",
                location=path or "<jaxpr>", primitive=name))
        elif name in _CALLBACK_PRIMS:
            findings.append(error(
                "determinism.host-callback",
                f"host callback '{name}' in a jitted path — callbacks "
                "escape the compiled computation (unordered on replay, "
                "serializes the pipeline); hoist it out of the epoch or "
                "record via the Metrics callback instead",
                location=path or "<jaxpr>", primitive=name))
        # descend into every sub-jaxpr (scan/while/cond/pjit/shard_map/...)
        if name == "cond":
            for i, b in enumerate(eqn.params.get("branches", ())):
                sub = _as_jaxpr(b)
                if sub is not None:
                    _walk(sub, f"{path}/{name}[{i}]", findings)
            continue
        for sub in _sub_jaxprs(eqn.params):
            _walk(sub, f"{path}/{name}", findings)


def audit_jaxpr(closed_jaxpr: Any, path: str = "") -> List[Finding]:
    """Walk a (Closed)Jaxpr and return determinism findings."""
    jaxpr = _as_jaxpr(closed_jaxpr)
    if jaxpr is None:
        jaxpr = closed_jaxpr
    findings: List[Finding] = []
    _walk(jaxpr, path, findings)
    return findings


def audit(fn: Any, *args: Any, **kwargs: Any) -> List[Finding]:
    """Abstractly trace ``fn(*args)`` (ShapeDtypeStructs welcome — nothing
    executes) and audit the resulting jaxpr."""
    import jax

    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return audit_jaxpr(closed)
