"""Static VMEM planner: per-kernel footprints from the *actual* BlockSpecs.

TPU Pallas kernels live or die on the ~16 MB/core VMEM budget, and the repo's
kernels declare their VMEM residency entirely through ``pl.BlockSpec``s /
``pltpu.VMEM`` scratch (``kernels/{gibbs,embedding_bag}``). This module turns
that residency into an enforced check: it captures the exact specs a kernel
wrapper constructs at a given geometry and sums buffer bytes against the
budget — at launch, not three hours into an epoch when a (K, cap, tile)
geometry finally overflows.

How capture works: ``pl.pallas_call`` is temporarily replaced with a recorder
while the wrapper is traced under ``jax.eval_shape`` — the wrapper's own
Python runs (so the recorded grid/BlockSpecs are the ones the real call
would use, not a re-derivation), but nothing compiles or allocates. Works at
any geometry, including the paper's 10⁵-topic scale.

Footprint model (per buffer):
  * block bytes = prod(block_shape) × dtype.itemsize, with the trailing two
    dims padded to the (8, 128) TPU tile — Mosaic allocates whole tiles, so
    a (256, 1) int32 block really occupies 256×128;
  * grid-varying blocks (index_map output changes across the grid) count
    2× — the pipeline double-buffers them to overlap DMA with compute;
    grid-constant blocks are fetched once;
  * ``MemorySpace.ANY`` (HBM-resident, e.g. the embedding-bag table) and
    scalar-prefetch operands (SMEM) contribute zero VMEM;
  * scratch ``pltpu.VMEM`` shapes count once (no pipelining).

The model intentionally over-approximates slightly (real Mosaic may hold
both in/out views of an aliased buffer); a kernel that fails here needs a
smaller tile or the HBM-resident path, not a tighter estimate.
"""
from __future__ import annotations

import contextlib
import dataclasses
import inspect
from typing import Any, Iterator, List, Sequence, Tuple

from repro.analysis.report import Finding, error, info

VMEM_BUDGET_BYTES = 16 * 1024 * 1024      # ~16 MB/core (v4/v5e class)
_TILE = (8, 128)                          # f32/i32 sublane × lane tile


# ---------------------------------------------------------------- capture ---


@dataclasses.dataclass
class CapturedCall:
    """One recorded ``pl.pallas_call`` invocation (trace-time)."""

    kernel_name: str
    grid: Tuple[int, ...]
    num_scalar_prefetch: int
    in_specs: Sequence[Any]
    out_specs: Sequence[Any]
    scratch_shapes: Sequence[Any]
    arg_avals: Sequence[Tuple[Tuple[int, ...], Any]]   # (shape, dtype)/arg
    out_avals: Sequence[Tuple[Tuple[int, ...], Any]]
    param_names: Sequence[str]                         # kernel fn signature


def _kernel_fn_of(kernel: Any) -> Any:
    while hasattr(kernel, "func"):        # unwrap functools.partial chains
        kernel = kernel.func
    return kernel


def _as_seq(x: Any) -> Tuple[Any, ...]:
    if x is None:
        return ()
    if isinstance(x, (list, tuple)):
        return tuple(x)
    return (x,)


@contextlib.contextmanager
def _patched_pallas_call(captured: List[CapturedCall]) -> Iterator[None]:
    from jax.experimental import pallas as pl_mod

    real = pl_mod.pallas_call

    def fake(kernel: Any, **kw: Any) -> Any:
        def runner(*args: Any) -> Any:
            import jax
            import jax.numpy as jnp

            grid_spec = kw.get("grid_spec")
            if grid_spec is not None:
                grid = tuple(grid_spec.grid)
                nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
                in_specs = _as_seq(getattr(grid_spec, "in_specs", ()))
                out_specs = _as_seq(getattr(grid_spec, "out_specs", ()))
                scratch = _as_seq(getattr(grid_spec, "scratch_shapes", ()))
            else:
                grid = tuple(kw.get("grid") or ())
                nsp = 0
                in_specs = _as_seq(kw.get("in_specs"))
                out_specs = _as_seq(kw.get("out_specs"))
                scratch = _as_seq(kw.get("scratch_shapes"))
            out_shape = kw["out_shape"]
            out_leaves = _as_seq(out_shape)
            kfn = _kernel_fn_of(kernel)
            try:
                names: Sequence[str] = [
                    p.name for p in
                    inspect.signature(kfn).parameters.values()
                    if p.kind in (p.POSITIONAL_ONLY,
                                  p.POSITIONAL_OR_KEYWORD)]
            except (TypeError, ValueError):
                names = []
            captured.append(CapturedCall(
                kernel_name=getattr(kfn, "__name__", str(kfn)),
                grid=grid, num_scalar_prefetch=nsp,
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=scratch,
                arg_avals=[(tuple(a.shape), a.dtype) for a in args],
                out_avals=[(tuple(s.shape), s.dtype) for s in out_leaves],
                param_names=names,
            ))
            outs = [jnp.zeros(s.shape, s.dtype) for s in out_leaves]
            del jax
            return outs if isinstance(out_shape, (list, tuple)) else outs[0]

        return runner

    pl_mod.pallas_call = fake
    try:
        yield
    finally:
        pl_mod.pallas_call = real


def unjitted(fn: Any) -> Any:
    """The raw python function under a ``jax.jit`` wrapper (identity for
    plain functions). Capture must trace the *python* body — a jitted
    wrapper with a warm trace cache would skip it and record nothing."""
    return getattr(fn, "__wrapped__", fn)


def capture_pallas_calls(fn: Any, *args: Any,
                         **kwargs: Any) -> List[CapturedCall]:
    """Trace ``fn(*args)`` abstractly and record every ``pallas_call`` it
    makes. ``args`` may be ShapeDtypeStructs — nothing compiles or
    allocates, so paper-scale geometries are fine. Pass kernel wrappers
    through :func:`unjitted` first if they are jitted."""
    import jax

    captured: List[CapturedCall] = []
    with _patched_pallas_call(captured):
        jax.eval_shape(lambda *a: fn(*a, **kwargs), *args)
    return captured


# ------------------------------------------------------------------ plan ----


@dataclasses.dataclass(frozen=True)
class BufferPlan:
    """One kernel operand/output/scratch buffer's VMEM accounting."""

    name: str
    kind: str                 # in | out | scratch | prefetch(SMEM) | any(HBM)
    block_shape: Tuple[int, ...]
    dtype: str
    bytes_raw: int            # unpadded block bytes
    bytes_padded: int         # (8, 128)-tile padded block bytes
    buffers: int              # 1, or 2 when the pipeline double-buffers

    @property
    def vmem_bytes(self) -> int:
        return self.bytes_padded * self.buffers


@dataclasses.dataclass
class KernelPlan:
    """The static VMEM plan of one captured kernel call."""

    kernel: str
    grid: Tuple[int, ...]
    buffers: List[BufferPlan]

    @property
    def vmem_bytes(self) -> int:
        return sum(b.vmem_bytes for b in self.buffers)

    def table(self) -> str:
        """The per-buffer table a failing check prints."""
        rows = [f"  {'buffer':<14} {'kind':<9} {'block':<18} {'dtype':<8} "
                f"{'padded':>12} {'x':>2} {'vmem':>12}"]
        for b in self.buffers:
            rows.append(
                f"  {b.name:<14} {b.kind:<9} {str(list(b.block_shape)):<18} "
                f"{b.dtype:<8} {b.bytes_padded:>12,} {b.buffers:>2} "
                f"{b.vmem_bytes:>12,}")
        rows.append(f"  {'TOTAL':<14} {'':<9} {'':<18} {'':<8} {'':>12} "
                    f"{'':>2} {self.vmem_bytes:>12,}")
        return "\n".join(rows)


def _pad_to_tile(shape: Sequence[int]) -> int:
    """Elements of a block padded to whole (8, 128) tiles (trailing 2 dims)."""
    dims = list(shape) if shape else [1]
    if len(dims) >= 1:
        dims[-1] = -(-dims[-1] // _TILE[1]) * _TILE[1]
    if len(dims) >= 2:
        dims[-2] = -(-dims[-2] // _TILE[0]) * _TILE[0]
    n = 1
    for d in dims:
        n *= int(d)
    return n


def _index_varies(spec: Any, grid: Tuple[int, ...],
                  num_scalar_prefetch: int) -> bool:
    """Whether the BlockSpec's index_map output changes across the grid —
    grid-varying blocks are double-buffered by the pipeline."""
    index_map = getattr(spec, "index_map", None)
    if index_map is None or not grid:
        return False
    pads = (None,) * num_scalar_prefetch   # prefetch refs unused by our maps

    def at(point: Tuple[int, ...]) -> Any:
        try:
            return index_map(*point, *pads)
        except TypeError:
            return index_map(*point)

    try:
        origin = at(tuple(0 for _ in grid))
        for dim, size in enumerate(grid):
            if size <= 1:
                continue
            probe = tuple(size - 1 if i == dim else 0
                          for i in range(len(grid)))
            if at(probe) != origin:
                return True
        return False
    except Exception:
        return True                        # unknown map: assume the worst


def _dtype_size(dtype: Any) -> int:
    import numpy as np

    return int(np.dtype(dtype).itemsize)


def _buffer(name: str, kind: str, spec: Any, aval: Tuple[Tuple[int, ...], Any],
            grid: Tuple[int, ...], nsp: int) -> BufferPlan:
    shape, dtype = aval
    memory_space = getattr(spec, "memory_space", None)
    ms_name = str(memory_space).lower() if memory_space is not None else ""
    if "any" in ms_name or "smem" in ms_name:
        block = tuple(shape)
        return BufferPlan(name, "any(HBM)" if "any" in ms_name else "smem",
                          block, str(dtype), 0, 0, 1)
    block_shape = getattr(spec, "block_shape", None)
    block = tuple(int(d) for d in block_shape) if block_shape is not None \
        else tuple(shape)
    raw = _dtype_size(dtype)
    for d in block:
        raw *= d
    padded = _pad_to_tile(block) * _dtype_size(dtype)
    buffers = 2 if _index_varies(spec, grid, nsp) else 1
    return BufferPlan(name, kind, block, str(dtype), raw, padded, buffers)


def plan_call(call: CapturedCall) -> KernelPlan:
    """Turn one captured ``pallas_call`` into a VMEM plan."""
    names = list(call.param_names)

    def name_for(i: int, fallback: str) -> str:
        return names[i] if i < len(names) else fallback

    buffers: List[BufferPlan] = []
    nsp = call.num_scalar_prefetch
    # scalar-prefetch operands ride SMEM: zero VMEM, listed for the table
    for i in range(nsp):
        shape, dtype = call.arg_avals[i]
        buffers.append(BufferPlan(name_for(i, f"sref{i}"), "smem",
                                  tuple(shape), str(dtype), 0, 0, 1))
    for j, spec in enumerate(call.in_specs):
        aval = call.arg_avals[nsp + j]
        buffers.append(_buffer(name_for(nsp + j, f"in{j}"), "in", spec,
                               aval, call.grid, nsp))
    base = nsp + len(call.in_specs)
    for j, spec in enumerate(call.out_specs):
        aval = call.out_avals[j] if j < len(call.out_avals) \
            else call.out_avals[-1]
        buffers.append(_buffer(name_for(base + j, f"out{j}"), "out", spec,
                               aval, call.grid, nsp))
    base += len(call.out_specs)
    for j, scratch in enumerate(call.scratch_shapes):
        shape = getattr(scratch, "shape", None)
        if shape is None:                  # semaphores etc: no VMEM block
            buffers.append(BufferPlan(name_for(base + j, f"scratch{j}"),
                                      "scratch", (), str(scratch), 0, 0, 1))
            continue
        dtype = getattr(scratch, "dtype", "float32")
        raw = _dtype_size(dtype)
        for d in shape:
            raw *= int(d)
        padded = _pad_to_tile(tuple(shape)) * _dtype_size(dtype)
        buffers.append(BufferPlan(name_for(base + j, f"scratch{j}"),
                                  "scratch", tuple(shape), str(dtype),
                                  raw, padded, 1))
    return KernelPlan(call.kernel_name, call.grid, buffers)


def plan_fn(fn: Any, *args: Any, **kwargs: Any) -> List[KernelPlan]:
    """Capture + plan every kernel ``fn(*args)`` dispatches."""
    return [plan_call(c) for c in capture_pallas_calls(fn, *args, **kwargs)]


# ------------------------------------------------------------------ check ---


def check_vmem(plans: Sequence[KernelPlan],
               budget_bytes: int = VMEM_BUDGET_BYTES) -> List[Finding]:
    """Budget verdict per kernel plan; failures carry the per-buffer table."""
    findings: List[Finding] = []
    for plan in plans:
        data = {
            "kernel": plan.kernel, "grid": list(plan.grid),
            "vmem_bytes": plan.vmem_bytes, "budget_bytes": budget_bytes,
            "buffers": [dataclasses.asdict(b) for b in plan.buffers],
        }
        if plan.vmem_bytes > budget_bytes:
            findings.append(error(
                "vmem.budget",
                f"kernel '{plan.kernel}' needs "
                f"{plan.vmem_bytes / 1e6:.1f} MB VMEM > "
                f"{budget_bytes / 1e6:.1f} MB budget at this geometry — "
                f"shrink the tile or move the big operand to "
                f"MemorySpace.ANY (HBM-resident):\n{plan.table()}",
                location=plan.kernel, **data))
        else:
            findings.append(info(
                "vmem.budget",
                f"kernel '{plan.kernel}' fits: "
                f"{plan.vmem_bytes / 1e6:.2f} MB of "
                f"{budget_bytes / 1e6:.1f} MB VMEM",
                location=plan.kernel, **data))
    return findings


# ------------------------------------- the repo's kernels, by geometry ------


def repo_kernel_plans(n_topics: int, rows_per_device: int,
                      package_len: int, sampler: str = "dense",
                      embedding_dim: int = 64,
                      bag_fields: int = 8) -> List[KernelPlan]:
    """Plans for the kernels a session with this geometry would dispatch.

    ``sampler="dense"`` plans the gibbs plane-scan kernel. ``"alias"`` plans
    no sampler kernel: the alias build and MH probe (``kernels/alias/ops.py``)
    are XLA programs, which hold no BlockSpecs for this planner to price.
    The embedding-bag kernel is always planned — its table rides HBM so it
    is geometry-insensitive, and including it keeps the audit exhaustive
    over the Pallas kernels in ``kernels/*``.
    """
    import jax
    import jax.numpy as jnp

    sds = jax.ShapeDtypeStruct
    K = int(n_topics)
    rows = max(1, int(rows_per_device))
    T = max(8, int(package_len))
    plans: List[KernelPlan] = []

    if sampler != "alias":
        from repro.kernels.gibbs import kernel as gk

        plans += plan_fn(
            lambda *a: unjitted(gk.gibbs_argmax_pallas)(*a, vocab_size=rows),
            sds((T, K), jnp.float32), sds((T, K), jnp.float32),
            sds((T, K), jnp.float32), sds((K,), jnp.float32),
            sds((), jnp.float32), sds((T,), jnp.uint32),
            sds((), jnp.uint32))

    from repro.kernels.embedding_bag import kernel as ek

    plans += plan_fn(
        lambda table, ids: unjitted(ek.embedding_bag_pallas)(table, ids),
        sds((max(rows, 16), embedding_dim), jnp.float32),
        sds((16, bag_fields), jnp.int32))
    return plans
