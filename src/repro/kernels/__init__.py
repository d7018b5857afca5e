# Kernel ops and their dispatch. The Gibbs and embedding-bag ops run a Pallas
# kernel on TPU (interpret mode on CPU when forced); the alias ops are jnp
# programs that XLA compiles on every backend (kernels/alias/ops.py).

_MODES = (None, "pallas", "interpret", "ref")
_on_tpu_cached = None          # backend probe result, computed once


def on_tpu() -> bool:
    """Whether the default jax backend is TPU — probed ONCE per process.

    jax.default_backend() walks the backend registry every call; inside the
    per-package sampling loop that probe used to re-run on every dispatch.
    The backend cannot change after jax initializes, so one probe suffices.
    A backend that fails to initialize raises here: it is never read as
    "not a TPU".
    """
    global _on_tpu_cached
    if _on_tpu_cached is None:
        import jax

        _on_tpu_cached = jax.default_backend() == "tpu"
    return _on_tpu_cached


def kernel_mode(force=None) -> str:
    """Resolve a dispatch mode: an explicit ``force`` first, then the
    backend: ``"pallas"`` (the compiled kernel) on TPU, ``"ref"``
    elsewhere."""
    if force is not None:
        if force not in _MODES:
            raise ValueError(
                f"kernel mode must be one of {_MODES}, got {force!r}")
        return force
    return "pallas" if on_tpu() else "ref"
