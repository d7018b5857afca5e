# Pallas TPU kernels for compute hot-spots (validated in interpret mode on CPU).

_MODES = (None, "pallas", "interpret", "ref")
_on_tpu_cached = None          # backend probe result, computed once
_default_mode = None           # config-pinned mode (TrainerConfig.kernel_mode)


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


def set_kernel_mode(mode) -> None:
    """Pin the process-wide default dispatch mode for all kernel ops.

    ``None`` restores backend autodetection (on TPU each op's compiled
    default, ``ref`` elsewhere).
    ``TrainerConfig.kernel_mode`` routes here so a session can force e.g.
    ``interpret`` in CI or ``ref`` on an accelerator for A/B debugging.
    """
    global _default_mode
    if mode not in _MODES:
        raise ValueError(f"kernel mode must be one of {_MODES}, got {mode!r}")
    _default_mode = mode


def kernel_mode(force=None, *, tpu: str = "pallas") -> str:
    """Resolve a dispatch mode: explicit ``force`` > pinned default > backend.

    ``tpu`` is what an op runs on a TPU backend when nothing is forced or
    pinned: ``"pallas"`` (its compiled kernel) unless the op's kernel cannot
    run there (see ``kernels/alias/ops.py``). Elsewhere the default is
    ``"ref"``.
    """
    if force is not None:
        if force not in _MODES:
            raise ValueError(
                f"kernel mode must be one of {_MODES}, got {force!r}")
        return force
    if _default_mode is not None:
        return _default_mode
    return tpu if on_tpu() else "ref"
