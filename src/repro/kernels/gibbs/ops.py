"""Dispatching wrapper for the fused Gibbs/RT-LDA op.

On TPU the Pallas kernel runs compiled; everywhere else (this CPU container, unit
tests) we run either the kernel under ``interpret=True`` or the jnp oracle — both
produce identical results. The default for library callers is the oracle path on
CPU (fast to trace) and the kernel on TPU; both the backend probe and the default
can be pinned process-wide (``repro.kernels.set_kernel_mode``, fed by
``TrainerConfig.kernel_mode``) — the probe itself is cached, so dispatch inside
jitted loops never re-walks the backend registry.
"""
from __future__ import annotations

from repro import kernels as kernels_mod
from repro.kernels.gibbs.kernel import gibbs_argmax_pallas
from repro.kernels.gibbs.ref import gibbs_argmax_ref


def gibbs_argmax(
    phi_rows, psi_rows, theta_rows, alpha, beta, token_uid, seed,
    vocab_size: int, temperature: float = 1.0, *, force: str | None = None,
):
    """force in {None, "pallas", "interpret", "ref"}; None defers to the
    pinned process default (``repro.kernels.set_kernel_mode``), then to the
    cached backend probe."""
    mode = kernels_mod.kernel_mode(force)
    if mode == "pallas":
        return gibbs_argmax_pallas(
            phi_rows, psi_rows, theta_rows, alpha, beta, token_uid, seed,
            vocab_size, temperature)
    if mode == "interpret":
        return gibbs_argmax_pallas(
            phi_rows, psi_rows, theta_rows, alpha, beta, token_uid, seed,
            vocab_size, temperature, interpret=True)
    return gibbs_argmax_ref(
        phi_rows, psi_rows, theta_rows, alpha, beta, token_uid, seed,
        vocab_size, temperature)
