"""A whole rehearsal run with the timed path broken underneath: the run
skips the look for a chip, drives the program at the rehearsal sizes on
the CPU, and must come out as not correct for every fault a cell can have
(one chip: no exchange between chips to leave out). Unbroken, it must
come out as correct."""
import time

import jax
import jax.numpy as jnp
import pytest

from harness import env, train


def run_cell(cell, seed=5):
    out = train.run(env.load_spec(cell), seed, 1.0, jax.devices()[:1],
                    time.perf_counter(), env.CompileCounter(), None,
                    rehearse=True)
    return env.first_failure(out["checks"]) is None


@pytest.fixture
def mh(monkeypatch):
    """Plant a fault in the alias MH draw the ring epoch makes."""
    from repro.kernels.alias import ops

    orig = ops.mh_resample

    def plant(fault):
        assert fault == "half"

        def broken(*args, **kw):
            z_new = orig(*args, **kw)
            z = args[12]
            keep = (jnp.arange(z.shape[0]) % 2) == 1
            return jnp.where(keep, z, z_new)
        monkeypatch.setattr(ops, "mh_resample", broken)
    return plant


def test_train_sound_run_is_correct():
    assert run_cell("train-alias-query")


def test_train_epoch_that_returns_its_state_unchanged(monkeypatch):
    from repro.core import distributed

    monkeypatch.setattr(
        distributed, "make_ring_epoch",
        lambda mesh, cfg: (lambda phi, psi, wl, dl, uid, z, *rest:
                           (phi, psi, wl, dl, uid, z)))
    assert not run_cell("train-alias-query")


def test_train_sampler_leaves_half_the_batch_out(mh):
    mh("half")
    assert not run_cell("train-alias-query")


def test_train_token_altered_in_the_epoch_output(monkeypatch):
    from repro.core import distributed

    make = distributed.make_ring_epoch

    def broken(mesh, cfg):
        epoch = make(mesh, cfg)

        def run(*args):
            *head, z = epoch(*args)
            return (*head, z.at[0, 0, 0].set((z[0, 0, 0] + 1) % cfg.n_topics))
        return run
    monkeypatch.setattr(distributed, "make_ring_epoch", broken)
    assert not run_cell("train-alias-query")
