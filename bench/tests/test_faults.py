"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run of a cell, at a size the CPU holds, with one fault planted in the
program: a step that returns its state unchanged; half of the batch (the
members, or the tiles of a single member) left unstepped; the halo
exchange left out; an answer (pt) altered where the step produces it.
The sound run of the same cell comes out correct."""

import jax.numpy as jnp
import pytest

import run
from small import cell_data, cells

SEED = 2**31 + 11


def _run(name):
    cell, cfg, traffic, limits = cell_data(name)
    out = run.run(cell, cfg, traffic, limits, SEED, 0.5, False,
                  peaks={"hbm_bytes_per_s": 1.0}, require_accelerator=False)
    return out


def _patch_iteration(monkeypatch, change):
    """Wrap the step's remap iteration (the whole of a physics step's
    work, k_split times) with ``change(state_in, state_out)``."""
    from repro.fv3 import dyncore

    orig = dyncore._remap_iteration

    def broken(cfg, runners, params, halo_fn, state, *a, **kw):
        return change(state, orig(cfg, runners, params, halo_fn, state,
                                  *a, **kw))

    monkeypatch.setattr(dyncore, "_remap_iteration", broken)


def _half(state_in, state_out):
    """First half of the leading axis stepped, the rest left as it was:
    members of an ensemble, tiles of a single member."""
    out = {}
    for k, v in state_out.items():
        n = v.shape[0] // 2
        out[k] = jnp.concatenate([v[:n], state_in[k][n:]])
    return out


FAULTS = {
    "unchanged": lambda mp: _patch_iteration(mp, lambda i, o: dict(i)),
    "half_batch": lambda mp: _patch_iteration(mp, _half),
    "altered_answer": lambda mp: _patch_iteration(
        mp, lambda i, o: {**o, "pt": o["pt"] * (1.0 + 1e-3)}),
}


def _no_halo(monkeypatch):
    from repro.fv3 import dyncore

    monkeypatch.setattr(dyncore, "_reference_halo_fn",
                        lambda cfg: (lambda st, names: dict(st)))


FAULTS["no_halo_exchange"] = _no_halo


@pytest.mark.parametrize("name", cells())
def test_sound_run_is_correct(name):
    out = _run(name)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", cells())
def test_fault_is_not_correct(name, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = _run(name)
    assert not out["correct"], out["checks"]
