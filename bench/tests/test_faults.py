"""A run with the timed path broken underneath reads not correct: once for
each fault a cell can have.  The harness's look for a chip is skipped;
everything else of a run is driven at a small size on the CPU."""

import jax
import jax.numpy as jnp
import pytest

from small import run_small


@pytest.fixture(autouse=True)
def _fresh_programs():
    jax.clear_caches()
    yield
    jax.clear_caches()


def _state_unchanged(monkeypatch):
    from repro.core import dynamics

    def frozen_as_is(cfg, params, state, chunk):
        return state._replace(frozen=jnp.ones_like(state.frozen), t=state.t + chunk)

    monkeypatch.setattr(dynamics, "_advance_chunk_batched", frozen_as_is)


def _half_batch(monkeypatch):
    from repro.core import dynamics

    orig = dynamics._run_batched

    def first_half_only(cfg, params, phase0, keys):
        res = orig(cfg, params, phase0, keys)
        keep = jnp.arange(phase0.shape[0]) < phase0.shape[0] // 2
        unsolved = dynamics._batch_result(cfg, dynamics._init_carry(cfg, phase0, keys))
        return jax.tree.map(
            lambda a, b: jnp.where(keep.reshape((-1,) + (1,) * (a.ndim - 1)), a, b),
            res, unsolved)

    monkeypatch.setattr(dynamics, "_run_batched", first_half_only)


def _answer_altered(monkeypatch):
    from repro.engine import adapters

    orig = adapters.RetrievalEngineSolver.solve_bucket

    def flipped(self, *args, **kwargs):
        out = orig(self, *args, **kwargs)
        first = out[0]
        out[0] = first._replace(final_sigma=first.final_sigma.at[..., 0].multiply(-1))
        return out

    monkeypatch.setattr(adapters.RetrievalEngineSolver, "solve_bucket", flipped)


def _sweep_unchanged(monkeypatch):
    from repro.core import ising

    monkeypatch.setattr(ising, "staggered_sweep", lambda cfg, w, sigma, key, **kw: sigma)


def _maxcut_half_batch(monkeypatch):
    from repro.core import ising

    orig = ising.solve_maxcut_batch

    def first_half_twice(cfg, adjacency, keys, **kwargs):
        res = orig(cfg, adjacency, keys, **kwargs)
        half = adjacency.shape[0] // 2
        return jax.tree.map(lambda a: jnp.concatenate([a[:half], a[:adjacency.shape[0] - half]]), res)

    monkeypatch.setattr(ising, "solve_maxcut_batch", first_half_twice)


def _cut_altered(monkeypatch):
    from repro.engine import adapters

    orig = adapters.MaxCutEngineSolver.solve_bucket

    def plus_one(self, *args, **kwargs):
        out = orig(self, *args, **kwargs)
        out[0] = out[0]._replace(cut_value=out[0].cut_value + 1)
        return out

    monkeypatch.setattr(adapters.MaxCutEngineSolver, "solve_bucket", plus_one)


FAULTS = [
    ("onn506.batch", _state_unchanged),
    ("onn506.batch", _half_batch),
    ("onn506.batch", _answer_altered),
    ("maxcut_g1.batch", _sweep_unchanged),
    ("maxcut_g1.batch", _maxcut_half_batch),
    ("maxcut_g1.batch", _cut_altered),
]


def test_unbroken_small_runs_are_correct():
    for cell in ("onn506.batch", "maxcut_g1.batch"):
        assert run_small(cell, 5)["correct"], cell


@pytest.mark.parametrize("cell,fault", FAULTS, ids=lambda x: getattr(x, "__name__", x))
def test_fault_reads_not_correct(monkeypatch, cell, fault):
    fault(monkeypatch)
    res = run_small(cell, 7)
    assert not res["correct"]
    assert res["checks"]["mismatched_requests"]["value"] > 0

