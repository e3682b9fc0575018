"""Functional pytree API: compile-once semantics, backends, explicit PRNG.

The acceptance surface of the api_redesign: params are traced (one compiled
executable per (config, shape), vmappable over problems), the weighted-sum
backends are bit-exact, and randomness is explicit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import dynamics


def _instance(seed, n, bias=False):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.integers(-15, 16, (n, n)), jnp.int8)
    b = jnp.asarray(rng.integers(-5, 6, (n,)), jnp.int32) if bias else None
    sigma0 = jnp.asarray(rng.choice([-1, 1], (n,)), jnp.int8)
    return w, b, sigma0


# ---------------------------------------------------------------------------
# One compile per (config, shape)
# ---------------------------------------------------------------------------


def test_same_shape_different_weights_share_one_trace():
    """Two distinct same-N weight matrices must hit a single trace of run."""
    cfg = api.ONNConfig(n=12, max_cycles=13)  # distinctive cfg → fresh cache key
    w1, _, sigma0 = _instance(0, 12)
    w2, _, _ = _instance(1, 12)
    assert not jnp.array_equal(w1, w2)
    phase0 = api.initial_phase(cfg, sigma0)

    before = dynamics.TRACE_COUNTER["run"]
    out1 = api.run(cfg, api.make_params(cfg, w1), phase0)
    after_first = dynamics.TRACE_COUNTER["run"]
    out2 = api.run(cfg, api.make_params(cfg, w2), phase0)
    after_second = dynamics.TRACE_COUNTER["run"]

    assert after_first == before + 1, "first call must trace"
    assert after_second == after_first, "second weights must reuse the executable"
    # and the runs really saw different problems
    assert out1.final_sigma.shape == out2.final_sigma.shape == (12,)


def test_retrieve_shares_one_trace_across_weights():
    cfg = api.ONNConfig(n=10, max_cycles=17)
    w1, _, s = _instance(2, 10)
    w2, _, _ = _instance(3, 10)
    batch = jnp.stack([s, -s, s])

    before = dynamics.TRACE_COUNTER["retrieve"]
    api.retrieve(cfg, api.make_params(cfg, w1), batch)
    api.retrieve(cfg, api.make_params(cfg, w2), batch)
    assert dynamics.TRACE_COUNTER["retrieve"] == before + 1


def test_vmap_over_params_many_problems_one_compile():
    """jax.vmap over OnnParams: a stack of problems through one executable."""
    n, k = 8, 4
    cfg = api.ONNConfig(n=n, max_cycles=19)
    ws = [_instance(10 + i, n)[0] for i in range(k)]
    _, _, sigma0 = _instance(42, n)
    phase0 = api.initial_phase(cfg, sigma0)
    stacked = api.OnnParams(
        weights=jnp.stack(ws), bias=jnp.zeros((k, n), jnp.int32)
    )

    out = jax.vmap(lambda p: dynamics.run(cfg, p, phase0))(stacked)
    assert out.final_sigma.shape == (k, n)
    for i, w in enumerate(ws):
        ref = api.run(cfg, api.make_params(cfg, w), phase0)
        np.testing.assert_array_equal(
            np.asarray(out.final_sigma[i]), np.asarray(ref.final_sigma)
        )


# ---------------------------------------------------------------------------
# Backend dispatch: bit-exactness across schedules
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,chunk", [(20, 4), (20, 7), (9, 2), (48, 5)])
def test_backends_bit_exact(n, chunk):
    """pallas and hybrid (the chunk doubling as a ragged MAC width P) match
    parallel."""
    w, b, sigma0 = _instance(n * 100 + chunk, n, bias=True)
    batch = jnp.stack([sigma0, -sigma0])
    results = {}
    specs = {
        "parallel": {},
        "pallas": {},
        "hybrid": {"parallel_factor": chunk},
    }
    for name, kw in specs.items():
        cfg = api.ONNConfig(n=n, backend=name, max_cycles=20, **kw)
        params = api.make_params(cfg, w, b)
        results[name] = np.asarray(api.retrieve(cfg, params, batch).final_sigma)
    for name in ("pallas", "hybrid"):
        np.testing.assert_array_equal(results["parallel"], results[name], err_msg=name)


@pytest.mark.parametrize(
    "kwargs,error",
    [
        ({"backend": "serial"}, ValueError),
        ({"serial_chunk": 2}, TypeError),
        ({"hybrid_impl": "pallas"}, TypeError),
        ({"parallel_factor": 4}, ValueError),
        ({"use_kernel": True}, TypeError),
    ],
    ids=["backend-serial", "serial_chunk", "hybrid_impl", "bare-parallel_factor", "use_kernel"],
)
def test_removed_route_spellings_raise(kwargs, error):
    """One spelling per schedule: removed backends, route options and the
    bare ``parallel_factor`` (which once selected hybrid) are errors."""
    assert api.ONNConfig(n=4).backend == "parallel"
    with pytest.raises(error, match=next(iter(kwargs))):
        api.ONNConfig(n=4, **kwargs)


def test_step_rejects_rtl_mode():
    """step() is the functional-mode map; an rtl config must not silently
    get functional dynamics."""
    cfg = api.ONNConfig(n=4, mode="rtl")
    w, _, sigma0 = _instance(30, 4)
    state = api.init_state(cfg, sigma0)
    with pytest.raises(ValueError, match="rtl"):
        api.step(cfg, api.make_params(cfg, w), state)


# ---------------------------------------------------------------------------
# Period-2 detection and the removed 255 sentinel
# ---------------------------------------------------------------------------


def test_period_two_cycle_detected():
    w = jnp.asarray([[0, -15], [-15, 0]], jnp.int8)  # antiferromagnetic pair
    cfg = api.ONNConfig(n=2, max_cycles=10)
    out = api.run(cfg, api.make_params(cfg, w), api.initial_phase(cfg, jnp.asarray([1, 1], jnp.int8)))
    assert bool(out.cycled) and not bool(out.settled)


def test_phase_255_is_a_legal_state_at_8_phase_bits():
    """With phase_bits=8, phase 255 is valid; the old 255 'no previous state'
    sentinel collided with it.  A run started at all-255 phases on zero
    couplings must settle immediately and must not be flagged as cycled."""
    n = 4
    cfg = api.ONNConfig(n=n, phase_bits=8, max_cycles=5)
    params = api.make_params(cfg, jnp.zeros((n, n), jnp.int8))
    phase0 = jnp.full((n,), 255, jnp.uint8)
    out = api.run(cfg, params, phase0)
    assert bool(out.settled) and int(out.settle_cycle) == 0
    assert not bool(out.cycled)
    np.testing.assert_array_equal(np.asarray(out.final_phase), np.asarray(phase0))


def test_first_cycle_flag_in_state():
    cfg = api.ONNConfig(n=4)
    _, _, sigma0 = _instance(7, 4)
    state = api.init_state(cfg, sigma0)
    assert bool(state.first_cycle)
    w, _, _ = _instance(8, 4)
    state2 = api.step(cfg, api.make_params(cfg, w), state)
    assert not bool(state2.first_cycle)
    assert int(state2.cycle) == 1


# ---------------------------------------------------------------------------
# Explicit PRNG in retrieve
# ---------------------------------------------------------------------------


def test_retrieve_requires_keys_when_randomness_is_drawn():
    cfg = api.ONNConfig(n=4, mode="rtl", sync_jitter=True)
    w, _, sigma0 = _instance(20, 4)
    params = api.make_params(cfg, w)
    batch = jnp.stack([sigma0, -sigma0])
    with pytest.raises(ValueError, match="keys"):
        api.retrieve(cfg, params, batch)
    # a single key is split per request; a (B, 2) batch is used as-is
    out1 = api.retrieve(cfg, params, batch, jax.random.PRNGKey(0))
    out2 = api.retrieve(cfg, params, batch, jax.random.split(jax.random.PRNGKey(0), 2))
    assert out1.final_sigma.shape == out2.final_sigma.shape == (2, 4)


def test_retrieve_accepts_new_style_typed_keys():
    """Typed keys (jax.random.key): a scalar splits, a batch is used as-is."""
    cfg = api.ONNConfig(n=4, mode="rtl", sync_jitter=True)
    w, _, sigma0 = _instance(22, 4)
    params = api.make_params(cfg, w)
    batch = jnp.stack([sigma0, -sigma0])
    out1 = api.retrieve(cfg, params, batch, jax.random.key(0))
    out2 = api.retrieve(cfg, params, batch, jax.random.split(jax.random.key(0), 2))
    assert out1.final_sigma.shape == out2.final_sigma.shape == (2, 4)
    np.testing.assert_array_equal(np.asarray(out1.final_sigma), np.asarray(out2.final_sigma))


def test_retrieve_single_key_decorrelates_requests():
    """Splitting one key must give each request its own stream (the old
    hidden PRNGKey(0) default gave every jittered run the same one)."""
    cfg = api.ONNConfig(n=6, mode="rtl", sync_jitter=True, max_cycles=8)
    w, _, sigma0 = _instance(21, 6)
    params = api.make_params(cfg, w)
    batch = jnp.broadcast_to(sigma0, (32, 6))

    split = jax.vmap(
        lambda k: jax.random.randint(k, (), 0, cfg.clocks_per_cycle)
    )(jax.random.split(jax.random.PRNGKey(0), 32))
    assert len(np.unique(np.asarray(split))) > 1  # jitter offsets differ
    out = api.retrieve(cfg, params, batch, jax.random.PRNGKey(0))
    assert out.final_sigma.shape == (32, 6)


def test_solver_protocol():
    """RetrievalSolver and MaxCutSolver both satisfy the Solver protocol."""
    from repro.core.ising import random_graph
    from repro.data import load_dataset

    xi = load_dataset("3x3")
    retr = api.RetrievalSolver.from_patterns(xi, architecture="hybrid")
    mc = api.MaxCutSolver(sweeps=4)
    assert isinstance(retr, api.Solver) and isinstance(mc, api.Solver)

    out = retr.solve(xi)
    np.testing.assert_array_equal(np.asarray(out.final_sigma), np.asarray(xi))
    adj = random_graph(jax.random.PRNGKey(0), 12, 0.5)
    res = mc.solve(adj, jax.random.PRNGKey(1))
    assert float(res.cut_value) >= 0
    with pytest.raises(ValueError):
        mc.solve(adj)
