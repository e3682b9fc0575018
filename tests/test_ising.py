"""Batched oscillatory Ising machine (repro.core.ising).

Acceptance surface of the Max-Cut rebuild:
  * cut values match brute-force enumeration at small N;
  * the multi-replica solve is bit-exact across parallel / serial / pallas /
    hybrid(scan) / hybrid(pallas) backends for every (N, P, replicas);
  * grouped staggering: K = N is the asynchronous sweep (energy monotone),
    K < N keeps the solver's bookkeeping invariants;
  * engine results are invariant to bucket policy and occupancy — the same
    (adjacency, key) returns the same cut no matter how it was padded;
  * async_sweep accumulates float couplings without truncation;
  * the engine path compiles one executable per (config, bucket) — no
    unbounded per-install cache.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    from hypothesis_fallback import given, settings, st

from repro import api
from repro import engine as engine_lib
from repro.core import dynamics
from repro.core import ising
from repro.core.dynamics import ONNConfig, async_sweep
from repro.core.energy import hamiltonian
from repro.core.quantization import quantize_weights
from repro.engine import adapters

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


def _brute_force_cut(adj: jax.Array) -> float:
    n = adj.shape[0]
    sigs = jnp.asarray(np.array(list(itertools.product([-1, 1], repeat=n)), np.int8))
    return float(jnp.max(ising.cut_value_exact(adj, sigs)))


def _fields_equal(a: ising.MaxCutResult, b: ising.MaxCutResult) -> None:
    for field in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, field)), np.asarray(getattr(b, field)), err_msg=field
        )


# ---------------------------------------------------------------------------
# Correctness: brute force, result invariants
# ---------------------------------------------------------------------------


def test_cut_value_exact_matches_direct_count():
    adj = ising.random_graph(jax.random.PRNGKey(3), 7, 0.6)
    a = np.asarray(adj)
    sigma = np.asarray([1, -1, 1, 1, -1, -1, 1], np.int8)
    direct = sum(a[i, j] for i in range(7) for j in range(i + 1, 7) if sigma[i] != sigma[j])
    assert float(ising.cut_value_exact(adj, jnp.asarray(sigma))) == float(direct)
    # batched form: one row per assignment
    batch = jnp.asarray(np.stack([sigma, -sigma, np.ones(7, np.int8)]))
    vals = ising.cut_value_exact(adj, batch)
    assert vals.shape == (3,)
    assert float(vals[0]) == float(vals[1]) == float(direct)  # spin-flip symmetry
    assert float(vals[2]) == 0.0


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_batched_solve_reaches_bruteforce_optimum(seed):
    key = jax.random.PRNGKey(seed)
    adj = ising.random_graph(key, 10, 0.5)
    cfg = ONNConfig(n=10, max_cycles=64)
    res = ising.solve_maxcut_batch(cfg, adj, jax.random.fold_in(key, 1), replicas=16)
    assert float(res.cut_value) == _brute_force_cut(adj)
    # the reported assignment really achieves the reported cut
    assert float(ising.cut_value_exact(adj, res.sigma)) == float(res.cut_value)


@given(st.integers(0, 2**31 - 1), st.integers(4, 10))
def test_solve_matches_bruteforce_enumeration(seed, n):
    key = jax.random.PRNGKey(seed)
    adj = ising.random_graph(key, n, 0.5)
    cfg = ONNConfig(n=n, max_cycles=64)
    res = ising.solve_maxcut_batch(cfg, adj, jax.random.fold_in(key, 1), replicas=16)
    assert float(res.cut_value) == _brute_force_cut(adj)


def test_result_bookkeeping_invariants():
    key = jax.random.PRNGKey(11)
    adj = ising.random_graph(key, 24, 0.5)
    cfg = ONNConfig(n=24, max_cycles=20)
    res = ising.solve_maxcut_batch(
        cfg, adj, jax.random.fold_in(key, 1), replicas=4, stagger_groups=6
    )
    trace = np.asarray(res.trace)
    assert trace.shape == (20,)
    assert np.all(np.diff(trace) >= 0)  # best-so-far is monotone
    assert trace[-1] == float(res.cut_value)
    assert float(np.max(np.asarray(res.replica_cuts))) == float(res.cut_value)
    assert int(res.sweeps_run) == 20
    assert float(ising.cut_value_exact(adj, res.sigma)) == float(res.cut_value)


# ---------------------------------------------------------------------------
# Backend bit-exactness matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [12, 33])
@pytest.mark.parametrize("p", [1, 8])
@pytest.mark.parametrize("replicas", [1, 3])
def test_backends_bit_exact(n, p, replicas):
    key = jax.random.PRNGKey(100 + n)
    adj = ising.random_graph(key, n, 0.5)
    skey = jax.random.fold_in(key, 2)

    def solve(**cfg_kw):
        cfg = ONNConfig(n=n, max_cycles=12, **cfg_kw)
        return ising.solve_maxcut_batch(cfg, adj, skey, replicas=replicas)

    ref = solve(backend="parallel")
    _fields_equal(ref, solve(backend="pallas"))
    _fields_equal(ref, solve(backend="hybrid", parallel_factor=p))


# ---------------------------------------------------------------------------
# Grouped staggering: K = N is asynchronous (energy monotone); K < N trades
# ---------------------------------------------------------------------------


@given(st.integers(0, 2**31 - 1), st.integers(4, 20))
def test_async_limit_never_increases_energy(seed, n):
    """K = N fires one oscillator per enable window — the asynchronous
    Hopfield sweep, whose energy-monotonicity the retrieval physics relies
    on — through the grouped-staggered machinery."""
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    adj = ising.random_graph(k1, n, 0.5)
    w = ising.maxcut_couplings(adj).values
    cfg = ONNConfig(n=n)
    sigma = jax.random.choice(k2, jnp.array([-1, 1], jnp.int8), shape=(2, n))
    e = np.asarray(jax.vmap(lambda s: hamiltonian(w, s))(sigma))
    for t in range(3):
        sigma = ising.staggered_sweep(cfg, w, sigma, jax.random.fold_in(k3, t), groups=n)
        e2 = np.asarray(jax.vmap(lambda s: hamiltonian(w, s))(sigma))
        assert np.all(e2 <= e + 1e-4)
        e = e2


@given(st.integers(0, 2**31 - 1), st.sampled_from([2, 4, 7]))
def test_grouped_staggering_monotone_best_energy(seed, groups):
    """With K < N groups (simultaneous in-group updates) the retained-best
    energy still never increases: the trace is the running max cut, and the
    returned assignment achieves it exactly."""
    n = 16
    key = jax.random.PRNGKey(seed)
    adj = ising.random_graph(key, n, 0.5)
    cfg = ONNConfig(n=n, max_cycles=12)
    res = ising.solve_maxcut_batch(
        cfg, adj, jax.random.fold_in(key, 1), replicas=2, stagger_groups=groups
    )
    trace = np.asarray(res.trace)
    assert np.all(np.diff(trace) >= 0)
    assert float(ising.cut_value_exact(adj, res.sigma)) == float(res.cut_value)
    assert trace[-1] == float(res.cut_value)


def test_stagnation_early_exit():
    key = jax.random.PRNGKey(5)
    adj = ising.random_graph(key, 16, 0.5)
    cfg = ONNConfig(n=16, max_cycles=200, settle_chunk=4)
    res = ising.solve_maxcut_batch(cfg, adj, jax.random.fold_in(key, 1), replicas=4, stagnation=5)
    full = ising.solve_maxcut_batch(cfg, adj, jax.random.fold_in(key, 1), replicas=4)
    assert int(res.sweeps_run) < 200  # froze long before the sweep budget
    assert int(full.sweeps_run) == 200
    trace = np.asarray(res.trace)
    # the un-run tail repeats the final best
    assert np.all(trace[int(res.sweeps_run):] == float(res.cut_value))
    assert float(ising.cut_value_exact(adj, res.sigma)) == float(res.cut_value)


# ---------------------------------------------------------------------------
# Padding determinism (the bucket-policy bugfix)
# ---------------------------------------------------------------------------


def test_padded_solve_bit_identical_direct():
    key = jax.random.PRNGKey(7)
    adj = ising.random_graph(key, 20, 0.5)
    skey = jax.random.fold_in(key, 1)
    ref = ising.solve_maxcut_batch(ONNConfig(n=20, max_cycles=16), adj, skey, replicas=3)
    for nb in (32, 64):
        padded = jnp.pad(adj, ((0, nb - 20), (0, nb - 20)))
        got = ising.solve_maxcut_batch(
            ONNConfig(n=nb, max_cycles=16), padded, skey, replicas=3, true_n=20
        )
        np.testing.assert_array_equal(
            np.asarray(got.sigma)[:20], np.asarray(ref.sigma), err_msg=f"nb={nb}"
        )
        np.testing.assert_array_equal(np.asarray(got.trace), np.asarray(ref.trace))
        assert float(got.cut_value) == float(ref.cut_value)
        np.testing.assert_array_equal(np.asarray(got.replica_cuts), np.asarray(ref.replica_cuts))


@pytest.mark.parametrize("n_policy", ["exact", "pow2", (64,)])
def test_engine_results_invariant_to_bucket_policy(n_policy):
    """Satellite bugfix: the same (adjacency, key) request returns the same
    cut under every n_policy and any bucket occupancy."""
    key = jax.random.PRNGKey(21)
    adj = ising.random_graph(key, 20, 0.5)
    req_key = jax.random.fold_in(key, 1)
    solver = api.MaxCutSolver(sweeps=10, replicas=2)
    ref = solver.solve(adj, req_key)

    eng = engine_lib.Engine(jax.random.PRNGKey(33), batch_buckets=(1, 2, 4), n_policy=n_policy)
    eng.install("cuts", solver.as_engine_solver())
    # occupancy varies: the pinned-key request rides alone and coalesced
    # with a different-size instance in the same bucket.
    f_alone = eng.submit(engine_lib.Request("cuts", adj, key=req_key))
    eng.flush()
    other = ising.random_graph(jax.random.fold_in(key, 9), 17, 0.5)
    f_coalesced = eng.submit(engine_lib.Request("cuts", adj, key=req_key))
    eng.submit(engine_lib.Request("cuts", other))
    eng.drain()

    for fut in (f_alone, f_coalesced):
        got = fut.result()
        np.testing.assert_array_equal(np.asarray(got.sigma), np.asarray(ref.sigma))
        assert float(got.cut_value) == float(ref.cut_value)
        np.testing.assert_array_equal(np.asarray(got.trace), np.asarray(ref.trace))


def test_sweeps_run_invariant_to_slab_occupancy():
    """With stagnation early exit, a frozen instance coalesced next to a
    longer-running one must report the sweeps until *its* replicas froze —
    not the slab's loop iterations."""
    key = jax.random.PRNGKey(81)
    adj = ising.random_graph(key, 16, 0.5)
    req_key = jax.random.fold_in(key, 1)
    solver = api.MaxCutSolver(sweeps=120, replicas=2, stagnation=3, settle_chunk=1)
    ref = solver.solve(adj, req_key)
    assert int(ref.sweeps_run) < 120  # the instance actually exits early

    eng = engine_lib.Engine(jax.random.PRNGKey(82), batch_buckets=(1, 2, 4))
    eng.install("cuts", solver.as_engine_solver())
    fut = eng.submit(engine_lib.Request("cuts", adj, key=req_key))
    # sibling instance with a much longer anneal horizon in the same slab
    hard = ising.random_graph(jax.random.fold_in(key, 9), 15, 0.5)
    eng.submit(engine_lib.Request("cuts", hard, key=jax.random.fold_in(key, 10)))
    eng.drain()
    got = fut.result()
    assert int(got.sweeps_run) == int(ref.sweeps_run)
    np.testing.assert_array_equal(np.asarray(got.trace), np.asarray(ref.trace))
    assert float(got.cut_value) == float(ref.cut_value)


# ---------------------------------------------------------------------------
# async_sweep float couplings (the silent-truncation bugfix)
# ---------------------------------------------------------------------------


def test_async_sweep_float_weights_match_dequantized_int():
    """Float couplings must not be truncated toward zero: a sweep on the
    dequantized weights (values · positive scale) takes exactly the sign
    decisions of the int sweep on the quantized values."""
    rng = np.random.default_rng(0)
    w_float = rng.normal(size=(12, 12)).astype(np.float32) * 0.1
    w_float = (w_float + w_float.T) / 2
    np.fill_diagonal(w_float, 0.0)
    q = quantize_weights(jnp.asarray(w_float), bits=5)
    sigma = jnp.asarray(rng.choice([-1, 1], 12), jnp.int8)
    order = jnp.asarray(rng.permutation(12))
    out_int = async_sweep(q.values, sigma, order)
    out_float = async_sweep(q.dequantize(), sigma, order)
    np.testing.assert_array_equal(np.asarray(out_int), np.asarray(out_float))
    # sub-unit fields used to truncate to 0 (tie → keep): with |w| < 1 a
    # float sweep must still flip spins where the field's sign says so.
    w_small = q.dequantize() * (0.9 / float(jnp.max(jnp.abs(q.dequantize()))))
    out_small = async_sweep(w_small, sigma, order)
    np.testing.assert_array_equal(np.asarray(out_small), np.asarray(out_int))


# ---------------------------------------------------------------------------
# Engine compile-cache bounds (the unbounded-lru bugfix)
# ---------------------------------------------------------------------------


def test_engine_compile_cache_bounded():
    """The old module-level ``functools.lru_cache`` held one vmapped jitted
    executable per install(..., sweeps=...) setting forever.  Compiles now
    key through the core jit's (config, shape) cache: repeated installs of
    the same settings add no traces, and the adapter's per-bucket config
    dict is bounded by the buckets actually touched."""
    assert not hasattr(adapters, "_batched_maxcut")

    adj = ising.random_graph(jax.random.PRNGKey(41), 8, 0.5)
    before = dynamics.TRACE_COUNTER["solve_maxcut_batch"]
    solvers = []
    for sweeps in (9, 13):  # two distinct settings, three installs each
        for i in range(3):
            eng = engine_lib.Engine(jax.random.PRNGKey(50 + i), batch_buckets=(1,))
            eng.install("cuts", "maxcut", sweeps=sweeps, replicas=2)
            eng.submit(engine_lib.Request("cuts", adj))
            eng.drain()
            solvers.append(eng.solver("cuts"))
    traces = dynamics.TRACE_COUNTER["solve_maxcut_batch"] - before
    assert traces <= 2, (
        f"{traces} maxcut traces for 2 distinct settings × 3 installs — "
        "compiles must be shared per (config, bucket), not per install"
    )
    assert all(len(s._cfgs) == 1 for s in solvers)  # one N bucket touched


# ---------------------------------------------------------------------------
# API surface + planner quotes
# ---------------------------------------------------------------------------


def test_maxcut_solver_requires_key_and_batches():
    solver = api.MaxCutSolver(sweeps=6, replicas=2, backend="hybrid", parallel_factor=4)
    key = jax.random.PRNGKey(51)
    adjs = jnp.stack([ising.random_graph(jax.random.fold_in(key, i), 14, 0.5) for i in range(3)])
    with pytest.raises(ValueError, match="requires a PRNG key"):
        solver.solve(adjs[0])
    one = solver.solve(adjs[0], jax.random.fold_in(key, 10))
    assert one.sigma.shape == (14,) and one.replica_cuts.shape == (2,)
    batch = solver.solve(adjs, jax.random.fold_in(key, 11))
    assert batch.sigma.shape == (3, 14)
    assert batch.cut_value.shape == (3,)
    assert batch.trace.shape == (3, 6)


def test_engine_maxcut_quotes_fpga_tradeoff():
    """Acceptance: Ising requests carry non-None per-design hardware quotes."""
    eng = engine_lib.Engine(jax.random.PRNGKey(61), batch_buckets=(1, 2))
    eng.install("cuts", "maxcut", sweeps=8, replicas=4, backend="hybrid", parallel_factor=8)
    adj = ising.random_graph(jax.random.PRNGKey(62), 24, 0.5)
    est = eng.estimate("cuts", adj)
    assert est.fpga_tradeoff is not None
    assert {"recurrent", "hybrid[P=1]", "hybrid[P=8]"} <= set(est.fpga_tradeoff)
    assert est.fpga_tradeoff["hybrid[P=1]"] > est.fpga_tradeoff["hybrid[P=8]"]
    assert est.fpga_seconds == pytest.approx(est.fpga_tradeoff["hybrid[P=8]"])
    fut = eng.submit(engine_lib.Request("cuts", adj))
    stats = eng.drain()
    assert fut.result().replica_cuts.shape == (4,)
    assert stats["solvers"]["cuts"]["replicas"] == 4
    # cost model charges replicas × sweeps × streamed rows × pass grid: a
    # sweep's K groups each evaluate a ceil(N/K)-row window, not the full N
    solver = eng.solver("cuts")
    nb = 32
    k = ising.resolve_stagger_groups(0, nb)
    rows_per_sweep = k * (-(-nb // k))
    passes = -(-nb // 8)
    assert solver.cost_units(nb, 2) == pytest.approx(2 * 4 * 8 * rows_per_sweep * passes * 8)


def test_legacy_solve_maxcut_still_serves_small_instances():
    key = jax.random.PRNGKey(71)
    adj = ising.random_graph(key, 10, 0.5)
    res = ising.solve_maxcut(adj, jax.random.fold_in(key, 1), sweeps=32)
    assert res.sigma.shape == (10,)
    assert res.trace.shape == (32,)
    assert res.replica_cuts is None and res.sweeps_run is None
    cut = float(res.cut_value)
    assert cut == float(ising.cut_value_exact(adj, res.sigma))
    edges = float(jnp.sum(jnp.triu(adj, 1)))
    # single-chain anneal: beats the |E|/2 random baseline, bounded by OPT
    assert edges / 2 <= cut <= _brute_force_cut(adj)
