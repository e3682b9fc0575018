"""Batched-native solve path: early-exit bit-exactness and its satellites.

The acceptance surface of the batched refactor: ``retrieve``/``run_batch``
drive one (B, N) state through a chunked early-exit ``lax.while_loop``, and
every field of the result (phases, settle_cycle, settled, cycled) must be
bit-identical, lane for lane, with the fixed-length scan of ``run`` — across
all three backends, both modes, and pinned ``sync_jitter`` keys.  Plus: the
loop really does stop early, the sharded solve matches the unsharded one,
deprecations warn, and engine latency quotes tighten with measured settle
cycles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import example, given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:
    from hypothesis_fallback import example, given, settings, st

from repro import api
from repro.core import dynamics
from repro.core.learning import diederich_opper_i
from repro.core.quantization import quantize_weights


def _instance(seed, n, batch=5):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.integers(-15, 16, (n, n)), jnp.int8)
    b = jnp.asarray(rng.integers(-3, 4, (n,)), jnp.int32)
    sigma0 = jnp.asarray(rng.choice([-1, 1], (batch, n)), jnp.int8)
    return w, b, sigma0


def _trained(seed, n, batch):
    """A fast-settling instance (DO-I on random patterns) — exercises freeze."""
    rng = np.random.default_rng(seed)
    xi = jnp.asarray(rng.choice([-1, 1], (max(2, n // 6), n)), jnp.int8)
    qw = quantize_weights(diederich_opper_i(xi).weights, bits=5)
    targets = xi[rng.integers(0, xi.shape[0], batch)]
    flips = jnp.asarray(rng.random((batch, n)) < 0.15)
    return qw.values, jnp.where(flips, -targets, targets).astype(jnp.int8)


def _fixed_scan_reference(cfg, params, sigma0_batch, keys=None):
    """The pre-batched architecture: per-lane fixed scans under vmap."""
    phase0 = dynamics.initial_phase(cfg, sigma0_batch)
    lane_keys = dynamics._lane_keys(cfg, keys, sigma0_batch.shape[0])
    if lane_keys is None:
        return jax.vmap(lambda p: dynamics.run(cfg, params, p))(phase0)
    return jax.vmap(lambda p, k: dynamics.run(cfg, params, p, k))(phase0, lane_keys)


def _assert_results_equal(got, ref, msg=""):
    for field in ref._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)),
            np.asarray(getattr(ref, field)),
            err_msg=f"{msg} field {field!r}",
        )


# ---------------------------------------------------------------------------
# Early-exit equivalence: bit-identical with the fixed-length scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "backend,mode,architecture,settle_chunk",
    [
        ("parallel", "functional", "hybrid", 1),
        ("parallel", "functional", "recurrent", 8),
        ("parallel", "rtl", "hybrid", 4),
        ("parallel", "rtl", "recurrent", 8),
        ("pallas", "functional", "hybrid", 8),
        ("pallas", "functional", "recurrent", 3),
        ("pallas", "rtl", "hybrid", 8),
        ("hybrid", "functional", "hybrid", 4),
        ("hybrid", "rtl", "hybrid", 3),
        ("hybrid", "functional", "recurrent", 8),
        ("hybrid", "rtl", "recurrent", 5),
    ],
)
def test_retrieve_bit_exact_with_fixed_scan(backend, mode, architecture, settle_chunk):
    """Random couplings: every result field matches jax.vmap(run) exactly —
    rtl configs run with sync_jitter and pinned per-lane keys."""
    n = 12
    w, b, sigma0 = _instance(hash((backend, mode, architecture)) % 1000, n)
    jitter = mode == "rtl"
    cfg = dynamics.ONNConfig(
        n=n,
        backend=backend,
        parallel_factor=5 if backend == "hybrid" else 0,  # ragged: 5 ∤ 12
        mode=mode,
        architecture=architecture,
        max_cycles=12,
        settle_chunk=settle_chunk,
        sync_jitter=jitter,
    )
    params = dynamics.make_params(cfg, w, b)
    keys = jax.random.PRNGKey(7) if jitter else None
    got = dynamics.retrieve(cfg, params, sigma0, keys)
    ref = _fixed_scan_reference(cfg, params, sigma0, keys)
    _assert_results_equal(got, ref, f"{backend}/{mode}/{architecture}")


@pytest.mark.parametrize("max_cycles", [9, 10])
def test_period_two_parity_reconstruction(max_cycles):
    """Lanes frozen inside a period-2 orbit must report the phase the fixed
    scan would have reached at max_cycles — both parities of the remaining
    cycle count, mixed with settling lanes in one batch."""
    w = (
        jnp.zeros((4, 4), jnp.int8)
        .at[0, 1].set(-15).at[1, 0].set(-15)  # antiferro pair → period-2
        .at[2, 3].set(15).at[3, 2].set(15)  # ferro pair → settles
    )
    cfg = dynamics.ONNConfig(n=4, max_cycles=max_cycles, settle_chunk=3)
    params = dynamics.make_params(cfg, w)
    batch = jnp.asarray([[1, 1, 1, 1], [1, -1, 1, 1], [-1, -1, -1, -1]], jnp.int8)
    got = dynamics.retrieve(cfg, params, batch)
    ref = _fixed_scan_reference(cfg, params, batch)
    _assert_results_equal(got, ref, f"max_cycles={max_cycles}")
    assert bool(got.cycled[0]) and not bool(got.settled[0])


def test_settle_chunk_does_not_change_results():
    """The chunk size is a scheduling knob only: all values (1, coprime,
    larger than max_cycles, 0 = fixed) give identical results."""
    w, b, sigma0 = _instance(77, 10)
    results = []
    for chunk in (0, 1, 3, 8, 200):
        cfg = dynamics.ONNConfig(n=10, max_cycles=14, settle_chunk=chunk)
        results.append(dynamics.retrieve(cfg, dynamics.make_params(cfg, w, b), sigma0))
    for r in results[1:]:
        _assert_results_equal(r, results[0])


def test_run_batch_matches_vmapped_run_and_key_split():
    """run_batch: lanes-first results equal per-lane run; a single key equals
    the explicit per-lane split (and randomness is required when drawn)."""
    n = 8
    w, b, sigma0 = _instance(5, n, batch=4)
    cfg = dynamics.ONNConfig(
        n=n, mode="rtl", sync_jitter=True, max_cycles=6, settle_chunk=2
    )
    params = dynamics.make_params(cfg, w, b)
    phase0 = dynamics.initial_phase(cfg, sigma0)
    key = jax.random.PRNGKey(3)
    out_single = dynamics.run_batch(cfg, params, phase0, key)
    out_split = dynamics.run_batch(cfg, params, phase0, jax.random.split(key, 4))
    _assert_results_equal(out_single, out_split)
    ref = jax.vmap(lambda p, k: dynamics.run(cfg, params, p, k))(
        phase0, jax.random.split(key, 4)
    )
    _assert_results_equal(out_single, ref)
    with pytest.raises(ValueError, match="keys"):
        dynamics.run_batch(cfg, params, phase0)


def test_early_exit_stops_scanning(monkeypatch):
    """The while_loop really stops: a fast-settling batch at max_cycles=100
    computes a couple of settle_chunk-sized bursts of weighted sums, not 100."""
    calls = {"n": 0}
    orig = dynamics.BACKENDS["parallel"]

    def counting(cfg, w, sigma):
        calls["n"] += 1
        return orig(cfg, w, sigma)

    monkeypatch.setitem(dynamics.BACKENDS, "parallel", counting)
    w, sigma0 = _trained(11, 18, batch=6)
    cfg = dynamics.ONNConfig(n=18, max_cycles=100, settle_chunk=5)
    params = dynamics.make_params(cfg, w)
    with jax.disable_jit():
        out = dynamics.retrieve(cfg, params, sigma0)
    assert bool(jnp.all(out.settled | out.cycled))
    assert calls["n"] <= 3 * 5, (
        f"{calls['n']} weighted sums for a fast-settling batch — early exit "
        "should stop after a few settle_chunk bursts, not scan max_cycles"
    )


def test_batched_backends_bit_exact():
    """The (B,N)-first dispatch keeps all three schedules bit-exact."""
    w, b, sigma0 = _instance(21, 20, batch=4)
    results = {}
    for backend in ("parallel", "pallas", "hybrid"):
        cfg = dynamics.ONNConfig(
            n=20,
            backend=backend,
            parallel_factor=7 if backend == "hybrid" else 0,  # ragged: 7 ∤ 20
            max_cycles=15,
            settle_chunk=4,
        )
        params = dynamics.make_params(cfg, w, b)
        results[backend] = dynamics.retrieve(cfg, params, sigma0)
    _assert_results_equal(results["pallas"], results["parallel"])
    _assert_results_equal(results["hybrid"], results["parallel"])


# ---------------------------------------------------------------------------
# Property test: random couplings, all backends, both modes, pinned keys
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 2**16),
    backend=st.sampled_from(["parallel", "pallas", "hybrid"]),
    mode=st.sampled_from(["functional", "rtl"]),
    settle_chunk=st.integers(1, 9),
)
# N=4, P=1, recurrent, rtl: the draw that once failed XLA's CPU compiler.
@example(seed=0, backend="hybrid", mode="rtl", settle_chunk=1)
def test_property_early_exit_bit_exact(seed, backend, mode, settle_chunk):
    """Chunked while_loop ≡ fixed-length scan, bit for bit, on random int8
    couplings (phases, settle_cycle, settled, cycled) — rtl draws jitter from
    a pinned key so the comparison covers the randomized path too.  The
    hybrid backend draws a random MAC width (ragged tails included)."""
    n = 4 + seed % 9
    w, b, sigma0 = _instance(seed, n, batch=4)
    jitter = mode == "rtl"
    cfg = dynamics.ONNConfig(
        n=n,
        backend=backend,
        parallel_factor=1 + seed % (n + 1) if backend == "hybrid" else 0,
        mode=mode,
        architecture="hybrid" if seed % 2 else "recurrent",
        max_cycles=10,
        settle_chunk=settle_chunk,
        sync_jitter=jitter,
    )
    params = dynamics.make_params(cfg, w, b)
    keys = jax.random.PRNGKey(seed) if jitter else None
    got = dynamics.retrieve(cfg, params, sigma0, keys)
    ref = _fixed_scan_reference(cfg, params, sigma0, keys)
    _assert_results_equal(got, ref, f"seed={seed} {backend}/{mode}")


# ---------------------------------------------------------------------------
# Sharded retrieve: the mesh recipe is bit-exact (1-device smoke)
# ---------------------------------------------------------------------------


def test_sharded_retrieve_matches_unsharded():
    """Serving under an active mesh + rules context (the --shard-batch
    recipe) constrains the batch and params without changing results."""
    from repro.distributed import sharding as shard_lib

    devices = np.asarray(jax.devices()).reshape(len(jax.devices()), 1)
    mesh = jax.sharding.Mesh(devices, ("data", "model"))
    w, b, sigma0 = _instance(31, 16, batch=4)
    cfg = dynamics.ONNConfig(n=16, max_cycles=23, settle_chunk=4)
    params = dynamics.make_params(cfg, w, b)
    sharded_params = jax.device_put(
        params, shard_lib.onn_param_shardings(mesh, layout="replicated")
    )
    with shard_lib.use_rules(shard_lib.single_pod_rules(), mesh):
        got = dynamics.retrieve(cfg, sharded_params, sigma0)
    ref = _fixed_scan_reference(cfg, params, sigma0)
    _assert_results_equal(got, ref)


def test_sharding_context_gets_its_own_executable():
    """A warmed-up no-mesh cache must not swallow the mesh context (and vice
    versa): each sharding context traces its own executable, same-context
    calls reuse it."""
    from repro.distributed import sharding as shard_lib

    devices = np.asarray(jax.devices()).reshape(len(jax.devices()), 1)
    mesh = jax.sharding.Mesh(devices, ("data", "model"))
    w, b, sigma0 = _instance(41, 10, batch=3)
    cfg = dynamics.ONNConfig(n=10, max_cycles=27, settle_chunk=4)  # fresh cache key
    params = dynamics.make_params(cfg, w, b)

    before = dynamics.TRACE_COUNTER["run_batch"]
    dynamics.retrieve(cfg, params, sigma0)  # warm the no-context cache
    assert dynamics.TRACE_COUNTER["run_batch"] == before + 1
    with shard_lib.use_rules(shard_lib.single_pod_rules(), mesh):
        dynamics.retrieve(cfg, params, sigma0)  # mesh context: fresh trace
        assert dynamics.TRACE_COUNTER["run_batch"] == before + 2
        dynamics.retrieve(cfg, params, sigma0)  # same context: cached
        assert dynamics.TRACE_COUNTER["run_batch"] == before + 2
    dynamics.retrieve(cfg, params, sigma0)  # back outside: cached again
    assert dynamics.TRACE_COUNTER["run_batch"] == before + 2


# ---------------------------------------------------------------------------
# Deprecation hygiene
# ---------------------------------------------------------------------------


def test_use_kernel_flag_removed():
    """The deprecated use_kernel alias is gone: passing it is an error, not
    a silent no-op (dataclasses reject unknown keywords with TypeError)."""
    with pytest.raises(TypeError, match="use_kernel"):
        dynamics.ONNConfig(n=4, use_kernel=True)


def test_onn_class_shim_removed():
    """The legacy class wrapper (deprecated since PR 1) no longer imports,
    and the core facade no longer re-exports it."""
    import repro.core as core

    with pytest.raises(ModuleNotFoundError):
        import repro.core.onn  # noqa: F401
    assert not hasattr(core, "ONN")


# ---------------------------------------------------------------------------
# Engine cost model: quotes tighten as measured settle cycles flow in
# ---------------------------------------------------------------------------


def test_engine_quotes_tighten_with_measured_settles():
    from repro import engine as engine_lib

    rng = np.random.default_rng(3)
    xi = jnp.asarray(rng.choice([-1, 1], (3, 16)), jnp.int8)
    solver = api.RetrievalSolver.from_patterns(xi, max_cycles=80)
    eng = engine_lib.Engine(jax.random.PRNGKey(0), batch_buckets=(1, 2, 4))
    adapter = eng.install("letters", solver.as_engine_solver())

    cold_units = adapter.cost_units(16, 2)  # 2 lanes → batch bucket 2
    est_cold = eng.estimate("letters", xi[:2])
    assert est_cold.units == pytest.approx(cold_units)
    assert adapter.expected_cycles() == pytest.approx(80.0)  # worst case

    for i in range(3):
        eng.submit(engine_lib.Request("letters", xi))  # stable patterns: settle fast
        eng.drain()

    stats = eng.stats()["solvers"]["letters"]
    assert stats["settle_slabs_observed"] == 3
    assert stats["settle_ema_cycles"] < 5
    assert stats["expected_cycles"] < 80.0  # blended toward the measurement
    assert adapter.cost_units(16, 2) < cold_units  # quotes tightened
