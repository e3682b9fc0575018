"""repro.engine acceptance surface: one engine, many workloads, bucketed
compiles, masked-lane bit-exactness, explicit PRNG, planner estimates."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro import engine as engine_lib
from repro.core import dynamics
from repro.core.ising import random_graph
from repro.engine import bucketing
from repro.engine.planner import Planner


def _patterns(seed: int, p: int, n: int) -> jax.Array:
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.choice([-1, 1], (p, n)), jnp.int8)


def _solver(seed: int, n: int, **kw) -> api.RetrievalSolver:
    return api.RetrievalSolver.from_patterns(_patterns(seed, 3, n), **kw)


# ---------------------------------------------------------------------------
# Bucketing + planner units
# ---------------------------------------------------------------------------


def test_bucket_n_policies():
    assert bucketing.bucket_n(100, "pow2") == 128
    assert bucketing.bucket_n(64, "pow2") == 64
    assert bucketing.bucket_n(3, "pow2") == bucketing.MIN_POW2_N
    assert bucketing.bucket_n(100, "exact") == 100
    assert bucketing.bucket_n(100, (64, 128, 256)) == 128
    with pytest.raises(ValueError):
        bucketing.bucket_n(300, (64, 128, 256))


def test_chop_and_waste():
    assert bucketing.chop(0, (1, 2, 4, 8)) == ()
    assert bucketing.chop(3, (1, 2, 4, 8)) == (4,)
    assert bucketing.chop(21, (1, 2, 4, 8)) == (8, 8, 8)
    assert bucketing.pad_waste(3, (4,)) == pytest.approx(0.25)
    assert bucketing.pad_waste(8, (8,)) == 0.0


def test_planner_ema_and_cold_start():
    pl = Planner(batch_buckets=(1, 2, 4), ema_alpha=0.5)
    cold = pl.estimate("k", units=1000.0)
    assert cold.source == "model" and cold.seconds > 0
    pl.observe("k", seconds=2.0, units=1000.0)  # first: compile-dominated
    warm = pl.estimate("k")
    assert warm.source == "ema" and warm.seconds == pytest.approx(2.0)
    assert not pl.snapshot()["cost_rate_fitted"]  # first obs excluded
    pl.observe("k", seconds=1.0, units=1000.0)
    assert pl.snapshot()["cost_rate_fitted"]
    assert pl.estimate("k").seconds == pytest.approx(1.5)  # EMA(2, 1; α=.5)
    other = pl.estimate("other", units=2000.0)
    assert other.source == "model"
    assert other.seconds == pytest.approx(2000.0 * 1.0 / 1000.0)  # fitted rate
    assert pl.plan(5) == (4, 1)


# ---------------------------------------------------------------------------
# Acceptance: mixed-size retrieval stream, one compile per (config, bucket)
# ---------------------------------------------------------------------------


def test_mixed_retrieval_stream_compiles_once_per_bucket():
    """N∈{64,100} solvers bucketed to one padded N=128 config: a stream of
    batch∈{1..8} requests traces retrieve at most once per batch bucket,
    and every padded result is bit-exact with the unpadded solve."""
    # max_cycles=37 gives these configs their own jit cache entries.
    s64 = _solver(0, 64, max_cycles=37)
    s100 = _solver(1, 100, max_cycles=37)

    eng = engine_lib.Engine(
        jax.random.PRNGKey(0),
        batch_buckets=(1, 2, 4, 8),
        n_policy=(128,),  # both instances share the padded N bucket
        coalesce=False,  # one slab per request → batch bucket = lane bucket
    )
    eng.install("letters64", s64.as_engine_solver())
    eng.install("letters100", s100.as_engine_solver())

    rng = np.random.default_rng(7)
    requests = []
    for i in range(10):
        name, solver = ("letters64", s64) if i % 2 == 0 else ("letters100", s100)
        b = int(rng.integers(1, 9))  # batch ∈ {1..8}
        n = solver.config.n
        batch = jnp.asarray(rng.choice([-1, 1], (b, n)), jnp.int8)
        requests.append((name, solver, batch))

    before = dynamics.TRACE_COUNTER["retrieve"]
    futures = [
        eng.submit(engine_lib.Request(name, batch)) for name, _, batch in requests
    ]
    eng.drain()
    traces = dynamics.TRACE_COUNTER["retrieve"] - before

    used_buckets = {bucketing.bucket_batch(b.shape[0], (1, 2, 4, 8)) for _, _, b in requests}
    assert traces <= len(used_buckets), (
        f"{traces} retrieve traces for batch buckets {sorted(used_buckets)} — "
        "padded instances must share one executable per (config, bucket)"
    )

    # Bit-exactness: bucket-padded lanes match the unpadded solve exactly.
    for (name, solver, batch), fut in zip(requests, futures):
        got = fut.result()
        ref = solver.solve(batch)
        np.testing.assert_array_equal(np.asarray(got.final_sigma), np.asarray(ref.final_sigma))
        np.testing.assert_array_equal(np.asarray(got.final_phase), np.asarray(ref.final_phase))
        np.testing.assert_array_equal(np.asarray(got.settle_cycle), np.asarray(ref.settle_cycle))
        np.testing.assert_array_equal(np.asarray(got.settled), np.asarray(ref.settled))
        np.testing.assert_array_equal(np.asarray(got.cycled), np.asarray(ref.cycled))


def test_coalesced_lanes_bit_exact_and_padded():
    """Lanes from many requests share one slab; results split back exactly."""
    s = _solver(2, 20, max_cycles=41)
    eng = engine_lib.Engine(jax.random.PRNGKey(1), batch_buckets=(1, 2, 4, 8))
    eng.install("letters", s.as_engine_solver())
    rng = np.random.default_rng(11)
    batches = [jnp.asarray(rng.choice([-1, 1], (b, 20)), jnp.int8) for b in (1, 2, 3)]
    futs = [eng.submit(engine_lib.Request("letters", b)) for b in batches]
    stats = eng.drain()
    assert stats["slabs"] == 1  # 6 lanes coalesced into one bucket-8 slab
    assert stats["pad_fraction"] == pytest.approx(2 / 8)
    for b, f in zip(batches, futs):
        np.testing.assert_array_equal(
            np.asarray(f.result().final_sigma), np.asarray(s.solve(b).final_sigma)
        )


def test_rtl_jitter_padded_lanes_bit_exact_with_explicit_keys():
    """Randomized (rtl sync_jitter) solves stay bit-exact under bucket
    padding when the request key is pinned: the engine splits the same
    per-lane keys the direct API call derives."""
    s = _solver(3, 12, mode="rtl", sync_jitter=True, max_cycles=6)
    eng = engine_lib.Engine(jax.random.PRNGKey(2), batch_buckets=(1, 2, 4))
    eng.install("letters", s.as_engine_solver())
    rng = np.random.default_rng(13)
    batch = jnp.asarray(rng.choice([-1, 1], (3, 12)), jnp.int8)
    key = jax.random.PRNGKey(99)
    fut = eng.submit(engine_lib.Request("letters", batch, key=key))
    eng.drain()
    ref = s.solve(batch, key)
    np.testing.assert_array_equal(
        np.asarray(fut.result().final_sigma), np.asarray(ref.final_sigma)
    )


# ---------------------------------------------------------------------------
# Acceptance: one engine, three workloads
# ---------------------------------------------------------------------------


def test_one_engine_serves_retrieval_maxcut_and_lm():
    xi = _patterns(4, 3, 24)
    eng = engine_lib.Engine(jax.random.PRNGKey(3), batch_buckets=(1, 2, 4))
    eng.install("letters", "retrieval", xi=xi, max_cycles=43)
    eng.install("cuts", "maxcut", sweeps=6)
    eng.install("lm", arch="qwen2-1.5b", key=jax.random.PRNGKey(4))

    f_ret = eng.submit(engine_lib.Request("letters", xi[0]))
    adj = random_graph(jax.random.PRNGKey(5), 10, 0.5)
    f_cut = eng.submit(engine_lib.Request("cuts", adj))
    f_lm = eng.submit(
        engine_lib.Request(
            "lm", {"tokens": jnp.zeros((8,), jnp.int32), "max_new_tokens": 3}
        )
    )
    stats = eng.drain()
    assert stats["completed"] == 3 and stats["failed"] == 0

    ret = f_ret.result()
    np.testing.assert_array_equal(np.asarray(ret.final_sigma), np.asarray(xi[0]))
    cut = f_cut.result()
    assert cut.sigma.shape == (10,) and float(cut.cut_value) >= 0
    lm_tokens = f_lm.result()
    assert lm_tokens.shape == (3,)  # single-lane payload → unbatched tokens


def test_lm_lane_padding_does_not_change_outputs():
    """Batch-padded LM lanes are dead rows: a request served alone and the
    same request coalesced with others decode identical tokens."""
    key = jax.random.PRNGKey(6)
    eng1 = engine_lib.Engine(jax.random.PRNGKey(7), batch_buckets=(1, 2, 4))
    eng1.install("lm", arch="qwen2-1.5b", key=key)
    prompt = jnp.arange(8, dtype=jnp.int32) % 100
    payload = {"tokens": prompt, "max_new_tokens": 4}
    f_alone = eng1.submit(engine_lib.Request("lm", payload))
    eng1.drain()

    eng2 = engine_lib.Engine(jax.random.PRNGKey(8), batch_buckets=(1, 2, 4))
    eng2.install("lm", arch="qwen2-1.5b", key=key)  # same params (same key)
    f_a = eng2.submit(engine_lib.Request("lm", payload))
    f_b = eng2.submit(
        engine_lib.Request("lm", {"tokens": prompt[::-1], "max_new_tokens": 4})
    )
    f_c = eng2.submit(engine_lib.Request("lm", payload))
    stats = eng2.drain()
    assert stats["slabs"] == 1  # 3 lanes coalesced into one bucket-4 slab
    np.testing.assert_array_equal(np.asarray(f_a.result()), np.asarray(f_alone.result()))
    np.testing.assert_array_equal(np.asarray(f_c.result()), np.asarray(f_alone.result()))
    assert f_b.result().shape == (4,)


# ---------------------------------------------------------------------------
# Registry, errors, PRNG, stats
# ---------------------------------------------------------------------------


def test_registry_catalog_and_duplicates():
    cat = engine_lib.available_solvers()
    assert {"retrieval", "maxcut", "lm"} <= set(cat)
    with pytest.raises(ValueError, match="already registered"):
        engine_lib.register_solver("retrieval", lambda **kw: None)
    with pytest.raises(KeyError, match="no solver"):
        engine_lib.solver_factory("nonexistent")


def test_install_and_submit_errors():
    eng = engine_lib.Engine(jax.random.PRNGKey(9), batch_buckets=(1, 2))
    with pytest.raises(KeyError, match="no installed solver"):
        eng.submit(engine_lib.Request("nowhere", None))
    s = _solver(5, 8, max_cycles=47)
    eng.install("letters", s.as_engine_solver())
    with pytest.raises(ValueError, match="already installed"):
        eng.install("letters", s.as_engine_solver())
    # payload with the wrong N is rejected at submit, not at drain
    with pytest.raises(ValueError, match="N=9"):
        eng.submit(engine_lib.Request("letters", jnp.ones((9,), jnp.int8)))
    # more lanes than the largest batch bucket is an explicit error
    with pytest.raises(ValueError, match="lanes"):
        eng.submit(engine_lib.Request("letters", jnp.ones((3, 8), jnp.int8)))


class _ExplodingSolver:
    def lane_count(self, payload):
        return 1

    def signature(self, payload):
        return 1

    def bucket(self, signature, n_policy):
        return 1

    def solve_bucket(self, bucket_sig, payloads, keys, batch_bucket):
        raise RuntimeError("boom")

    def cost_units(self, bucket_sig, batch_bucket):
        return 1.0

    def fpga_seconds(self, bucket_sig):
        return None


def test_solver_failure_propagates_through_futures():
    eng = engine_lib.Engine(jax.random.PRNGKey(10), batch_buckets=(1,))
    eng.install("bad", _ExplodingSolver())
    fut = eng.submit(engine_lib.Request("bad", 0))
    stats = eng.drain()
    assert stats["failed"] == 1 and stats["completed"] == 0
    with pytest.raises(RuntimeError, match="boom"):
        fut.result()


def test_engine_key_split_per_request_decorrelates_maxcut():
    """Two identical max-cut submissions with no explicit keys get distinct
    engine-split subkeys (no hidden shared PRNGKey(0))."""
    eng = engine_lib.Engine(jax.random.PRNGKey(11), batch_buckets=(1,))
    eng.install("cuts", "maxcut", sweeps=4)
    adj = random_graph(jax.random.PRNGKey(12), 16, 0.5)
    f1 = eng.submit(engine_lib.Request("cuts", adj))
    f2 = eng.submit(engine_lib.Request("cuts", adj))
    eng.drain()
    # Same instance, different anneal trajectories (traces differ with
    # overwhelming probability; cut values may still coincide).
    t1, t2 = np.asarray(f1.result().trace), np.asarray(f2.result().trace)
    s1, s2 = np.asarray(f1.result().sigma), np.asarray(f2.result().sigma)
    assert not (np.array_equal(t1, t2) and np.array_equal(s1, s2))


def test_auto_flush_serves_full_buckets_on_submit():
    s = _solver(6, 8, max_cycles=53)
    eng = engine_lib.Engine(
        jax.random.PRNGKey(13), batch_buckets=(1, 2), auto_flush=True
    )
    eng.install("letters", s.as_engine_solver())
    f1 = eng.submit(engine_lib.Request("letters", _patterns(20, 1, 8)[0]))
    assert not f1.done()  # one lane < max bucket: still queued
    f2 = eng.submit(engine_lib.Request("letters", _patterns(21, 1, 8)[0]))
    assert f1.done() and f2.done()  # bucket filled → flushed inside submit


def test_stats_and_estimates():
    s = _solver(7, 16, max_cycles=59)
    eng = engine_lib.Engine(jax.random.PRNGKey(14), batch_buckets=(1, 2, 4))
    eng.install("letters", s.as_engine_solver())
    est = eng.estimate("letters", _patterns(22, 2, 16))
    assert est.source == "model" and est.seconds >= 0
    assert est.fpga_seconds is not None and est.fpga_seconds > 0
    fut = eng.submit(engine_lib.Request("letters", _patterns(23, 2, 16)))
    pending = eng.stats()["pending"]
    assert sum(v["requests"] for v in pending.values()) == 1
    stats = eng.drain()
    assert fut.done()
    assert stats["completed"] == 1 and not stats["pending"]
    warm = eng.estimate("letters", _patterns(24, 2, 16))
    assert warm.source == "ema"  # measured by the drained slab


def test_fpga_tradeoff_quotes_partitioned_design_past_the_wall():
    from repro.core import hardware_model as hw
    from repro.engine.adapters import _fpga_design_tradeoff

    bits = hw.BitConfig()
    # At the paper's capacity point the single board still fits: no K key.
    at_wall = _fpga_design_tradeoff(506, 100.0, bits, 1)
    assert at_wall["hybrid[P=1]"] is not None
    assert not any(k.startswith("hybrid[K=") for k in at_wall)
    # Past it, the non-fitting hybrid quotes its cheapest partitioned
    # sibling: rows over the fewest power-of-two boards that fit.
    past = _fpga_design_tradeoff(4096, 100.0, bits, 1)
    assert past["hybrid[P=1]"] is None
    k = hw.min_boards(4096, bits)
    quoted = past[f"hybrid[K={k},P=1]"]
    assert quoted is not None and quoted > 0
    assert quoted == pytest.approx(
        hw.partitioned_time_to_solution(4096, k, 100.0, bits)
    )


# ---------------------------------------------------------------------------
# Host results: one fetch per slab, payload shapes read on the host
# ---------------------------------------------------------------------------


def _retrieval_slab():
    """A retrieval solver, a mix of single- and multi-lane payloads that fill
    one bucket-8 slab, and each request's solve alone (a single lane as a
    batch of one, unbatched)."""
    s = _solver(8, 20, max_cycles=61)
    rng = np.random.default_rng(17)
    shapes = [(20,), (3, 20), (20,), (2, 20)]
    payloads = [rng.choice([-1, 1], shape).astype(np.int8) for shape in shapes]

    def alone(p, key):
        res = s.solve(jnp.atleast_2d(jnp.asarray(p)))
        return jax.tree.map(lambda x: x[0], res) if p.ndim == 1 else res

    return s, [(p, None) for p in payloads], alone


def _maxcut_slab():
    """A max-cut solver and graphs of two sizes padded to one N bucket."""
    s = api.MaxCutSolver(sweeps=6, replicas=2)
    root = jax.random.PRNGKey(19)
    return s, [
        (
            np.asarray(random_graph(jax.random.fold_in(root, i), n, 0.5)),
            jax.random.fold_in(root, 100 + i),
        )
        for i, n in enumerate((20, 24, 24, 20))
    ], s.solve


@pytest.mark.parametrize("make", [_retrieval_slab, _maxcut_slab], ids=["retrieval", "maxcut"])
def test_drained_slab_results_are_host_values_fetched_once(make, monkeypatch):
    """A mixed slab's results are numpy values, bit-identical in value, dtype
    and shape to solving each request alone, from one device_get per slab."""
    from repro.engine import adapters

    solver, requests, alone = make()
    eng = engine_lib.Engine(jax.random.PRNGKey(15), batch_buckets=(1, 2, 4, 8))
    eng.install("w", solver.as_engine_solver())
    futs = [eng.submit(engine_lib.Request("w", p, key=k)) for p, k in requests]
    fetches = []
    device_get = adapters.jax.device_get
    monkeypatch.setattr(
        adapters.jax, "device_get", lambda x: fetches.append(1) or device_get(x)
    )
    stats = eng.drain()
    monkeypatch.undo()
    assert stats["slabs"] == 1 and stats["completed"] == len(requests)
    assert len(fetches) == stats["slabs"]
    for (p, k), fut in zip(requests, futs):
        got, want = fut.result(), alone(p, k)
        for g, w in zip(got, want):
            assert isinstance(g, (np.ndarray, np.generic))
            w = np.asarray(w)
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(g, w)


def _retrieval_validate():
    adapter = _solver(9, 12, max_cycles=67).as_engine_solver()
    good = [(np.ones((12,), np.int8), 1, 12), (np.ones((3, 12), np.int8), 3, 12)]
    bad = [(np.ones((2, 9), np.int8), "N=9")]
    return adapter, good, bad


def _maxcut_validate():
    adapter = api.MaxCutSolver(sweeps=4).as_engine_solver()
    good = [(np.zeros((10, 10), np.int8), 1, 10), (np.zeros((24, 24), np.int8), 1, 24)]
    bad = [(np.zeros((10, 12), np.int8), "square"), (np.zeros((10,), np.int8), "square")]
    return adapter, good, bad


@pytest.mark.parametrize(
    "make", [_retrieval_validate, _maxcut_validate], ids=["retrieval", "maxcut"]
)
def test_validate_reads_payload_shapes_on_the_host(make, monkeypatch):
    """lane_count and signature read numpy payloads without a transfer: the
    same counts and signatures, and the same ValueError on a wrong shape."""
    adapter, good, bad = make()

    def no_transfer(*args, **kwargs):
        raise AssertionError("payload copied to the device")

    monkeypatch.setattr(jnp, "asarray", no_transfer)
    monkeypatch.setattr(jax, "device_put", no_transfer)
    with jax.transfer_guard_host_to_device("disallow"):
        for payload, lanes, sig in good:
            assert adapter.lane_count(payload) == lanes
            assert adapter.signature(payload) == sig
        for payload, match in bad:
            with pytest.raises(ValueError, match=match):
                adapter.signature(payload)
