"""Model-parallel (row-sharded coupling matrix) multi-device tests.

Each test spawns a subprocess with ``XLA_FLAGS`` forcing 8 host devices (4
for the planned-engine tests) —
the main test process must keep seeing 1 device (see tests/test_sharding.py,
which pins that invariant).  Every subprocess prints one JSON line; the
assertions run here so failures carry readable context.

Covered (ISSUE satellite: CI-runnable multi-device coverage):
  * bit-exactness of the row-sharded ``weighted_sum`` collective vs the
    replicated path, across all three backends × mesh shapes 1×8 / 2×4 / 4×2,
    including a non-divisible N (zero-row padding inside the shard_map);
  * retrieve / run end-to-end exactness under an active ShardPlan;
  * the N = 4096 acceptance solve: row-sharded on 8 virtual devices,
    bit-exact with replicated, per-device weight bytes = 1/8 of the matrix;
  * streaming mid-flight join on a sharded slab (engine-style chunked
    advance with lanes installed while the slab is in flight);
  * the compressed int8 collectives: error-feedback round-trip of
    ``compressed_psum_mean`` under shard_map, and a bit-exact
    ``ShardPlan(compressed=True)`` solve in the small-field regime;
  * a ``RetrievalSolver`` that holds ``ShardPlan(1x4)`` installed on an
    engine: W placed in four row shards, a mixed slab bit-exact with the
    unplanned engine and the per-lane oracle, from another thread and from a
    ``ContinuousEngine`` with no ambient context, the slab span's plan
    attributes, a hot swap that keeps the row sharding, and the daemon's
    retrieval workloads holding the plan.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest


def _run_subprocess(script: str, timeout: int = 420) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_PRELUDE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core import dynamics
    from repro.core.dynamics import ONNConfig, make_params
    from repro.distributed import ShardPlan
    from repro.distributed import sharding as shard_lib

    assert jax.device_count() == 8

    def sym_weights(rng, n, lo=-15, hi=16):
        w = rng.integers(lo, hi, (n, n), dtype=np.int8)
        w = ((w + w.T) // 2).astype(np.int8)
        np.fill_diagonal(w, 0)
        return w

    def trees_equal(a, b):
        return all(
            bool((np.asarray(x) == np.asarray(y)).all()) for x, y in zip(a, b)
        )
    """
)


_EXACTNESS_SCRIPT = _PRELUDE + textwrap.dedent(
    """
    rng = np.random.default_rng(0)
    meshes = ((1, 8), (2, 4), (4, 2))
    backends = ("parallel", "pallas", "hybrid")

    # 1) weighted_sum level: every backend x mesh, divisible and non-divisible N
    ws_exact = True
    for n in (48, 50):
        w = jnp.asarray(sym_weights(rng, n))
        sigma = jnp.asarray(rng.choice([-1, 1], (6, n)).astype(np.int8))
        for backend in backends:
            cfg = ONNConfig(n=n, backend=backend, max_cycles=8)
            ref = np.asarray(dynamics.weighted_sum(cfg, w, sigma))
            for bm in meshes:
                plan = ShardPlan(batch=bm[0], model=bm[1])
                with plan.context():
                    out = np.asarray(dynamics.weighted_sum(cfg, w, sigma))
                if not (out == ref).all():
                    ws_exact = False

    # 2) retrieve level: one backend per mesh at a non-divisible N, with the
    # coupling matrix actually device_put into the plan's at-rest placement
    rt_exact = True
    n = 50
    w = jnp.asarray(sym_weights(rng, n))
    sig0 = jnp.asarray(rng.choice([-1, 1], (6, n)).astype(np.int8))
    for backend, bm in (("hybrid", (1, 8)), ("pallas", (2, 4)),
                        ("parallel", (4, 2))):
        cfg = ONNConfig(n=n, backend=backend, max_cycles=12)
        params = make_params(cfg, w)
        ref = dynamics.retrieve(cfg, params, sig0)
        plan = ShardPlan(batch=bm[0], model=bm[1])
        mesh = plan.make_mesh()
        params_s = shard_lib.shard_onn_params(params, plan, mesh)
        with plan.context(mesh):
            out = dynamics.retrieve(cfg, params_s, sig0)
        if not trees_equal(ref, out):
            rt_exact = False

    # 3) single-shot run() under an active plan
    cfg = ONNConfig(n=48, backend="parallel", max_cycles=12)
    w = jnp.asarray(sym_weights(rng, 48))
    params = make_params(cfg, w)
    ph0 = dynamics.initial_phase(
        cfg, jnp.asarray(rng.choice([-1, 1], 48).astype(np.int8))
    )
    ref = dynamics.run(cfg, params, ph0)
    with ShardPlan(batch=1, model=8).context():
        out = dynamics.run(cfg, params, ph0)
    run_exact = trees_equal(ref, out)

    print(json.dumps({
        "devices": jax.device_count(),
        "weighted_sum_exact": ws_exact,
        "retrieve_exact": rt_exact,
        "run_exact": run_exact,
    }))
    """
)


@pytest.mark.slow
def test_rowsharded_weighted_sum_bit_exact_all_backends_meshes():
    """Row-sharded collective == replicated path for every backend × mesh,
    including N = 50 (non-divisible: zero-row padded inside the shard_map)."""
    result = _run_subprocess(_EXACTNESS_SCRIPT, timeout=600)
    assert result["devices"] == 8
    assert result["weighted_sum_exact"], "weighted_sum collective diverged"
    assert result["retrieve_exact"], "retrieve under plan diverged"
    assert result["run_exact"], "run() under plan diverged"


_N4096_SCRIPT = _PRELUDE + textwrap.dedent(
    """
    rng = np.random.default_rng(2)
    n = 4096
    w = rng.integers(-15, 16, (n, n), dtype=np.int8)
    w = ((w + w.T) // 2).astype(np.int8)
    np.fill_diagonal(w, 0)
    cfg = ONNConfig(n=n, backend="parallel", max_cycles=5, settle_chunk=0)
    params = make_params(cfg, jnp.asarray(w))
    sig0 = jnp.asarray(rng.choice([-1, 1], (2, n)).astype(np.int8))
    ref = dynamics.retrieve(cfg, params, sig0)

    plan = ShardPlan(batch=1, model=8)
    mesh = plan.make_mesh()
    params_s = shard_lib.shard_onn_params(params, plan, mesh)
    shard_bytes = sorted(
        s.data.nbytes for s in params_s.weights.addressable_shards
    )
    with plan.context(mesh):
        out = dynamics.retrieve(cfg, params_s, sig0)

    print(json.dumps({
        "devices": jax.device_count(),
        "exact": trees_equal(ref, out),
        "n_shards": len(shard_bytes),
        "max_shard_bytes": shard_bytes[-1],
        "full_bytes": int(np.asarray(params.weights).nbytes),
    }))
    """
)


@pytest.mark.slow
def test_n4096_retrieval_rowsharded_bit_exact():
    """The wall-breaker acceptance point: N = 4096 retrieval, coupling matrix
    row-sharded 8 ways, bit-exact with replicated and 1/8 weight bytes/device."""
    result = _run_subprocess(_N4096_SCRIPT, timeout=600)
    assert result["devices"] == 8
    assert result["exact"], "N=4096 row-sharded retrieve diverged from replicated"
    assert result["n_shards"] == 8
    assert result["max_shard_bytes"] == result["full_bytes"] // 8


_STREAMING_SCRIPT = _PRELUDE + textwrap.dedent(
    """
    from repro.core import ising

    rng = np.random.default_rng(1)

    # 1) ising maxcut batch (vmap over the shard_map collective)
    n, b = 48, 3
    adjs = (rng.random((b, n, n)) < 0.3).astype(np.int8)
    adjs = np.triu(adjs, 1)
    adjs = adjs + adjs.transpose(0, 2, 1)
    cfg = ONNConfig(n=n, backend="parallel", max_cycles=8)
    keys = jax.random.split(jax.random.PRNGKey(0), b)
    ref = ising.solve_maxcut_batch(cfg, jnp.asarray(adjs), keys, replicas=2)
    with ShardPlan(batch=2, model=4).context():
        out = ising.solve_maxcut_batch(cfg, jnp.asarray(adjs), keys, replicas=2)
    ising_exact = trees_equal(ref, out)

    # 2) streaming mid-flight join on a sharded slab
    n = 64
    w = jnp.asarray(sym_weights(rng, n))
    cfg = ONNConfig(n=n, backend="pallas", max_cycles=24, settle_chunk=4)
    params = make_params(cfg, w)
    sig = jnp.asarray(rng.choice([-1, 1], (8, n)).astype(np.int8))
    ph = dynamics.initial_phase(cfg, sig)
    ref = dynamics.retrieve(cfg, params, sig)

    plan = ShardPlan(batch=2, model=4)
    mesh = plan.make_mesh()
    params_s = shard_lib.shard_onn_params(params, plan, mesh)
    with plan.context(mesh):
        state = dynamics.init_batch_state(cfg, ph[:4])
        state = dynamics.install_lanes(
            dynamics.dead_batch_state(cfg, 8), state, jnp.arange(4)
        )
        state = dynamics.advance_chunk(cfg, params_s, state)
        late = dynamics.init_batch_state(cfg, ph[4:])
        state = dynamics.install_lanes(state, late, jnp.arange(4, 8))
        for _ in range(12):
            state = dynamics.advance_chunk(cfg, params_s, state)
        done = dynamics.batch_done(cfg, state)
        res = dynamics.batch_result(cfg, state)

    print(json.dumps({
        "devices": jax.device_count(),
        "ising_exact": ising_exact,
        "all_done": bool(np.asarray(done).all()),
        "join_exact": trees_equal(ref, res),
    }))
    """
)


@pytest.mark.slow
def test_streaming_midflight_join_on_sharded_slab():
    """Engine-style chunked slab with lanes joining mid-flight, coupling
    matrix row-sharded: every lane bit-exact with the one-shot solve; plus
    the vmapped Ising path under the same plan."""
    result = _run_subprocess(_STREAMING_SCRIPT, timeout=600)
    assert result["devices"] == 8
    assert result["ising_exact"], "ising batch under plan diverged"
    assert result["all_done"], "sharded slab failed to settle"
    assert result["join_exact"], "mid-flight join diverged from one-shot solve"


_COMPRESSED_SCRIPT = _PRELUDE + textwrap.dedent(
    """
    import functools
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.optim import compress

    # 1) error-feedback round-trip of the gradient collective on the 8-way
    # mesh: the EF telescoping identity — summed over shards AND steps, the
    # decoded means (x n_dev) plus the final residuals reconstruct the raw
    # gradients (quantization error never accumulates, it only carries).
    mesh = jax.make_mesh((8,), ("data",), (AxisType.Auto,))
    fn = jax.jit(jax.shard_map(
        functools.partial(compress.compressed_psum_mean, axis_name="data"),
        mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P("data")),
    ))
    rng = np.random.default_rng(3)
    grads = [jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))
             for _ in range(4)]
    err = jnp.zeros((8, 64), jnp.float32)
    decoded_sum = jnp.zeros((64,), jnp.float32)
    for g in grads:
        mean, err = fn(g, err)
        decoded_sum = decoded_sum + mean[0] * 8.0
    raw_total = sum(grads).sum(axis=0)
    resid = float(jnp.max(jnp.abs(decoded_sum + err.sum(axis=0) - raw_total)))
    ef_ok = resid < 1e-3

    # 2) compressed inference wire: ShardPlan(compressed=True) solve is
    # bit-exact in the small-field regime (weight_bits=2 -> |S| <= 127)
    cfg = ONNConfig(n=40, weight_bits=2, backend="parallel", max_cycles=12)
    w = rng.integers(-1, 2, (40, 40)).astype(np.int8)
    np.fill_diagonal(w, 0)
    params = make_params(cfg, jnp.asarray(w))
    s0 = jnp.asarray(rng.choice([-1, 1], (4, 40)).astype(np.int8))
    ref = dynamics.retrieve(cfg, params, s0)
    with ShardPlan(batch=2, model=4, compressed=True).context():
        out = dynamics.retrieve(cfg, params, s0)
    solve_ok = trees_equal(ref, out)

    print(json.dumps({
        "devices": jax.device_count(),
        "ef_residual": resid,
        "ef_roundtrip_ok": ef_ok,
        "compressed_solve_exact": solve_ok,
    }))
    """
)


@pytest.mark.slow
def test_compressed_collectives_roundtrip():
    """int8 wire format on the 8-device mesh: error-feedback round-trip of
    the gradient psum-mean, and a bit-exact compressed-plan inference solve."""
    result = _run_subprocess(_COMPRESSED_SCRIPT, timeout=600)
    assert result["devices"] == 8
    assert result["ef_roundtrip_ok"], (
        f"EF telescoping identity violated: residual {result['ef_residual']}"
    )
    assert result["compressed_solve_exact"], "compressed-plan solve diverged"


# ---------------------------------------------------------------------------
# The plan held by an installed solver (engine path, four devices)
# ---------------------------------------------------------------------------

_PLANNED_ENGINE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import json
    import threading
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro import api
    from repro.core import dynamics
    from repro.core.learning import hebbian
    from repro.core.quantization import quantize_weights
    from repro.distributed import ShardPlan
    from repro.distributed import sharding as shard_lib
    from repro.engine import Engine, Request, engine as engine_mod, spans
    from repro.serving import ContinuousEngine

    assert jax.device_count() == 4
    N, P_STORED = 512, 16
    rng = np.random.default_rng(16)

    def library(seed):
        xi = np.where(np.random.default_rng(seed).random((P_STORED, N)) < 0.5, 1, -1)
        w = quantize_weights(hebbian(jnp.asarray(xi, jnp.int8), self_coupling=False), 5)
        return xi.astype(np.int8), w.values

    def probes(xi, lanes):
        clean = xi[rng.integers(0, P_STORED, lanes)]
        flip = rng.random((lanes, N)) < 0.15
        return np.where(flip, -clean, clean).astype(np.int8)

    def same(a, b):
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        return len(la) == len(lb) and all(
            x.shape == y.shape and x.dtype == y.dtype and np.array_equal(x, y)
            for x, y in zip(map(np.asarray, la), map(np.asarray, lb)))

    def all_same(xs, ys):
        return len(xs) == len(ys) and all(same(x, y) for x, y in zip(xs, ys))

    def shard_bytes(params):
        shards = params.weights.addressable_shards
        return len({s.device for s in shards}), sorted(int(s.data.nbytes) for s in shards)

    def row_sharded(params):
        return params.weights.sharding.spec == jax.sharding.PartitionSpec("model", None)

    xi, w = library(0)
    cfg = api.ONNConfig(n=N, backend="pallas", max_cycles=40)
    plan = ShardPlan(batch=1, model=4)
    plain = api.RetrievalSolver(config=cfg, params=api.make_params(cfg, w))
    planned = dataclasses.replace(plain, plan=plan)
    payloads = [probes(xi, 1)[0], probes(xi, 8), probes(xi, 1)[0]]

    attrs = []
    orig_span = spans.span

    def spy(name, **kw):
        if name == spans.SLAB:
            attrs.append(kw)
        return orig_span(name, **kw)

    engine_mod.spans.span = spy

    # Trace-time count of the row-sharded collective: a solve traced outside
    # the plan's context never reaches it.
    collective_traces = [0]
    orig_collective = dynamics._model_sharded_sum

    def counted(*args, **kw):
        collective_traces[0] += 1
        return orig_collective(*args, **kw)

    dynamics._model_sharded_sum = counted

    def drained(solver, from_thread=False):
        eng = Engine(jax.random.PRNGKey(0))
        adapter = eng.install("mem", solver.as_engine_solver())
        futs = [eng.submit(Request("mem", p)) for p in payloads]
        if from_thread:
            seen = {}
            def work():
                seen["plan"] = shard_lib.current_plan()
                eng.drain()
            t = threading.Thread(target=work)
            t.start()
            t.join()
            assert seen["plan"] is None
        else:
            eng.drain()
        return [f.result() for f in futs], adapter, eng

    unplanned, _, _ = drained(plain)
    attrs_unplanned = list(attrs)
    traced = [collective_traces[0]]
    got, adapter, eng = drained(planned)
    attrs_planned = attrs[len(attrs_unplanned):]
    traced.append(collective_traces[0])
    retrieves = dynamics.TRACE_COUNTER["retrieve"]
    threaded, thread_adapter, _ = drained(planned, from_thread=True)
    thread_retraced = dynamics.TRACE_COUNTER["retrieve"] - retrieves

    oracle_cfg = api.ONNConfig(n=N, backend="parallel", max_cycles=40)
    oracle_params = api.make_params(oracle_cfg, w)
    oracle = []
    for p in payloads:
        lanes = [dynamics.run(oracle_cfg, oracle_params, dynamics.initial_phase(oracle_cfg, jnp.asarray(s)))
                 for s in np.atleast_2d(p)]
        r = jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]), *lanes)
        oracle.append(jax.tree.map(lambda x: x[0], r) if p.ndim == 1 else r)

    solve_exact = same(planned.solve(jnp.asarray(payloads[1])), plain.solve(jnp.asarray(payloads[1])))

    ceng = ContinuousEngine(jax.random.PRNGKey(0), slab_lanes=16)
    cadapter = ceng.install("mem", planned.as_engine_solver())
    cfuts = [ceng.submit(Request("mem", p)) for p in payloads]
    ceng.flush()
    continuous = [f.result() for f in cfuts]
    traced.append(collective_traces[0])

    xi2, w2 = library(1)
    eng.hot_swap("mem", api.make_params(cfg, w2))
    _, params_b = adapter._padded_instance(N)
    futs = [eng.submit(Request("mem", p)) for p in payloads]
    eng.drain()
    swapped = [f.result() for f in futs]
    plain2 = api.RetrievalSolver(config=cfg, params=api.make_params(cfg, w2))
    expect2, _, _ = drained(plain2)

    # The daemon's workloads: both retrieval solvers hold the plan.
    from repro import serving

    def served_mixed(p):
        ce = ContinuousEngine(jax.random.PRNGKey(0), slab_lanes=16)
        serving.install_mixed_workloads(ce, plan=p)
        reqs = [r for r in serving.mixed_requests(8, seed=0) if r.workload != "cuts"]
        futs = [ce.submit(r) for r in reqs]
        ce.flush()
        return [f.result() for f in futs], ce

    mixed_plain, _ = served_mixed(None)
    mixed_planned, mixed_eng = served_mixed(plan)
    mixed_adapters = [mixed_eng.solver(name) for name in ("small", "large")]

    print(json.dumps({
        "mixed_exact": all_same(mixed_planned, mixed_plain),
        "mixed_held": [a._plan == plan for a in mixed_adapters],
        "mixed_sharded_slabs": [a.stats()["sharded_slabs"] for a in mixed_adapters],
        "mixed_row_sharded": [sorted((nb, row_sharded(pb)) for nb, (_, pb) in a._padded.items())
                              for a in mixed_adapters],
        "engine_exact": all_same(got, unplanned),
        "oracle_exact": all_same(got, oracle),
        "solve_exact": solve_exact,
        "thread_exact": all_same(threaded, got),
        "continuous_exact": all_same(continuous, got),
        "shards": shard_bytes(adapter.solver.params),
        "collective_traces": traced,
        "thread_retraced": thread_retraced,
        "sharded_slabs": [adapter.stats()["sharded_slabs"], thread_adapter.stats()["sharded_slabs"],
                          cadapter.stats()["sharded_slabs"]],
        "unplanned_attrs": [sorted(a) for a in attrs_unplanned],
        "planned_attrs": [{k: a[k] for k in ("plan", "devices", "w_bytes_per_device",
                                             "combine_bytes_per_cycle", "width")}
                          for a in attrs_planned],
        "swap_row_sharded": [row_sharded(adapter.solver.params), row_sharded(params_b)],
        "swap_shards": shard_bytes(params_b),
        "swap_exact": all_same(swapped, expect2),
    }))
    """
)

_N_PLANNED = 512


@pytest.fixture(scope="module")
def planned_engine():
    return _run_subprocess(_PLANNED_ENGINE_SCRIPT, timeout=600)


def test_planned_engine_slab_matches_unplanned_and_oracle(planned_engine):
    """A drained slab mixing 1- and 8-lane requests through a solver that
    holds ShardPlan(1x4) is bit-identical to the unplanned engine and to the
    per-lane ``dynamics.run`` oracle; ``RetrievalSolver.solve`` under its own
    plan matches the unplanned solve."""
    assert planned_engine["engine_exact"]
    assert planned_engine["oracle_exact"]
    assert planned_engine["solve_exact"]


def test_planned_solver_places_w_in_four_row_shards(planned_engine):
    devices, per_device = planned_engine["shards"]
    assert devices == 4
    assert per_device == [_N_PLANNED * _N_PLANNED // 4] * 4


def test_planned_drain_from_another_thread_and_continuous_slab(planned_engine):
    """No ambient context is needed: a drain on a fresh thread and a
    ContinuousEngine slab solve sharded, with the same results."""
    assert planned_engine["thread_exact"]
    assert planned_engine["continuous_exact"]
    assert all(c >= 1 for c in planned_engine["sharded_slabs"])
    # The unplanned solve never traced the collective; the planned drain
    # did; the thread's drain reused that executable (the same plan
    # context in its cache key); the continuous slab traced it again.
    unplanned, planned, continuous = planned_engine["collective_traces"]
    assert unplanned == 0 and planned > 0 and continuous > planned
    assert planned_engine["thread_retraced"] == 0


def test_planned_slab_span_names_the_plan(planned_engine):
    """``engine.slab`` carries the plan's attributes on sharded slabs only."""
    assert all("plan" not in a for a in planned_engine["unplanned_attrs"])
    attrs = planned_engine["planned_attrs"]
    assert attrs and all(
        a["plan"] == "1x4" and a["devices"] == 4
        and a["w_bytes_per_device"] == _N_PLANNED * _N_PLANNED // 4
        and a["combine_bytes_per_cycle"] == a["width"] * _N_PLANNED * 4
        for a in attrs
    )


def test_planned_hot_swap_keeps_row_sharding(planned_engine):
    assert planned_engine["swap_row_sharded"] == [True, True]
    devices, per_device = planned_engine["swap_shards"]
    assert devices == 4 and per_device == [_N_PLANNED * _N_PLANNED // 4] * 4
    assert planned_engine["swap_exact"]


def test_daemon_retrieval_workloads_hold_the_plan(planned_engine):
    """``serving.install_mixed_workloads(plan=)``, the daemon's installer,
    gives both retrieval solvers the plan: their padded couplings sit in row
    shards, their slabs solve sharded, and the results match the unplanned
    workloads bit for bit."""
    assert planned_engine["mixed_held"] == [True, True]
    for buckets in planned_engine["mixed_row_sharded"]:  # each N bucket a slab used
        assert buckets and all(nb % 4 == 0 and placed for nb, placed in buckets)
    assert all(c >= 1 for c in planned_engine["mixed_sharded_slabs"])
    assert planned_engine["mixed_exact"]
