#!/usr/bin/env python3
"""Smoke run of the ONN system's main paths on a TPU.

Drives the chip once through the entry points a user calls, at the paper's
full width, and checks every result bit for bit against the plain
``jax.numpy`` route (``backend="parallel"``) run on the same chip:

* engine retrieval — ``RetrievalSolver.from_patterns`` on the 22x22 letter
  set (N=484, the multi-cycle kernel route) installed on an
  ``engine.Engine``, 64 requests at 10% corruption, drained;
* the paper's headline design — ``api.retrieve`` with the hybrid backend,
  P=32 MAC lanes, N=506, 128 lanes, seeded symmetric 5-bit weights;
* Ising — ``api.MaxCutSolver`` (pallas, 8 replicas) at N=506 on seeded
  graphs, submitted through the engine;
* continuous serving — a handful of the retrieval requests served through
  a ``serving.ContinuousEngine`` tick loop.

With ``--chips 4`` it runs only the row-sharded solve: a ``RetrievalSolver``
holding ``ShardPlan(batch=1, model=4)``, installed on an engine, at N=4096
and at N=506 (zero-row-padded route), compared with the unsharded solve in
the same process.

Each phase prints one ``smoke-phase`` line with its compile and run seconds
(smoke timings from the host clock around ``block_until_ready``, not
benchmark metrics).  The last line is ``{"ok": true, "device": {...}}``; it
is printed only when every check passed.  Without a TPU the script exits
non-zero before any phase runs.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # the sharded solve on four chips
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402 — the imports below need the src/ path above
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import api  # noqa: E402
from repro.core import dynamics, ising  # noqa: E402
from repro.data import patterns as pat  # noqa: E402
from repro.distributed import ShardPlan  # noqa: E402
from repro.engine import Engine, Request, bucketing  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving import ContinuousEngine  # noqa: E402

SEED = 0
RETRIEVAL_DATASET = "22x22"
CORRUPTION = 0.10
RETRIEVAL_REQUESTS = 64
CONTINUOUS_REQUESTS = 8
MAXCUT_N = 506
MAXCUT_REQUESTS = 4
MAXCUT_REPLICAS = 8
HYBRID_N = 506
HYBRID_LANES = 128
SHARDED_NS = (4096, 506)
SHARDED_LANES = 64


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def timed(fn):
    """(result, seconds) of ``fn()``, waiting for every device array."""
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def cold_and_warm(fn):
    """Run ``fn`` twice; the first call compiles.  Returns (result, compile_s, run_s)."""
    _, first = timed(fn)
    out, second = timed(fn)
    return out, max(first - second, 0.0), second


def same(a, b) -> bool:
    """Bit-for-bit equality of two result pytrees (every field)."""
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.shape == y.shape and x.dtype == y.dtype and np.array_equal(x, y)
        for x, y in zip(map(np.asarray, la), map(np.asarray, lb))
    )


def has_kernel(fn, *args) -> bool:
    """Whether the compiled program of ``fn(*args)`` contains a Pallas kernel."""
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def engine_instance(solver):
    """(config, params) of a retrieval solver padded to its engine bucket."""
    n_b = bucketing.bucket_n(solver.config.n)
    return (
        dynamics.pad_config(solver.config, n_b),
        dynamics.pad_params(solver.config, solver.params, n_b),
    )


def report(phase: str, **fields) -> None:
    print("smoke-phase " + json.dumps({"phase": phase, **fields}), flush=True)


def seeded_weights(n: int, seed: int):
    """Symmetric zero-diagonal 5-bit couplings in [-15, 15]."""
    rng = np.random.default_rng(seed)
    w = rng.integers(-15, 16, (n, n), dtype=np.int8)
    w = ((w.astype(np.int16) + w.T) // 2).astype(np.int8)
    np.fill_diagonal(w, 0)
    return jnp.asarray(w)


def seeded_spins(n: int, lanes: int, seed: int):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.choice(np.array([-1, 1], np.int8), (lanes, n)))


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------


def retrieval_requests(xi, n_requests: int):
    """(targets, corrupted) drawn as ``launch.retrieve.serve_requests`` does."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(SEED))
    which = jax.random.randint(k1, (n_requests,), 0, xi.shape[0])
    targets = xi[which]
    ckeys = jax.random.split(k2, n_requests)
    corrupted = jax.vmap(lambda t, k: pat.corrupt(t, k, CORRUPTION))(targets, ckeys)
    return targets, corrupted


def accuracy(results, targets) -> float:
    """Fraction retrieved exactly, up to the global spin flip."""
    out = np.asarray(results.final_sigma).astype(np.int32)
    tgt = np.asarray(targets).astype(np.int32)
    match = (out == tgt).all(axis=1) | (out == -tgt).all(axis=1)
    return float(match.mean())


def engine_solve(workload: str, engine_solver, payloads):
    """Submit every payload to a fresh ``Engine``, drain, stack the results."""
    eng = Engine(jax.random.PRNGKey(SEED + 1))
    eng.install(workload, engine_solver)
    futures = [eng.submit(Request(workload, p)) for p in payloads]
    eng.drain()
    return jax.tree.map(lambda *xs: jnp.stack(xs), *[f.result() for f in futures])


def phase_engine_retrieval(solver, xi):
    targets, corrupted = retrieval_requests(xi, RETRIEVAL_REQUESTS)
    payloads = list(corrupted)
    res, compile_s, run_s = cold_and_warm(
        lambda: engine_solve("retrieval", solver.as_engine_solver(), payloads)
    )
    parallel = dataclasses.replace(
        solver, config=dataclasses.replace(solver.config, backend="parallel")
    )
    ref = engine_solve("retrieval", parallel.as_engine_solver(), payloads)
    check(same(res, ref), "engine retrieval: pallas results differ from parallel")
    # The engine pads N to its pow2 bucket and coalesces the requests into
    # one slab; compile that solve to see the kernel in it.
    cfg_b, params_b = engine_instance(solver)
    slab = jnp.ones((RETRIEVAL_REQUESTS, cfg_b.n), jnp.int8)
    kernel = has_kernel(lambda p, s: api.retrieve(cfg_b, p, s), params_b, slab)
    check(kernel, "engine retrieval: no tpu_custom_call in the compiled solve")
    acc = accuracy(res, targets)
    check(acc >= 0.9, f"engine retrieval: accuracy {acc} < 0.9 at 10% corruption")
    report("engine_retrieval", n=solver.config.n, requests=RETRIEVAL_REQUESTS,
           smoke_compile_s=compile_s, smoke_run_s=run_s, accuracy=acc,
           bit_exact_vs_parallel=True, tpu_custom_call=kernel)
    return targets, corrupted, ref


def phase_continuous(solver, targets, corrupted, engine_ref):
    count = CONTINUOUS_REQUESTS

    def serve():
        ceng = ContinuousEngine(jax.random.PRNGKey(SEED + 2), slab_lanes=count)
        ceng.install("retrieval", solver.as_engine_solver())
        futures = [ceng.submit(Request("retrieval", corrupted[i])) for i in range(count)]
        ticks = 0
        while not ceng.idle:
            ceng.step()
            ticks += 1
            check(ticks <= 10 * solver.config.max_cycles, "continuous serving never drained")
        return jax.tree.map(lambda *xs: jnp.stack(xs), *[f.result() for f in futures])

    res, compile_s, run_s = cold_and_warm(serve)
    ref = jax.tree.map(lambda x: x[:count], engine_ref)
    check(same(res, ref), "continuous serving: results differ from the parallel engine")
    cfg_b, params_b = engine_instance(solver)
    state = dynamics.dead_batch_state(cfg_b, count)
    kernel = has_kernel(lambda p, s: dynamics.advance_chunk(cfg_b, p, s), params_b, state)
    check(kernel, "continuous serving: no tpu_custom_call in the settle-chunk advance")
    report("continuous_serving", n=solver.config.n, requests=count,
           smoke_compile_s=compile_s, smoke_run_s=run_s,
           accuracy=accuracy(res, targets[:count]),
           bit_exact_vs_parallel=True, tpu_custom_call=kernel)


def phase_hybrid():
    cfg = api.ONNConfig(n=HYBRID_N, backend="hybrid", parallel_factor=32, hybrid_impl="pallas")
    ref_cfg = api.ONNConfig(n=HYBRID_N, backend="parallel")
    params = api.make_params(cfg, seeded_weights(HYBRID_N, SEED))
    sigma = seeded_spins(HYBRID_N, HYBRID_LANES, SEED + 3)
    res, compile_s, run_s = cold_and_warm(lambda: api.retrieve(cfg, params, sigma))
    ref = api.retrieve(ref_cfg, params, sigma)
    check(same(res, ref), "hybrid P=32: results differ from parallel")
    kernel = has_kernel(lambda p, s: api.retrieve(cfg, p, s), params, sigma)
    check(kernel, "hybrid P=32: no tpu_custom_call in the compiled solve")
    # Random couplings store no patterns, so there is no accuracy to report;
    # the settled and period-2 fractions show where the dynamics ended.
    report("hybrid_p32", n=HYBRID_N, lanes=HYBRID_LANES, parallel_factor=32,
           smoke_compile_s=compile_s, smoke_run_s=run_s, accuracy=None,
           settled_fraction=float(res.settled.mean()),
           cycled_fraction=float(res.cycled.mean()),
           bit_exact_vs_parallel=True, tpu_custom_call=kernel)


def phase_maxcut():
    keys = jax.random.split(jax.random.PRNGKey(SEED + 4), MAXCUT_REQUESTS)
    graphs = [ising.random_graph(k, MAXCUT_N, 0.1) for k in keys]
    solver = api.MaxCutSolver(backend="pallas", replicas=MAXCUT_REPLICAS)
    ref_solver = api.MaxCutSolver(backend="parallel", replicas=MAXCUT_REPLICAS)
    res, compile_s, run_s = cold_and_warm(
        lambda: engine_solve("maxcut", solver.as_engine_solver(), graphs)
    )
    ref = engine_solve("maxcut", ref_solver.as_engine_solver(), graphs)
    check(same(res, ref), "max-cut: pallas results differ from parallel")
    exact = np.asarray(jax.vmap(ising.cut_value_exact)(np.stack(graphs), res.sigma))
    check(np.array_equal(exact, np.asarray(res.cut_value)),
          "max-cut: reported cut differs from the cut of the returned spins")
    half_edges = np.asarray([float(np.triu(np.asarray(g), 1).sum()) / 2 for g in graphs])
    ratio = np.asarray(res.cut_value) / half_edges
    check(bool((ratio > 1.0).all()), f"max-cut: cut not above |E|/2 ({ratio})")
    # The engine pads N to its pow2 bucket; compile that slab's solve.
    n_b = bucketing.bucket_n(MAXCUT_N)
    cfg_b = solver.config(n_b)
    adjs = np.zeros((MAXCUT_REQUESTS, n_b, n_b), np.int8)
    kernel = has_kernel(
        lambda a, k: api.solve_maxcut_batch(cfg_b, a, k, replicas=MAXCUT_REPLICAS),
        adjs, keys,
    )
    check(kernel, "max-cut: no tpu_custom_call in the compiled anneal")
    report("maxcut", n=MAXCUT_N, requests=MAXCUT_REQUESTS, replicas=MAXCUT_REPLICAS,
           smoke_compile_s=compile_s, smoke_run_s=run_s, accuracy=None,
           mean_cut_over_half_edges=float(ratio.mean()),
           bit_exact_vs_parallel=True, tpu_custom_call=kernel)


def one_chip_phases() -> None:
    xi = pat.load_dataset(RETRIEVAL_DATASET)
    solver = api.RetrievalSolver.from_patterns(xi, backend="pallas")
    targets, corrupted, engine_ref = phase_engine_retrieval(solver, xi)
    phase_hybrid()
    phase_maxcut()
    phase_continuous(solver, targets, corrupted, engine_ref)


# ---------------------------------------------------------------------------
# Four-chip phase
# ---------------------------------------------------------------------------


def sharded_phase(chips: int) -> None:
    plan = ShardPlan(batch=1, model=chips)
    for n in SHARDED_NS:
        cfg = api.ONNConfig(n=n, backend="pallas")
        solver = api.RetrievalSolver(config=cfg, params=api.make_params(cfg, seeded_weights(n, n)))
        sigma = seeded_spins(n, SHARDED_LANES, n + 1)
        ref, ref_compile_s, ref_run_s = cold_and_warm(lambda: solver.solve(sigma))
        # The planned solver on an engine: the adapter places W and runs
        # every solve under the plan; nothing here enters the plan's context.
        engine = Engine(jax.random.PRNGKey(SEED), n_policy="exact")
        adapter = engine.install(
            "retrieval", dataclasses.replace(solver, plan=plan).as_engine_solver())

        def served():
            fut = engine.submit(Request("retrieval", sigma))
            engine.drain()
            return fut.result()

        res, compile_s, run_s = cold_and_warm(served)
        check(same(res, ref), f"sharded N={n}: results differ from the unsharded solve")
        check(adapter.stats()["sharded_slabs"] == 2, f"sharded N={n}: slabs not solved sharded")
        weights = adapter.solver.params.weights
        with plan.context():
            kernel = has_kernel(lambda p, s: api.retrieve(cfg, p, s), adapter.solver.params, sigma)
        check(kernel, f"sharded N={n}: no tpu_custom_call in the compiled solve")
        shards = weights.addressable_shards
        devices = {s.device for s in shards}
        full = int(np.asarray(solver.params.weights).nbytes)
        per_device = sorted(int(s.data.nbytes) for s in shards)
        if n % chips == 0:
            check(len(devices) == chips, f"sharded N={n}: weights on {len(devices)} devices")
            check(per_device == [full // chips] * chips,
                  f"sharded N={n}: shard bytes {per_device} != {full // chips} each")
        report("sharded_retrieval", n=n, lanes=SHARDED_LANES, mesh=f"1x{chips}",
               smoke_compile_s=compile_s, smoke_run_s=run_s,
               unsharded_smoke_compile_s=ref_compile_s, unsharded_smoke_run_s=ref_run_s,
               weight_devices=len(devices), weight_shard_bytes=per_device,
               full_weight_bytes=full, bit_exact_vs_unsharded=True, tpu_custom_call=kernel)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the row-sharded solve")
    args = ap.parse_args(argv)

    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r} "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    try:
        if args.chips == 1:
            one_chip_phases()
        else:
            sharded_phase(args.chips)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
