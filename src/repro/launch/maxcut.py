"""Max-cut serving CLI: the oscillatory Ising machine behind ``repro.engine``.

Generates a stream of Erdős–Rényi instances, installs a batched
``repro.api.MaxCutSolver`` on a serving engine, and submits each instance
as one request.  The engine coalesces instances into shape-bucketed slabs;
the batched annealer (``repro.core.ising.solve_maxcut_batch``) runs every
slab through the configured weighted-sum backend — ``--backend hybrid
--parallel-factor P`` computes with the paper's serialized-MAC datapath,
``--hybrid-impl pallas`` with the fused pass-group kernels — with
``--replicas`` independent anneals per instance and ``--stagger-groups``
update groups per sweep (N = fully asynchronous, small K = the
parallelization trade).  Bucket padding is bit-identical on the real
vertices: the same (instance, seed) returns the same cut under every
``--n-policy``.

``--mesh BxM`` activates a :class:`repro.distributed.ShardPlan`: request
slabs split B ways over the data axis while the coupling field of every
instance is computed through the M-way row-sharded ``weighted_sum``
collective (``auto`` asks ``ft.propose_mesh``).  The legacy
``--shard-batch`` flag still works as a deprecated alias for an all-data
mesh.

Usage:
  PYTHONPATH=src python -m repro.launch.maxcut --n 128 --requests 32 \
      --backend hybrid --parallel-factor 32 --replicas 8 --stagger-groups 16
"""

from __future__ import annotations

import argparse
import contextlib
import json
import time
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.api import MaxCutSolver
from repro.core.ising import random_graph
from repro.distributed import ShardPlan
from repro.engine import DEFAULT_BATCH_BUCKETS, Engine, Request
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.retrieve import _plan_of_mesh_kwarg, resolve_plan_args


def serve_cuts(
    solver: MaxCutSolver,
    n: int,
    n_requests: int,
    edge_prob: float = 0.5,
    seed: int = 0,
    *,
    batch_buckets: Tuple[int, ...] = DEFAULT_BATCH_BUCKETS,
    n_policy: Any = "pow2",
    coalesce: bool = True,
    mesh: Optional[jax.sharding.Mesh] = None,  # deprecated: pass plan=
    plan: Optional[ShardPlan] = None,
) -> Dict[str, Any]:
    """Solve ``n_requests`` random G(n, edge_prob) instances through one engine."""
    if mesh is not None and plan is None:
        warnings.warn(
            "serve_cuts(mesh=...) is deprecated; pass plan=ShardPlan(...)",
            DeprecationWarning,
            stacklevel=2,
        )
    plan = _plan_of_mesh_kwarg(mesh, plan)
    key = jax.random.PRNGKey(seed)
    k_graphs, k_engine = jax.random.split(key)
    graph_keys = jax.random.split(k_graphs, n_requests)
    adjs = [random_graph(k, n, edge_prob) for k in graph_keys]

    rules_ctx = (
        contextlib.nullcontext() if plan is None or plan.devices == 1
        else plan.context()
    )
    eng = Engine(k_engine, batch_buckets=batch_buckets, n_policy=n_policy, coalesce=coalesce)
    eng.install("maxcut", solver.as_engine_solver())
    quote = eng.estimate("maxcut", adjs[0])

    t0 = time.perf_counter()
    with rules_ctx:
        futures = [eng.submit(Request("maxcut", a)) for a in adjs]
        stats = eng.drain()
    results = [f.result() for f in futures]
    jax.block_until_ready(results[-1].sigma)
    dt = time.perf_counter() - t0

    edges = jnp.stack([jnp.sum(jnp.triu(a, 1)) for a in adjs]).astype(jnp.float32)
    cuts = jnp.stack([r.cut_value for r in results])
    ratios = cuts / jnp.maximum(edges / 2.0, 1.0)  # vs the |E|/2 random baseline
    sweeps_run = jnp.stack([r.sweeps_run for r in results])
    return {
        "n": n,
        "edge_prob": edge_prob,
        "requests": n_requests,
        "replicas": solver.replicas,
        "stagger_groups": solver.stagger_groups,
        "backend": solver.backend,
        "mean_cut": round(float(jnp.mean(cuts)), 2),
        "mean_ratio_vs_half_edges": round(float(jnp.mean(ratios)), 4),
        "min_ratio_vs_half_edges": round(float(jnp.min(ratios)), 4),
        "mean_sweeps_run": round(float(jnp.mean(sweeps_run.astype(jnp.float32))), 2),
        "wall_s": round(dt, 3),
        "requests_per_s": round(n_requests / max(dt, 1e-9), 1),
        "estimate": {
            "seconds": round(quote.seconds, 6),
            "source": quote.source,
            "fpga_seconds": quote.fpga_seconds,
            # The paper's architecture trade, quoted per Ising request.
            "fpga_tradeoff": quote.fpga_tradeoff,
        },
        "engine": {
            "slabs": stats["slabs"],
            "pad_fraction": round(stats["pad_fraction"], 3),
            "slabs_per_bucket": stats["slabs_per_bucket"],
            "maxcut": stats["solvers"].get("maxcut", {}),
        },
        "mesh_devices": 1 if plan is None else plan.devices,
    }


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=64, help="vertices per instance")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--edge-prob", type=float, default=0.5)
    ap.add_argument("--sweeps", type=int, default=64)
    ap.add_argument("--replicas", type=int, default=4, help="independent anneals per instance")
    ap.add_argument("--stagger-groups", type=int, default=0,
                    help="update groups K per sweep (0 = auto, N = fully async)")
    ap.add_argument("--stagnation", type=int, default=0,
                    help="sweeps without improvement before a replica stops "
                         "(0 = run all sweeps)")
    ap.add_argument("--weight-bits", type=int, default=5)
    ap.add_argument("--backend", default="parallel",
                    choices=["parallel", "serial", "pallas", "hybrid"],
                    help="weighted-sum schedule for the coupling field")
    ap.add_argument("--parallel-factor", type=int, default=0,
                    help="MAC width P of --backend hybrid (0 = auto)")
    ap.add_argument("--hybrid-impl", default="scan", choices=["scan", "pallas"])
    ap.add_argument("--settle-chunk", type=int, default=8, help="sweeps between early-exit checks")
    ap.add_argument("--n-policy", default="pow2",
                    help='engine N bucketing: "pow2", "exact", or comma sizes')
    ap.add_argument("--max-batch", type=int, default=max(DEFAULT_BATCH_BUCKETS),
                    help="largest engine batch bucket")
    ap.add_argument("--no-coalesce", action="store_true",
                    help="serve each request in its own slab (latency-first)")
    ap.add_argument("--mesh", default=None, metavar="BxM",
                    help="ShardPlan mesh: B-way data-parallel instances x "
                         "M-way row-sharded coupling sum (e.g. 2x4), or "
                         "'auto' (ft.propose_mesh over the local devices)")
    ap.add_argument("--shard-batch", action="store_true",
                    help="deprecated: use --mesh Bx1; splits request slabs "
                         "over all local devices (no-op on one device)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    solver = MaxCutSolver(
        sweeps=args.sweeps,
        weight_bits=args.weight_bits,
        replicas=args.replicas,
        stagger_groups=args.stagger_groups,
        stagnation=args.stagnation,
        backend=args.backend,
        parallel_factor=args.parallel_factor,
        hybrid_impl=args.hybrid_impl,
        settle_chunk=args.settle_chunk,
    )
    policy: Any = args.n_policy
    if policy not in ("pow2", "exact"):
        policy = tuple(int(s) for s in policy.split(","))
    buckets = tuple(b for b in DEFAULT_BATCH_BUCKETS if b <= args.max_batch) or (1,)
    print(json.dumps(serve_cuts(
        solver, args.n, args.requests, args.edge_prob, args.seed,
        batch_buckets=buckets, n_policy=policy, coalesce=not args.no_coalesce,
        plan=resolve_plan_args(args.mesh, args.shard_batch),
    ), indent=1))


if __name__ == "__main__":
    main()
