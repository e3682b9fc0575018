"""ONN pattern-retrieval CLI: a thin adapter over the ``repro.engine`` engine.

Loads (or trains, via Diederich–Opper I) coupling weights for a letter
dataset into a ``repro.api.RetrievalSolver``, installs it on a serving
engine, and submits each corrupted pattern as one request.  The engine
coalesces request lanes into shape-bucketed slabs — every (N bucket, batch
bucket) compiles once, padded lanes are masked and bit-exact with unpadded
solves — and the drained results are aggregated into the paper's Fig. 7
accuracy/settle statistics.

Because the solver is the functional pytree API (weights traced, config
static), re-training or hot-swapping the weight matrix does NOT recompile
the serving executable: any same-bucket solver reuses the first compile.

Bucket solves are one call into the batched-native ``retrieve``: the slab
advances through one (B,N)×(N,N) contraction per cycle and exits as soon as
every lane settles (``--settle-chunk`` sets the check granularity).
``--mesh BxM`` activates a :class:`repro.distributed.ShardPlan` — B-way
data-parallel lanes × M-way row-sharded coupling matrix (``auto`` asks
``ft.propose_mesh``); the legacy ``--shard-batch`` recipe still works as a
deprecated alias for an all-data mesh.

Usage:
  PYTHONPATH=src python -m repro.launch.retrieve --dataset 10x10 \
      --corruption 0.25 --requests 256 --architecture hybrid --backend pallas
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import RetrievalSolver
from repro.data import patterns as pat
from repro.distributed import ShardPlan, plan_of_legacy_shard_batch
from repro.engine import DEFAULT_BATCH_BUCKETS, Engine, Request
from repro.launch.compile_cache import enable_compile_cache


def build_solver(
    dataset: str,
    architecture: str = "hybrid",
    mode: str = "functional",
    weight_bits: int = 5,
    phase_bits: int = 4,
    max_cycles: int = 100,
    backend: str = "parallel",
    settle_chunk: int = 8,
    parallel_factor: int = 0,
    hybrid_impl: str = "scan",
) -> Tuple[RetrievalSolver, jax.Array]:
    """Train a solver for one letter dataset; returns (solver, patterns)."""
    xi = pat.load_dataset(dataset)  # (P, N) ±1
    solver = RetrievalSolver.from_patterns(
        xi,
        weight_bits=weight_bits,
        phase_bits=phase_bits,
        architecture=architecture,
        mode=mode,
        max_cycles=max_cycles,
        backend=backend,
        settle_chunk=settle_chunk,
        parallel_factor=parallel_factor,
        hybrid_impl=hybrid_impl,
    )
    return solver, xi


def batch_mesh() -> Optional[jax.sharding.Mesh]:
    """Deprecated: a ("data", "model") mesh over all local devices, data-major.

    The old per-launcher sharded-retrieve recipe (lanes over every device,
    coupling matrix replicated).  Superseded by
    ``repro.distributed.ShardPlan`` — ``plan_of_legacy_shard_batch()`` is
    the equivalent plan, and ``--mesh BxM`` composes data- and
    model-parallelism.  Returns None on a single device.
    """
    devices = jax.devices()
    if len(devices) < 2:
        return None
    return jax.sharding.Mesh(
        np.asarray(devices).reshape(len(devices), 1), ("data", "model")
    )


def _plan_of_mesh_kwarg(
    mesh: Optional[jax.sharding.Mesh], plan: Optional[ShardPlan]
) -> Optional[ShardPlan]:
    """Fold the deprecated ``mesh=`` kwarg into a ShardPlan (legacy recipe)."""
    if plan is not None:
        return plan
    if mesh is None:
        return None
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    return ShardPlan(
        batch=shape.get("data", 1), model=shape.get("model", 1),
        layout="replicated",
    )


def resolve_plan_args(
    mesh_spec: Optional[str], shard_batch: bool
) -> Optional[ShardPlan]:
    """The ShardPlan implied by the ``--mesh`` / legacy ``--shard-batch`` flags."""
    if mesh_spec is not None and shard_batch:
        raise SystemExit("--mesh and --shard-batch are mutually exclusive")
    if mesh_spec is not None:
        return ShardPlan.parse(mesh_spec)
    if shard_batch:
        warnings.warn(
            "--shard-batch is deprecated; use --mesh Bx1 (or --mesh auto)",
            DeprecationWarning,
            stacklevel=2,
        )
        if jax.device_count() < 2:
            return None
        return plan_of_legacy_shard_batch()
    return None


def serve_requests(
    solver: RetrievalSolver,
    xi: jax.Array,
    corruption: float,
    n_requests: int,
    seed: int = 0,
    *,
    batch_buckets: Tuple[int, ...] = DEFAULT_BATCH_BUCKETS,
    n_policy: Any = "pow2",
    coalesce: bool = True,
    mesh: Optional[jax.sharding.Mesh] = None,  # deprecated: pass plan=
    plan: Optional[ShardPlan] = None,
) -> Dict[str, Any]:
    if mesh is not None and plan is None:
        warnings.warn(
            "serve_requests(mesh=...) is deprecated; pass plan=ShardPlan(...)",
            DeprecationWarning,
            stacklevel=2,
        )
    plan = _plan_of_mesh_kwarg(mesh, plan)
    p, n = xi.shape
    key = jax.random.PRNGKey(seed)
    k1, k2, k_engine = jax.random.split(key, 3)
    which = jax.random.randint(k1, (n_requests,), 0, p)
    targets = xi[which]
    ckeys = jax.random.split(k2, n_requests)
    corrupted = jax.vmap(lambda t, k: pat.corrupt(t, k, corruption))(targets, ckeys)

    if plan is not None:
        solver = dataclasses.replace(solver, plan=plan)
    eng = Engine(
        k_engine, batch_buckets=batch_buckets, n_policy=n_policy, coalesce=coalesce
    )
    eng.install("retrieval", solver.as_engine_solver())

    t0 = time.perf_counter()
    futures = [
        eng.submit(Request("retrieval", corrupted[i])) for i in range(n_requests)
    ]
    stats = eng.drain()
    sigma = jnp.stack([f.result().final_sigma for f in futures])
    settle_cycle = jnp.stack([f.result().settle_cycle for f in futures])
    settled = jnp.stack([f.result().settled for f in futures])
    jax.block_until_ready(sigma)
    dt = time.perf_counter() - t0

    # Phase patterns are defined up to a global flip (spin symmetry).
    out = sigma.astype(jnp.int32)
    match = jnp.all(out == targets, axis=1) | jnp.all(out == -targets, axis=1)
    acc = float(jnp.mean(match.astype(jnp.float32)))
    max_cycles = solver.config.max_cycles
    settle = float(jnp.mean(jnp.where(settled, settle_cycle, max_cycles)))
    return {
        "n_oscillators": n,
        "requests": n_requests,
        "corruption": corruption,
        "accuracy": acc,
        "mean_settle_cycles": round(settle, 2),
        "timeouts": int(jnp.sum(~settled)),
        "wall_s": round(dt, 3),
        "requests_per_s": round(n_requests / max(dt, 1e-9), 1),
        "engine": {
            "slabs": stats["slabs"],
            "pad_fraction": round(stats["pad_fraction"], 3),
            "slabs_per_bucket": stats["slabs_per_bucket"],
            # Measured settle-cycle cost model: quotes start at max_cycles
            # and tighten toward the early-exit EMA as slabs are served.
            "retrieval": stats["solvers"].get("retrieval", {}),
        },
        "mesh_devices": 1 if plan is None else plan.devices,
        "shard_plan": None if plan is None else dataclasses.asdict(plan),
    }


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dataset", default="10x10", choices=list(pat.DATASET_SHAPES))
    ap.add_argument("--architecture", default="hybrid", choices=["hybrid", "recurrent"])
    ap.add_argument("--mode", default="functional", choices=["functional", "rtl"])
    ap.add_argument("--corruption", type=float, default=0.25)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--backend", default="parallel",
                    choices=["parallel", "serial", "pallas", "hybrid"],
                    help="weighted-sum schedule for the coupling sum")
    ap.add_argument("--parallel-factor", type=int, default=0,
                    help="MAC width P of --backend hybrid: the coupling sum "
                         "serializes into ceil(N/P) passes (0 = auto)")
    ap.add_argument("--hybrid-impl", default="scan", choices=["scan", "pallas"],
                    help="execution route of --backend hybrid: lax.scan "
                         "reference or blocked pass-group Pallas kernels")
    ap.add_argument("--settle-chunk", type=int, default=8,
                    help="cycles between early-exit checks (0 = fixed scan)")
    ap.add_argument("--mesh", default=None, metavar="BxM",
                    help="ShardPlan mesh: B-way data-parallel lanes x M-way "
                         "row-sharded coupling matrix (e.g. 2x4), or 'auto' "
                         "(ft.propose_mesh over the local devices)")
    ap.add_argument("--shard-batch", action="store_true",
                    help="deprecated: use --mesh Bx1; splits request slabs "
                         "over all local devices (no-op on one device)")
    ap.add_argument("--n-policy", default="pow2",
                    help='engine N bucketing: "pow2", "exact", or comma sizes')
    ap.add_argument("--max-batch", type=int, default=max(DEFAULT_BATCH_BUCKETS),
                    help="largest engine batch bucket")
    ap.add_argument("--no-coalesce", action="store_true",
                    help="serve each request in its own slab (latency-first)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    solver, xi = build_solver(
        args.dataset, args.architecture, args.mode, backend=args.backend,
        settle_chunk=args.settle_chunk, parallel_factor=args.parallel_factor,
        hybrid_impl=args.hybrid_impl,
    )
    policy: Any = args.n_policy
    if policy not in ("pow2", "exact"):
        policy = tuple(int(s) for s in policy.split(","))
    buckets = tuple(b for b in DEFAULT_BATCH_BUCKETS if b <= args.max_batch) or (1,)
    print(json.dumps(serve_requests(
        solver, xi, args.corruption, args.requests, args.seed,
        batch_buckets=buckets, n_policy=policy, coalesce=not args.no_coalesce,
        plan=resolve_plan_args(args.mesh, args.shard_batch),
    ), indent=1))


if __name__ == "__main__":
    main()
