"""Model-parallel sharding: wall clock + per-device weight memory vs replicated.

The single-device wall for the fully connected ONN is the (N, N) coupling
matrix: at N = 506 (the paper's largest board) the weights already dominate
FPGA block RAM, and past it one device simply cannot hold W.  The
``repro.distributed.ShardPlan`` row-shards W over the ``"model"`` mesh axis
and turns ``weighted_sum`` into a psum-of-row-blocks collective — this bench
measures what that buys and what it costs on an 8-virtual-device host mesh:

* ``replicated_s`` / ``sharded_s`` — best-of-trials retrieve wall clock for
  a fixed-cycle slab solve, replicated vs row-sharded (the collective tax;
  on one physical CPU the 8 "devices" share cores, so sharded wall clock is
  an overhead measure, not a speedup claim).
* ``per_device_weight_mb`` vs ``full_weight_mb`` — the at-rest coupling
  bytes each device holds: ~1/model of the matrix when N divides the model
  degree (``memory_headroom_x`` stamps the ratio).  This is the number that
  breaks the N = 506 wall.

N ∈ {506, 1024, 4096}.  506 does not divide 8, so it runs on a 4×2 mesh
(model degree 2, 253 rows/device); 1024 and 4096 run 1×8.  Every sharded
solve is asserted bit-exact against its replicated reference before being
timed — a wrong fast collective never lands in the JSON.

The bench runs its measurements in a child process pinned to the CPU
(``JAX_PLATFORMS=cpu``) with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``, so the parent
(benchmarks/run.py, check_regression.py, pytest) keeps its own jax runtime
untouched and the child never contends for an accelerator the parent holds.
Its numbers are a CPU correctness and collective-overhead run, not device
times.

  PYTHONPATH=src python -m benchmarks.sharding                      # full
  PYTHONPATH=src python -m benchmarks.sharding --smoke --out BENCH_sharding.json
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import Any, Dict, List, Optional

#: (n, mesh spec) design points; the mesh at each N is the largest model
#: degree on 8 devices that divides N (even NamedSharding at rest).
DESIGN_POINTS = ((506, "4x2"), (1024, "1x8"), (4096, "1x8"))


def _child_main(smoke: bool) -> None:
    """Measure on 8 forced host devices; print one JSON line (child only)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import calibration
    from repro.core import dynamics
    from repro.distributed import ShardPlan
    from repro.distributed import sharding as shard_lib

    if jax.default_backend() != "cpu" or jax.device_count() != 8:
        raise RuntimeError("sharding child must see the 8-device CPU host mesh")
    max_cycles = 4 if smoke else 16
    trials = 3 if smoke else 7
    lanes = 4 if smoke else 8

    rows: List[Dict[str, Any]] = []
    with calibration.window() as cal:
        for n, mesh_spec in DESIGN_POINTS:
            before = cal.sample()
            rng = np.random.default_rng(n)
            w = rng.integers(-15, 16, (n, n), dtype=np.int8)
            w = ((w + w.T) // 2).astype(np.int8)
            np.fill_diagonal(w, 0)
            cfg = dynamics.ONNConfig(
                n=n, backend="parallel", max_cycles=max_cycles, settle_chunk=0
            )
            params = dynamics.make_params(cfg, jnp.asarray(w))
            sig0 = jnp.asarray(rng.choice([-1, 1], (lanes, n)).astype(np.int8))

            plan = ShardPlan.parse(mesh_spec)
            mesh = plan.make_mesh()
            params_s = shard_lib.shard_onn_params(params, plan, mesh)
            per_device = max(
                s.data.nbytes for s in params_s.weights.addressable_shards
            )
            full = int(np.asarray(params.weights).nbytes)

            ref = dynamics.retrieve(cfg, params, sig0)
            with plan.context(mesh):
                out = dynamics.retrieve(cfg, params_s, sig0)
            exact = all(
                bool((np.asarray(a) == np.asarray(b)).all())
                for a, b in zip(ref, out)
            )
            if not exact:
                raise RuntimeError(
                    f"N={n} mesh={mesh_spec}: sharded solve diverged from "
                    "replicated — refusing to time a wrong collective"
                )

            replicated_s = calibration.time_best(
                lambda: dynamics.retrieve(cfg, params, sig0), trials
            )
            with plan.context(mesh):
                sharded_s = calibration.time_best(
                    lambda: dynamics.retrieve(cfg, params_s, sig0), trials
                )
            rows.append({
                "n": n,
                "mesh": mesh_spec,
                "model_degree": plan.model,
                "lanes": lanes,
                "max_cycles": max_cycles,
                "replicated_s": round(replicated_s, 6),
                "sharded_s": round(sharded_s, 6),
                "full_weight_mb": round(full / 1e6, 3),
                "per_device_weight_mb": round(per_device / 1e6, 3),
                "memory_headroom_x": round(full / per_device, 2),
                "exact": exact,
                "calibration_s": min(before, cal.sample()),
            })
    print(json.dumps({"calibration_s": cal(), "rows": rows}))


def main(
    smoke: bool = False,
    out: Optional[str] = None,
) -> List[Dict[str, Any]]:
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(repo_root, "src"), env.get("PYTHONPATH")) if p
    )
    cmd = [sys.executable, "-m", "benchmarks.sharding", "--child"]
    if smoke:
        cmd.append("--smoke")
    proc = subprocess.run(
        cmd, capture_output=True, text=True, env=env, cwd=repo_root,
        timeout=3600,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"sharding child failed:\n{proc.stderr[-4000:]}")
    child = json.loads(proc.stdout.strip().splitlines()[-1])
    rows = child["rows"]

    print("# model-parallel sharding vs replicated: CPU correctness/overhead "
          "run on 8 virtual host devices (not device times)")
    print("n,mesh,replicated_s,sharded_s,per_device_weight_mb,"
          "full_weight_mb,memory_headroom_x")
    for r in rows:
        print(f"{r['n']},{r['mesh']},{r['replicated_s']},{r['sharded_s']},"
              f"{r['per_device_weight_mb']},{r['full_weight_mb']},"
              f"{r['memory_headroom_x']}")
        if r["n"] % r["model_degree"] == 0:
            want = r["model_degree"]
            got = r["memory_headroom_x"]
            if not (want * 0.99 <= got <= want * 1.01):
                raise RuntimeError(
                    f"N={r['n']}: per-device weight bytes not 1/{want} of the "
                    f"matrix (headroom {got}x)"
                )
    biggest = rows[-1]
    print(f"# N={biggest['n']}: each device holds "
          f"{biggest['per_device_weight_mb']} MB of the "
          f"{biggest['full_weight_mb']} MB coupling matrix "
          f"({biggest['memory_headroom_x']}x headroom) — past the "
          "single-board N=506 wall")

    if out:
        payload = {
            "bench": "sharding",
            "smoke": smoke,
            "platform": "cpu",
            "devices": 8,
            "calibration_s": child["calibration_s"],
            "rows": rows,
        }
        with open(out, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {out}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="small trial counts (CI)")
    ap.add_argument("--child", action="store_true",
                    help="internal: run the measurement child (8 forced devices)")
    ap.add_argument("--out", default="BENCH_sharding.json",
                    help="JSON output path ('' disables)")
    args = ap.parse_args()
    if args.child:
        _child_main(smoke=args.smoke)
    else:
        main(smoke=args.smoke, out=args.out or None)
