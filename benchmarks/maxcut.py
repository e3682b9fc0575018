"""Batched oscillatory-Ising-machine max-cut: scaling + quality benchmark.

The paper motivates large all-to-all ONNs with combinatorial optimization
(§2.2); this bench makes max-cut a first-class scaling scenario on the
batched ONN core.  For N ∈ {48, 128, 506} (the paper's design sizes plus
the serving bucket) it solves Erdős–Rényi instances with the multi-replica
grouped-staggered annealer (``repro.core.ising.solve_maxcut_batch``)
through each weighted-sum backend, and measures:

* **wall clock** of the batched solve vs the pre-batched baseline — the
  vmap-of-``lax.scan`` sequential-sweep solver (one oscillator at a time,
  ``solve_maxcut``), vmapped over the same replica count;
* **bit-exactness** of the batched solve across backends (asserted on
  every row before timing anything);
* **quality** — the cut ratio vs the |E|/2 random-cut baseline.

  PYTHONPATH=src python -m benchmarks.maxcut                      # full
  PYTHONPATH=src python -m benchmarks.maxcut --smoke --out BENCH_ising.json
"""

from __future__ import annotations

import argparse
import json
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import calibration
from repro.core import dynamics
from repro.core import ising

SIZES = (48, 128, 506)
#: (backend, parallel_factor) sweep.
BACKENDS = (
    ("parallel", 0),
    ("hybrid", 32),
)
STAGGER_GROUPS = 16


@partial(jax.jit, static_argnums=(2, 3))
def _legacy_replicas(adj: jax.Array, keys: jax.Array, sweeps: int, weight_bits: int):
    """The old solver shape: vmap over replica keys of the sequential-sweep
    ``lax.scan`` annealer (what ``MaxCutEngineSolver`` executed pre-rebuild)."""
    return jax.vmap(
        lambda k: ising.solve_maxcut(adj, k, sweeps=sweeps, weight_bits=weight_bits)
    )(keys)


def _cfg(n: int, backend: str, p: int, sweeps: int) -> dynamics.ONNConfig:
    return dynamics.ONNConfig(
        n=n, backend=backend, parallel_factor=p, max_cycles=sweeps, settle_chunk=0
    )


def bench_size(n: int, replicas: int, sweeps: int, trials: int) -> List[Dict[str, Any]]:
    """All backend rows for one instance size.

    The parallel reference solve and the legacy vmap-of-scan baseline — the
    slowest executable in the benchmark — are built and timed once per N and
    shared across backend rows (each row asserts bit-exactness against the
    reference before its timing means anything).
    """
    key = jax.random.PRNGKey(1000 + n)
    adj = ising.random_graph(key, n, 0.5)
    solve_key = jax.random.fold_in(key, 7)
    edges = float(jnp.sum(jnp.triu(adj, 1)))

    def solve(cfg):
        return ising.solve_maxcut_batch(
            cfg, adj, solve_key, replicas=replicas, stagger_groups=STAGGER_GROUPS
        )

    ref_cfg = _cfg(n, "parallel", 0, sweeps)
    ref = solve(ref_cfg)
    legacy_keys = jax.random.split(solve_key, replicas)
    legacy = _legacy_replicas(adj, legacy_keys, sweeps, ref_cfg.weight_bits)
    legacy_cut = float(jnp.max(legacy.cut_value))
    legacy_s = calibration.time_best(
        lambda: _legacy_replicas(adj, legacy_keys, sweeps, ref_cfg.weight_bits).cut_value,
        trials,
    )

    rows = []
    for backend, p in BACKENDS:
        cfg = _cfg(n, backend, p, sweeps)
        # Bit-exactness gate: every backend row must replay the parallel
        # reference exactly before its timing means anything.
        res = ref if cfg == ref_cfg else solve(cfg)
        for field in ref._fields:
            got, want = np.asarray(getattr(res, field)), np.asarray(getattr(ref, field))
            if not np.array_equal(got, want):
                raise AssertionError(
                    f"maxcut backend {backend} P={p} diverged from parallel "
                    f"at N={n}, field {field!r}"
                )
        solve_s = calibration.time_best(lambda: solve(cfg).cut_value, trials)
        label = backend if backend != "hybrid" else f"hybrid[P={p}]"
        rows.append({
            "n": n,
            "backend": label,
            "parallel": p,
            "replicas": replicas,
            "sweeps": sweeps,
            "stagger_groups": STAGGER_GROUPS,
            "edges": int(edges),
            "cut": float(res.cut_value),
            "cut_ratio": round(float(res.cut_value) / (edges / 2.0), 4),
            "legacy_cut": legacy_cut,
            "solve_s": round(solve_s, 5),
            "legacy_s": round(legacy_s, 5),
            "speedup_vs_legacy": round(legacy_s / solve_s, 2),
        })
    return rows


def main(smoke: bool = False, out: Optional[str] = None) -> List[Dict]:
    trials = 3 if smoke else 5
    sweeps = 16 if smoke else 48
    replicas = 8 if smoke else 16
    rows = []
    print("# maxcut: batched grouped-staggered annealer vs vmap-of-scan baseline")
    print("n,backend,replicas,sweeps,edges,cut,cut_ratio,solve_s,legacy_s," "speedup_vs_legacy")
    with calibration.window() as cal:
        for n in SIZES:
            before = cal.sample()
            size_rows = bench_size(n, replicas, sweeps, trials)
            after = cal.sample()
            for r in size_rows:
                r["calibration_s"] = min(before, after)
                rows.append(r)
                print(
                    f"{r['n']},{r['backend']},{r['replicas']},{r['sweeps']},"
                    f"{r['edges']},{r['cut']},{r['cut_ratio']},{r['solve_s']},"
                    f"{r['legacy_s']},{r['speedup_vs_legacy']}"
                )
    # Headline acceptance: the batched solve beats the old vmap-of-scan
    # solver's wall clock at the paper's hybrid capacity point N=506.
    big = [r for r in rows if r["n"] == max(SIZES)]
    worst = min(r["speedup_vs_legacy"] for r in big)
    print(f"# N={max(SIZES)} speedup vs vmap-of-scan: worst {worst:.2f}x")
    if out:
        payload = {
            "bench": "ising",
            "smoke": smoke,
            "calibration_s": cal(),
            "replicas": replicas,
            "sweeps": sweeps,
            "rows": rows,
        }
        with open(out, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {out}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="small trial counts (CI)")
    ap.add_argument("--out", default="BENCH_ising.json", help="JSON output path ('' disables)")
    args = ap.parse_args()
    main(smoke=args.smoke, out=args.out or None)
