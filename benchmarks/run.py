"""Benchmark aggregator: one section per paper table/figure + repo extras.

  PYTHONPATH=src python -m benchmarks.run            # full (CI) trial counts
  PYTHONPATH=src python -m benchmarks.run --quick    # smoke trial counts

A failing section no longer silently disappears into the log: every
exception is caught, reported in a final summary, and turns the exit code
non-zero — so CI (and the bench-regression gate that trusts this runner)
sees partial benchmark runs as failures.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


def run_sections(sections) -> list:
    """Run ``(name, fn, kwargs)`` sections, returning [(name, exception)]."""
    failures = []
    for name, fn, kw in sections:
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        try:
            fn(**kw)
        except Exception as exc:  # noqa: BLE001 — collected into the summary
            traceback.print_exc()
            failures.append((name, exc))
            print(f"===== {name} FAILED after {time.time()-t0:.1f}s =====", flush=True)
        else:
            print(f"===== {name} done in {time.time()-t0:.1f}s =====", flush=True)
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--trials", type=int, default=None,
                    help="retrieval trials per pattern (default 200 / 50 quick)")
    args = ap.parse_args(argv)
    trials = args.trials or (50 if args.quick else 200)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        capacity, comparison, dynamics, engine, hybrid_scaling, kernels,
        maxcut, retrieval, roofline, scaling, serving, sharding,
    )

    sections = [
        ("table2_comparison", comparison.main, {}),
        ("figs9_11_scaling", scaling.main, {}),
        ("storage_capacity_curve", capacity.main, {"smoke": args.quick}),
        ("tables6_7_retrieval", retrieval.main, {"trials": trials}),
        ("kernels", kernels.main, {"smoke": args.quick}),
        ("maxcut_ising", maxcut.main, {"smoke": args.quick}),
        ("roofline", roofline.main, {}),
        ("engine_bucket_policies", engine.main, {"smoke": args.quick}),
        ("dynamics_early_exit", dynamics.main, {"smoke": args.quick}),
        ("hybrid_serialization", hybrid_scaling.main, {"smoke": args.quick}),
        ("serving_continuous_batching", serving.main, {"smoke": args.quick}),
        ("model_parallel_sharding", sharding.main, {"smoke": args.quick}),
    ]
    t_all = time.time()
    failures = run_sections(sections)
    print(f"\n# all benchmarks done in {time.time()-t_all:.1f}s")
    if failures:
        print(f"# {len(failures)}/{len(sections)} sections FAILED:", file=sys.stderr)
        for name, exc in failures:
            print(f"#   {name}: {type(exc).__name__}: {exc}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
