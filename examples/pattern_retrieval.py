"""End-to-end driver: the paper's pattern-retrieval benchmark as a batched
serving workload (the ONN analogue of "serve a small model with batched
requests").

    PYTHONPATH=src python examples/pattern_retrieval.py [--requests 512]

Serves ``--requests`` corrupted-pattern requests through both FPGA
architectures (recurrent where it fits, hybrid everywhere) across all five
paper datasets, reporting accuracy / settle cycles / throughput — several
hundred ONN evolution steps per request batch, i.e. the paper-appropriate
version of "a few hundred steps end-to-end".
"""

import argparse

from repro.launch.retrieve import build_solver, serve_requests


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=512)
    ap.add_argument("--corruption", type=float, default=0.25)
    ap.add_argument("--backend", default="parallel",
                    choices=["parallel", "pallas"])
    args = ap.parse_args()

    print("dataset,arch,requests,accuracy,settle_cycles,req_per_s")
    for dataset in ("3x3", "5x4", "7x6", "10x10", "22x22"):
        n = {"3x3": 9, "5x4": 20, "7x6": 42, "10x10": 100, "22x22": 484}[dataset]
        archs = ["recurrent", "hybrid"] if n <= 48 else ["hybrid"]
        for arch in archs:
            solver, xi = build_solver(dataset, arch, backend=args.backend)
            out = serve_requests(solver, xi, args.corruption, args.requests)
            print(
                f"{dataset},{arch},{out['requests']},{out['accuracy']:.3f},"
                f"{out['mean_settle_cycles']},{out['requests_per_s']}"
            )


if __name__ == "__main__":
    main()
