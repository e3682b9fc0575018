"""Train → install → serve → measure, in one process.

Trains the paper's associative memory with quantization-aware DO-I
(:mod:`repro.train`) and installs the result into a **live** serving engine
mid-stream: the daemon starts on plain Hebbian 5-bit weights, serves a
corrupted-probe stream, hot-swaps the trained weights at a settle-chunk
boundary (in-flight lanes finish on the Hebbian weights; not one executable
recompiles), then serves the same probe stream again.  The report shows the
retrieval-accuracy jump the swap bought, the training telemetry (sweeps,
min κ margin on the quantized weights) and the serving counters.

Optionally checkpoints the trained ONN (``--ckpt-dir``); the install then
goes through a save → load round trip, proving the restore path the serve
daemon uses.

Usage:
  PYTHONPATH=src python -m repro.launch.train_onn --dataset 10x10
  PYTHONPATH=src python -m repro.launch.train_onn --dataset 7x6 --corruption 0.2
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import api, serving, train
from repro.checkpoint import load_onn, save_onn
from repro.core import dynamics
from repro.core.learning import hebbian
from repro.core.quantization import quantize_weights
from repro.data import patterns as data
from repro.engine import Request, adapters


def _hebbian_solver(xi: jax.Array, **cfg_kwargs: Any) -> api.RetrievalSolver:
    """The baseline the swap replaces: one-shot Hebbian at 5-bit weights."""
    n = xi.shape[1]
    cfg = dynamics.ONNConfig(n=n, **cfg_kwargs)
    qw = quantize_weights(hebbian(xi, self_coupling=False), cfg.weight_bits)
    return api.RetrievalSolver(config=cfg, params=dynamics.make_params(cfg, qw.values))


def _probe_batch(
    xi: np.ndarray, probes: int, corruption: float, seed: int
) -> List[np.ndarray]:
    """Probe i is pattern i % P with an exact-count random corruption."""
    p = xi.shape[0]
    out = []
    for i in range(probes):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        out.append(np.asarray(data.corrupt(jnp.asarray(xi[i % p]), key, corruption)))
    return out


def _accuracy(results: List[Any], targets: List[np.ndarray]) -> float:
    """Fraction of probes retrieved exactly (up to a global spin flip)."""
    hits = 0
    for res, tgt in zip(results, targets):
        sigma = np.asarray(res.final_sigma)
        hits += int(np.array_equal(sigma, tgt) or np.array_equal(-sigma, tgt))
    return hits / max(1, len(results))


def _serve_probes(
    eng: serving.ContinuousEngine, probes: List[np.ndarray]
) -> List[Any]:
    futs = [eng.submit(Request("retrieval", jnp.asarray(p, jnp.int8))) for p in probes]
    eng.flush()
    return [f.result() for f in futs]


def run_train_serve(
    *,
    dataset: str = "10x10",
    corruption: float = 0.15,
    probes: int = 24,
    seed: int = 0,
    ckpt_dir: Optional[str] = None,
    max_sweeps: int = 500,
    qat: bool = True,
    backend: str = "parallel",
    settle_chunk: int = 4,
) -> Dict[str, Any]:
    xi = data.load_dataset(dataset)
    xi_np = np.asarray(xi)
    eng = serving.ContinuousEngine(jax.random.PRNGKey(seed), slab_lanes=probes)
    solver = adapters.RetrievalEngineSolver(
        solver=_hebbian_solver(xi, backend=backend, settle_chunk=settle_chunk)
    )
    eng.install("retrieval", solver)
    probe_set = _probe_batch(xi_np, probes, corruption, seed)
    targets = [xi_np[i % xi_np.shape[0]] for i in range(probes)]

    # Warm the serving executables (advance/harvest) so the retrace counter
    # below isolates the swap, then run phase 1 for real.
    _serve_probes(eng, probe_set)

    # Phase 1: submit every probe and take one tick — slab_lanes == probes,
    # so this admits the whole stream into one live slab on Hebbian weights.
    futs = [eng.submit(Request("retrieval", jnp.asarray(p, jnp.int8))) for p in probe_set]
    eng.step()

    # Train while the slab is in flight; install at the settle-chunk
    # boundary.  In-flight lanes finish on the Hebbian weights they started
    # with, so the phase-1 accuracy below is purely pre-swap.
    serve_traces = sum(dynamics.TRACE_COUNTER.values())
    swap = train.HotSwap(eng, "retrieval")
    cfg_train = train.TrainConfig(
        qat_bits=solver.config.weight_bits if qat else 0, max_sweeps=max_sweeps
    )
    result = train.train_doi(xi, cfg_train)
    params, qw = train.trained_params(solver.config, result.weights)
    checkpoint_path = None
    if ckpt_dir is not None:
        # Install through the save → load round trip (the daemon restore path).
        checkpoint_path = save_onn(
            os.path.join(ckpt_dir, "onn"),
            solver.config,
            qw,
            extra_meta={"dataset": dataset, "rule": "qat_doi" if qat else "doi"},
        )
        params = load_onn(checkpoint_path).params
    swap.install(params)
    eng.flush()
    acc_hebbian = _accuracy([f.result() for f in futs], targets)

    # Phase 2: the same probes on the trained weights — zero recompiles.
    after = _serve_probes(eng, probe_set)
    acc_trained = _accuracy(after, targets)
    serving_retraces = sum(dynamics.TRACE_COUNTER.values()) - serve_traces

    stats = eng.stats()
    return {
        "dataset": dataset,
        "patterns": int(xi_np.shape[0]),
        "n": int(xi_np.shape[1]),
        "probes": probes,
        "corruption": corruption,
        "rule": "qat_doi" if qat else "doi",
        "train": {
            "sweeps": int(result.sweeps),
            "converged": bool(result.converged),
            "kappa_min": float(result.kappa_min),
        },
        "accuracy_hebbian": acc_hebbian,
        "accuracy_trained": acc_trained,
        "hot_swaps": stats["serving"]["hot_swaps"],
        "serving_retraces_after_swap": serving_retraces,
        "checkpoint": checkpoint_path,
        "ticks": stats["serving"]["ticks"],
        "completed": stats["completed"],
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dataset", default="10x10", help="pattern dataset (e.g. 7x6, 10x10)")
    ap.add_argument("--corruption", type=float, default=0.15)
    ap.add_argument("--probes", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint the trained ONN here (default: temp dir)")
    ap.add_argument("--max-sweeps", type=int, default=500)
    ap.add_argument("--no-qat", action="store_true",
                    help="train float DO-I instead of quantization-aware DO-I")
    ap.add_argument("--backend", default="parallel",
                    choices=("parallel", "pallas", "hybrid"))
    ap.add_argument("--settle-chunk", type=int, default=4)
    args = ap.parse_args()
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="onn_ckpt_")
    report = run_train_serve(
        dataset=args.dataset,
        corruption=args.corruption,
        probes=args.probes,
        seed=args.seed,
        ckpt_dir=ckpt_dir,
        max_sweeps=args.max_sweeps,
        qat=not args.no_qat,
        backend=args.backend,
        settle_chunk=args.settle_chunk,
    )
    print(json.dumps(report, indent=1, default=str))


if __name__ == "__main__":
    main()
