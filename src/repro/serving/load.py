"""Shared load generation for the serving benchmark, CLI and example.

One deterministic mixed request stream (two retrieval pattern sizes plus
max-cut instances, spread over tenants) and an open-loop Poisson arrival
schedule: arrival times are drawn once, up front, independent of service
progress — the load does not slow down when the server falls behind, which
is what makes sustained-throughput and tail-latency numbers honest.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ising import random_graph
from repro.data import patterns as pat
from repro.engine.engine import Request

#: Default tenant mix: id → fair-share weight (the CLI/bench default).
DEFAULT_TENANTS: Tuple[Tuple[str, float], ...] = (("alpha", 2.0), ("beta", 1.0))


def install_mixed_workloads(
    engine: Any,
    *,
    sweeps: int = 8,
    replicas: int = 1,
    small_ckpt: Optional[str] = None,
    plan: Optional[Any] = None,
) -> None:
    """Install the stream's three workloads (same shapes as the engine bench):
    ``small`` retrieval (N=42), ``large`` retrieval (N=100), ``cuts`` max-cut.

    ``small_ckpt`` restores the ``small`` workload from an ONN checkpoint
    (:func:`repro.checkpoint.load_onn`) instead of training in-process — the
    daemon-restart path after ``repro.launch.train_onn`` persisted a trained
    matrix.  The checkpoint must be N=42 (the stream's small probes).

    ``plan`` (a :class:`repro.distributed.ShardPlan`) is held by both
    retrieval solvers, whose adapters place the couplings and solve under
    it; Max-Cut holds no plan and runs under the caller's ``plan.context()``.
    """
    from repro import api
    from repro.engine.adapters import RetrievalEngineSolver

    if small_ckpt is None:
        small = api.RetrievalSolver.from_patterns(jnp.asarray(pat.load_dataset("7x6")))
    else:
        small = restore_retrieval(small_ckpt, n=42)
    large = api.RetrievalSolver.from_patterns(jnp.asarray(pat.load_dataset("10x10")))
    for name, solver in (("small", small), ("large", large)):
        engine.install(
            name, RetrievalEngineSolver(solver=dataclasses.replace(solver, plan=plan))
        )
    engine.install("cuts", "maxcut", sweeps=sweeps, replicas=replicas)


def restore_retrieval(ckpt_path: str, n: Optional[int] = None) -> Any:
    """An ``api.RetrievalSolver`` restored from an ONN checkpoint."""
    from repro import api
    from repro.checkpoint import load_onn

    ck = load_onn(ckpt_path)
    if n is not None and ck.config.n != n:
        raise ValueError(f"checkpoint is N={ck.config.n}, the workload needs N={n}")
    return api.RetrievalSolver(config=ck.config, params=ck.params)


def mixed_requests(
    n_requests: int,
    seed: int = 0,
    tenants: Sequence[Tuple[str, float]] = DEFAULT_TENANTS,
    maxcut_every: int = 4,
) -> List[Request]:
    """A deterministic mixed stream with per-request keys pinned.

    Every request carries an explicit PRNG key, so the same stream solved
    through any scheduling policy (drain batching, continuous batching, one
    request at a time) returns bit-identical results per request.
    """
    rng = np.random.default_rng(seed)
    xi_small = pat.load_dataset("7x6")
    xi_large = pat.load_dataset("10x10")
    names = [t for t, _ in tenants]
    weights = np.asarray([w for _, w in tenants], np.float64)
    weights = weights / weights.sum()
    key = jax.random.PRNGKey(seed)
    out: List[Request] = []
    for i in range(n_requests):
        key, k_payload, k_req = jax.random.split(key, 3)
        tenant = names[int(rng.choice(len(names), p=weights))]
        if maxcut_every and i % maxcut_every == maxcut_every - 1:
            adj = random_graph(k_payload, int(rng.integers(16, 40)), 0.5)
            out.append(Request("cuts", adj, key=k_req, tenant=tenant))
        else:
            xi = xi_small if i % maxcut_every == 0 else xi_large
            row = int(rng.integers(0, xi.shape[0]))
            lanes = int(rng.integers(1, 5))
            batch = jax.vmap(lambda kk: pat.corrupt(xi[row], kk, 0.25))(
                jax.random.split(k_payload, lanes)
            )
            payload = batch[0] if lanes == 1 else batch
            out.append(Request("small" if i % maxcut_every == 0 else "large",
                               payload, key=k_req, tenant=tenant))
    return out


def poisson_offsets(n: int, rate_rps: float, seed: int = 0) -> List[float]:
    """Ascending arrival offsets (seconds) of an open-loop Poisson process."""
    if rate_rps <= 0:
        raise ValueError(f"rate_rps must be > 0, got {rate_rps}")
    gaps = np.random.default_rng(seed + 1).exponential(1.0 / rate_rps, size=n)
    return list(np.cumsum(gaps))


def timed_source(
    requests: Sequence[Request],
    offsets: Sequence[float],
    clock: Any = time.perf_counter,
) -> Iterator[Optional[List[Request]]]:
    """Open-loop daemon source: each tick releases every request now due.

    The schedule is anchored at the first ``next()``; the generator closes
    once the last request is released (the daemon then drains).
    """
    if len(requests) != len(offsets):
        raise ValueError(f"{len(requests)} requests vs {len(offsets)} offsets")
    t_start = clock()
    i = 0
    while i < len(requests):
        now = clock() - t_start
        due: List[Request] = []
        while i < len(requests) and offsets[i] <= now:
            due.append(requests[i])
            i += 1
        yield due or None


def ticked_source(
    requests: Sequence[Request], per_tick: int = 1
) -> Iterator[List[Request]]:
    """Deterministic source: ``per_tick`` requests per daemon tick (tests,
    examples — no wall-clock dependence)."""
    if per_tick < 1:
        raise ValueError(f"per_tick must be >= 1, got {per_tick}")
    for i in range(0, len(requests), per_tick):
        yield list(requests[i : i + per_tick])
