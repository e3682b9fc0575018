"""Max-Cut configurations: the oscillatory Ising machine behind the engine.

Set-up draws a pool of G-set-shaped graphs on the device from the seed and
keeps them on the host, as users send them, plus one PRNG key per request.
The program solves them through ``api.MaxCutSolver`` on an ``Engine``; the
check runs ``bench/references/maxcut.py`` on the same graphs and keys and
compares every field of every result.
"""

from __future__ import annotations

import contextlib
import time

import jax
import numpy as np

import generators as gen
from references import maxcut as ref


class System:
    workload = "maxcut"

    def __init__(self, cfg, traffic, rng, chips):
        from repro import api

        self.cfg = cfg
        self.traffic = traffic
        self.record = {}
        n = cfg["n"]
        key = jax.random.PRNGKey(int(rng.integers(0, 2**32)))
        k_graphs, k_req = jax.random.split(key)
        t0 = time.perf_counter()
        size, chunk = traffic["pool"], traffic.get("pool_chunk", 32)
        gkeys = jax.random.split(k_graphs, size)
        self.graphs = np.concatenate([
            np.asarray(jax.device_get(gen.random_graphs(gkeys[s:s + chunk], n, cfg["density"])))
            for s in range(0, size, chunk)
        ])
        self.keys = np.asarray(jax.device_get(jax.random.split(k_req, traffic["keys"])))
        self.half_edges = np.triu(self.graphs.astype(np.int64), 1).sum(axis=(1, 2)) / 2.0
        self.record["graphs_s"] = time.perf_counter() - t0
        self.record["mean_edges"] = float(self.half_edges.mean() * 2)
        self.solver = api.MaxCutSolver(
            sweeps=cfg["sweeps"], weight_bits=cfg["weight_bits"], replicas=cfg["replicas"],
            stagger_groups=cfg["stagger_groups"], stagnation=cfg["stagnation"],
            backend=cfg["backend"], settle_chunk=cfg["settle_chunk"],
        )

    def engine_solver(self):
        return self.solver.as_engine_solver()

    def context(self):
        return contextlib.nullcontext()

    def request(self, i):
        """(payload, key, request index): graph ``i`` of the pool (cycled), a
        fresh key per request."""
        return self.graphs[i % len(self.graphs)], self.keys[i % len(self.keys)], i

    def free(self):
        self.solver = None

    def check(self, served, control=False):
        """Compare every served result with the reference.

        With ``control`` the reference with its cut computed in bfloat16
        stands in for the program's results.  Returns (mismatched requests,
        None, numbers for the record).
        """
        block = self.traffic.get("reference_block", 64)
        bad = 0
        ratios = []
        for s in range(0, len(served), block):
            part = served[s:s + block]
            exp = self._reference([i for i, _ in part], "float32")
            if control:
                alt = self._reference([i for i, _ in part], "bfloat16")
                part = [(i, {f: alt[f][k] for f in ref.FIELDS}) for k, (i, _) in enumerate(part)]
            for k, (i, got) in enumerate(part):
                bad += not all(
                    np.array_equal(np.asarray(got[f]), np.asarray(exp[f][k]))
                    and np.asarray(got[f]).dtype == np.asarray(exp[f][k]).dtype
                    for f in ref.FIELDS
                )
                ratios.append(float(got["cut_value"]) / self.half_edges[i % len(self.graphs)])
        info = {"cut_over_half_edges_mean": float(np.mean(ratios)) if ratios else None,
                "cut_over_half_edges_min": float(np.min(ratios)) if ratios else None}
        return bad, None, info

    def _reference(self, idx, cut_dtype):
        # Every call gets a full block (the tail repeats its last request),
        # so the reference compiles once.
        cfg = self.cfg
        idx = list(idx) + [idx[-1]] * (self.traffic.get("reference_block", 64) - len(idx))
        return ref.solve(
            self.graphs[[i % len(self.graphs) for i in idx]],
            self.keys[[i % len(self.keys) for i in idx]],
            replicas=cfg["replicas"], sweeps=cfg["sweeps"],
            groups=cfg["stagger_groups"] or cfg["default_groups"],
            weight_bits=cfg["weight_bits"], cut_dtype=cut_dtype,
        )

    def useful_ops(self, served, cycles):
        """2·N² int8 operations per replica per sweep run (each sweep visits
        every coupling row once)."""
        n = self.cfg["n"]
        return float(sum(2.0 * n * n * self.cfg["replicas"] * int(got["sweeps_run"])
                         for _, got in served))
