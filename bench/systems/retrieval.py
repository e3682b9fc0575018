"""Retrieval configurations: a trained ONN behind the engine.

Set-up makes the stored patterns and the couplings on the device from the
seed (``bench/generators.py``), installs them through the program's public
API (``api.RetrievalSolver`` on an ``Engine``), and draws the probe pool.  The check runs ``bench/references/retrieval.py`` on the same
couplings and probes and compares every field of every result.
"""

from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

import generators as gen
from references import retrieval as ref


class System:
    workload = "retrieval"

    def __init__(self, cfg, traffic, rng, chips):
        from repro import api

        self.cfg = cfg
        self.traffic = traffic
        self.record = {}
        n, bits = cfg["n"], cfg["weight_bits"]
        coup = cfg["couplings"]
        k_pat, k_pool = jax.random.split(jax.random.PRNGKey(int(rng.integers(0, 2**32))))
        self.patterns = gen.random_patterns(k_pat, coup["patterns"], n)
        t0 = time.perf_counter()
        if coup["rule"] == "qat_doi":
            w_float, sweeps, conv = gen.train_qat_doi(
                self.patterns, coup["qat_bits"], coup["max_sweeps"], coup["threshold"])
            self.weights = gen.quantize(w_float, bits)
            self._control_weights = lambda: gen.quantize(w_float, bits - 1)
            self.record["train_sweeps"] = int(sweeps)
            self.record["train_converged"] = bool(conv)
        else:
            raise ValueError(f"unknown coupling rule {coup['rule']!r}")
        jax.block_until_ready(self.weights)
        self.record["couplings_s"] = time.perf_counter() - t0

        self.config = api.ONNConfig(
            n=n, weight_bits=bits, phase_bits=cfg["phase_bits"],
            architecture=cfg["architecture"], mode=cfg["mode"],
            max_cycles=cfg["max_cycles"], settle_chunk=cfg["settle_chunk"],
            backend=cfg["backend"],
        )
        self.solver = api.RetrievalSolver(config=self.config,
                                          params=api.make_params(self.config, self.weights))

        lanes = traffic["lanes_per_request"]
        lo, hi = traffic["corruption"]
        probes, which = gen.corrupt_pool(k_pool, self.patterns, traffic["pool"], lanes, lo, hi)
        self.pool = np.asarray(jax.device_get(probes))
        self.which = np.asarray(jax.device_get(which))
        self.patterns_host = np.asarray(jax.device_get(self.patterns)).astype(np.int32)
        self.lanes = lanes

    # -- what the traffic driver needs ---------------------------------------

    def engine_solver(self):
        return self.solver.as_engine_solver()

    def context(self):
        return contextlib.nullcontext()

    def request(self, i):
        """(payload, key, pool index) of request ``i``; no key: retrieval
        draws no randomness and the engine splits its own."""
        j = i % len(self.pool)
        payload = self.pool[j][0] if self.lanes == 1 else self.pool[j]
        return payload, None, j

    # -- the check ---------------------------------------------------------

    def free(self):
        """Drop the program's state (solver, couplings it holds)."""
        self.solver = None

    def check(self, served, control=False):
        """Compare every served result with the reference.

        ``served``: list of (pool index, result fields as numpy).  With
        ``control`` the reference on couplings of one bit fewer stands in
        for the program's results.  Returns (mismatched requests, {pool
        index: cycles its lanes needed}, numbers for the record).
        """
        used = sorted({j for j, _ in served})
        expect, cycles = self._reference(used, self.weights)
        if control:
            got_of, _ = self._reference(used, self._control_weights())
            served = [(j, got_of[j]) for j, _ in served]
        bad = hits = lanes = 0
        for j, got in served:
            exp = expect[j]
            ok = all(
                np.array_equal(np.asarray(got[f]).reshape(exp[f].shape), exp[f])
                and np.asarray(got[f]).dtype == exp[f].dtype
                for f in ref.FIELDS
            )
            bad += not ok
            sig = np.asarray(got["final_sigma"]).reshape(self.lanes, -1).astype(np.int32)
            tgt = self.patterns_host[self.which[j]]
            hits += int(((sig == tgt).all(1) | (sig == -tgt).all(1)).sum())
            lanes += self.lanes
        return bad, cycles, {"retrieval_accuracy": hits / max(lanes, 1)}

    def _reference(self, used, w):
        """{pool index: reference fields}, {pool index: cycles needed}."""
        cfg = self.config
        bias = jnp.zeros((cfg.n,), jnp.int32)
        block = max(1, self.traffic.get("reference_block_lanes", 512) // self.lanes)
        expect, cycles = {}, {}
        for s in range(0, len(used), block):
            js = used[s:s + block]
            # A full block every call (the tail repeats its last probe), so
            # the reference compiles once.
            probes = self.pool[js + [js[-1]] * (block - len(js))].reshape(-1, cfg.n)
            out, need = ref.solve(w, bias, jnp.asarray(probes),
                                  phase_bits=cfg.phase_bits, max_cycles=cfg.max_cycles)
            for k, j in enumerate(js):
                rows = slice(k * self.lanes, (k + 1) * self.lanes)
                expect[j] = {f: out[f][rows] for f in ref.FIELDS}
                cycles[j] = int(need[rows].sum())
        return expect, cycles

    def useful_ops(self, served, cycles):
        """Int8 operations the algorithm needs for ``served``: 2·N² per lane
        per cycle up to the lane's freeze (or ``max_cycles``)."""
        n = self.config.n
        return float(sum(2.0 * n * n * cycles[j] for j, _ in served))
