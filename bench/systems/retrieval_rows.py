"""Row-sharded retrieval: an associative-memory library too large for one chip.

Set-up draws the stored patterns and the probe pool from the seed, then
builds the couplings directly into W's row sharding over the plan's mesh
(:func:`row_quantized_hebbian`): each device sums the Hebbian couplings of
its own rows in chunks, the scale is the largest |w| over every block, and
each block is quantized with ``generators.quantize``'s definition, so W is
``quantize(hebbian(xi), bits)`` of the whole matrix.  The program gets W
through its public API, in a ``RetrievalSolver`` that holds the
configuration's ``ShardPlan``: the engine's adapter places W and runs every
solve under the plan, and the benchmark sets no plan of its own
(``context()`` stays ``retrieval.System``'s ``nullcontext``).  The check runs
``references/retrieval_rows.py`` on the benchmark's own W once the program's
state is freed.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import generators as gen
from references import retrieval_rows as ref
from systems import retrieval

#: Rows of W a device sums at once: (2048, N) float32 is 1.07 GB at N=131072.
CHUNK_ROWS = 2048


@partial(jax.jit, static_argnums=(1, 2, 3))
def row_quantized_hebbian(xi, mesh, bits: int, chunk_rows: int):
    """``quantize(hebbian(xi), bits)`` of the whole (N, N) matrix, built by rows.

    ``hebbian``: (1/N) Σ_μ ξ^μ ξ^μᵀ with a zero diagonal, the rule of
    ``repro.core.learning.hebbian(xi, self_coupling=False)``, summed exactly
    (an int8 dot of ±1 patterns into int32).  ``quantize``: the symmetric
    round to nearest of ``generators.quantize`` with the scale max|w| / qmax,
    max|w| taken over every device's rows first.  ``xi``: (P, N) ±1 int8 on
    every device of ``mesh``; returns (N, N) int8 split by rows over
    ``"model"``.
    """
    n = xi.shape[1]
    qmax = gen.qmax_of(bits)
    rows = n // mesh.shape["model"]

    def block(x, start):
        cols = jax.lax.dynamic_slice_in_dim(x, start, chunk_rows, axis=1)
        w = jnp.dot(cols.T, x, preferred_element_type=jnp.int32).astype(jnp.float32) / n
        diag = jnp.arange(n)[None, :] == (start + jnp.arange(chunk_rows))[:, None]
        return jnp.where(diag, jnp.float32(0.0), w)

    def own_rows(x):
        starts = jax.lax.axis_index("model") * rows + jnp.arange(0, rows, chunk_rows)
        local = jnp.max(jax.lax.map(lambda s: jnp.max(jnp.abs(block(x, s))), starts))
        absmax = jax.lax.pmax(local, "model")
        scale = jnp.where(absmax > 0, absmax / qmax, jnp.float32(1.0))
        q = jax.lax.map(
            lambda s: jnp.clip(jnp.round(block(x, s) / scale), -qmax, qmax).astype(jnp.int8),
            starts)
        return q.reshape(rows, n)

    return jax.shard_map(own_rows, mesh=mesh, in_specs=P(), out_specs=P("model", None))(xi)


class System(retrieval.System):
    def __init__(self, cfg, traffic, rng, chips):
        from repro import api
        from repro.distributed import ShardPlan

        if "plan" not in {f.name for f in dataclasses.fields(api.RetrievalSolver)}:
            raise SystemExit("bench: this program's RetrievalSolver holds no ShardPlan; "
                             "the row-sharded cell needs the solver to own its plan")
        self.cfg = cfg
        self.traffic = traffic
        self.record = {}
        n, bits = cfg["n"], cfg["weight_bits"]
        coup = cfg["couplings"]
        if coup["rule"] != "hebbian" or coup["self_coupling"]:
            raise ValueError(f"unknown coupling rule {coup!r}")
        plan = ShardPlan(**cfg["plan"])
        if plan.devices != chips:
            raise ValueError(f"plan {cfg['plan']} is not over the cell's {chips} chips")
        self.mesh = plan.make_mesh()
        self.chunk_rows = min(CHUNK_ROWS, n // plan.model)

        k_pat, k_pool = jax.random.split(jax.random.PRNGKey(int(rng.integers(0, 2**32))))
        patterns = gen.random_patterns(k_pat, coup["patterns"], n)
        lanes = traffic["lanes_per_request"]
        lo, hi = traffic["corruption"]
        probes, which = gen.corrupt_pool(k_pool, patterns, traffic["pool"], lanes, lo, hi)
        self.pool = np.asarray(jax.device_get(probes))
        self.which = np.asarray(jax.device_get(which))
        self.patterns_host = np.asarray(jax.device_get(patterns))
        self.lanes = lanes
        del probes, which

        t0 = time.perf_counter()
        self._xi = jax.device_put(patterns, NamedSharding(self.mesh, P()))
        del patterns
        self.weights = row_quantized_hebbian(self._xi, self.mesh, bits, self.chunk_rows)
        jax.block_until_ready(self.weights)
        self.record["couplings_s"] = time.perf_counter() - t0
        self.record["w_shard_bytes"] = sorted(
            int(s.data.nbytes) for s in self.weights.addressable_shards)

        self.config = api.ONNConfig(
            n=n, weight_bits=bits, phase_bits=cfg["phase_bits"],
            architecture=cfg["architecture"], mode=cfg["mode"],
            max_cycles=cfg["max_cycles"], settle_chunk=cfg["settle_chunk"],
            backend=cfg["backend"],
        )
        self.solver = api.RetrievalSolver(
            config=self.config, params=api.make_params(self.config, self.weights), plan=plan)

    def _control_weights(self):
        """The same library's couplings at one bit fewer, built the same way."""
        return row_quantized_hebbian(self._xi, self.mesh, self.cfg["weight_bits"] - 1,
                                     self.chunk_rows)

    def _reference(self, used, w):
        """{pool index: reference fields}, {pool index: cycles needed}, from the
        row-blocked reference in blocks of ``reference_block_lanes`` lanes."""
        cfg = self.config
        bias = np.zeros((cfg.n,), np.int32)
        block = max(1, self.traffic.get("reference_block_lanes", 512) // self.lanes)
        expect, cycles = {}, {}
        for s in range(0, len(used), block):
            js = used[s:s + block]
            # A full block every call (the tail repeats its last probe), so
            # the reference compiles once.
            probes = self.pool[js + [js[-1]] * (block - len(js))].reshape(-1, cfg.n)
            out, need = ref.solve(w, bias, probes, phase_bits=cfg.phase_bits,
                                  max_cycles=cfg.max_cycles)
            for k, j in enumerate(js):
                rows = slice(k * self.lanes, (k + 1) * self.lanes)
                expect[j] = {f: out[f][rows] for f in ref.FIELDS}
                cycles[j] = int(need[rows].sum())
        return expect, cycles
