"""Engine adapters: retrieval, max-cut and LM decode behind one surface.

Each adapter implements :class:`repro.engine.engine.EngineSolver`: it maps
request payloads to shape buckets, packs lanes from many requests into one
padded batch, and runs that batch through a single compiled executable.
The adapters are registered with :mod:`repro.engine.registry` — retrieval
and max-cut from ``repro.api`` (they wrap its ``Solver`` implementations),
the LM decode loop here — so one ``Engine`` serves all three workloads
concurrently.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, Hashable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dynamics
from repro.core import hardware_model as hw
from repro.core import ising as ising_lib
from repro.engine import bucketing, spans
from repro.engine.registry import register_solver
from repro.kernels import autotune


def _stack_keys(keys: List[jax.Array], pad_to: int) -> jax.Array:
    """Stack per-lane keys, padding with further splits of the last key."""
    if pad_to > len(keys):
        keys = keys + list(jax.random.split(keys[-1], pad_to - len(keys)))
    return jnp.stack(keys)


def _fpga_design_tradeoff(
    n: int, cycles: float, bits: hw.BitConfig, parallel: int
) -> Dict[str, Optional[float]]:
    """Per-design hardware quotes for one instance (paper Table 5 trade).

    Labels map to time-to-solution seconds, or None when the design does
    not fit the FPGA budget at this N — the fast-but-small recurrent
    against the slow-but-large hybrid, plus the configured P-wide hybrid
    when the backend serializes with ``parallel`` > 1.  Once N exceeds one
    board's hybrid capacity, each non-fitting hybrid design additionally
    quotes its cheapest partitioned sibling ``hybrid[K=k,P=p]`` — the
    coupling rows split over the fewest boards that fit
    (``hw.min_boards``), paying the per-update inter-board amplitude
    exchange ``hw.partitioned_time_to_solution`` models.  The hardware twin
    of the software ``ShardPlan`` model axis.
    """
    designs: Dict[str, Tuple[str, int]] = {
        "recurrent": ("recurrent", 1),
        "hybrid[P=1]": ("hybrid", 1),
    }
    if parallel > 1:
        designs[f"hybrid[P={parallel}]"] = ("hybrid", parallel)
    quotes: Dict[str, Optional[float]] = {
        label: (
            hw.time_to_solution(arch, n, cycles, bits, parallel=par)
            if hw.fits(arch, n, bits, parallel=par)
            else None
        )
        for label, (arch, par) in designs.items()
    }
    for label, (arch, par) in designs.items():
        if arch != "hybrid" or quotes[label] is not None:
            continue
        k = hw.min_boards(n, bits, parallel=par)
        if k is not None and k > 1:
            quotes[f"hybrid[K={k},P={par}]"] = hw.partitioned_time_to_solution(
                n, k, cycles, bits, parallel=par
            )
    return quotes


# ---------------------------------------------------------------------------
# Retrieval: batched associative memory (paper Fig. 7) on a fixed trained ONN
# ---------------------------------------------------------------------------


@dataclasses.dataclass(eq=False)
class RetrievalSlab:
    """One in-flight continuous-batching slab (padded config + live state).

    Held by the serving scheduler between ticks; ``state`` is replaced (not
    mutated) by :meth:`RetrievalEngineSolver.admit` / ``advance``, so each
    tick is a pure function of the previous state.
    """

    cfg: dynamics.ONNConfig
    params: dynamics.OnnParams
    state: dynamics.BatchState
    width: int


class RetrievalEngineSolver:
    """Serves (B, N) corrupted-pattern batches on one trained coupling matrix.

    Payload: ``(N,)`` or ``(B, N)`` ±1 spins.  Lanes from different requests
    coalesce; the oscillator count is padded to the N bucket with masked
    (zero-coupled) oscillators, which is bit-exact on the real lanes —
    ``repro.core.dynamics.pad_params``.  Padded configs/params are cached
    per bucket, so every request at a bucket reuses one ``retrieve``
    executable per batch slab size.

    A slab solve is one call into the batched-native ``retrieve`` (the whole
    slab advances per cycle and exits early once every lane freezes), and
    every slab feeds an EMA of the *measured* settle cycles back into
    :meth:`cost_units`, so latency quotes start at the worst-case
    ``max_cycles`` and tighten toward observed behaviour as traffic flows.
    """

    #: EMA smoothing for observed per-slab mean settle cycles.
    SETTLE_EMA_ALPHA = 0.3
    #: Blend ramp: after k observed slabs the EMA carries k/(k+WARMUP) of the
    #: quoted cycle count (the rest stays on the worst-case max_cycles).
    SETTLE_WARMUP = 8.0

    def __init__(self, solver: Optional[Any] = None, xi: Any = None, **cfg_kwargs: Any):
        from repro.api import RetrievalSolver  # local: api imports this module

        if solver is None:
            if xi is None:
                raise ValueError("RetrievalEngineSolver needs solver= or xi=")
            solver = RetrievalSolver.from_patterns(jnp.asarray(xi), **cfg_kwargs)
        elif cfg_kwargs or xi is not None:
            raise TypeError("pass either a built solver or xi= + config kwargs")
        plan = getattr(solver, "plan", None)
        # A plan over one device is no plan: nothing below runs for it.
        self._plan = plan if plan is not None and plan.devices > 1 else None
        self._mesh = self._plan.make_mesh() if self._plan is not None else None
        if self._plan is not None:
            solver = dataclasses.replace(solver, params=self._place(solver.params))
        self.solver = solver
        self._padded: Dict[int, Tuple[Any, Any]] = {}
        self._settle_ema: Optional[float] = None
        self._settle_obs: int = 0
        self._swaps: int = 0
        self._sharded_slabs: int = 0

    @property
    def config(self):
        return self.solver.config

    def lane_count(self, payload: Any) -> int:
        return 1 if np.ndim(payload) == 1 else np.shape(payload)[0]

    def signature(self, payload: Any) -> Hashable:
        n = np.shape(payload)[-1]
        if n != self.config.n:
            raise ValueError(f"payload N={n} != solver N={self.config.n}")
        return n

    def bucket(self, signature: int, n_policy: bucketing.NBucketPolicy) -> int:
        return bucketing.bucket_n(signature, n_policy)

    def _padded_instance(self, n_bucket: int):
        if n_bucket not in self._padded:
            cfg_b = dynamics.pad_config(self.config, n_bucket)
            params_b = self._place(
                dynamics.pad_params(self.config, self.solver.params, n_bucket))
            self._padded[n_bucket] = (cfg_b, params_b)
        return self._padded[n_bucket]

    # -- the solver's ShardPlan ----------------------------------------------

    def _place(self, params: dynamics.OnnParams) -> dynamics.OnnParams:
        """``params`` in the plan's at-rest placement (as they are without one)."""
        if self._plan is None:
            return params
        from repro.distributed import sharding

        return sharding.shard_onn_params(params, self._plan, self._mesh)

    def _planned(self):
        """The plan's context around a solve that traces (a no-op without one)."""
        if self._plan is None:
            return contextlib.nullcontext()
        return self._plan.context(self._mesh)

    def _slab_sharding(self, width: int):
        """The packed σ slab's sharding on the plan's mesh: lanes over
        ``"data"`` when the plan splits them, else replicated."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        lanes = "data" if self._plan.batch > 1 and width % self._plan.batch == 0 else None
        return NamedSharding(self._mesh, P(lanes, None))

    def slab_attrs(self, bucket_sig: int, batch_bucket: int) -> Dict[str, Any]:
        """Attributes of an ``engine.slab`` span that runs sharded: the plan,
        its devices, the coupling bytes each device holds at rest and the
        bytes each device puts on the wire per cycle to combine its row
        block's fields (the psum buffer: lanes x N bucket, int32, or int8
        on the compressed wire; 0 where W is not row-sharded)."""
        if self._plan is None:
            return {}
        plan = self._plan
        _, params_b = self._padded_instance(bucket_sig)
        w_bytes = max(s.data.nbytes for s in params_b.weights.addressable_shards)
        combine = 0
        if plan.model_sharded:
            lanes = batch_bucket
            if plan.batch > 1 and batch_bucket % plan.batch == 0:
                lanes //= plan.batch
            rows = -(-bucket_sig // plan.model) * plan.model  # W rows, zero-padded
            combine = lanes * rows * (1 if plan.compressed else 4)
        return {
            "plan": f"{plan.batch}x{plan.model}",
            "devices": plan.devices,
            "w_bytes_per_device": int(w_bytes),
            "combine_bytes_per_cycle": int(combine),
        }

    def _draws_randomness(self) -> bool:
        return self.config.mode == "rtl" and self.config.sync_jitter

    def install_params(self, params: dynamics.OnnParams) -> None:
        """Hot-install freshly trained weights; zero recompiles.

        The solver config is untouched and the new pytree has the same
        shapes/dtypes as the old one, so every cached ``retrieve`` /
        ``advance_chunk`` executable keyed on (config, shape) is reused —
        weights are a traced operand, not part of the compile key.  Padded
        per-bucket instances are rebuilt eagerly for the buckets already
        touched (``pad_params`` is a cheap device-side scatter at shapes the
        jit cache has seen).  Live streaming slabs are *not* rewritten: a
        :class:`RetrievalSlab` snapshots its params at ``begin_slab``, so
        in-flight lanes finish on the weights they started with — the
        scheduler retires those slabs at a settle-chunk boundary
        (:meth:`repro.serving.scheduler.ContinuousEngine.hot_swap`).
        """
        cfg = self.config
        weights = jnp.asarray(params.weights)
        if weights.shape != (cfg.n, cfg.n):
            raise ValueError(
                f"hot swap shape mismatch: weights {weights.shape} != ({cfg.n}, {cfg.n})"
            )
        if weights.dtype != jnp.int8:
            raise TypeError(f"hot swap needs int8 weights, got {weights.dtype}")
        dynamics.validate_weights(weights, cfg.weight_bits)
        params = self._place(params)
        self.solver = dataclasses.replace(self.solver, params=params)
        for nb in list(self._padded):
            cfg_b, _ = self._padded[nb]
            self._padded[nb] = (cfg_b, self._place(dynamics.pad_params(cfg, params, nb)))
        self._swaps += 1

    def solve_bucket(
        self,
        bucket_sig: int,
        payloads: List[Any],
        keys: List[jax.Array],
        batch_bucket: int,
    ) -> List[Any]:
        from repro import api  # local: api imports this module

        with spans.span(spans.PACK):
            autotune.warm(n=bucket_sig, batch=batch_bucket)
            cfg_b, params_b = self._padded_instance(bucket_sig)
            lanes2d = [jnp.atleast_2d(jnp.asarray(p, jnp.int8)) for p in payloads]
            counts = [x.shape[0] for x in lanes2d]
            batch = dynamics.pad_sigma(jnp.concatenate(lanes2d, axis=0), bucket_sig)
            total = batch.shape[0]
            if total < batch_bucket:
                pad_rows = jnp.ones((batch_bucket - total, bucket_sig), jnp.int8)
                batch = jnp.concatenate([batch, pad_rows], axis=0)
            if self._plan is not None:  # one transfer to the slab's place on the mesh
                batch = jax.device_put(batch, self._slab_sharding(batch_bucket))

            lane_keys = None
            if self._draws_randomness():
                per_lane: List[jax.Array] = []
                for k, c in zip(keys, counts):
                    per_lane.extend(jax.random.split(k, c))
                lane_keys = _stack_keys(per_lane, batch_bucket)

        with spans.span(spans.SOLVE), self._planned():
            res = api.retrieve(cfg_b, params_b, batch, lane_keys)
        if self._plan is not None:
            self._sharded_slabs += 1
        with spans.span(spans.SPLIT):
            with spans.span(spans.SYNC):
                host = jax.device_get(res)
            self._observe_settle(host, total)
            n = self.config.n
            out: List[Any] = []
            offset = 0
            for p, c in zip(payloads, counts):
                rows = slice(offset, offset + c)
                r = dynamics.ONNResult(
                    final_phase=host.final_phase[rows, :n],
                    final_sigma=host.final_sigma[rows, :n],
                    settle_cycle=host.settle_cycle[rows],
                    settled=host.settled[rows],
                    cycled=host.cycled[rows],
                )
                if np.ndim(p) == 1:  # single-lane payload → unbatched result
                    r = jax.tree.map(lambda x: x[0], r)
                out.append(r)
                offset += c
        return out

    # -- streaming slab protocol (continuous batching: repro.serving) -------
    #
    # A scheduler holds a RetrievalSlab per (N bucket, width), advances it
    # one settle-chunk per tick, harvests lanes as they freeze, and installs
    # queued requests into the freed slots.  Bit-exactness with
    # ``solve_bucket`` holds lane for lane: ``admit`` splits each request
    # key into per-lane keys exactly as the batch path does, and the core's
    # per-lane clocks (``repro.core.dynamics.BatchState``) make an installed
    # lane replay the isolated trajectory regardless of when it joins.

    def begin_slab(self, bucket_sig: int, width: int) -> RetrievalSlab:
        """A fresh all-dead slab of ``width`` lanes at the N bucket."""
        autotune.warm(n=bucket_sig, batch=width)
        cfg_b, params_b = self._padded_instance(bucket_sig)
        with self._planned():
            state = dynamics.dead_batch_state(cfg_b, width)
        if self._plan is not None:
            self._sharded_slabs += 1
        return RetrievalSlab(cfg=cfg_b, params=params_b, state=state, width=width)

    def admit(
        self,
        slab: RetrievalSlab,
        slots: Sequence[int],
        payload: Any,
        key: jax.Array,
    ) -> None:
        """Install one request's lanes into freed slab slots at t = 0."""
        lanes2d = jnp.atleast_2d(jnp.asarray(payload, jnp.int8))
        if len(slots) != lanes2d.shape[0]:
            raise ValueError(f"{len(slots)} slots for {lanes2d.shape[0]} lanes")
        sigma = dynamics.pad_sigma(lanes2d, slab.cfg.n)
        lane_keys = None
        if self._draws_randomness():
            # Identical split to solve_bucket's per-request fan-out.
            lane_keys = _stack_keys(
                list(jax.random.split(key, lanes2d.shape[0])), lanes2d.shape[0]
            )
        with self._planned():
            sub = dynamics.init_batch_state(
                slab.cfg, dynamics.initial_phase(slab.cfg, sigma), lane_keys
            )
            slab.state = dynamics.install_lanes(
                slab.state, sub, jnp.asarray(slots, jnp.int32)
            )

    def advance(self, slab: RetrievalSlab) -> None:
        """Advance every live lane by one settle-chunk (one device dispatch)."""
        with self._planned():
            slab.state = dynamics.advance_chunk(slab.cfg, slab.params, slab.state)

    def done_mask(self, slab: RetrievalSlab) -> Any:
        """(width,) host bool array: lanes whose results are final."""
        done = dynamics.batch_done(slab.cfg, slab.state)
        with spans.span(spans.SYNC):
            return jax.device_get(done)

    def results(self, slab: RetrievalSlab) -> dynamics.ONNResult:
        """Slab-wide results on the host (call once per harvest tick, then
        ``extract``).

        Fetched eagerly on purpose: the caller has already synced on
        ``done_mask``, so the chunk is finished, and host-side numpy rows
        let ``extract``/``observe`` slice without dispatching eager gathers
        against the slab's sharded device arrays (those compile per
        (shape, sharding) and would leak XLA compiles into steady-state
        serving)."""
        res = dynamics.batch_result(slab.cfg, slab.state)
        with spans.span(spans.SYNC):
            return jax.device_get(res)

    def extract(
        self, res: dynamics.ONNResult, slots: Sequence[int], payload: Any
    ) -> dynamics.ONNResult:
        """One request's result rows out of a slab-wide ``results``."""
        idx = np.asarray(slots, np.int32)
        n = self.config.n
        r = dynamics.ONNResult(
            final_phase=res.final_phase[idx, :n],
            final_sigma=res.final_sigma[idx, :n],
            settle_cycle=res.settle_cycle[idx],
            settled=res.settled[idx],
            cycled=res.cycled[idx],
        )
        if np.ndim(payload) == 1:  # single-lane payload → unbatched
            r = jax.tree.map(lambda x: x[0], r)
        return r

    def observe(self, res: dynamics.ONNResult, slots: Sequence[int]) -> None:
        """Feed harvested lanes into the settle-cycle EMA (streaming path)."""
        idx = np.asarray(slots, np.int32)
        rows = jax.tree.map(lambda x: x[idx], res)
        self._observe_settle(rows, len(slots))

    # -- measured settle-cycle cost model ----------------------------------

    def _observe_settle(self, res: Any, lanes: int) -> None:
        """Fold one slab's measured settle cycles into the EMA (real lanes
        only; unsettled/cycled lanes are charged the worst case).  ``res``
        holds host rows: the slab's result already fetched by the caller."""
        if lanes <= 0:
            return
        mc = self.config.max_cycles
        eff = np.where(res.settled[:lanes], res.settle_cycle[:lanes] + 1, mc)
        mean_eff = float(np.mean(eff.astype(np.float32)))
        a = self.SETTLE_EMA_ALPHA
        self._settle_ema = (
            mean_eff
            if self._settle_ema is None
            else (1 - a) * self._settle_ema + a * mean_eff
        )
        self._settle_obs += 1

    def expected_cycles(self) -> float:
        """Quoted oscillation cycles per solve: worst-case ``max_cycles``
        blended toward the measured settle-cycle EMA as slabs are observed
        (the early-exit batched solve really does stop at the EMA, so the
        quote converges on executed work instead of the scan bound)."""
        mc = float(self.config.max_cycles)
        if self._settle_ema is None:
            return mc
        c = self._settle_obs / (self._settle_obs + self.SETTLE_WARMUP)
        return c * min(self._settle_ema, mc) + (1.0 - c) * mc

    def stats(self) -> Dict[str, Any]:
        """Measured settle-cycle state (surfaced by ``Engine.stats()``)."""
        return {
            "max_cycles": self.config.max_cycles,
            "settle_ema_cycles": self._settle_ema,
            "settle_slabs_observed": self._settle_obs,
            "expected_cycles": round(self.expected_cycles(), 3),
            "hot_swaps": self._swaps,
            "sharded_slabs": self._sharded_slabs,
            "autotune": autotune.cache_info(),
        }

    def _hybrid_parallel(self) -> int:
        """MAC width P of the configured datapath (1 off the hybrid backend)."""
        cfg = self.config
        return cfg.hybrid_parallel if cfg.backend == "hybrid" else 1

    def cost_units(self, bucket_sig: int, batch_bucket: int) -> float:
        cfg = self.config
        if cfg.backend == "hybrid":
            # The serialized schedule charges the full pass grid, idle ragged-
            # tail MAC lanes included: ceil(N/P) passes of P lanes per row.
            p = min(cfg.hybrid_parallel, bucket_sig)
            per_cycle = bucket_sig * (-(-bucket_sig // p)) * p
        else:
            per_cycle = bucket_sig * bucket_sig
        cycles = self.expected_cycles() * (cfg.clocks_per_cycle if cfg.mode == "rtl" else 1)
        return float(batch_bucket) * per_cycle * cycles

    def _bits(self) -> hw.BitConfig:
        return hw.BitConfig(self.config.weight_bits, self.config.phase_bits)

    def fpga_seconds(self, bucket_sig: int) -> Optional[float]:
        # The paper hardware runs the *unpadded* instance; quote its design
        # at the configured serialized-MAC width (P=1 unless backend=hybrid).
        return hw.time_to_solution(
            self.config.architecture,
            self.config.n,
            self.config.max_cycles,
            self._bits(),
            parallel=self._hybrid_parallel(),
        )

    def fpga_tradeoff(self, bucket_sig: int) -> Dict[str, Optional[float]]:
        """Per-design hardware quotes for this instance (paper Table 5 trade);
        see :func:`_fpga_design_tradeoff`."""
        cfg = self.config
        return _fpga_design_tradeoff(cfg.n, cfg.max_cycles, self._bits(), self._hybrid_parallel())


# ---------------------------------------------------------------------------
# Max-cut: batched oscillatory Ising machine (paper §2.2)
# ---------------------------------------------------------------------------


class MaxCutEngineSolver:
    """Serves (N, N) adjacency matrices; one lane per request.

    Instances are padded to the N bucket with isolated (zero-degree)
    vertices, and the batched annealer's randomness is counter-based per
    vertex index (``repro.core.ising``), so a padded solve is *bit-identical*
    on the real vertices to the unpadded solve: the same (adjacency, key)
    returns the same cut under every bucket policy and occupancy.  Requests
    with different true N coalesce inside one bucket, each carrying its own
    ``true_n`` mask.

    Each request runs ``replicas`` independent anneals of ``sweeps``
    grouped-staggered sweeps through the configured ``backend``
    (parallel / serial / pallas / hybrid with ``parallel_factor``), with
    optional per-replica early exit on cut-value ``stagnation``.  Compiles
    are keyed through the core's one-executable-per-(config, shape) jit
    story — per-bucket configs live in a dict bounded by the buckets
    actually touched, and repeated installs of the same settings share one
    executable (there is no unbounded per-install compile cache).
    """

    def __init__(
        self,
        solver: Optional[Any] = None,
        sweeps: int = 64,
        weight_bits: int = 5,
        replicas: int = 1,
        stagger_groups: int = 0,
        stagnation: int = 0,
        backend: str = "parallel",
        parallel_factor: int = 0,
        hybrid_impl: str = "scan",
        settle_chunk: int = 8,
    ):
        if solver is not None:  # wrap an api.MaxCutSolver's settings
            sweeps, weight_bits = solver.sweeps, solver.weight_bits
            replicas, stagger_groups = solver.replicas, solver.stagger_groups
            stagnation, backend = solver.stagnation, solver.backend
            parallel_factor = solver.parallel_factor
            hybrid_impl, settle_chunk = solver.hybrid_impl, solver.settle_chunk
        self.sweeps = int(sweeps)
        self.weight_bits = int(weight_bits)
        self.replicas = int(replicas)
        self.stagger_groups = int(stagger_groups)
        self.stagnation = int(stagnation)
        self.parallel_factor = int(parallel_factor)
        self.hybrid_impl = str(hybrid_impl)
        self.settle_chunk = int(settle_chunk)
        if self.replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {self.replicas}")
        # Probe config: validates the backend/route combination once and
        # normalizes legacy spellings (parallel_factor>0 selects hybrid).
        probe = dynamics.ONNConfig(
            n=max(1, self.parallel_factor),
            weight_bits=self.weight_bits,
            max_cycles=self.sweeps,
            backend=str(backend),
            parallel_factor=self.parallel_factor,
            hybrid_impl=self.hybrid_impl,
            settle_chunk=self.settle_chunk,
        )
        self.backend = probe.backend
        self._cfgs: Dict[int, dynamics.ONNConfig] = {}  # bounded: one per N bucket

    def _bucket_config(self, n_bucket: int) -> dynamics.ONNConfig:
        if n_bucket not in self._cfgs:
            self._cfgs[n_bucket] = dynamics.ONNConfig(
                n=n_bucket,
                weight_bits=self.weight_bits,
                max_cycles=self.sweeps,
                backend=self.backend,
                parallel_factor=self.parallel_factor,
                hybrid_impl=self.hybrid_impl,
                settle_chunk=self.settle_chunk,
            )
        return self._cfgs[n_bucket]

    def lane_count(self, payload: Any) -> int:
        return 1

    def signature(self, payload: Any) -> Hashable:
        shape = np.shape(payload)
        if len(shape) != 2 or shape[0] != shape[1]:
            raise ValueError(f"max-cut payload must be square, got {shape}")
        return shape[0]

    def bucket(self, signature: int, n_policy: bucketing.NBucketPolicy) -> int:
        return bucketing.bucket_n(signature, n_policy)

    def solve_bucket(
        self,
        bucket_sig: int,
        payloads: List[Any],
        keys: List[jax.Array],
        batch_bucket: int,
    ) -> List[Any]:
        nb = bucket_sig
        # Ising's staggered sweep contracts (group, N) row slabs through the
        # same weighted_sum kernels; warm the tuner on the replica-expanded
        # batch so the first solve at this bucket resolves blocks cache-hot.
        with spans.span(spans.PACK):
            autotune.warm(
                n=nb, batch=max(1, batch_bucket * self.replicas), kinds=("step", "hybrid")
            )
            cfg = self._bucket_config(nb)
            padded, true_n = [], []
            for p in payloads:
                a = jnp.asarray(p)
                pad = nb - a.shape[0]
                padded.append(jnp.pad(a, ((0, pad), (0, pad))))
                true_n.append(a.shape[0])
            while len(padded) < batch_bucket:  # dead rows: zero graph, no vertices
                padded.append(jnp.zeros((nb, nb), padded[0].dtype))
                true_n.append(0)
            graphs = jnp.stack(padded)
            lane_keys = _stack_keys(list(keys), batch_bucket)
            true_n = jnp.asarray(true_n, jnp.int32)
        with spans.span(spans.SOLVE):
            res = ising_lib.solve_maxcut_batch(
                cfg,
                graphs,
                lane_keys,
                replicas=self.replicas,
                stagger_groups=self.stagger_groups,
                stagnation=self.stagnation,
                true_n=true_n,
            )
        with spans.span(spans.SPLIT):
            with spans.span(spans.SYNC):
                host = jax.device_get(res)
            out = [
                ising_lib.MaxCutResult(
                    sigma=host.sigma[i, : np.shape(p)[0]],
                    cut_value=host.cut_value[i],
                    trace=host.trace[i],
                    replica_cuts=host.replica_cuts[i],
                    sweeps_run=host.sweeps_run[i],
                )
                for i, p in enumerate(payloads)
            ]
        return out

    def stats(self) -> Dict[str, Any]:
        """Static solve parameters (surfaced by ``Engine.stats()``)."""
        return {
            "sweeps": self.sweeps,
            "replicas": self.replicas,
            "stagger_groups": self.stagger_groups,
            "stagnation": self.stagnation,
            "backend": self.backend,
            "n_buckets_compiled": sorted(self._cfgs),
        }

    def _hybrid_parallel(self, n: int) -> int:
        cfg = self._bucket_config(n)
        return cfg.hybrid_parallel if cfg.backend == "hybrid" else 1

    def _cycles(self) -> float:
        # One staggered sweep ≈ one oscillation cycle (every oscillator's
        # enable fires once per period); replicas anneal back to back.
        return float(self.sweeps * self.replicas)

    def _bits(self) -> hw.BitConfig:
        return hw.BitConfig(weight_bits=self.weight_bits)

    def cost_units(self, bucket_sig: int, batch_bucket: int) -> float:
        """Executed work of one slab: each of a sweep's K update groups
        evaluates the field only at its ceil(N/K)-row member window, so a
        full sweep streams K·ceil(N/K) ≥ N coupling rows (the over-covered
        window tail included) — on the hybrid backend each row costs the
        full pass grid (ceil(N/P) passes of P MAC lanes, idle tail
        included)."""
        cfg = self._bucket_config(bucket_sig)
        if cfg.backend == "hybrid":
            p = min(cfg.hybrid_parallel, bucket_sig)
            per_row = (-(-bucket_sig // p)) * p
        else:
            per_row = bucket_sig
        k = ising_lib.resolve_stagger_groups(self.stagger_groups, bucket_sig)
        rows_per_sweep = k * (-(-bucket_sig // k))
        return float(batch_bucket) * self.replicas * self.sweeps * rows_per_sweep * per_row

    def fpga_seconds(self, bucket_sig: int) -> Optional[float]:
        return hw.time_to_solution(
            "hybrid",
            bucket_sig,
            self._cycles(),
            self._bits(),
            parallel=self._hybrid_parallel(bucket_sig),
        )

    def fpga_tradeoff(self, bucket_sig: int) -> Dict[str, Optional[float]]:
        """Per-design hardware quotes for an Ising request — the planner
        shows the recurrent-vs-hybrid trade for max-cut exactly as it does
        for retrieval; see :func:`_fpga_design_tradeoff`."""
        return _fpga_design_tradeoff(
            bucket_sig,
            self._cycles(),
            self._bits(),
            self._hybrid_parallel(bucket_sig),
        )


# ---------------------------------------------------------------------------
# LM decode: the transformer/SSM serving loop as an engine workload
# ---------------------------------------------------------------------------


class LMEngineSolver:
    """Serves prompt → greedy-decode requests for one model instance.

    Payload: ``{"tokens": (L,) or (B, L) int32, "max_new_tokens": int}``
    plus optional ``"vision"`` / ``"frames"`` arrays for VLM/enc-dec
    families.  Buckets are (prompt_len, max_new_tokens[, extras]); lanes
    coalesce along batch, padded lanes decode zero prompts whose outputs are
    dropped (batch rows are independent, so real lanes are unaffected).
    PRNG: the construction key (params init) and per-slab cache key are
    explicit engine-split keys — no hidden ``PRNGKey(0)``.
    """

    def __init__(self, arch: str, key: jax.Array, reduced: bool = True):
        from repro import configs
        from repro.models import params as PM
        from repro.models import steps as steps_lib
        from repro.models.model import get_model

        self.arch = arch
        self.cfg = configs.get_reduced(arch) if reduced else configs.get_config(arch)
        self.model = get_model(self.cfg)
        k_params, self._cache_key = jax.random.split(jnp.asarray(key))
        self.params = PM.materialize(self.model.param_specs, k_params)
        self._generate = steps_lib.make_generate(self.model)
        self.last_timing: Dict[str, float] = {}
        #: Per-slab timings since construction (a drain may run many slabs).
        self.timings: List[Dict[str, float]] = []

    def lane_count(self, payload: Dict[str, Any]) -> int:
        toks = jnp.asarray(payload["tokens"])
        return 1 if toks.ndim == 1 else toks.shape[0]

    def signature(self, payload: Dict[str, Any]) -> Hashable:
        toks = jnp.asarray(payload["tokens"])
        extras = tuple(sorted(k for k in payload if k not in ("tokens", "max_new_tokens")))
        return (toks.shape[-1], int(payload["max_new_tokens"]), extras)

    def bucket(self, signature: Hashable, n_policy: bucketing.NBucketPolicy) -> Hashable:
        return signature  # prompts are not length-padded (no attention mask yet)

    def solve_bucket(
        self,
        bucket_sig: Hashable,
        payloads: List[Dict[str, Any]],
        keys: List[jax.Array],
        batch_bucket: int,
    ) -> List[Any]:
        prompt_len, max_new, extras = bucket_sig
        lanes = [jnp.atleast_2d(jnp.asarray(p["tokens"], jnp.int32)) for p in payloads]
        counts = [x.shape[0] for x in lanes]
        tokens = jnp.concatenate(lanes, axis=0)
        total = tokens.shape[0]
        if total < batch_bucket:
            tokens = jnp.concatenate(
                [tokens, jnp.zeros((batch_bucket - total, prompt_len), jnp.int32)]
            )
        batch_in: Dict[str, Any] = {"tokens": tokens}
        for name in extras:
            arrs = []
            for p in payloads:
                a = jnp.asarray(p[name])
                one = jnp.asarray(p["tokens"]).ndim == 1
                arrs.append(a[None] if one else a)
            extra = jnp.concatenate(arrs, axis=0)
            if total < batch_bucket:
                pad_shape = (batch_bucket - total,) + extra.shape[1:]
                extra = jnp.concatenate([extra, jnp.zeros(pad_shape, extra.dtype)])
            batch_in[name] = extra

        self._cache_key, ck = jax.random.split(self._cache_key)
        out_tokens, self.last_timing = self._generate(self.params, batch_in, max_new, ck)
        self.timings.append(self.last_timing)

        results = []
        offset = 0
        for p, c in zip(payloads, counts):
            rows = out_tokens[offset : offset + c]
            if jnp.asarray(p["tokens"]).ndim == 1:
                rows = rows[0]
            results.append(rows)
            offset += c
        return results

    def cost_units(self, bucket_sig: Hashable, batch_bucket: int) -> float:
        prompt_len, max_new, _ = bucket_sig
        # prefill is O(L · d²· layers); each decode step O(d² · layers).
        per_tok = self.cfg.n_layers * self.cfg.d_model * self.cfg.d_model
        return float(batch_bucket) * (prompt_len + max_new) * per_tok

    def fpga_seconds(self, bucket_sig: Hashable) -> Optional[float]:
        return None  # no ONN mapping for the LM workload


register_solver("lm", LMEngineSolver, "greedy LM decode loop (prefill + serve steps)")
