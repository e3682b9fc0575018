"""Public facade for the ONN reproduction: one import surface, one protocol.

Everything a caller needs rides on three ideas:

* **Config is static, numbers are traced.**  ``ONNConfig`` selects sizes,
  mode and weighted-sum backend; ``OnnParams`` (weights, bias) and
  ``OnnState`` are pytrees, so ``run``/``retrieve`` compile once per
  (config, shape) and compose with ``jax.vmap`` over params (many problem
  instances, one executable), sharding, and donation.

* **One backend table.**  ``ONNConfig.backend`` ∈ {"parallel", "pallas",
  "hybrid"} picks the weighted-sum schedule for *both* functional and rtl
  modes; all are bit-exact.  ``hybrid`` is the paper's serialized-MAC
  schedule as an executable model (``parallel_factor`` sets the MAC width
  P; ceil(N/P) passes per cycle): it is chosen for its FPGA quotes and is
  never faster on the chip.

* **One solver surface.**  A ``Solver`` maps a problem instance to a result
  under an explicit PRNG key.  ``RetrievalSolver`` (batched associative
  memory — the paper's benchmark task) and ``MaxCutSolver`` (oscillatory
  Ising machine — the paper's §2.2 motivation) both implement it, so serving
  loops and benchmarks can hold "a solver" without caring which workload it
  runs.

Quickstart::

    from repro import api

    cfg = api.ONNConfig(n=100, architecture="hybrid", backend="parallel")
    params = api.make_params(cfg, quantized_weights)
    out = api.retrieve(cfg, params, corrupted_batch, keys=jax.random.PRNGKey(0))
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Optional, Protocol, runtime_checkable

import jax

from repro.core import ising as _ising
from repro.core.dynamics import (  # noqa: F401 — re-exported API
    BACKENDS,
    ONNConfig,
    ONNResult,
    OnnParams,
    OnnState,
    async_sweep,
    functional_update,
    init_state,
    initial_phase,
    make_params,
    pad_config,
    pad_params,
    pad_sigma,
    retrieve,
    run,
    run_batch,
    sign_update,
    step,
    validate_weights,
    weighted_sum,
)
from repro.core.ising import (  # noqa: F401 — re-exported API
    MaxCutResult,
    solve_maxcut_batch,
)
from repro.core.learning import diederich_opper_i
from repro.core.quantization import quantize_weights
from repro.distributed.plan import ShardPlan
from repro.engine.registry import register_solver


@runtime_checkable
class Solver(Protocol):
    """A problem-instance → result map under an explicit PRNG key.

    ``instance`` is workload-specific: a batch of corrupted spin patterns for
    retrieval, an adjacency matrix for max-cut.  Implementations must be pure
    given (instance, key) — no hidden default keys.
    """

    def solve(self, instance: jax.Array, key: Optional[jax.Array] = None) -> Any:
        ...


@dataclasses.dataclass(frozen=True, eq=False)
class RetrievalSolver:
    """Batched pattern retrieval on a fixed trained ONN (paper Fig. 7).

    ``solve`` takes a (B, N) ±1 batch of (corrupted) patterns and an optional
    key — required only when the config draws randomness (rtl sync_jitter); a
    single key is split into one subkey per request.

    ``plan`` (a :class:`repro.distributed.ShardPlan`) belongs to the solver:
    installed on an engine, the adapter builds the plan's mesh once, places
    the coupling matrix for it (row-sharded over ``"model"``) and runs every
    solve under it, from whatever thread drains.  ``solve`` runs under it too.
    ``None`` (or a one-device plan) solves on one device.
    """

    config: ONNConfig
    params: OnnParams
    plan: Optional[ShardPlan] = None

    @classmethod
    def from_patterns(
        cls,
        xi: jax.Array,
        *,
        weight_bits: int = 5,
        **cfg_kwargs: Any,
    ) -> "RetrievalSolver":
        """Train DO-I couplings on patterns ``xi`` (P, N) and quantize."""
        do = diederich_opper_i(xi)
        qw = quantize_weights(do.weights, bits=weight_bits)
        cfg = ONNConfig(n=xi.shape[1], weight_bits=weight_bits, **cfg_kwargs)
        return cls(config=cfg, params=make_params(cfg, qw.values))

    def solve(self, instance: jax.Array, key: Optional[jax.Array] = None) -> ONNResult:
        planned = self.plan is not None and self.plan.devices > 1
        with self.plan.context() if planned else contextlib.nullcontext():
            return retrieve(self.config, self.params, instance, key)

    def as_engine_solver(self):
        """This solver as an installable ``repro.engine`` workload adapter
        (it carries ``plan`` to the adapter)."""
        from repro.engine.adapters import RetrievalEngineSolver

        return RetrievalEngineSolver(solver=self)


@dataclasses.dataclass(frozen=True)
class MaxCutSolver:
    """Batched oscillatory Ising machine on a max-cut embedding (paper §2.2).

    ``solve`` takes an (N, N) adjacency matrix — or a (B, N, N) batch of
    same-size instances — and a required key (initial spins + per-sweep
    update groups).  Each instance runs ``replicas`` independent anneals of
    ``sweeps`` grouped-staggered sweeps (``stagger_groups`` update groups
    per sweep; 0 → auto, N → fully asynchronous), with every field
    evaluation dispatched through the same ``backend`` table as retrieval —
    ``"hybrid"`` with ``parallel_factor`` runs the serialized-MAC datapath.
    ``stagnation`` > 0 freezes a replica after that many sweeps without a
    better cut (early exit, checked every ``settle_chunk`` sweeps).
    """

    sweeps: int = 64
    weight_bits: int = 5
    replicas: int = 1
    stagger_groups: int = 0  # update groups K per sweep (0 = auto, n = async)
    stagnation: int = 0  # sweeps without improvement before freeze (0 = off)
    backend: str = "parallel"
    parallel_factor: int = 0
    settle_chunk: int = 8

    def config(self, n: int) -> ONNConfig:
        """The backend-carrying ONN config of an N-vertex solve."""
        return ONNConfig(
            n=n,
            weight_bits=self.weight_bits,
            max_cycles=self.sweeps,
            backend=self.backend,
            parallel_factor=self.parallel_factor,
            settle_chunk=self.settle_chunk,
        )

    def solve(self, instance: jax.Array, key: Optional[jax.Array] = None) -> MaxCutResult:
        if key is None:
            raise ValueError("MaxCutSolver.solve requires a PRNG key")
        instance = jax.numpy.asarray(instance)
        return _ising.solve_maxcut_batch(
            self.config(instance.shape[-1]),
            instance,
            key,
            replicas=self.replicas,
            stagger_groups=self.stagger_groups,
            stagnation=self.stagnation,
        )

    def as_engine_solver(self):
        """This solver as an installable ``repro.engine`` workload adapter."""
        from repro.engine.adapters import MaxCutEngineSolver

        return MaxCutEngineSolver(solver=self)


# ---------------------------------------------------------------------------
# Engine registration: both Solver implementations serve through repro.engine
# ---------------------------------------------------------------------------


def _retrieval_engine_factory(**kwargs: Any):
    from repro.engine.adapters import RetrievalEngineSolver

    return RetrievalEngineSolver(**kwargs)


def _maxcut_engine_factory(**kwargs: Any):
    from repro.engine.adapters import MaxCutEngineSolver

    return MaxCutEngineSolver(**kwargs)


register_solver(
    "retrieval",
    _retrieval_engine_factory,
    "batched pattern retrieval on a trained ONN (xi= patterns or solver=)",
)
register_solver(
    "maxcut",
    _maxcut_engine_factory,
    "batched multi-replica Ising-machine max-cut (sweeps=, replicas=, "
    "stagger_groups=, backend=, parallel_factor=)",
)
