"""Pure functional ONN dynamics over registered pytrees.

This is the core API of the repo.  All entry points are pure functions of

* ``ONNConfig``  — the only *static* argument: sizes, bit widths, mode,
  backend.  Hashable frozen dataclass; jit specializes on it.
* ``OnnParams``  — the coupling matrix and bias as a *traced* pytree.  Two
  different weight matrices of the same N share one compiled executable,
  and params compose with ``jax.vmap`` (many problems, one compile),
  ``jax.device_put`` sharding, and donation.
* ``OnnState``   — the per-run dynamical state (phases + settle bookkeeping),
  also a traced pytree, so ``step`` can be scanned, checkpointed, or driven
  one cycle at a time from a server loop.

Simulation fidelities (``ONNConfig.mode``):

* ``functional`` — one synchronous phase update per oscillation cycle.  Both
  FPGA architectures compute the identical integer weighted sum, so in this
  mode they are the same map: σ(t+1) = sign-align(W σ(t)).
* ``rtl`` — clock-accurate: the phase is updated every slow-clock edge
  (2**phase_bits per oscillation cycle), amplitudes are evaluated in the lab
  frame, and the *hybrid* architecture consumes amplitudes sampled one slow
  clock earlier (paper Fig. 6).  ``sync_jitter`` randomizes the enable-signal
  offset within the period, as on the real board.

Weighted-sum backends (``ONNConfig.backend``), one dispatch table shared by
both modes:

* ``parallel`` — fully parallel einsum (the recurrent adder tree, Fig. 4).
* ``pallas``   — the blocked TPU kernel (``repro.kernels``), interpret mode
  on CPU.  In functional mode the full cycle is one fused kernel launch
  (int8 matmul + bias + phase-align epilogue over the real batch grid).
* ``hybrid``   — the cycle-faithful emulation of the paper's hybrid
  coupling datapath: the N×N coupling is serialized into
  ``ceil(N / parallel_factor)`` passes of ``parallel_factor``-wide integer
  MACs over int8-carried weights (``coupling.weighted_sum_serial``).
  ``parallel_factor`` (P) is the architecture's parallelism knob: P=1 is
  the paper's single-MAC hybrid, P=N degenerates to the recurrent parallel
  schedule.  It is the executable model of the FPGA schedule, chosen for
  its hardware quotes; it is never faster on a TPU.

All backends are bit-exact (integer associativity); spins are ±1 ``int8``,
weights ``weight_bits``-bit signed carried in ``int8``, sums exact ``int32``.

Batched-native solve (``run_batch`` / ``retrieve``): the serving hot path is
(B, N)-first — one compiled executable advances the whole request batch per
oscillation cycle and a chunked ``lax.while_loop`` exits as soon as every
lane is settled or in a detected period-2 orbit (``ONNConfig.settle_chunk``
sets the check granularity).  Early exit is bit-exact, lane for lane, with
the fixed-length scan of ``run`` — see the batched-dynamics section below
for the freeze/parity argument.  ``run`` keeps the fixed-length reference
scan; the equivalence is property-tested across backends and modes.
"""

from __future__ import annotations

import collections
import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import coupling as coupling_lib
from repro.core import oscillator as osc
from repro.core.quantization import check_weight_range

_BACKEND_NAMES = ("parallel", "pallas", "hybrid")

#: Auto ``parallel_factor`` (P) for ``backend="hybrid"`` when the config
#: leaves it 0: wide enough that the serialized schedule is usable in
#: software, small enough that the serialization is real (ceil(N/P) > 1 for
#: every N above the paper's recurrent capacity point).
DEFAULT_PARALLEL_FACTOR = 32

#: Traces per public entry point, incremented at trace (not call) time.
#: Tests assert "two same-shape weight matrices, one compile" against this.
TRACE_COUNTER: collections.Counter = collections.Counter()


@dataclasses.dataclass(frozen=True)
class ONNConfig:
    """Static configuration of one digital ONN instance.

    This is the only static argument of the functional API: everything
    numeric (weights, bias, phases) is traced.  ``backend`` selects the
    weighted-sum schedule, and ``parallel_factor`` is legal only with
    ``backend="hybrid"``.
    """

    n: int
    weight_bits: int = 5
    phase_bits: int = 4
    architecture: str = "hybrid"  # "recurrent" | "hybrid"
    mode: str = "functional"  # "functional" | "rtl"
    max_cycles: int = 100
    sync_jitter: bool = False  # randomize enable-signal offset (rtl hybrid)
    backend: str = "parallel"  # "parallel" | "pallas" | "hybrid"
    #: Parallelism P of the ``hybrid`` backend: the coupling sum is computed
    #: in ``ceil(n / P)`` serialized passes of P-wide integer MACs (the
    #: paper's serialized-MAC datapath with P parallel coupling elements).
    #: P=1 is the paper's single-MAC hybrid, P=n is one pass (the recurrent
    #: parallel schedule).  0 → auto (``DEFAULT_PARALLEL_FACTOR``, clamped
    #: to n).  Any other backend rejects a nonzero value.
    parallel_factor: int = 0
    #: Cycles simulated between early-exit checks of the batched solve
    #: (``run_batch``/``retrieve``).  Every ``settle_chunk`` cycles the
    #: while-loop tests whether all lanes have frozen (settled, or in a
    #: detected period-2 orbit) and stops — networks that settle in ~5
    #: cycles skip the remaining ~95 W·σ products of ``max_cycles``.
    #: 0 disables early exit (one fixed-length chunk of ``max_cycles``).
    settle_chunk: int = 8
    #: Move the 4-bit phase state across the kernel-operand boundary packed
    #: two counters per byte (the paper's precision-matched storage).  The
    #: solver state stays unpacked; on the ``pallas`` functional path the
    #: kernels read/write the packed layout and derive σ from θ in-register,
    #: halving the per-lane bytes per MAC tile.  Other backends are a
    #: documented bit-exact no-op (packing is a transport layout, not a
    #: semantic change), so the flag is legal on any backend.  Requires
    #: ``phase_bits <= 4`` (two counters must fit one byte).
    phase_pack: bool = False

    def __post_init__(self) -> None:
        if self.architecture not in ("recurrent", "hybrid"):
            raise ValueError(f"unknown architecture {self.architecture!r}")
        if self.mode not in ("functional", "rtl"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.settle_chunk < 0:
            raise ValueError(f"settle_chunk must be >= 0, got {self.settle_chunk}")
        if self.backend not in _BACKEND_NAMES:
            raise ValueError(
                f"unknown backend {self.backend!r}; expected one of {_BACKEND_NAMES}"
            )
        if self.parallel_factor < 0:
            raise ValueError(
                f"parallel_factor must be >= 0, got {self.parallel_factor}"
            )
        if self.backend != "hybrid" and self.parallel_factor > 0:
            # parallel_factor parameterizes only the hybrid schedule; a
            # nonzero value on another backend is a config mistake, not a
            # silent no-op (and would fork jit cache keys).
            raise ValueError(
                f"parallel_factor={self.parallel_factor} only applies to "
                f'backend="hybrid", not {self.backend!r}'
            )
        if self.phase_pack and self.phase_bits > 4:
            raise ValueError(
                f"phase_pack packs two phase counters per byte, which needs "
                f"phase_bits <= 4; got phase_bits={self.phase_bits}"
            )

    @property
    def clocks_per_cycle(self) -> int:
        return 1 << self.phase_bits

    @property
    def hybrid_parallel(self) -> int:
        """Resolved parallelism P of the hybrid schedule (clamped to n).

        ``pad_config`` freezes this resolved value before growing ``n``, so
        bucketing a hybrid instance never widens the datapath — padding adds
        idle passes over zero columns, not MAC lanes.
        """
        p = self.parallel_factor if self.parallel_factor > 0 else DEFAULT_PARALLEL_FACTOR
        return min(p, self.n)

    @property
    def hybrid_passes(self) -> int:
        """Serialized MAC passes per phase update: ``ceil(n / P)``."""
        p = self.hybrid_parallel
        return -(-self.n // p)


class OnnParams(NamedTuple):
    """Learned/embedded problem parameters — a traced pytree leaf pair."""

    weights: jax.Array  # (N, N) int8 coupling matrix
    bias: jax.Array  # (N,) int32 per-oscillator field offset


class OnnState(NamedTuple):
    """Dynamical state of one run — a traced pytree, scanned by ``run``."""

    phase: jax.Array  # (N,) uint8 rotating-frame phase counters
    prev_phase: jax.Array  # (N,) phases one cycle earlier (period-2 check)
    first_cycle: jax.Array  # bool: prev_phase not yet populated
    settle_cycle: jax.Array  # int32 first cycle with no phase change
    settled: jax.Array  # bool
    cycled: jax.Array  # bool: entered a period-2 orbit
    cycle: jax.Array  # int32 cycles elapsed


class ONNResult(NamedTuple):
    """Outcome of one ONN run.

    ``settle_cycle``: first oscillation cycle at which the phase state stopped
    changing (units of paper Table 7); only meaningful where ``settled``.
    ``cycled``: the synchronous dynamics entered a period-2 orbit (a Hopfield
    limit cycle — reported as a time-out, as the paper excludes them).
    """

    final_phase: jax.Array
    final_sigma: jax.Array
    settle_cycle: jax.Array
    settled: jax.Array
    cycled: jax.Array


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def make_params(
    cfg: ONNConfig, weights: jax.Array, bias: Optional[jax.Array] = None
) -> OnnParams:
    """Validate and canonicalize a coupling matrix + bias into ``OnnParams``."""
    weights = jnp.asarray(weights)
    if weights.shape != (cfg.n, cfg.n):
        raise ValueError(f"weights {weights.shape} != ({cfg.n}, {cfg.n})")
    if weights.dtype != jnp.int8:
        raise TypeError(f"weights must be int8, got {weights.dtype}")
    if bias is None:
        bias = jnp.zeros((cfg.n,), jnp.int32)
    else:
        bias = jnp.asarray(bias, jnp.int32)
        if bias.shape != (cfg.n,):
            raise ValueError(f"bias {bias.shape} != ({cfg.n},)")
    return OnnParams(weights=weights, bias=bias)


def validate_weights(weights: jax.Array, bits: int) -> None:
    """Raise if the coupling matrix is out of the representable range."""
    ok = bool(check_weight_range(weights, bits))
    if not ok:
        raise ValueError(f"coupling weights exceed {bits}-bit signed range")


# ---------------------------------------------------------------------------
# Masked-lane padding: grow an instance to a bucketed N without changing it
# ---------------------------------------------------------------------------
#
# The serving engine (repro.engine) pads every request to a small set of
# (batch, N) buckets so one jitted executable serves many problem sizes.  The
# padding is *exact*, not approximate, because of two properties of the sign
# dynamics:
#
# * a zero-padded coupling row/column contributes 0 to every real
#   oscillator's integer weighted sum, and
# * a padded oscillator sees field 0, and ties keep the current spin
#   (``sign_update``), so its phase never changes — it is settled from
#   cycle 0 and cannot trigger the period-2 detector.
#
# Hence ``run``/``retrieve`` on (pad_config, pad_params, pad_sigma) return
# bit-identical phases, settle cycles and settle/cycled flags on the first
# ``n`` oscillators as the unpadded solve (asserted in tests/test_engine.py).


def pad_config(cfg: ONNConfig, n_to: int) -> ONNConfig:
    """The same config at a bucketed oscillator count ``n_to`` ≥ cfg.n.

    The hybrid backend's resolved MAC width is frozen before growing ``n``:
    an auto (0) or clamped ``parallel_factor`` re-resolved at the padded
    size would widen the datapath, so the bucketed solve would run a
    different serialized schedule than the one configured, quoted by
    ``cost_units`` and modeled by ``fpga_seconds``.  Padding therefore only
    adds idle passes over zero columns, never MAC lanes.
    """
    if n_to < cfg.n:
        raise ValueError(f"pad_config: n_to={n_to} < cfg.n={cfg.n}")
    if cfg.backend == "hybrid":
        return dataclasses.replace(cfg, n=n_to, parallel_factor=cfg.hybrid_parallel)
    return dataclasses.replace(cfg, n=n_to)


def pad_params(cfg: ONNConfig, params: OnnParams, n_to: int) -> OnnParams:
    """Zero-pad couplings and bias from (cfg.n, cfg.n) to (n_to, n_to).

    Padded oscillators are uncoupled (zero row, zero column, zero bias), so
    the dynamics of the first ``cfg.n`` oscillators are bit-exact with the
    unpadded instance under any backend (integer sums gain only zeros).
    """
    if n_to < cfg.n:
        raise ValueError(f"pad_params: n_to={n_to} < cfg.n={cfg.n}")
    pad = n_to - cfg.n
    if pad == 0:
        return params
    return OnnParams(
        weights=jnp.pad(params.weights, ((0, pad), (0, pad))),
        bias=jnp.pad(params.bias, (0, pad)),
    )


def pad_sigma(sigma: jax.Array, n_to: int, value: int = 1) -> jax.Array:
    """Pad ±1 spin patterns (..., n) to (..., n_to) with constant spins.

    The pad value only seeds the (uncoupled, field-0) padded oscillators; any
    ±1 value leaves the real lanes untouched.
    """
    n = sigma.shape[-1]
    if n_to < n:
        raise ValueError(f"pad_sigma: n_to={n_to} < n={n}")
    if n_to == n:
        return sigma
    widths = [(0, 0)] * (sigma.ndim - 1) + [(0, n_to - n)]
    return jnp.pad(sigma, widths, constant_values=value)


# ---------------------------------------------------------------------------
# Weighted-sum backend dispatch (shared by functional and rtl modes)
# ---------------------------------------------------------------------------


def _parallel_sum(cfg: ONNConfig, w: jax.Array, sigma: jax.Array) -> jax.Array:
    return coupling_lib.weighted_sum_parallel(w, sigma)


def _pallas_sum(cfg: ONNConfig, w: jax.Array, sigma: jax.Array) -> jax.Array:
    from repro.kernels import ops as kernel_ops  # lazy: kernels are optional

    return kernel_ops.coupling_sum(w, sigma)


def _hybrid_sum(cfg: ONNConfig, w: jax.Array, sigma: jax.Array) -> jax.Array:
    return coupling_lib.weighted_sum_serial(w, sigma, chunk=cfg.hybrid_parallel)


BACKENDS = {
    "parallel": _parallel_sum,
    "pallas": _pallas_sum,
    "hybrid": _hybrid_sum,
}


def _model_plan():
    """The active (ShardPlan, Mesh) pair if the row-sharded collective is on.

    Trace-time state, like ``_shard_lanes``: the batched entry points
    discriminate their jit caches on :func:`_sharding_cache_key` (which
    includes the plan), so consulting a thread-local here is safe.
    """
    from repro.distributed import sharding as shard_lib

    plan, mesh = shard_lib.current_plan(), shard_lib.current_mesh()
    if plan is None or mesh is None or not plan.model_sharded:
        return None
    return plan, mesh


def _model_sharded_sum(
    cfg: ONNConfig, w: jax.Array, sigma: jax.Array, plan, mesh
) -> jax.Array:
    """S = W σ as a row-sharded ``shard_map`` collective over ``"model"``.

    The software analogue of partitioning the coupling fabric across boards:
    W's rows are split over the ``"model"`` mesh axis, each device runs the
    *configured backend* (parallel / pallas / hybrid — so the int8 MAC
    kernel executes per-device on its row block against the full σ),
    scatters its partial fields into a zero buffer at its block offset,
    and a ``psum`` combines them.  The blocks are disjoint and the zeros of
    other devices are exact, so the integer combine is bit-exact with the
    single-device path for every backend — at any N, including N not
    divisible by the model degree (W is zero-row padded first; padding rows
    is the established bit-exact invariant from ``pad_instance``).

    ``w`` may be a row slab (M ≤ N rows — the Ising window path); σ keeps
    the full contraction width N.  When the plan also data-parallelizes and
    the σ batch divides it, lanes split over ``"data"`` so both mesh axes do
    real work.  ``plan.compressed`` swaps the exact int32 combine for the
    int8 wire format :func:`repro.optim.compress.compressed_psum_scatter`.
    """
    from jax.sharding import PartitionSpec as P

    m = w.shape[0]
    parts = plan.model
    m_pad = -(-m // parts) * parts
    if m_pad != m:
        w = jnp.pad(w, ((0, m_pad - m), (0, 0)))
    blk = m_pad // parts

    def local_block(wb: jax.Array, s: jax.Array) -> jax.Array:
        part = BACKENDS[cfg.backend](cfg, wb, s)  # (..., blk) int32
        idx = jax.lax.axis_index("model")
        if plan.compressed:
            from repro.optim import compress

            return compress.compressed_psum_scatter(part, idx, parts, "model")
        buf = jnp.zeros(part.shape[:-1] + (m_pad,), jnp.int32)
        buf = jax.lax.dynamic_update_slice_in_dim(buf, part, idx * blk, axis=-1)
        return jax.lax.psum(buf, "model")

    lead = None
    if sigma.ndim == 2 and plan.batch > 1 and sigma.shape[0] % plan.batch == 0:
        lead = "data"
    sigma_spec = P(*([lead] + [None] * (sigma.ndim - 1)))
    out_spec = P(*([lead] + [None] * (sigma.ndim - 1)))
    out = jax.shard_map(
        local_block,
        mesh=mesh,
        in_specs=(P("model", None), sigma_spec),
        out_specs=out_spec,
        check_vma=False,
    )(w, sigma)
    return out[..., :m] if m_pad != m else out


def weighted_sum(cfg: ONNConfig, w: jax.Array, sigma: jax.Array) -> jax.Array:
    """S = W σ through the backend selected by ``cfg.backend``.

    Under an active model-sharded :class:`repro.distributed.ShardPlan` the
    backend runs per-device on its coupling-matrix row block inside a
    ``shard_map`` collective (:func:`_model_sharded_sum`) — bit-exact with
    the single-device schedule.  Every backend is bit-exact with
    ``parallel``; ``hybrid`` serializes the sum into ``cfg.hybrid_passes``
    P-wide MAC passes.
    """
    pm = _model_plan()
    if pm is not None:
        return _model_sharded_sum(cfg, w, sigma, *pm)
    return BACKENDS[cfg.backend](cfg, w, sigma)


def sign_update(field: jax.Array, sigma: jax.Array) -> jax.Array:
    """Hopfield sign dynamics with ties keeping the current spin."""
    return jnp.where(field > 0, 1, jnp.where(field < 0, -1, sigma)).astype(jnp.int8)


# ---------------------------------------------------------------------------
# Functional-mode dynamics
# ---------------------------------------------------------------------------


def initial_phase(cfg: ONNConfig, sigma0: jax.Array) -> jax.Array:
    """Canonical phases (0 / half-period) for an initial spin pattern."""
    return osc.phase_of_spin(sigma0, cfg.phase_bits)


def functional_update(cfg: ONNConfig, params: OnnParams, phase: jax.Array) -> jax.Array:
    """One synchronous phase update (rotating frame); ``phase``: (..., N).

    On the pallas backend the whole cycle is one fused kernel launch —
    blocked int8 matmul + bias + phase-align epilogue over the real batch
    grid (``repro.kernels.ops.phase_step``) — instead of a coupling-sum
    kernel followed by elementwise alignment.  With ``cfg.phase_pack`` the
    launch takes a single *packed* operand (two 4-bit counters per byte)
    and derives σ from θ in-register.  Bit-exact every way.

    Under a model-sharded ShardPlan the fused whole-cycle launches are
    bypassed — they need the full square W resident — and the cycle runs as
    coupling collective + bias + alignment instead; the pallas MAC kernel
    still executes, per-device on its row block inside the
    ``shard_map`` of :func:`_model_sharded_sum`.  Bit-exact either way.
    """
    model_sharded = _model_plan() is not None
    if cfg.backend == "pallas" and not model_sharded:
        from repro.kernels import ops as kernel_ops  # lazy: kernels are optional

        half = osc.n_positions(cfg.phase_bits) // 2
        if cfg.phase_pack:
            return kernel_ops.phase_step_packed(
                params.weights, params.bias, phase, half=half
            )
        sigma = osc.spin(phase, cfg.phase_bits)
        return kernel_ops.phase_step(
            params.weights, sigma, params.bias, phase, half=half
        )
    sigma = osc.spin(phase, cfg.phase_bits)
    s = weighted_sum(cfg, params.weights, sigma) + params.bias
    return osc.phase_align(phase, s, cfg.phase_bits)


def _state_of_phase(cfg: ONNConfig, phase0: jax.Array) -> OnnState:
    return OnnState(
        phase=phase0,
        # prev_phase starts as a copy of phase0; first_cycle guards it, so no
        # sentinel value is needed (a 255 sentinel collides with a legal phase
        # at phase_bits == 8).
        prev_phase=phase0,
        first_cycle=jnp.bool_(True),
        settle_cycle=jnp.int32(cfg.max_cycles),
        settled=jnp.bool_(False),
        cycled=jnp.bool_(False),
        cycle=jnp.int32(0),
    )


def init_state(cfg: ONNConfig, sigma0: jax.Array) -> OnnState:
    """Fresh dynamical state for an initial spin pattern."""
    return _state_of_phase(cfg, initial_phase(cfg, sigma0))


def step(cfg: ONNConfig, params: OnnParams, state: OnnState) -> OnnState:
    """One oscillation cycle of the synchronous (functional-mode) dynamics."""
    if cfg.mode != "functional":
        raise ValueError(
            "step() drives the synchronous functional-mode dynamics; "
            f"mode={cfg.mode!r} runs are only available through run()"
        )
    new_phase = functional_update(cfg, params, state.phase)
    unchanged = jnp.all(new_phase == state.phase)
    is_cycle2 = (
        jnp.all(new_phase == state.prev_phase) & ~unchanged & ~state.first_cycle
    )
    settle = jnp.where(unchanged & ~state.settled, state.cycle, state.settle_cycle)
    settled = state.settled | unchanged
    cycled = state.cycled | (is_cycle2 & ~settled)
    return OnnState(
        phase=new_phase,
        prev_phase=state.phase,
        first_cycle=jnp.bool_(False),
        settle_cycle=settle,
        settled=settled,
        cycled=cycled,
        cycle=state.cycle + 1,
    )


def _result_of_state(cfg: ONNConfig, state: OnnState) -> ONNResult:
    return ONNResult(
        final_phase=state.phase,
        final_sigma=osc.spin(state.phase, cfg.phase_bits),
        settle_cycle=state.settle_cycle,
        settled=state.settled,
        cycled=state.cycled,
    )


def _run_functional(cfg: ONNConfig, params: OnnParams, phase0: jax.Array) -> ONNResult:
    def body(state, _):
        return step(cfg, params, state), None

    state, _ = jax.lax.scan(
        body, _state_of_phase(cfg, phase0), None, length=cfg.max_cycles
    )
    return _result_of_state(cfg, state)


# ---------------------------------------------------------------------------
# RTL-mode dynamics
# ---------------------------------------------------------------------------


def _rtl_clock_edge(cfg: ONNConfig, params: OnnParams, carry, t):
    """One slow-clock edge in the lab frame."""
    phase, sigma_lab_prev = carry
    half = cfg.clocks_per_cycle // 2
    ref_phase = jnp.mod(t, cfg.clocks_per_cycle)
    sign_ref = jnp.where(ref_phase < half, jnp.int32(1), jnp.int32(-1))
    # Lab-frame spins *now*:
    theta_lab = (phase.astype(jnp.int32) + ref_phase) % cfg.clocks_per_cycle
    sigma_lab = osc.spin(theta_lab.astype(jnp.uint8), cfg.phase_bits)
    # The hybrid's serialized sum consumed amplitudes from one slow clock
    # earlier; the recurrent adder tree is combinational (current amps).
    sigma_used = sigma_lab_prev if cfg.architecture == "hybrid" else sigma_lab
    s = weighted_sum(cfg, params.weights, sigma_used) + params.bias
    # Reference level is absolute (high iff S>0); aligning the oscillator
    # to it in the lab frame == rotating-frame target sign(S)·sign_ref.
    s_rel = s * sign_ref
    new_phase = osc.phase_align(phase, s_rel, cfg.phase_bits)
    return (new_phase, sigma_lab), new_phase


def _run_rtl(
    cfg: ONNConfig, params: OnnParams, phase0: jax.Array, key: Optional[jax.Array]
) -> ONNResult:
    clocks = cfg.clocks_per_cycle
    if cfg.sync_jitter:
        if key is None:
            raise ValueError("sync_jitter requires a PRNG key")
        t0 = jax.random.randint(key, (), 0, clocks, dtype=jnp.int32)
    else:
        t0 = jnp.int32(0)

    ref0 = jnp.mod(t0, clocks)
    theta_lab0 = (phase0.astype(jnp.int32) + ref0) % clocks
    sigma_lab0 = osc.spin(theta_lab0.astype(jnp.uint8), cfg.phase_bits)

    def cycle_body(carry, cycle_idx):
        phase, sigma_prev, settle, settled, cycled, snapshot, first = carry

        def clock_body(inner, k):
            (ph, sp), _ = _rtl_clock_edge(
                cfg, params, inner, t0 + cycle_idx * clocks + k
            )
            return (ph, sp), None

        (new_phase, new_sigma_prev), _ = jax.lax.scan(
            clock_body, (phase, sigma_prev), jnp.arange(clocks)
        )
        unchanged = jnp.all(new_phase == phase)
        is_cycle2 = jnp.all(new_phase == snapshot) & ~unchanged & ~first
        settle = jnp.where(unchanged & ~settled, cycle_idx, settle)
        settled = settled | unchanged
        cycled = cycled | (is_cycle2 & ~settled)
        return (
            new_phase,
            new_sigma_prev,
            settle,
            settled,
            cycled,
            phase,
            jnp.bool_(False),
        ), None

    init = (
        phase0,
        sigma_lab0,
        jnp.int32(cfg.max_cycles),
        jnp.bool_(False),
        jnp.bool_(False),
        # snapshot starts as phase0, guarded by the first-cycle flag (no 255
        # sentinel — that value is a legal phase at phase_bits == 8).
        phase0,
        jnp.bool_(True),
    )
    (phase, _, settle, settled, cycled, _, _), _ = jax.lax.scan(
        cycle_body, init, jnp.arange(cfg.max_cycles)
    )
    return ONNResult(
        final_phase=phase,
        final_sigma=osc.spin(phase, cfg.phase_bits),
        settle_cycle=settle,
        settled=settled,
        cycled=cycled,
    )


# ---------------------------------------------------------------------------
# Batched-native dynamics: (B, N)-first solve with per-lane early exit
# ---------------------------------------------------------------------------
#
# The hot path of the serving engine is a *batch* of problems against shared
# coupling hardware — the paper's Table 7 settles in a handful of cycles, so
# scanning all ``max_cycles`` wastes ~95% of the W·σ products.  The batched
# runner below drives one (B, N) state through a chunked ``lax.while_loop``
# that stops as soon as every lane is *frozen*, and the weighted sums hit the
# backends with the real batch dimension (one (B,N)×(N,N) contraction per
# cycle) instead of a vmap closure over per-lane matvecs.
#
# Bit-exactness with the fixed-length scan is by construction, not by
# approximation.  A lane freezes only when its *full* per-cycle carry — phase
# plus, in rtl mode, the lab-frame spins the hybrid consumes one slow clock
# later — is provably on its final trajectory:
#
# * carry fixed point (carry(t+1) == carry(t)): the cycle map is
#   deterministic and time-invariant, so the remaining cycles are no-ops;
# * carry period-2 orbit (carry(t+1) == carry(t-1) != carry(t)): the lane
#   alternates between two states forever; the phase the fixed scan would
#   report at ``max_cycles`` is recovered from the parity of the remaining
#   cycle count (``frozen_p2`` lanes in ``_batch_result``).
#
# Lanes whose *phase* looks settled/period-2 while the rtl hybrid's amplitude
# history still differs keep running (the flags latch exactly as in the
# fixed scan, but no freeze), so pathological trajectories stay bit-exact at
# the price of a longer scan.  The settle bookkeeping (settled / cycled /
# settle_cycle) updates with the same formulas as ``step`` until freeze, and
# a frozen lane's flags cannot change in the fixed scan afterwards.


class BatchState(NamedTuple):
    """Resumable state of the batched runner (all lanes-first).

    Each lane carries its *own* cycle clock ``t`` and enable-signal offset
    ``t0``, so lanes of different ages coexist in one slab: a lane installed
    into a freed slot mid-solve (continuous batching — ``repro.serving``)
    starts at ``t = 0`` and advances through exactly the trajectory it would
    follow in a slab of its own.  ``run_batch``/``retrieve`` initialize every
    lane at ``t = 0`` and this degenerates to a shared clock.

    The pytree is public so a host-side scheduler can hold it between
    :func:`advance_chunk` calls, scatter fresh lanes in with
    :func:`install_lanes`, and read results with :func:`batch_result`.
    """

    phase: jax.Array  # (B, N) uint8 phases, cycle t
    prev_phase: jax.Array  # (B, N) phases, cycle t-1
    aux: jax.Array  # (B, N) rtl lab spins one clock back ((B, 1) zeros otherwise)
    prev_aux: jax.Array  # (B, N) aux one cycle earlier
    settle_cycle: jax.Array  # (B,) int32 first cycle with no phase change
    settled: jax.Array  # (B,) bool
    cycled: jax.Array  # (B,) bool: phase-level period-2 detected
    frozen: jax.Array  # (B,) bool: lane provably on its final trajectory
    frozen_p2: jax.Array  # (B,) bool: frozen inside a period-2 orbit
    freeze_cycle: jax.Array  # (B,) int32 per-lane cycle count at freeze
    t: jax.Array  # (B,) int32 per-lane cycles elapsed
    t0: jax.Array  # (B,) int32 per-lane enable-signal offsets


#: Backward-compatible internal alias (the carry predates the public name).
_BatchCarry = BatchState


def _shard_lanes(x: jax.Array) -> jax.Array:
    """Constrain a lanes-first array to the mesh batch axis.

    A no-op without an active :mod:`repro.distributed.sharding` rules
    context; under a mesh it splits the request batch across devices so a
    multi-device solve shards the (B,N)×(N,N) contraction by rows of σ.
    """
    from repro.distributed import sharding as shard_lib

    return shard_lib.shard(x, "batch", *([None] * (x.ndim - 1)))


def _constrain_params(params: OnnParams) -> OnnParams:
    from repro.distributed import sharding as shard_lib

    return shard_lib.constrain_onn(params)


def _rtl_cycle_batch(
    cfg: ONNConfig,
    params: OnnParams,
    t0: jax.Array,
    t: jax.Array,
    phase: jax.Array,
    aux: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """One oscillation cycle (= ``clocks_per_cycle`` slow-clock edges) of the
    rtl dynamics for all lanes at once; ``t0``/``t``: (B,) per-lane enable
    offsets and cycle counts (lanes installed mid-slab run their own clock)."""
    clocks = cfg.clocks_per_cycle
    half = clocks // 2

    def edge(carry, k):
        ph, sigma_prev = carry
        ref_phase = jnp.mod(t0 + t * clocks + k, clocks)  # (B,)
        sign_ref = jnp.where(ref_phase < half, jnp.int32(1), jnp.int32(-1))
        theta_lab = (ph.astype(jnp.int32) + ref_phase[:, None]) % clocks
        sigma_lab = osc.spin(theta_lab.astype(jnp.uint8), cfg.phase_bits)
        sigma_used = sigma_prev if cfg.architecture == "hybrid" else sigma_lab
        s = weighted_sum(cfg, params.weights, sigma_used) + params.bias
        new_ph = osc.phase_align(ph, s * sign_ref[:, None], cfg.phase_bits)
        return (new_ph, sigma_lab), None

    (phase, aux), _ = jax.lax.scan(edge, (phase, aux), jnp.arange(clocks))
    return phase, aux


def _batch_step(cfg: ONNConfig, params: OnnParams, c: _BatchCarry) -> _BatchCarry:
    """One cycle of the batched dynamics + settle/freeze bookkeeping.

    Every quantity is per lane, including the clock: a lane's ``t`` advances
    only while the lane is active, so lanes installed into the slab at
    different real times each see the cycle sequence 0, 1, 2, … of an
    isolated solve (the dynamics of one lane never read another lane's row
    — integer weighted sums are row-independent — nor the shared tick
    count, which is what makes mid-flight backfill bit-exact)."""
    if cfg.mode == "functional":
        new_phase = functional_update(cfg, params, c.phase)
        new_aux = c.aux
    else:
        new_phase, new_aux = _rtl_cycle_batch(cfg, params, c.t0, c.t, c.phase, c.aux)
    new_phase = _shard_lanes(new_phase)

    t = c.t
    active = ~c.frozen & (t < cfg.max_cycles)
    not_first = t > 0
    lane_unchanged = jnp.all(new_phase == c.phase, axis=-1)
    phase_p2 = jnp.all(new_phase == c.prev_phase, axis=-1)
    is_cycle2 = phase_p2 & ~lane_unchanged & not_first
    # Flag bookkeeping: identical per lane to step()/_run_rtl's fixed scan.
    settle_cycle = jnp.where(active & lane_unchanged & ~c.settled, t, c.settle_cycle)
    settled = c.settled | (active & lane_unchanged)
    cycled = c.cycled | (active & is_cycle2 & ~settled)
    # Freeze decisions: require the FULL carry (phase and amplitude history)
    # to repeat, so frozen lanes are provably on their final trajectory.
    aux_unchanged = jnp.all(new_aux == c.aux, axis=-1)
    aux_p2 = jnp.all(new_aux == c.prev_aux, axis=-1)
    carry_fixed = lane_unchanged & aux_unchanged
    carry_p2 = phase_p2 & aux_p2 & ~carry_fixed & not_first
    newly_frozen = active & (carry_fixed | carry_p2)

    upd = active[:, None]
    return _BatchCarry(
        phase=jnp.where(upd, new_phase, c.phase),
        prev_phase=jnp.where(upd, c.phase, c.prev_phase),
        aux=jnp.where(upd, new_aux, c.aux),
        prev_aux=jnp.where(upd, c.aux, c.prev_aux),
        settle_cycle=settle_cycle,
        settled=settled,
        cycled=cycled,
        frozen=c.frozen | newly_frozen,
        frozen_p2=c.frozen_p2 | (newly_frozen & carry_p2),
        freeze_cycle=jnp.where(newly_frozen, t + 1, c.freeze_cycle),
        t=jnp.where(active, t + 1, t),
        t0=c.t0,
    )


def _batch_result(cfg: ONNConfig, c: _BatchCarry) -> ONNResult:
    """Final state → result, with the period-2 parity reconstruction.

    A lane frozen at cycle ``freeze_cycle`` inside a period-2 orbit holds
    carry C(freeze_cycle); the fixed scan would have kept alternating, ending
    on C(freeze_cycle) iff ``max_cycles - freeze_cycle`` is even, else on the
    other orbit state (held in ``prev_phase``).
    """
    parity_odd = ((cfg.max_cycles - c.freeze_cycle) % 2) == 1
    swap = c.frozen_p2 & parity_odd
    final_phase = jnp.where(swap[:, None], c.prev_phase, c.phase)
    return ONNResult(
        final_phase=final_phase,
        final_sigma=osc.spin(final_phase, cfg.phase_bits),
        settle_cycle=c.settle_cycle,
        settled=c.settled,
        cycled=c.cycled,
    )


# ---------------------------------------------------------------------------
# Whole-chunk advance: the per-cycle settle/freeze bookkeeping of
# ``_batch_step`` is exact but expensive to run every cycle — ~20 masked
# elementwise updates between every W·σ product, and (on backend="pallas")
# one kernel launch per cycle.  In functional mode the bookkeeping can be
# reconstructed *after* the chunk instead, because two invariants hold:
#
# * the functional aux carry is constant, so a carry fixed point is exactly a
#   phase fixed point and a carry period-2 orbit exactly a phase period-2
#   orbit (``settled ⇒ frozen`` at every chunk boundary);
# * every flag event (settle / cycle detection) therefore coincides with the
#   lane's FIRST freeze event — there is nothing to record before it and the
#   lane is inert after it.
#
# So the chunk runs as a bare ``scan`` of phase updates (or ONE multi-cycle
# kernel launch), and the first fixed-point / period-2 event in the stacked
# trajectory replays the ``_batch_step`` updates bit-exactly.  rtl mode keeps
# the per-cycle loop: its aux (amplitude-history) carry is live, so freezing
# needs the full per-cycle comparison.
# ---------------------------------------------------------------------------

def _multi_kernel_eligible(cfg: ONNConfig) -> bool:
    """Whether the whole-chunk Pallas kernel can hold this instance's W.

    The padded-N ceiling lives in ``repro.kernels.autotune``
    (``MULTI_KERNEL_MAX_N``) next to the VMEM budget it derives from;
    imported lazily because the kernels package is optional.
    """
    if cfg.mode != "functional" or cfg.backend != "pallas":
        return False
    from repro.kernels import autotune  # lazy: kernels are optional

    return -(-cfg.n // 128) * 128 <= autotune.MULTI_KERNEL_MAX_N


def _chunk_multi(
    cfg: ONNConfig, params: OnnParams, c: _BatchCarry, chunk: int
) -> _BatchCarry:
    """One settle-chunk as ONE multi-cycle kernel launch (backend="pallas").

    W stays resident in VMEM across all ``chunk`` cycles and the phase state
    ping-pongs through the kernel's loop carry; with ``cfg.phase_pack`` the
    state crosses the launch boundary in the packed 4-bit layout.
    """
    from repro.kernels import ops as kernel_ops  # lazy: kernels are optional

    half = osc.n_positions(cfg.phase_bits) // 2
    (
        phase, prev_phase, settle_cycle, settled, cycled, frozen, frozen_p2,
        freeze_cycle, t,
    ) = kernel_ops.phase_step_multi(
        params.weights, params.bias, c.phase, c.prev_phase, c.t,
        c.settle_cycle, c.settled, c.cycled, c.frozen, c.frozen_p2,
        c.freeze_cycle,
        half=half, chunk=chunk, max_cycles=cfg.max_cycles,
        packed=cfg.phase_pack,
    )
    return c._replace(
        phase=_shard_lanes(phase),
        prev_phase=_shard_lanes(prev_phase),
        settle_cycle=settle_cycle,
        settled=settled,
        cycled=cycled,
        frozen=frozen,
        frozen_p2=frozen_p2,
        freeze_cycle=freeze_cycle,
        t=t,
    )


def _chunk_fused(
    cfg: ONNConfig, params: OnnParams, c: _BatchCarry, chunk: int
) -> _BatchCarry:
    """One settle-chunk as a bare phase scan + post-hoc exact bookkeeping.

    The scan stacks the chunk's trajectory; the first fixed-point/period-2
    event per lane (masked to its remaining cycle budget) reconstructs every
    ``_batch_step`` flag update bit-exactly — see the section comment above
    for why the first event is the only one.  Frozen lanes apply 0 cycles
    (their stacked trajectory is computed speculatively and discarded), so
    over-stepping a done lane never perturbs its result.
    """

    def body(ph, _):
        nf = _shard_lanes(functional_update(cfg, params, ph))
        return nf, nf

    _, traj = jax.lax.scan(body, c.phase, None, length=chunk)
    ext = jnp.concatenate([c.prev_phase[None], c.phase[None], traj], axis=0)
    nxt, cur, prv = ext[2:], ext[1:-1], ext[:-2]
    unchanged = jnp.all(nxt == cur, axis=-1)  # (chunk, B)
    p2 = jnp.all(nxt == prv, axis=-1)
    tk = c.t[None, :] + jnp.arange(chunk, dtype=jnp.int32)[:, None]
    in_budget = tk < cfg.max_cycles
    fixed_evt = unchanged & in_budget
    p2_evt = p2 & ~unchanged & (tk > 0) & in_budget
    evt = fixed_evt | p2_evt
    any_evt = jnp.any(evt, axis=0)
    kf = jnp.argmax(evt, axis=0).astype(jnp.int32)  # first event per lane
    budget = jnp.clip(cfg.max_cycles - c.t, 0, chunk)
    applied = jnp.where(any_evt, jnp.minimum(kf + 1, budget), budget)
    applied = jnp.where(c.frozen, 0, applied)
    live_evt = any_evt & ~c.frozen
    is_fixed = live_evt & jnp.take_along_axis(fixed_evt, kf[None, :], 0)[0]
    is_p2 = live_evt & jnp.take_along_axis(p2_evt, kf[None, :], 0)[0]
    sel = applied[None, :, None].astype(jnp.int32)
    new_prev = jnp.take_along_axis(ext, sel, axis=0)[0]
    new_phase = jnp.take_along_axis(ext, sel + 1, axis=0)[0]
    newly = is_fixed | is_p2
    return c._replace(
        phase=new_phase,
        prev_phase=new_prev,
        settle_cycle=jnp.where(is_fixed & ~c.settled, c.t + kf, c.settle_cycle),
        settled=c.settled | is_fixed,
        cycled=c.cycled | is_p2,
        frozen=c.frozen | newly,
        frozen_p2=c.frozen_p2 | is_p2,
        freeze_cycle=jnp.where(newly, c.t + kf + 1, c.freeze_cycle),
        t=c.t + applied,
    )


def _advance_chunk_batched(
    cfg: ONNConfig, params: OnnParams, state: _BatchCarry, chunk: int
) -> _BatchCarry:
    """Advance the slab by one settle-chunk through the fastest exact route.

    functional + pallas (W fits VMEM) → one multi-cycle kernel launch;
    functional otherwise → fused scan + post-hoc bookkeeping; rtl → the
    per-cycle ``_batch_step`` loop (its amplitude-history carry is live).
    All routes are bit-exact with ``chunk`` iterations of ``_batch_step``.
    """
    if cfg.mode == "functional":
        # The multi-cycle kernel keeps the full square W resident in VMEM,
        # which a model-sharded plan has deliberately split; fall through to
        # the fused scan, whose per-cycle weighted sums run the row-sharded
        # collective (bit-exact — see _model_sharded_sum).
        if _multi_kernel_eligible(cfg) and _model_plan() is None:
            return _chunk_multi(cfg, params, state, chunk)
        return _chunk_fused(cfg, params, state, chunk)
    return jax.lax.fori_loop(
        0, chunk, lambda _, cc: _batch_step(cfg, params, cc), state
    )


def _jitter_offsets(
    cfg: ONNConfig, keys: Optional[jax.Array], batch: int
) -> jax.Array:
    """Per-lane enable-signal offsets t0 ∈ [0, clocks); zeros without jitter."""
    if not (cfg.mode == "rtl" and cfg.sync_jitter):
        return jnp.zeros((batch,), jnp.int32)
    if keys is None:
        raise ValueError("sync_jitter requires PRNG keys")
    return jax.vmap(
        lambda k: jax.random.randint(k, (), 0, cfg.clocks_per_cycle, dtype=jnp.int32)
    )(keys)


def _init_carry(
    cfg: ONNConfig, phase0: jax.Array, keys: Optional[jax.Array]
) -> _BatchCarry:
    """Fresh per-lane carry at t = 0; ``phase0``: (B, N), ``keys``: (B,) or None."""
    b = phase0.shape[0]
    t0 = _jitter_offsets(cfg, keys, b)
    if cfg.mode == "rtl":
        clocks = cfg.clocks_per_cycle
        ref0 = jnp.mod(t0, clocks)
        theta_lab0 = (phase0.astype(jnp.int32) + ref0[:, None]) % clocks
        aux0 = osc.spin(theta_lab0.astype(jnp.uint8), cfg.phase_bits)
    else:
        aux0 = jnp.zeros((b, 1), jnp.int8)  # no amplitude history to track
    return _BatchCarry(
        phase=phase0,
        prev_phase=phase0,
        aux=aux0,
        prev_aux=aux0,
        settle_cycle=jnp.full((b,), cfg.max_cycles, jnp.int32),
        settled=jnp.zeros((b,), bool),
        cycled=jnp.zeros((b,), bool),
        frozen=jnp.zeros((b,), bool),
        frozen_p2=jnp.zeros((b,), bool),
        freeze_cycle=jnp.full((b,), cfg.max_cycles, jnp.int32),
        t=jnp.zeros((b,), jnp.int32),
        t0=t0,
    )


def resolve_chunk(cfg: ONNConfig) -> int:
    """Cycles per early-exit check: ``settle_chunk`` clamped to [1, max_cycles]."""
    chunk = cfg.settle_chunk if cfg.settle_chunk > 0 else cfg.max_cycles
    return max(1, min(chunk, cfg.max_cycles))


def _lane_done(cfg: ONNConfig, c: _BatchCarry) -> jax.Array:
    """(B,) bool: lane frozen or out of cycle budget (its result is final)."""
    return c.frozen | (c.t >= cfg.max_cycles)


def _run_batched(
    cfg: ONNConfig,
    params: OnnParams,
    phase0: jax.Array,
    keys: Optional[jax.Array],
) -> ONNResult:
    """The batched early-exit runner; ``phase0``: (B, N), ``keys``: (B,) or None."""
    TRACE_COUNTER["run_batch"] += 1
    params = _constrain_params(params)
    phase0 = _shard_lanes(phase0)
    carry0 = _init_carry(cfg, phase0, keys)
    chunk = resolve_chunk(cfg)

    def body(c: _BatchCarry) -> _BatchCarry:
        return _advance_chunk_batched(cfg, params, c, chunk)

    def cond(c: _BatchCarry) -> jax.Array:
        return ~jnp.all(_lane_done(cfg, c))

    final = jax.lax.while_loop(cond, body, carry0)
    return _batch_result(cfg, final)


def _lane_keys(
    cfg: ONNConfig, keys: Optional[jax.Array], batch: int
) -> Optional[jax.Array]:
    """One key per lane: a single key is split per request; batches pass through.

    New-style typed keys are scalars (a batch has ndim 1); legacy uint32 keys
    have shape (2,) (a batch has ndim 2).
    """
    if keys is None:
        return None
    typed = jnp.issubdtype(keys.dtype, jax.dtypes.prng_key)
    if keys.ndim == (0 if typed else 1):
        keys = jax.random.split(keys, batch)
    return keys


def _require_keys_if_random(cfg: ONNConfig, keys: Optional[jax.Array], what: str) -> None:
    if keys is None and cfg.mode == "rtl" and cfg.sync_jitter:
        raise ValueError(
            f"{what}: this config draws randomness (rtl sync_jitter); pass "
            "keys= (a (B, 2) batch of keys, or one key to split per request)"
        )


def _sharding_cache_key() -> Optional[Tuple]:
    """The active sharding rules/mesh/plan context as a jit-cache key.

    ``_shard_lanes``/``_constrain_params``/``_model_plan`` bake sharding
    constraints and the shard_map collective in at *trace* time from a
    thread-local context that ``jax.jit``'s cache key knows nothing about.
    The batched entry points therefore pass this key as an extra *static*
    argument (None outside any context), so each context traces its own
    executable — otherwise whichever call happened first would decide
    whether a mesh context actually shards (a warmed-up cache would make
    ``--mesh`` silently a no-op, and the reverse order would leak mesh-bound
    executables outside the context).  The :class:`ShardPlan` is a frozen
    hashable dataclass, so it rides the key directly.
    """
    from repro.distributed import sharding as shard_lib

    rules, mesh = shard_lib.current_rules(), shard_lib.current_mesh()
    plan = shard_lib.current_plan()
    if rules is None and mesh is None and plan is None:
        return None
    rules_key = None if rules is None else tuple(sorted(rules.items()))
    return (rules_key, mesh, plan)


# ---------------------------------------------------------------------------
# Public jitted entry points: one compile per (config, shape)
# ---------------------------------------------------------------------------


def _run(
    cfg: ONNConfig,
    params: OnnParams,
    phase0: jax.Array,
    key: Optional[jax.Array] = None,
) -> ONNResult:
    TRACE_COUNTER["run"] += 1
    if cfg.mode == "functional":
        return _run_functional(cfg, params, phase0)
    return _run_rtl(cfg, params, phase0, key)


@partial(jax.jit, static_argnums=(0, 4))
def _run_traced(
    cfg: ONNConfig,
    params: OnnParams,
    phase0: jax.Array,
    key: Optional[jax.Array] = None,
    _ctx: Optional[Tuple] = None,  # static sharding-context discriminator
) -> ONNResult:
    return _run(cfg, params, phase0, key)


def run(
    cfg: ONNConfig,
    params: OnnParams,
    phase0: jax.Array,
    key: Optional[jax.Array] = None,
) -> ONNResult:
    """Evolve one ONN to steady state; pure in ``params`` and ``phase0``.

    ``phase0``: (N,) uint8 initial phases.  ``key`` seeds the enable-signal
    jitter (rtl mode with ``sync_jitter``); ignored otherwise and may be None.

    Only ``cfg`` (plus the ambient sharding context) is static: two
    different weight matrices of the same N reuse one compiled executable,
    and ``jax.vmap(run, in_axes=(None, 0, None))`` batches over *problems*.
    """
    return _run_traced(cfg, params, phase0, key, _sharding_cache_key())


@partial(jax.jit, static_argnums=(0, 4))
def _retrieve(
    cfg: ONNConfig,
    params: OnnParams,
    sigma0_batch: jax.Array,
    keys: Optional[jax.Array] = None,
    _ctx: Optional[Tuple] = None,  # static sharding-context discriminator
) -> ONNResult:
    TRACE_COUNTER["retrieve"] += 1
    phase0 = initial_phase(cfg, sigma0_batch)  # elementwise: works lanes-first
    return _run_batched(cfg, params, phase0, _lane_keys(cfg, keys, sigma0_batch.shape[0]))


def retrieve(
    cfg: ONNConfig,
    params: OnnParams,
    sigma0_batch: jax.Array,
    keys: Optional[jax.Array] = None,
) -> ONNResult:
    """Run a (B, N) batch of initial spin patterns to steady state.

    Batched-native: the whole batch advances through one (B,N)×(N,N) coupling
    contraction per cycle and stops early once every lane has settled or
    entered a detected period-2 orbit — bit-exact with the fixed-length scan
    of :func:`run` per lane (``cfg.settle_chunk`` sets the early-exit check
    granularity; 0 disables).

    PRNG use is explicit: pass ``keys`` of shape (B, 2) — one key per request
    — or a single key (shape (2,)), which is split into one subkey per
    request.  There is no implicit default key: configurations that consume
    randomness (``mode="rtl"`` with ``sync_jitter``) raise if ``keys`` is
    None instead of silently correlating every run in the batch.
    """
    _require_keys_if_random(cfg, keys, "retrieve")
    return _retrieve(cfg, params, sigma0_batch, keys, _sharding_cache_key())


def run_batch(
    cfg: ONNConfig,
    params: OnnParams,
    phase0_batch: jax.Array,
    keys: Optional[jax.Array] = None,
) -> ONNResult:
    """Evolve a (B, N) batch of phase states to steady state, early-exiting.

    The lanes-first sibling of :func:`run`: one compiled executable advances
    the whole batch per oscillation cycle (the backends see the real batch
    dimension) inside a chunked ``lax.while_loop`` that stops as soon as
    every lane is settled or in a detected period-2 orbit.  Results are
    bit-exact, lane for lane, with ``jax.vmap(run)`` over the same inputs —
    including ``settle_cycle``/``settled``/``cycled`` and rtl ``sync_jitter``
    (each lane draws its own enable-signal offset from its key).

    ``keys`` is one key per lane ((B, 2) legacy or (B,) typed), or a single
    key split per lane; required only when the config draws randomness.
    """
    _require_keys_if_random(cfg, keys, "run_batch")
    return _run_batch_traced(cfg, params, phase0_batch, keys, _sharding_cache_key())


@partial(jax.jit, static_argnums=(0, 4))
def _run_batch_traced(
    cfg: ONNConfig,
    params: OnnParams,
    phase0_batch: jax.Array,
    keys: Optional[jax.Array] = None,
    _ctx: Optional[Tuple] = None,  # static sharding-context discriminator
) -> ONNResult:
    return _run_batched(
        cfg, params, phase0_batch, _lane_keys(cfg, keys, phase0_batch.shape[0])
    )


# ---------------------------------------------------------------------------
# Resumable chunked solve: the continuous-batching entry points
# ---------------------------------------------------------------------------
#
# `run_batch`/`retrieve` drive the whole solve inside one `lax.while_loop`;
# a continuous-batching scheduler (repro.serving) instead holds the
# :class:`BatchState` on the host and advances it one settle-chunk at a time,
# harvesting lanes as they freeze and scattering fresh requests into the
# freed slots.  Bit-exactness with the one-shot path follows from two facts:
# lane dynamics never read another lane's row (integer weighted sums are
# row-independent), and every clock (`t`, `t0`) is per lane — so an installed
# lane replays exactly the trajectory it would follow in a slab of its own.


@partial(jax.jit, static_argnums=(0, 3))
def _init_batch_state_traced(
    cfg: ONNConfig,
    phase0_batch: jax.Array,
    keys: Optional[jax.Array] = None,
    _ctx: Optional[Tuple] = None,  # static sharding-context discriminator
) -> BatchState:
    return _init_carry(
        cfg, _shard_lanes(phase0_batch), _lane_keys(cfg, keys, phase0_batch.shape[0])
    )


def init_batch_state(
    cfg: ONNConfig,
    phase0_batch: jax.Array,
    keys: Optional[jax.Array] = None,
) -> BatchState:
    """Fresh :class:`BatchState` for a (B, N) batch of phase states at t = 0.

    ``keys`` follows the :func:`run_batch` contract: one key per lane, or a
    single key split per lane; required only when the config draws
    randomness (rtl ``sync_jitter``).
    """
    _require_keys_if_random(cfg, keys, "init_batch_state")
    return _init_batch_state_traced(cfg, phase0_batch, keys, _sharding_cache_key())


@partial(jax.jit, static_argnums=(0, 1))
def dead_batch_state(cfg: ONNConfig, batch: int) -> BatchState:
    """An all-frozen (batch, N) placeholder slab.

    Every lane is born frozen with its budget spent, so it never holds the
    early-exit loop open and :func:`advance_chunk` leaves it untouched; the
    scheduler overwrites slots with real requests via :func:`install_lanes`.
    """
    aux_n = cfg.n if cfg.mode == "rtl" else 1
    full = jnp.full((batch,), cfg.max_cycles, jnp.int32)
    return BatchState(
        phase=jnp.zeros((batch, cfg.n), jnp.uint8),
        prev_phase=jnp.zeros((batch, cfg.n), jnp.uint8),
        aux=jnp.zeros((batch, aux_n), jnp.int8),
        prev_aux=jnp.zeros((batch, aux_n), jnp.int8),
        settle_cycle=full,
        settled=jnp.zeros((batch,), bool),
        cycled=jnp.zeros((batch,), bool),
        frozen=jnp.ones((batch,), bool),
        frozen_p2=jnp.zeros((batch,), bool),
        freeze_cycle=full,
        t=full,
        t0=jnp.zeros((batch,), jnp.int32),
    )


@jax.jit
def install_lanes(state: BatchState, sub: BatchState, slots: jax.Array) -> BatchState:
    """Scatter the lanes of ``sub`` (width K) into ``state`` at rows ``slots``.

    Pure scatter: untouched rows keep their arrays bit-identical, so lanes
    mid-solve are unaffected by neighbours joining the slab.
    """
    return jax.tree.map(lambda a, b: a.at[slots].set(b), state, sub)


@partial(jax.jit, static_argnums=(0, 3))
def _advance_chunk_traced(
    cfg: ONNConfig,
    params: OnnParams,
    state: BatchState,
    _ctx: Optional[Tuple] = None,  # static sharding-context discriminator
) -> BatchState:
    TRACE_COUNTER["advance_chunk"] += 1
    params = _constrain_params(params)
    return _advance_chunk_batched(cfg, params, state, resolve_chunk(cfg))


def advance_chunk(cfg: ONNConfig, params: OnnParams, state: BatchState) -> BatchState:
    """Advance every live lane by one settle-chunk of cycles.

    Runs ``resolve_chunk(cfg)`` iterations of the same per-lane step the
    one-shot runner uses; frozen or budget-exhausted lanes are masked no-ops,
    so over-stepping a done lane never perturbs its result.  One compile per
    (config, slab shape) — the scheduler's tick is a single device dispatch.
    """
    return _advance_chunk_traced(cfg, params, state, _sharding_cache_key())


@partial(jax.jit, static_argnums=0)
def batch_done(cfg: ONNConfig, state: BatchState) -> jax.Array:
    """(B,) bool: which lanes are final (frozen or out of cycle budget)."""
    return _lane_done(cfg, state)


@partial(jax.jit, static_argnums=0)
def batch_result(cfg: ONNConfig, state: BatchState) -> ONNResult:
    """Results for a slab; valid per lane once :func:`batch_done` is True.

    Applies the same period-2 parity reconstruction as the one-shot runner,
    so harvested lanes match ``run_batch``/``retrieve`` bit for bit.
    """
    return _batch_result(cfg, state)


# ---------------------------------------------------------------------------
# Asynchronous sweeps (Ising solver + energy-monotonicity properties)
# ---------------------------------------------------------------------------


def async_sweep(w: jax.Array, sigma: jax.Array, order: jax.Array) -> jax.Array:
    """One asynchronous (sequential) Hopfield sweep: σ_i ← sign(Σ W_ij σ_j).

    Used by the Ising solver and by the energy-monotonicity property tests
    (asynchronous updates on symmetric zero-diagonal couplings never increase
    the Hamiltonian).  Ties keep the current spin.

    Integer couplings accumulate in exact int32; float couplings (e.g.
    unquantized Hebbian/DO-I output from :mod:`repro.core.learning`) keep a
    float accumulator — casting them to int32 would silently truncate
    fractional fields toward zero and flip the sign decision near zero.
    """
    if jnp.issubdtype(w.dtype, jnp.integer):
        acc_dtype = jnp.int32
    else:
        acc_dtype = jnp.promote_types(w.dtype, jnp.float32)

    def body(s, i):
        field = w[i].astype(acc_dtype) @ s.astype(acc_dtype)
        new_si = jnp.where(field > 0, 1, jnp.where(field < 0, -1, s[i])).astype(s.dtype)
        return s.at[i].set(new_si), None

    sigma, _ = jax.lax.scan(body, sigma, order)
    return sigma
