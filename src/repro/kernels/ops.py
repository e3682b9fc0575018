"""Public wrappers around the Pallas kernels.

Handles padding to hardware-aligned block multiples, batch reshaping, and
execution mode: the kernels compile on a TPU and run in interpret mode only
when JAX's default backend is the CPU (the test suite).  ``use_pallas=False``
takes the pure-jnp reference route instead; nothing on the solver path
passes it, so only a caller that asks for it gets it.

Block resolution happens *here*, in plain Python, before the jitted inner
implementation is entered: explicit ``block_*`` arguments are honored (and
clamped to the operand extent as before), while the default ``None`` asks
the per-bucket autotuner (:mod:`repro.kernels.autotune`) for the tuned tile
of this ``(N, batch)`` bucket.  The resolved ints are *static* arguments of
the inner jit — resolved once per bucket shape, not re-derived per call —
so repeated calls (and repeated engine installs) on a warmed bucket are
pure jit-cache hits.  ``TRACE_COUNTER`` increments at trace time of each
inner implementation; tests assert it stays flat across installs.
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from repro.core.checks import require_int_dtype as _require_int_dtype
from repro.kernels import autotune
from repro.kernels import coupling_kernel as _k
from repro.kernels import ref as _ref

#: Traces per inner kernel wrapper, incremented at trace (not call) time.
TRACE_COUNTER: collections.Counter = collections.Counter()


def _interpret() -> bool:
    """Interpret the kernels iff JAX's default backend is the CPU."""
    return jax.default_backend() == "cpu"


def _pick_block(size: int, preferred: int, minimum: int = 8) -> int:
    """Largest power-of-two block ≤ preferred that keeps padding small."""
    b = preferred
    while b > minimum and b > size:
        b //= 2
    return max(b, minimum)


def _batch_extent(x: jax.Array) -> int:
    b = 1
    for d in x.shape[:-1]:
        b *= d
    return max(b, 1)


def _resolve_blocks(kind, b, m, n, block_b, block_i, block_k, k_minimum=8):
    """(bb, bi, bk): explicit values clamped as before, ``None`` autotuned."""
    tuned = None
    if block_b is None or block_i is None or block_k is None:
        tuned = autotune.blocks_for(kind, n=n, batch=b, m=m)
    bb = tuned.block_b if block_b is None else _pick_block(b, block_b)
    bi = tuned.block_i if block_i is None else _pick_block(m, block_i)
    bk = tuned.block_k if block_k is None else _pick_block(n, block_k, minimum=k_minimum)
    return bb, bi, bk


@functools.partial(jax.jit, static_argnames=("use_pallas", "block_b", "block_i", "block_k"))
def _coupling_sum_jit(w, sigma, *, use_pallas, block_b, block_i, block_k):
    TRACE_COUNTER["coupling_sum"] += 1
    _require_int_dtype(w, "w")
    squeeze = sigma.ndim == 1
    batch_shape = sigma.shape[:-1]
    m, n = w.shape
    sig2d = sigma.reshape(-1, n).astype(jnp.int8)
    if not use_pallas:
        out = _ref.coupling_sum_ref(w, sig2d)
    else:
        sig_p = _k.pad_to_blocks(sig2d, (block_b, block_k))
        w_p = _k.pad_to_blocks(w.astype(jnp.int8), (block_i, block_k))
        out = _k.coupling_sum_pallas(
            sig_p, w_p, block_b=block_b, block_i=block_i, block_k=block_k,
            interpret=_interpret(),
        )[: sig2d.shape[0], :m]
    return out.reshape(m) if squeeze else out.reshape(*batch_shape, m)


def coupling_sum(
    w: jax.Array,
    sigma: jax.Array,
    *,
    use_pallas: bool = True,
    block_b: int | None = None,
    block_i: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """S = W σ for spins σ of shape (N,) or (..., N); returns int32.

    ``w`` is (M, N): M == N for the full coupling matrix, M < N for a row
    slab (the Ising solver evaluates the field only at staggered update-
    group members); returns (..., M).
    """
    m, n = w.shape
    bb, bi, bk = _resolve_blocks(
        "step", _batch_extent(sigma), m, n, block_b, block_i, block_k
    )
    return _coupling_sum_jit(
        w, sigma, use_pallas=use_pallas, block_b=bb, block_i=bi, block_k=bk
    )


@functools.partial(jax.jit, static_argnames=("use_pallas", "block_b", "block_i", "block_k"))
def _onn_step_jit(w, sigma, bias, *, use_pallas, block_b, block_i, block_k):
    TRACE_COUNTER["onn_step"] += 1
    _require_int_dtype(w, "w")
    _require_int_dtype(bias, "bias")
    squeeze = sigma.ndim == 1
    batch_shape = sigma.shape[:-1]
    n = w.shape[0]
    sig2d = sigma.reshape(-1, n).astype(jnp.int8)
    h = jnp.zeros((n,), jnp.int32) if bias is None else bias.astype(jnp.int32)
    if not use_pallas:
        out = _ref.onn_step_ref(w, sig2d, h)
    else:
        sig_p = _k.pad_to_blocks(sig2d, (block_b, block_k))
        w_p = _k.pad_to_blocks(w.astype(jnp.int8), (block_i, block_k))
        h_p = _k.pad_to_blocks(h, (block_i,))
        out = _k.onn_step_pallas(
            sig_p, w_p, h_p, block_b=block_b, block_i=block_i, block_k=block_k,
            interpret=_interpret(),
        )[: sig2d.shape[0], :n]
    return out.reshape(n) if squeeze else out.reshape(*batch_shape, n)


def onn_step(
    w: jax.Array,
    sigma: jax.Array,
    bias: jax.Array | None = None,
    *,
    use_pallas: bool = True,
    block_b: int | None = None,
    block_i: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """Fused ONN phase-update step: σ' = sign-align(W σ + h)."""
    n = w.shape[0]
    bb, bi, bk = _resolve_blocks(
        "step", _batch_extent(sigma), n, n, block_b, block_i, block_k
    )
    return _onn_step_jit(
        w, sigma, bias, use_pallas=use_pallas, block_b=bb, block_i=bi, block_k=bk
    )


@functools.partial(
    jax.jit, static_argnames=("half", "use_pallas", "block_b", "block_i", "block_k")
)
def _phase_step_jit(w, sigma, bias, phase, *, half, use_pallas, block_b, block_i, block_k):
    TRACE_COUNTER["phase_step"] += 1
    _require_int_dtype(w, "w")
    _require_int_dtype(bias, "bias")
    squeeze = sigma.ndim == 1
    batch_shape = sigma.shape[:-1]
    n = w.shape[0]
    sig2d = sigma.reshape(-1, n).astype(jnp.int8)
    ph2d = phase.reshape(-1, n).astype(jnp.int32)
    h = jnp.zeros((n,), jnp.int32) if bias is None else bias.astype(jnp.int32)
    if not use_pallas:
        out = _ref.phase_step_ref(w, sig2d, h, ph2d, half)
    else:
        sig_p = _k.pad_to_blocks(sig2d, (block_b, block_k))
        w_p = _k.pad_to_blocks(w.astype(jnp.int8), (block_i, block_k))
        h_p = _k.pad_to_blocks(h, (block_i,))
        ph_p = _k.pad_to_blocks(ph2d, (block_b, block_i))
        out = _k.phase_step_pallas(
            sig_p, w_p, h_p, ph_p,
            half=half, block_b=block_b, block_i=block_i, block_k=block_k,
            interpret=_interpret(),
        )[: sig2d.shape[0], :n]
    out = out.astype(phase.dtype)
    return out.reshape(n) if squeeze else out.reshape(*batch_shape, n)


def phase_step(
    w: jax.Array,
    sigma: jax.Array,
    bias: jax.Array | None,
    phase: jax.Array,
    *,
    half: int,
    use_pallas: bool = True,
    block_b: int | None = None,
    block_i: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """Fused functional-mode cycle: θ' = phase-align(W σ + h, θ).

    ``sigma``/``phase`` of shape (N,) or (..., N); ``phase`` is returned in
    its input dtype.  One kernel launch per oscillation cycle — the batched
    ONN hot path (``repro.core.dynamics``, backend="pallas") lands here with
    the full request batch as the real ``block_b`` grid dimension.
    """
    n = w.shape[0]
    bb, bi, bk = _resolve_blocks(
        "step", _batch_extent(sigma), n, n, block_b, block_i, block_k
    )
    return _phase_step_jit(
        w, sigma, bias, phase,
        half=half, use_pallas=use_pallas, block_b=bb, block_i=bi, block_k=bk,
    )


@functools.partial(
    jax.jit, static_argnames=("half", "use_pallas", "block_b", "block_i", "block_k")
)
def _phase_step_packed_jit(w, bias, phase, *, half, use_pallas, block_b, block_i, block_k):
    TRACE_COUNTER["phase_step_packed"] += 1
    _require_int_dtype(w, "w")
    _require_int_dtype(bias, "bias")
    squeeze = phase.ndim == 1
    batch_shape = phase.shape[:-1]
    n = w.shape[0]
    ph2d = phase.reshape(-1, n)
    h = jnp.zeros((n,), jnp.int32) if bias is None else bias.astype(jnp.int32)
    if not use_pallas:
        out = _ref.phase_step_packed_ref(w, h, ph2d, half)
    else:
        # The packed array feeds both the σ-derivation tile and the
        # epilogue's keep-θ tile, so one square column block serves both
        # (block-halves layout at that width) and W stays square at the
        # padded size.
        blk = -(-max(block_i, block_k) // _k.PACKED_BLOCK_MULTIPLE) * _k.PACKED_BLOCK_MULTIPLE
        n_pad = -(-n // blk) * blk
        ph_p = _k.pad_to_blocks(ph2d, (block_b, 0))
        ph_p = jnp.pad(ph_p, ((0, 0), (0, n_pad - n)))
        w_p = jnp.pad(w.astype(jnp.int8), ((0, n_pad - n), (0, n_pad - n)))
        h_p = jnp.pad(h, (0, n_pad - n))
        out = _k.phase_step_packed_pallas(
            _k.pack_block_halves(ph_p, blk), w_p, h_p,
            half=half, block_b=block_b, block_i=blk, block_k=blk,
            interpret=_interpret(),
        )[: ph2d.shape[0], :n]
    out = out.astype(phase.dtype)
    return out.reshape(n) if squeeze else out.reshape(*batch_shape, n)


def phase_step_packed(
    w: jax.Array,
    bias: jax.Array | None,
    phase: jax.Array,
    *,
    half: int,
    use_pallas: bool = True,
    block_b: int | None = None,
    block_i: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """Packed-operand functional-mode cycle: θ' = phase-align(W σ(θ) + h, θ).

    Takes *unpacked* (..., N) phase counters and no σ operand: σ is a pure
    function of θ (σ = +1 iff θ < half), so the kernel derives it in-register
    from the packed 4-bit layout (two counters per byte) and moves half the
    σ/phase bytes per MAC tile.  Bit-exact with :func:`phase_step` fed
    ``osc.spin(phase)``.
    """
    n = w.shape[0]
    bb, bi, bk = _resolve_blocks(
        "step", _batch_extent(phase), n, n, block_b, block_i, block_k
    )
    return _phase_step_packed_jit(
        w, bias, phase,
        half=half, use_pallas=use_pallas, block_b=bb, block_i=bi, block_k=bk,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "half", "chunk", "max_cycles", "packed", "use_pallas", "block_b"
    ),
)
def _phase_step_multi_jit(
    w, bias, phase, prev_phase, t, settle_cycle, settled, cycled, frozen,
    frozen_p2, freeze_cycle, *, half, chunk, max_cycles, packed, use_pallas, block_b
):
    TRACE_COUNTER["phase_step_multi"] += 1
    _require_int_dtype(w, "w")
    _require_int_dtype(bias, "bias")
    b, n = phase.shape
    h = jnp.zeros((n,), jnp.int32) if bias is None else bias.astype(jnp.int32)
    cols = (t, settle_cycle, settled, cycled, frozen, frozen_p2, freeze_cycle)
    cols32 = tuple(c.astype(jnp.int32)[:, None] for c in cols)
    if not use_pallas:
        outs = _ref.phase_step_multi_ref(
            w, h, phase.astype(jnp.int32), prev_phase.astype(jnp.int32), *cols32,
            half=half, chunk=chunk, max_cycles=max_cycles,
        )
        ph_o, prev_o = outs[0], outs[1]
        flag_o = outs[2:]
    else:
        # N pads to a lane multiple (256 for the packed layout): padded
        # oscillators carry θ = 0 against zero weight rows/columns, so they
        # never change and never perturb the all-lanes reductions.  Batch
        # pads with born-frozen lanes (t = max_cycles), inert under the
        # active mask.
        n_mult = _k.PACKED_BLOCK_MULTIPLE if packed else 128
        n_pad = -(-n // n_mult) * n_mult
        b_pad = -(-b // block_b) * block_b
        w_p = jnp.pad(w.astype(jnp.int8), ((0, n_pad - n), (0, n_pad - n)))
        h_p = jnp.pad(h, (0, n_pad - n))
        ph_p = jnp.pad(phase.astype(jnp.int32), ((0, b_pad - b), (0, n_pad - n)))
        prev_p = jnp.pad(prev_phase.astype(jnp.int32), ((0, b_pad - b), (0, n_pad - n)))
        if packed:
            ph_p = _k.pack_block_halves(ph_p, n_pad)
            prev_p = _k.pack_block_halves(prev_p, n_pad)
        pad_dead = ((0, b_pad - b), (0, 0))
        t_p = jnp.pad(cols32[0], pad_dead, constant_values=max_cycles)
        fz_p = jnp.pad(cols32[4], pad_dead, constant_values=1)
        rest = [jnp.pad(cols32[i], pad_dead) for i in (1, 2, 3, 5, 6)]
        outs = _k.phase_step_multi_pallas(
            w_p, h_p, ph_p, prev_p, t_p, rest[0], rest[1], rest[2], fz_p,
            rest[3], rest[4],
            half=half, chunk=chunk, max_cycles=max_cycles, packed=packed,
            block_b=block_b, interpret=_interpret(),
        )
        ph_o, prev_o = outs[0][:b], outs[1][:b]
        if packed:
            ph_o = _k.unpack_block_halves(ph_o, n_pad).astype(jnp.int32)
            prev_o = _k.unpack_block_halves(prev_o, n_pad).astype(jnp.int32)
        ph_o, prev_o = ph_o[:, :n], prev_o[:, :n]
        flag_o = tuple(o[:b] for o in outs[2:])
    sc_o, sd_o, cy_o, fz_o, fp2_o, fc_o, t_o = flag_o
    return (
        ph_o.astype(phase.dtype),
        prev_o.astype(prev_phase.dtype),
        sc_o[:, 0].astype(settle_cycle.dtype),
        (sd_o[:, 0] != 0) if settled.dtype == jnp.bool_ else sd_o[:, 0].astype(settled.dtype),
        (cy_o[:, 0] != 0) if cycled.dtype == jnp.bool_ else cy_o[:, 0].astype(cycled.dtype),
        (fz_o[:, 0] != 0) if frozen.dtype == jnp.bool_ else fz_o[:, 0].astype(frozen.dtype),
        (fp2_o[:, 0] != 0) if frozen_p2.dtype == jnp.bool_ else fp2_o[:, 0].astype(frozen_p2.dtype),
        fc_o[:, 0].astype(freeze_cycle.dtype),
        t_o[:, 0].astype(t.dtype),
    )


def phase_step_multi(
    w: jax.Array,
    bias: jax.Array | None,
    phase: jax.Array,
    prev_phase: jax.Array,
    t: jax.Array,
    settle_cycle: jax.Array,
    settled: jax.Array,
    cycled: jax.Array,
    frozen: jax.Array,
    frozen_p2: jax.Array,
    freeze_cycle: jax.Array,
    *,
    half: int,
    chunk: int,
    max_cycles: int,
    packed: bool = False,
    use_pallas: bool = True,
    block_b: int | None = None,
):
    """Run ``chunk`` functional-mode cycles + settle/freeze bookkeeping in one
    kernel launch (``phase_step_multi_pallas``): the weight matrix stays
    resident in VMEM across all cycles instead of streaming once per cycle.

    ``phase``/``prev_phase``: (B, N) phase counters (any integer dtype);
    ``t``/``settle_cycle``/``freeze_cycle``: (B,) int32;
    ``settled``/``cycled``/``frozen``/``frozen_p2``: (B,) bool.  Returns the
    9-tuple (phase, prev_phase, settle_cycle, settled, cycled, frozen,
    frozen_p2, freeze_cycle, t) in the input dtypes — exactly the per-cycle
    bookkeeping of ``repro.core.dynamics._batch_step`` applied ``chunk``
    times.  ``packed`` moves the phase state through the kernel boundary in
    the 4-bit packed layout (two counters per byte).
    """
    b = phase.shape[0]
    if block_b is None:
        block_b = autotune.blocks_for("multi", n=phase.shape[1], batch=b).block_b
    else:
        block_b = _pick_block(b, block_b)
    return _phase_step_multi_jit(
        w, bias, phase, prev_phase, t, settle_cycle, settled, cycled, frozen,
        frozen_p2, freeze_cycle,
        half=half, chunk=chunk, max_cycles=max_cycles, packed=packed,
        use_pallas=use_pallas, block_b=block_b,
    )


@functools.partial(
    jax.jit, static_argnames=("parallel", "use_pallas", "block_b", "block_i", "block_k")
)
def _hybrid_coupling_sum_jit(w, sigma, *, parallel, use_pallas, block_b, block_i, block_k):
    TRACE_COUNTER["hybrid_coupling_sum"] += 1
    _require_int_dtype(w, "w")
    squeeze = sigma.ndim == 1
    batch_shape = sigma.shape[:-1]
    m, n = w.shape
    sig2d = sigma.reshape(-1, n).astype(jnp.int8)
    if not use_pallas:
        out = _ref.hybrid_coupling_sum_ref(w, sig2d, parallel)
    else:
        _, width = _k.hybrid_pass_groups(parallel, block_k)
        sig_p = _k.pad_to_blocks(sig2d, (block_b, width))
        w_p = _k.pad_to_blocks(w.astype(jnp.int8), (block_i, width))
        out = _k.hybrid_coupling_sum_pallas(
            sig_p, w_p, parallel=parallel, block_b=block_b, block_i=block_i,
            block_k=block_k, interpret=_interpret(),
        )[: sig2d.shape[0], :m]
    return out.reshape(m) if squeeze else out.reshape(*batch_shape, m)


def hybrid_coupling_sum(
    w: jax.Array,
    sigma: jax.Array,
    *,
    parallel: int,
    use_pallas: bool = True,
    block_b: int | None = None,
    block_i: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """S = W σ through the hybrid serialized pass-group schedule.

    ``parallel`` is the MAC width P: the contraction serializes into
    ``ceil(N / P)`` passes, grouped so every kernel launch covers one
    hardware-aligned pass-group (``repro.kernels.coupling_kernel``).
    Bit-exact with :func:`coupling_sum` for every P.  Like
    :func:`coupling_sum`, ``w`` may be a (M, N) row slab.
    """
    m, n = w.shape
    bb, bi, bk = _resolve_blocks(
        "hybrid", _batch_extent(sigma), m, n, block_b, block_i, block_k
    )
    return _hybrid_coupling_sum_jit(
        w, sigma, parallel=parallel, use_pallas=use_pallas,
        block_b=bb, block_i=bi, block_k=bk,
    )


@functools.partial(
    jax.jit,
    static_argnames=("half", "parallel", "use_pallas", "block_b", "block_i", "block_k"),
)
def _hybrid_phase_step_jit(
    w, sigma, bias, phase, *, half, parallel, use_pallas, block_b, block_i, block_k
):
    TRACE_COUNTER["hybrid_phase_step"] += 1
    _require_int_dtype(w, "w")
    _require_int_dtype(bias, "bias")
    squeeze = sigma.ndim == 1
    batch_shape = sigma.shape[:-1]
    n = w.shape[0]
    sig2d = sigma.reshape(-1, n).astype(jnp.int8)
    ph2d = phase.reshape(-1, n).astype(jnp.int32)
    h = jnp.zeros((n,), jnp.int32) if bias is None else bias.astype(jnp.int32)
    if not use_pallas:
        out = _ref.hybrid_phase_step_ref(w, sig2d, h, ph2d, half, parallel)
    else:
        _, width = _k.hybrid_pass_groups(parallel, block_k)
        sig_p = _k.pad_to_blocks(sig2d, (block_b, width))
        w_p = _k.pad_to_blocks(w.astype(jnp.int8), (block_i, width))
        h_p = _k.pad_to_blocks(h, (block_i,))
        ph_p = _k.pad_to_blocks(ph2d, (block_b, block_i))
        out = _k.hybrid_phase_step_pallas(
            sig_p, w_p, h_p, ph_p,
            half=half, parallel=parallel,
            block_b=block_b, block_i=block_i, block_k=block_k,
            interpret=_interpret(),
        )[: sig2d.shape[0], :n]
    out = out.astype(phase.dtype)
    return out.reshape(n) if squeeze else out.reshape(*batch_shape, n)


def hybrid_phase_step(
    w: jax.Array,
    sigma: jax.Array,
    bias: jax.Array | None,
    phase: jax.Array,
    *,
    half: int,
    parallel: int,
    use_pallas: bool = True,
    block_b: int | None = None,
    block_i: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """Fused hybrid functional-mode cycle: θ' = phase-align(W σ + h, θ) with
    the coupling sum serialized into pass-group launches of MAC width
    ``parallel``.  Same calling convention as :func:`phase_step`; the
    batched ONN hot path (backend="hybrid", hybrid_impl="pallas") lands
    here with the request batch as a real grid dimension.
    """
    n = w.shape[0]
    bb, bi, bk = _resolve_blocks(
        "hybrid", _batch_extent(sigma), n, n, block_b, block_i, block_k
    )
    return _hybrid_phase_step_jit(
        w, sigma, bias, phase,
        half=half, parallel=parallel, use_pallas=use_pallas,
        block_b=bb, block_i=bi, block_k=bk,
    )


@functools.partial(jax.jit, static_argnames=("use_pallas", "block_b", "block_m", "block_k"))
def _quantized_matvec_jit(w_q, scale, x, *, use_pallas, block_b, block_m, block_k):
    TRACE_COUNTER["quantized_matvec"] += 1
    _require_int_dtype(w_q, "w_q")
    squeeze = x.ndim == 1
    batch_shape = x.shape[:-1]
    m, kdim = w_q.shape
    x2d = x.reshape(-1, kdim).astype(jnp.float32)
    scale_full = jnp.broadcast_to(scale, (m,)).astype(jnp.float32)
    if not use_pallas:
        out = _ref.quantized_matvec_ref(w_q, scale_full, x2d)
    else:
        x_p = _k.pad_to_blocks(x2d, (block_b, block_k))
        w_p = _k.pad_to_blocks(w_q.astype(jnp.int8), (block_m, block_k))
        s_p = _k.pad_to_blocks(scale_full, (block_m,))
        out = _k.quantized_matvec_pallas(
            x_p, w_p, s_p, block_b=block_b, block_m=block_m, block_k=block_k,
            interpret=_interpret(),
        )[: x2d.shape[0], :m]
    return out.reshape(m) if squeeze else out.reshape(*batch_shape, m)


def quantized_matvec(
    w_q: jax.Array,
    scale: jax.Array,
    x: jax.Array,
    *,
    use_pallas: bool = True,
    block_b: int | None = None,
    block_m: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """y = (W_q · scale) @ x with per-row scale; x: (..., K) f32."""
    m, kdim = w_q.shape
    bb, bm, bk = _resolve_blocks(
        "matvec", _batch_extent(x), m, kdim, block_b, block_m, block_k, k_minimum=128
    )
    return _quantized_matvec_jit(
        w_q, scale, x, use_pallas=use_pallas, block_b=bb, block_m=bm, block_k=bk
    )
