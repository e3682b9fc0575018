"""Raw-speed pass acceptance: packed 4-bit phases, the whole-chunk fused
kernel, and per-bucket block autotuning are all bit-exact with the paths
they replace — and resolving them compiles nothing new per call."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:  # optional dep (see pyproject.toml): skip, not fail
    from hypothesis_fallback import given, settings, st

from repro import engine as engine_lib
from repro.core import dynamics
from repro.kernels import autotune, ops, ref
from repro.kernels.coupling_kernel import pack_block_halves, unpack_block_halves
from repro.serving import ContinuousEngine

RESULT_FIELDS = ("final_phase", "final_sigma", "settle_cycle", "settled", "cycled")


def _instance(n: int, batch: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    w = rng.integers(-15, 16, (n, n))
    w = jnp.asarray((w + w.T) // 2, jnp.int8)
    sigma0 = jnp.asarray(rng.choice([-1, 1], (batch, n)), jnp.int8)
    return w, sigma0


# ---------------------------------------------------------------------------
# pack_block_halves / unpack_block_halves (the packed kernels' layout)
# ---------------------------------------------------------------------------


@given(
    st.sampled_from([2, 8, 256]),
    st.integers(1, 3),
    st.integers(1, 5),
    st.integers(0, 2**31 - 1),
)
@settings(max_examples=30, deadline=None)
def test_pack_unpack_roundtrip(width, blocks, b, seed):
    rng = np.random.default_rng(seed)
    phases = jnp.asarray(rng.integers(0, 16, (b, width * blocks)), jnp.uint8)
    packed = pack_block_halves(phases, width)
    assert packed.dtype == jnp.uint8
    assert packed.shape == (b, width * blocks // 2)
    back = unpack_block_halves(packed, width)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(phases))


def test_pack_unpack_edge_shapes():
    # Byte j of each w-wide block holds counter j (low) and j + w/2 (high).
    vals = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.uint8)
    packed = pack_block_halves(vals, 4)
    np.testing.assert_array_equal(np.asarray(packed), [[0x31, 0x42, 0x75, 0x86]])
    np.testing.assert_array_equal(np.asarray(unpack_block_halves(packed, 4)), vals)
    with pytest.raises(ValueError):
        pack_block_halves(jnp.zeros((2, 6), jnp.uint8), 4)  # 6 is not a multiple of 4
    with pytest.raises(ValueError):
        unpack_block_halves(jnp.zeros((2, 3), jnp.uint8), 4)


def test_phase_pack_requires_4bit_phases():
    with pytest.raises(ValueError, match="phase_pack"):
        dynamics.ONNConfig(n=8, phase_bits=5, phase_pack=True)


# ---------------------------------------------------------------------------
# Packed-operand solve: bit-exact across backends, ragged tails included
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["parallel", "pallas", "hybrid"])
@pytest.mark.parametrize("n", [47, 48, 129])
def test_packed_config_bit_exact_with_unpacked(n, backend):
    w, sigma0 = _instance(n, 5, seed=n)
    kw = dict(n=n, backend=backend, max_cycles=40, settle_chunk=4)
    cfg_u = dynamics.ONNConfig(**kw)
    cfg_p = dynamics.ONNConfig(**kw, phase_pack=True)
    params = dynamics.make_params(cfg_u, w)
    res_u = dynamics.retrieve(cfg_u, params, sigma0)
    res_p = dynamics.retrieve(cfg_p, params, sigma0)
    for field in RESULT_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(res_p, field)), np.asarray(getattr(res_u, field)), field
        )


@pytest.mark.parametrize("n", [128, 506])
def test_packed_pallas_matches_vmap_run_at_paper_sizes(n):
    w, sigma0 = _instance(n, 3, seed=n)
    cfg = dynamics.ONNConfig(n=n, backend="pallas", max_cycles=30, settle_chunk=8,
                             phase_pack=True)
    params = dynamics.make_params(cfg, w)
    res = dynamics.retrieve(cfg, params, sigma0)
    phase0 = dynamics.initial_phase(cfg, sigma0)
    ref_res = jax.vmap(lambda p: dynamics.run(cfg, params, p))(phase0)
    for field in RESULT_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(res, field)), np.asarray(getattr(ref_res, field)), field
        )


def test_phase_step_packed_matches_ref():
    for n, b in ((9, 1), (48, 4), (130, 3)):
        rng = np.random.default_rng(n * 7 + b)
        w = jnp.asarray(rng.integers(-15, 16, (n, n)), jnp.int8)
        bias = jnp.asarray(rng.integers(-3, 4, (n,)), jnp.int32)
        phase = jnp.asarray(rng.choice([0, 8], (b, n)), jnp.uint8)
        got = ops.phase_step_packed(w, bias, phase, half=8)
        want = ref.phase_step_packed_ref(w, bias, phase, 8)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Whole-chunk multi-cycle kernel vs the per-cycle oracle
# ---------------------------------------------------------------------------


def _random_multi_state(n, b, max_cycles, seed):
    rng = np.random.default_rng(seed)
    w = rng.integers(-15, 16, (n, n))
    w = jnp.asarray((w + w.T) // 2, jnp.int8)
    bias = jnp.asarray(rng.integers(-2, 3, (n,)), jnp.int32)
    phase = jnp.asarray(rng.choice([0, 8], (b, n)), jnp.int32)
    prev = jnp.asarray(rng.choice([0, 8], (b, n)), jnp.int32)
    t = jnp.asarray(rng.integers(0, max_cycles + 1, (b,)), jnp.int32)
    full = jnp.full((b,), max_cycles, jnp.int32)
    frozen = jnp.asarray(rng.random(b) < 0.3)
    return dict(
        w=w, bias=bias, phase=phase, prev_phase=prev, t=t,
        settle_cycle=full, settled=jnp.zeros((b,), bool),
        cycled=jnp.zeros((b,), bool), frozen=frozen,
        frozen_p2=jnp.zeros((b,), bool), freeze_cycle=full,
    )


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("n,b", [(16, 3), (37, 5), (130, 2)])
def test_phase_step_multi_matches_ref(n, b, packed):
    """The ops wrapper (padding, packing, dtype restore) against the explicit
    Python-loop oracle — mixed live/frozen lanes, mid-budget clocks."""
    max_cycles, chunk = 20, 6
    s = _random_multi_state(n, b, max_cycles, seed=n * 31 + b)
    flags = (s["t"], s["settle_cycle"], s["settled"], s["cycled"], s["frozen"],
             s["frozen_p2"], s["freeze_cycle"])
    got = ops.phase_step_multi(
        s["w"], s["bias"], s["phase"], s["prev_phase"], *flags,
        half=8, chunk=chunk, max_cycles=max_cycles, packed=packed
    )
    # the oracle speaks the kernel's (B, 1) bookkeeping-column layout
    want = ref.phase_step_multi_ref(
        s["w"], s["bias"], s["phase"], s["prev_phase"],
        *(f[:, None] for f in flags),
        half=8, chunk=chunk, max_cycles=max_cycles
    )
    want = tuple(x[:, 0] if x.ndim == 2 and x.shape[1] == 1 else x for x in want)
    names = ("phase", "prev_phase", "settle_cycle", "settled", "cycled",
             "frozen", "frozen_p2", "freeze_cycle", "t")
    for name, g, w_ in zip(names, got, want):
        np.testing.assert_array_equal(
            np.asarray(g, dtype=np.int64), np.asarray(w_, dtype=np.int64), name
        )


def test_phase_step_multi_detects_p2_orbits_and_budget():
    """Negative self-coupling flips every spin every cycle (a guaranteed
    period-2 orbit): p2 events inside the chunk, plus lanes whose budget
    expires mid-chunk, all match the oracle."""
    n, b, max_cycles, chunk = 13, 6, 10, 8
    w = jnp.asarray(-7 * np.eye(n), jnp.int8)
    bias = jnp.zeros((n,), jnp.int32)
    rng = np.random.default_rng(3)
    phase = jnp.asarray(rng.choice([0, 8], (b, n)), jnp.int32)
    t = jnp.asarray([0, 0, 5, 8, 9, 10], jnp.int32)  # some expire mid-chunk
    full = jnp.full((b,), max_cycles, jnp.int32)
    zeros = jnp.zeros((b,), bool)
    flags = (t, full, zeros, zeros, zeros, zeros, full)
    got = ops.phase_step_multi(
        w, bias, phase, phase, *flags, half=8, chunk=chunk, max_cycles=max_cycles
    )
    want = ref.phase_step_multi_ref(
        w, bias, phase, phase, *(f[:, None] for f in flags),
        half=8, chunk=chunk, max_cycles=max_cycles
    )
    want = tuple(x[:, 0] if x.ndim == 2 and x.shape[1] == 1 else x for x in want)
    for g, w_ in zip(got, want):
        np.testing.assert_array_equal(
            np.asarray(g, dtype=np.int64), np.asarray(w_, dtype=np.int64)
        )
    assert int(np.asarray(want[4]).sum()) > 0, "test instance should produce p2 orbits"


# ---------------------------------------------------------------------------
# Autotuner: determinism, budget, cache behaviour
# ---------------------------------------------------------------------------


def test_autotune_blocks_deterministic_and_within_budget():
    from repro.kernels import coupling_kernel as ck

    for kind in ("step", "hybrid", "matvec"):
        for n in (9, 48, 128, 506, 2048):
            for batch in (1, 16, 256):
                a = autotune.blocks_for(kind, n=n, batch=batch)
                b = autotune.blocks_for(kind, n=n, batch=batch)
                assert a == b
                assert ck.vmem_bytes(a.block_b, a.block_i, a.block_k, fused=True) \
                    <= autotune.VMEM_BUDGET_BYTES


def test_autotune_cache_hits_and_warm_idempotent():
    autotune.clear_cache()
    info0 = autotune.cache_info()
    assert info0 == {"entries": 0, "hits": 0, "misses": 0}
    autotune.warm(n=48, batch=16)
    after_first = autotune.cache_info()
    assert after_first["entries"] == after_first["misses"] == 3
    autotune.warm(n=48, batch=16)  # idempotent: pure hits
    after_second = autotune.cache_info()
    assert after_second["entries"] == after_first["entries"]
    assert after_second["misses"] == after_first["misses"]
    assert after_second["hits"] == after_first["hits"] + 3
    with pytest.raises(ValueError):
        autotune.blocks_for("nope", n=48, batch=16)
    with pytest.raises(ValueError):
        autotune.blocks_for("step", n=0, batch=16)


# ---------------------------------------------------------------------------
# Zero retraces: repeated engine installs resolve blocks once per bucket
# ---------------------------------------------------------------------------


def test_engine_reinstall_keeps_trace_counters_flat():
    """solve → hot weight install → solve again: the autotuned block tuples
    resolve to identical statics, so neither the kernel wrappers nor the
    dynamics entry points trace anything new."""
    n = 24
    rng = np.random.default_rng(0)
    xi = jnp.asarray(rng.choice([-1, 1], (3, n)), jnp.int8)
    payload = jnp.asarray(rng.choice([-1, 1], (2, n)), jnp.int8)

    eng = engine_lib.Engine(jax.random.PRNGKey(0), batch_buckets=(1, 2, 4))
    eng.install("mem", "retrieval", xi=xi, backend="pallas",
                max_cycles=40, settle_chunk=4)
    fut = eng.submit(engine_lib.Request("mem", payload))
    eng.flush()
    first = fut.result()

    ops_before = dict(ops.TRACE_COUNTER)
    dyn_before = dict(dynamics.TRACE_COUNTER)
    solver = eng.solver("mem")
    solver.install_params(solver.solver.params)  # same weights, new install
    fut2 = eng.submit(engine_lib.Request("mem", payload))
    eng.flush()
    # the second retrieve dispatch is counted, but nothing re-traces
    dyn_after = dict(dynamics.TRACE_COUNTER)
    assert dict(ops.TRACE_COUNTER) == ops_before, "kernel wrapper re-traced"
    assert dyn_after == dyn_before, "dynamics entry point re-traced"
    for field in RESULT_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(fut2.result(), field)),
            np.asarray(getattr(first, field)),
        )


# ---------------------------------------------------------------------------
# Streaming: a packed-config slab admits mid-flight lanes bit-exactly
# ---------------------------------------------------------------------------


def test_packed_slab_mid_flight_join_bit_exact():
    n = 24
    rng = np.random.default_rng(7)
    xi = jnp.asarray(rng.choice([-1, 1], (3, n)), jnp.int8)

    def corrupt(row, flips, seed):
        r = np.random.default_rng(seed)
        v = np.asarray(xi[row]).copy()
        idx = r.choice(v.size, flips, replace=False)
        v[idx] = -v[idx]
        return jnp.asarray(v, jnp.int8)

    kw = dict(max_cycles=60, settle_chunk=1, backend="pallas", phase_pack=True)
    payload_a = jnp.stack([corrupt(0, 5, 1), corrupt(1, 5, 2)])
    payload_b = corrupt(2, 5, 3)

    ceng = ContinuousEngine(jax.random.PRNGKey(0), batch_buckets=(1, 2, 4),
                            slab_lanes=4)
    ceng.install("mem", "retrieval", xi=xi, **kw)
    fut_a = ceng.submit(engine_lib.Request("mem", payload_a))
    ceng.step()  # slab live: A's lanes have advanced one chunk
    fut_b = ceng.submit(engine_lib.Request("mem", payload_b))
    ceng.flush()
    assert ceng.stats()["serving"]["mid_flight_joins"] >= 1
    assert ceng.stats()["serving"]["autotune"]["entries"] > 0

    solo = engine_lib.Engine(jax.random.PRNGKey(99), batch_buckets=(1, 2, 4))
    solo.install("mem", "retrieval", xi=xi, **kw)
    ref_a = solo.submit(engine_lib.Request("mem", payload_a))
    solo.flush()
    ref_b = solo.submit(engine_lib.Request("mem", payload_b))
    solo.flush()

    for got, want in ((fut_a.result(), ref_a.result()), (fut_b.result(), ref_b.result())):
        for field in RESULT_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, field)), np.asarray(getattr(want, field)), field
            )
