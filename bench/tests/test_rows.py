"""The row-sharded cell (``onn131072.sharded4``) at N=256 on four virtual CPU
devices: its row-blocked couplings are the whole matrix's, its row-blocked
reference is the plain one, the program reads correct, and the control and a
program whose psum is left out read not correct.

Four devices need ``XLA_FLAGS`` before JAX starts, so each case runs in a
child process that prints one JSON line.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

import run

CELL = "onn131072.sharded4"
SEEDS = [3, 2**31 + 11, 987654321987]

_PRELUDE = textwrap.dedent(
    """
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{bench!r}, {src!r}]
    import numpy as np
    import jax
    import jax.numpy as jnp
    assert jax.device_count() == 4
    import run

    CONFIG = {{"n": 256, "couplings": {{"rule": "hebbian", "patterns": 16,
                                        "self_coupling": False}}}}
    TRAFFIC = {{"pool": 4, "lanes_per_request": 8, "reference_block_lanes": 16}}

    def run_small(seed, control=False):
        r = run.run_cell({cell!r}, seed, 1.0, False, control=control, require_tpu=False,
                         config_overrides=CONFIG, traffic_overrides=TRAFFIC)
        return {{"correct": r["correct"], "attempted": r["attempted"],
                 "mismatched": r["checks"]["mismatched_requests"]["value"]}}
    """
).format(bench=run.BENCH, src=os.path.join(run.ROOT, "src"), cell=CELL)


def _child(body):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _PRELUDE + textwrap.dedent(body)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_PARTS = """
    from jax.sharding import NamedSharding, PartitionSpec as P
    import generators as gen
    from references import retrieval as plain
    from references import retrieval_rows as rows
    from systems.retrieval_rows import row_quantized_hebbian
    from repro.core.learning import hebbian
    from repro.distributed import ShardPlan

    mesh = ShardPlan(batch=1, model=4).make_mesh()
    xi = gen.random_patterns(jax.random.PRNGKey(5), 16, 256)
    xi_all = jax.device_put(xi, NamedSharding(mesh, P()))
    whole = {b: np.asarray(gen.quantize(hebbian(xi, self_coupling=False), b)) for b in (5, 4)}
    built = {b: row_quantized_hebbian(xi_all, mesh, b, 16) for b in (5, 4)}
    equal = {b: bool(np.array_equal(np.asarray(built[b]), whole[b])) for b in (5, 4)}
    split = [built[5].sharding.spec[0],
             sorted(int(s.data.nbytes) for s in built[5].addressable_shards)]

    flips = np.random.default_rng(6).random((24, 256)) < 0.2
    clean = np.asarray(xi)[np.arange(24) % 16]
    probes = np.where(flips, -clean, clean).astype(np.int8)
    bias = np.zeros((256,), np.int32)
    got, got_need = rows.solve(built[5], bias, probes, phase_bits=4, max_cycles=100)
    want, want_need = plain.solve(jnp.asarray(whole[5]), jnp.asarray(bias), jnp.asarray(probes),
                                  phase_bits=4, max_cycles=100)
    same = {f: bool(np.array_equal(got[f], want[f]) and got[f].dtype == want[f].dtype)
            for f in plain.FIELDS}
    print(json.dumps({"equal": equal, "split": split, "fields": list(rows.FIELDS),
                      "same": same, "need_same": bool(np.array_equal(got_need, want_need)),
                      "settled": int(want["settled"].sum())}))
"""


@pytest.fixture(scope="module")
def parts():
    return _child(_PARTS)


def test_row_blocked_hebbian_equals_the_whole_matrix(parts):
    """``quantize(hebbian(xi), bits)`` of the whole matrix, at 5 bits and at
    the control's 4, built row block by row block on four devices."""
    assert parts["equal"] == {"5": True, "4": True}
    assert parts["split"] == ["model", [256 * 256 // 4] * 4]


def test_rows_reference_equals_the_plain_reference(parts):
    assert parts["fields"] == list(parts["same"])
    assert all(parts["same"].values()), parts["same"]
    assert parts["need_same"]
    assert parts["settled"] > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_program_correct_and_control_not(seed):
    got = _child(f"""
        print(json.dumps({{"program": run_small({seed}), "control": run_small({seed}, True)}}))
    """)
    assert got["program"]["correct"] and got["program"]["mismatched"] == 0
    assert got["program"]["attempted"] > 0
    assert not got["control"]["correct"] and got["control"]["mismatched"] > 0


def test_psum_left_out_reads_not_correct():
    """Each shard's partial field used alone: the combine of the row-sharded
    solve is dropped (the reference gathers phases and uses no psum)."""
    got = _child("""
        jax.lax.psum = lambda x, axis_name, **kw: x
        print(json.dumps(run_small(7)))
    """)
    assert not got["correct"] and got["mismatched"] > 0


def _trace_ctx(monkeypatch, ops_per_device, slabs):
    import xplane as tr
    from repro.engine.spans import Record

    mod = run.reader("metrics", "collective_exposed_ms")
    recs = [Record(i, "engine.slab", 0, 1, None, attrs) for i, attrs in enumerate(slabs)]
    monkeypatch.setattr(mod, "window_records", lambda ctx: recs)
    trace = tr.Trace()
    for i, ops in enumerate(ops_per_device):
        dev = tr.Device(f"/device:TPU:{i}")
        dev.ops = ops
        trace.devices.append(dev)
    ctx = run.Context(trace=trace, tr=tr, lo=0.0, hi=1000.0, chips=len(ops_per_device))
    return mod, ctx


_FUSION = "%fusion.3 = s32[8,64]{1,0} fusion(s32[8,64]{1,0} %psum.9), kind=kLoop"
_PSUM = "%psum.9 = s32[8,64]{1,0:T(8,128)} all-reduce(s32[8,64]{1,0} %dus.1), channel_id=1"
_START = "%all-reduce-start.2 = (s32[8,64]{1,0}, s32[8,64]{1,0}) all-reduce-start(s32[8,64] %x)"
_DONE = "%all-reduce-done.2 = s32[8,64]{1,0} all-reduce-done((s32[8,64], s32[8,64]) %all-reduce-start.2)"
_KERNEL = "%_coupling_sum_jit.1 = s32[8,16]{1,0} custom-call(s8[8,64]{1,0} %a, s8[16,64]{1,0} %b)"
_WHILE = "%while.47 = (s32[], s32[8,64]{1,0}) while((s32[], s32[8,64]{1,0}) %tuple.3), condition=%c, body=%b"


def test_collective_exposed_ms_counts_collective_time_no_other_op_covers(monkeypatch):
    """Device 0: a psum over 50-300 ns, other ops over 0-100 and 400-500 (one
    reads the psum's result), all inside a while loop's op: 200 ns exposed.
    Device 1: an async pair, 600 ns exposed in all.  Two requests on sharded
    slabs; the unsharded slab's five do not count."""
    mod, ctx = _trace_ctx(monkeypatch, [
        [(0, 900, _WHILE), (0, 100, _FUSION), (50, 300, _PSUM), (400, 500, _KERNEL)],
        [(0, 100, _START), (100, 200, _KERNEL), (200, 700, _DONE)],
    ], [{"requests": 2, "plan": "1x4"}, {"requests": 5}])
    assert mod.read(ctx) == pytest.approx((200 + 600) / 2 / 1e6 / 2)


def test_collective_exposed_ms_none_without_collectives_or_plan(monkeypatch):
    mod, ctx = _trace_ctx(monkeypatch, [[(0, 900, _WHILE), (0, 100, _FUSION), (400, 500, _KERNEL)]],
                          [{"requests": 2, "plan": "1x4"}])
    assert mod.read(ctx) is None
    mod, ctx = _trace_ctx(monkeypatch, [[(50, 300, _PSUM)]], [{"requests": 2}])
    assert mod.read(ctx) is None
