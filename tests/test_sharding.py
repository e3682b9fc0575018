"""Sharding and dry-run machinery tests.

Multi-device tests spawn a subprocess with XLA_FLAGS forcing 8 host devices —
the main test process must keep seeing 1 device (the assignment's explicit
constraint), so the flag never leaks into this process.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as sh
from repro.models import params as PM
from repro.models.params import ParamSpec, logical_to_pspec


def test_main_process_sees_one_device():
    assert jax.device_count() == 1, "smoke-test process must not see the dry-run mesh"


def test_logical_to_pspec_basic():
    rules = sh.single_pod_rules()
    assert logical_to_pspec(("embed", "mlp"), rules) == P("data", "model")
    assert logical_to_pspec((None, "heads"), rules) == P(None, "model")
    assert logical_to_pspec(("batch",), {"batch": ("pod", "data")}) == P(("pod", "data"))


def test_logical_to_pspec_no_duplicate_mesh_axis():
    rules = {"a": "model", "b": "model"}
    spec = logical_to_pspec(("a", "b"), rules)
    assert spec == P("model")  # second use of "model" dropped


def test_divisibility_fallback():
    rules = sh.single_pod_rules()
    sizes = {"data": 16, "model": 16}
    # 8 kv heads cannot shard 16 ways → replicated
    assert logical_to_pspec(
        ("embed", "kv_heads", None), rules, (2560, 8, 128), sizes
    ) == P("data")
    # 32 heads can
    assert logical_to_pspec(
        ("embed", "heads", None), rules, (2560, 32, 128), sizes
    ) == P("data", "model")
    # composed batch axes: (pod, data) = 32 must divide
    r2 = sh.multi_pod_rules()
    sizes2 = {"pod": 2, "data": 16, "model": 16}
    assert logical_to_pspec(("batch", None), r2, (256, 4096), sizes2) == P(("pod", "data"))
    # partial fallback: 24 % (2·16) ≠ 0 but 24 % 2 == 0 → keep the pod axis
    assert logical_to_pspec(("batch", None), r2, (24, 4096), sizes2) == P("pod")


def test_pspecs_tree_and_shard_noop_outside_rules():
    tree = {"w": ParamSpec((64, 128), ("embed", "mlp"))}
    specs = PM.pspecs(tree, sh.single_pod_rules())
    assert specs["w"] == P("data", "model")
    # shard() outside rule context is identity
    import jax.numpy as jnp

    x = jnp.ones((4, 4))
    assert sh.shard(x, "batch", None) is x


def test_long_context_rules_shard_kv_seq():
    r = sh.long_context_rules(multi_pod=False)
    assert r["batch"] is None and r["kv_seq"] == "data"


_SUBPROCESS_TEST = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro import configs
    from repro.distributed import sharding as shrules
    from repro.models import params as PM
    from repro.models import steps as steps_lib
    from repro.models.config import ShapeConfig

    assert jax.device_count() == 8
    mesh = jax.make_mesh((4, 2), ("data", "model"), (AxisType.Auto,) * 2)
    rules = shrules.single_pod_rules()
    cfg = configs.get_reduced("qwen3-4b")
    shape = ShapeConfig("tiny_train", 64, 8, "train")
    with shrules.use_rules(rules, mesh):
        cell = steps_lib.build_cell(
            cfg, shape, rules, dp_size=4, axis_sizes=PM.mesh_axis_sizes(mesh)
        )
        in_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, s), cell.in_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        jitted = jax.jit(cell.step_fn, in_shardings=in_sh, donate_argnums=cell.donate)
        lowered = jitted.lower(*cell.abstract_args)
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    text = compiled.as_text()
    has_collectives = any(
        op in text for op in ("all-reduce", "all-gather", "reduce-scatter")
    )
    print(json.dumps({
        "devices": jax.device_count(),
        "flops": float(cost.get("flops", 0)),
        "has_collectives": has_collectives,
    }))
    """
)


@pytest.mark.slow
def test_mesh_lowering_8_devices(tmp_path):
    """End-to-end: reduced model lowers+compiles on an 8-device host mesh with
    collectives in the partitioned HLO (the dry-run machinery, miniaturized)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_TEST],
        capture_output=True, text=True, env=env, timeout=420,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["devices"] == 8
    assert result["flops"] > 0
    assert result["has_collectives"], "partitioned HLO contains no collectives"


def test_hlo_collective_parser():
    from repro.launch.hlo_analysis import parse_collectives

    text = """
  %ar = f32[16,128]{1,0} all-reduce(%x), replica_groups=[16,16]<=[256], to_apply=%sum
  %ag = bf16[4,256]{1,0} all-gather(%y), replica_groups={{0,1,2,3}}, dimensions={1}
  %rs = f32[2,64]{1,0} reduce-scatter(%z), replica_groups=[2,128]<=[256], dimensions={0}
  %cp = s8[1024]{0} collective-permute(%w), source_target_pairs={{0,1}}
"""
    stats = parse_collectives(text, 256)
    assert stats.counts == {
        "all-reduce": 1, "all-gather": 1, "reduce-scatter": 1, "collective-permute": 1
    }
    # all-reduce: 2·(16−1)/16 · 16·128·4 bytes
    assert abs(stats.bytes["all-reduce"] - 2 * 15 / 16 * 16 * 128 * 4) < 1e-6
    # all-gather group size 4: 3/4 of result bytes
    assert abs(stats.bytes["all-gather"] - 0.75 * 4 * 256 * 2) < 1e-6
    # reduce-scatter group 128: (128−1) × result bytes
    assert abs(stats.bytes["reduce-scatter"] - 127 * 2 * 64 * 4) < 1e-6
    assert stats.bytes["collective-permute"] == 1024


def test_roofline_terms():
    from repro.launch.hlo_analysis import Roofline

    r = Roofline(
        flops_per_device=197e12,  # exactly one second of compute
        hbm_bytes_per_device=819e9 / 2,
        collective_bytes_per_device=50e9 / 4,
        n_devices=256,
    )
    assert abs(r.compute_s - 1.0) < 1e-9
    assert abs(r.memory_s - 0.5) < 1e-9
    assert abs(r.collective_s - 0.25) < 1e-9
    assert r.dominant == "compute"


def test_auto_microbatches():
    from repro.models.config import SHAPES
    from repro.models.steps import auto_microbatches

    # train_4k on 16-way DP: 256·4096/16 = 65536 tokens/dev → 4 microbatches
    assert auto_microbatches(SHAPES["train_4k"], 16) == 4
    # decode shapes never microbatch
    assert auto_microbatches(SHAPES["decode_32k"], 16) == 1
    # 32-way DP halves it
    assert auto_microbatches(SHAPES["train_4k"], 32) == 2
