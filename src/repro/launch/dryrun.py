"""Multi-pod dry-run driver (deliverable e).

Lowers + compiles every (architecture × input shape) cell — plus the two ONN
cells — against the production mesh, WITHOUT allocating any real arrays
(ShapeDtypeStruct stand-ins only), and records:

* ``compiled.memory_analysis()``  — proves the cell fits per-device HBM,
* ``compiled.cost_analysis()``    — HLO FLOPs / bytes for §Roofline,
* collective wire bytes parsed from the compiled HLO (§Roofline third term),

into ``artifacts/dryrun/<arch>__<shape>__<mesh>[__<tag>].json``.

``main`` forces 512 placeholder host devices through ``XLA_FLAGS`` before
anything initializes a JAX backend (the device count is fixed at first
init).  Importing this module changes nothing: smoke tests and benches keep
seeing 1 device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --onn onn_506 --mesh single
  ... hillclimb knobs: --microbatches 4 --no-remat --rule heads= --tag v2
"""

import argparse
import dataclasses
import json
import os
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs.onn import ONN_CELLS
from repro.core import dynamics as dyn
from repro.distributed import sharding as shrules
from repro.launch import hlo_analysis as hlo
from repro.launch.mesh import make_production_mesh, mesh_devices
from repro.models import params as PM
from repro.models import steps as steps_lib
from repro.models.config import SHAPES
from repro.models.model import get_model

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")


def _is_pspec(x) -> bool:
    return isinstance(x, P)


def _to_shardings(tree, mesh):
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), tree, is_leaf=_is_pspec
    )


def _memory_dict(mem) -> Dict[str, Any]:
    out = {}
    for attr in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        try:
            out[attr] = int(getattr(mem, attr))
        except Exception:  # noqa: BLE001 — backend-specific fields
            pass
    if not out:
        out["repr"] = str(mem)
    return out


def _active_fraction_flops(cfg) -> float:
    """N_active/N_total for MoE archs (expert FLOPs scale by top_k/E)."""
    if cfg.family != "moe" or not cfg.n_experts:
        return 1.0
    # expert params per layer: 3 matrices (wg, wu, wd) of d_model×d_ff each
    expert = cfg.n_layers * cfg.n_experts * 3 * cfg.d_model * cfg.d_ff
    model = get_model(cfg)
    total = PM.count_params(model.param_specs)
    active = total - expert * (1.0 - cfg.top_k / cfg.n_experts)
    return active / total


def rules_for(arch: str, shape_name: str, multi_pod: bool) -> Dict[str, Any]:
    if shape_name == "long_500k":
        rules = shrules.long_context_rules(multi_pod)
    elif multi_pod:
        rules = shrules.multi_pod_rules()
    else:
        rules = shrules.single_pod_rules()
    rules.update(configs.sharding_overrides(arch))
    return rules


def _compile_cell(cfg, shape, rules, mesh, *, optimizer, microbatches, dp_size,
                  accum_dtype=jnp.float32):
    with shrules.use_rules(rules, mesh):
        cell = steps_lib.build_cell(
            cfg, shape, rules, optimizer_name=optimizer,
            microbatches=microbatches, dp_size=dp_size,
            axis_sizes=PM.mesh_axis_sizes(mesh),
            accum_dtype=accum_dtype,
        )
        in_sh = _to_shardings(cell.in_specs, mesh)
        jitted = jax.jit(cell.step_fn, in_shardings=in_sh, donate_argnums=cell.donate)
        t0 = time.time()
        lowered = jitted.lower(*cell.abstract_args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    return cell, compiled, (t_lower, t_compile)


def _accounting_cfg(cfg, shape):
    """Config for the cost-accounting compile (B): every scan unrolled.

    Chunk sizes stay at production values for *causal* attention (the static
    causal block-skip means chunking granularity changes counted flops), but
    long prefill/decode contexts scale chunks to seq/16 to bound HLO size —
    a documented ≤~6 % attention-flops inflation at 32k (EXPERIMENTS.md).
    """
    kw: Dict[str, Any] = {"scan_layers": False}
    if shape.kind != "train":
        kw["attn_chunk"] = max(cfg.attn_chunk, shape.seq_len // 16)
        kw["q_chunk"] = max(cfg.q_chunk, shape.seq_len // 16)
        kw["ssm_chunk"] = max(cfg.ssm_chunk, min(1024, shape.seq_len // 32))
        kw["loss_chunk"] = max(cfg.loss_chunk, shape.seq_len // 8)
    return dataclasses.replace(cfg, **kw)


def _layer_points(cfg):
    """(group_count, cfg_kwargs(k)) for the cost-extrapolation probes.

    Layer stacks are homogeneous, so every cost (flops, bytes, collective
    traffic) is affine in the number of layer groups:  C(k) = base + k·group.
    Two probe compiles (k=1, 2) recover base and group exactly; the full-depth
    cost is base + G·group.  This replaces a full-unroll compile that takes
    7+ minutes per cell with two ~20 s compiles (validated against a full
    unroll on qwen2 train_4k — EXPERIMENTS.md §Dry-run).
    """
    fam = cfg.family
    if fam in ("dense", "moe"):
        return cfg.n_layers, lambda k: {"n_layers": k}
    if fam == "vlm":
        g = cfg.n_layers // cfg.cross_every
        return g, lambda k: {"n_layers": k * cfg.cross_every}
    if fam == "zamba":
        g = cfg.n_layers // cfg.shared_attn_every
        return g, lambda k: {"n_layers": k * cfg.shared_attn_every}
    if fam == "xlstm":
        g = cfg.n_layers // cfg.slstm_every
        return g, lambda k: {"n_layers": k * cfg.slstm_every}
    raise ValueError(fam)


def _cost_measures(compiled, ndev) -> Dict[str, Any]:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    coll = hlo.parse_collectives(compiled.as_text(), ndev)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_counts": dict(coll.counts),
        "coll_bytes": dict(coll.bytes),
    }


def _affine_combine(c1: Dict, c2: Dict, k1: int, k2: int, full: int, scale: float) -> Dict:
    """C(full) = C(k1) + (full−k1)/(k2−k1) · (C(k2)−C(k1)), then × scale."""
    f = (full - k1) / (k2 - k1)

    def ext(a, b):
        return max(0.0, (a + f * (b - a))) * scale

    keys = set(c1["coll_bytes"]) | set(c2["coll_bytes"])
    return {
        "flops": ext(c1["flops"], c2["flops"]),
        "bytes": ext(c1["bytes"], c2["bytes"]),
        "coll_counts": {
            k: int(ext(c1["coll_counts"].get(k, 0), c2["coll_counts"].get(k, 0)))
            for k in keys
        },
        "coll_bytes": {
            k: ext(c1["coll_bytes"].get(k, 0.0), c2["coll_bytes"].get(k, 0.0))
            for k in keys
        },
    }


def _solve_linear(points, features_full) -> Dict[str, Any]:
    """Least-squares fit of cost = Σ coef·feature over probe points, then
    evaluate at the full-size feature vector.  Exact when the model spans the
    true affine structure (homogeneous stacks × per-example batch work)."""
    import numpy as np

    feats = np.array([p[0] for p in points], dtype=float)  # (n_pts, n_feat)
    keys = set()
    for _, m in points:
        keys |= set(m["coll_bytes"])

    def fit(getter) -> float:
        ys = np.array([getter(m) for _, m in points], dtype=float)
        coef, *_ = np.linalg.lstsq(feats, ys, rcond=None)
        return float(max(0.0, np.dot(coef, features_full)))

    return {
        "flops": fit(lambda m: m["flops"]),
        "bytes": fit(lambda m: m["bytes"]),
        "coll_counts": {
            k: int(fit(lambda m, k=k: m["coll_counts"].get(k, 0))) for k in keys
        },
        "coll_bytes": {
            k: fit(lambda m, k=k: m["coll_bytes"].get(k, 0.0)) for k in keys
        },
    }


def _cost_by_extrapolation(
    cfg, shape, rules, mesh, *, optimizer, dp_size, mb, accum_dtype=jnp.float32
) -> Dict[str, Any]:
    """Full-size unrolled cost via tiny probe compiles.

    Every cost is affine in (a) the number of homogeneous layer groups and
    (b) the global batch (per-example work + batch-independent weight/
    optimizer work), so probes at {1,2} groups × {dp, 2·dp} examples fit
    cost = a + k·c + b·d + k·b·e exactly — each probe compiles in seconds
    instead of the minutes a full-depth full-batch unroll takes.
    """
    ndev = mesh_devices(mesh)
    acc_cfg = _accounting_cfg(cfg, shape)
    scale = 1.0
    b_full = shape.global_batch
    if shape.kind == "train" and mb > 1:
        b_full = shape.global_batch // mb
        scale = float(mb)
    b1 = max(1, min(dp_size, b_full))
    b2 = min(2 * b1, b_full)
    if b2 == b1:
        b2 = b1  # degenerate batch dim: single point, feature dropped

    t0 = time.time()
    points = []
    if cfg.family == "encdec":
        depth_pts = [(1, 1), (2, 1), (1, 2)]
        for (e, d) in depth_pts:
            for b in {b1, b2}:
                cfg_k = dataclasses.replace(acc_cfg, n_encoder_layers=e, n_layers=d)
                shp = dataclasses.replace(shape, global_batch=b)
                _, comp, _ = _compile_cell(
                    cfg_k, shp, rules, mesh,
                    optimizer=optimizer, microbatches=1, dp_size=dp_size,
                )
                feats = [1.0, e, d, b, e * b, d * b]
                points.append((feats, _cost_measures(comp, ndev)))
        full_feats = [
            1.0, cfg.n_encoder_layers, cfg.n_layers, b_full,
            cfg.n_encoder_layers * b_full, cfg.n_layers * b_full,
        ]
    else:
        full, kw = _layer_points(cfg)
        ks = (1, 2) if full >= 2 else (full,)
        for k in ks:
            for b in sorted({b1, b2}):
                cfg_k = dataclasses.replace(acc_cfg, **kw(k))
                shp = dataclasses.replace(shape, global_batch=b)
                _, comp, _ = _compile_cell(
                    cfg_k, shp, rules, mesh,
                    optimizer=optimizer, microbatches=1, dp_size=dp_size,
                )
                feats = [1.0, k, b, k * b]
                points.append((feats, _cost_measures(comp, ndev)))
        full_feats = [1.0, full, b_full, full * b_full]

    # drop degenerate feature columns (single k or single b probes)
    import numpy as np

    fmat = np.array([p[0] for p in points])
    keep = [i for i in range(fmat.shape[1]) if len(set(fmat[:, i])) > 1 or i == 0]
    points = [([p[0][i] for i in keep], p[1]) for p in points]
    out = _solve_linear(points, [full_feats[i] for i in keep])
    for key in ("flops", "bytes"):
        out[key] *= scale
    out["coll_counts"] = {k: int(v * scale) for k, v in out["coll_counts"].items()}
    out["coll_bytes"] = {k: v * scale for k, v in out["coll_bytes"].items()}
    out["probe_s"] = round(time.time() - t0, 2)
    out["cost_scale"] = scale
    out["n_probes"] = len(points)
    return out


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    *,
    microbatches: int = 0,
    remat: Optional[bool] = None,
    rule_overrides: Optional[Dict[str, Any]] = None,
    optimizer: Optional[str] = None,
    tag: str = "",
    outdir: str = ARTIFACT_DIR,
    verbose: bool = True,
    cost_compile: Optional[bool] = None,
    accum_dtype=jnp.float32,
    zero3: bool = False,
) -> Dict[str, Any]:
    """One dry-run cell.

    Per single-pod cell:
      A (scan mode)       — memory_analysis: the fits-in-HBM proof.
      cost extrapolation  — two shallow unrolled probe compiles recover the
        full-depth flops/bytes/collective traffic exactly (XLA counts a while
        body once regardless of trip count, so rolled scans undercount; full
        unrolls compile for 7+ min).  Train cells probe at 1/microbatches of
        the global batch and scale ×microbatches (optimizer + grad-sync
        collectives get scaled too — bounded, documented).
    Multi-pod cells run compile A only (the roofline table is single-pod).
    """
    cfg = configs.get_config(arch)
    if remat is not None:
        cfg = dataclasses.replace(cfg, remat=remat)
    if zero3:
        cfg = dataclasses.replace(cfg, zero3_gather=True)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_for(arch, shape_name, multi_pod)
    if rule_overrides:
        rules.update(rule_overrides)
    if cost_compile is None:
        cost_compile = not multi_pod

    # data-parallel degree = product of mesh axes carrying the batch rule
    batch_axes = rules.get("batch")
    if batch_axes is None:
        dp_size = 1
    else:
        axes = batch_axes if isinstance(batch_axes, tuple) else (batch_axes,)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp_size = 1
        for a in axes:
            dp_size *= sizes.get(a, 1)
    mb = microbatches or steps_lib.auto_microbatches(shape, dp_size)

    # --- compile A: memory / fits-proof ------------------------------------
    cell, compiled, timings = _compile_cell(
        cfg, shape, rules, mesh,
        optimizer=optimizer, microbatches=mb, dp_size=dp_size,
        accum_dtype=accum_dtype,
    )

    cost = None
    if cost_compile:
        cost = _cost_by_extrapolation(
            cfg, shape, rules, mesh, optimizer=optimizer, dp_size=dp_size, mb=mb,
            accum_dtype=accum_dtype,
        )

    return _analyze(
        compiled,
        mesh,
        name=cell.name,
        kind=shape.kind,
        # processed tokens per step: full sequence for train/prefill, one new
        # token per request for decode
        tokens=shape.global_batch * (1 if shape.kind == "decode" else shape.seq_len),
        cfg=cfg,
        mesh_name="multi" if multi_pod else "single",
        timings=timings,
        tag=tag,
        outdir=outdir,
        verbose=verbose,
        cost_override=cost,
        extra={
            "microbatches": mb,
            "remat": cfg.remat,
            "optimizer": optimizer,
            "rule_overrides": {k: str(v) for k, v in (rule_overrides or {}).items()},
        },
    )


def _analyze(
    compiled,
    mesh,
    *,
    name: str,
    kind: str,
    tokens: int,
    cfg,
    mesh_name: str,
    timings,
    tag: str,
    outdir: str,
    verbose: bool,
    extra: Dict[str, Any],
    cost_override: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    ndev = mesh_devices(mesh)
    mem = _memory_dict(compiled.memory_analysis())
    if cost_override is not None:
        flops = cost_override["flops"]
        byts = cost_override["bytes"]
        coll = hlo.CollectiveStats(
            counts=cost_override["coll_counts"], bytes=cost_override["coll_bytes"]
        )
        extra = dict(extra, cost_probe_s=cost_override.get("probe_s"),
                     cost_scale=cost_override.get("cost_scale"))
    else:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost.get("flops", 0.0))
        byts = float(cost.get("bytes accessed", 0.0))
        coll = hlo.parse_collectives(compiled.as_text(), ndev)

    roof = hlo.Roofline(
        flops_per_device=flops,
        hbm_bytes_per_device=byts,
        collective_bytes_per_device=coll.total_bytes,
        n_devices=ndev,
    )
    result: Dict[str, Any] = {
        "cell": name,
        "kind": kind,
        "mesh": mesh_name,
        "n_devices": ndev,
        "lower_s": round(timings[0], 2),
        "compile_s": round(timings[1], 2),
        "memory_analysis": mem,
        "cost_analysis": {"flops": flops, "bytes_accessed": byts},
        "collectives": {"counts": coll.counts, "bytes": coll.bytes},
        "roofline": roof.to_dict(),
        **extra,
    }
    if cfg is not None:
        model = get_model(cfg)
        n_params = PM.count_params(model.param_specs)
        frac = _active_fraction_flops(cfg)
        useful = hlo.model_flops(kind, int(n_params * frac), tokens)
        result["n_params"] = n_params
        result["model_flops_global"] = useful
        # cost_analysis flops are per-device post-SPMD
        hlo_global = flops * ndev
        result["useful_flops_ratio"] = useful / hlo_global if hlo_global else 0.0

    os.makedirs(outdir, exist_ok=True)
    fname = name.replace(":", "__").replace("/", "_") + f"__{mesh_name}"
    if tag:
        fname += f"__{tag}"
    path = os.path.join(outdir, fname + ".json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    if verbose:
        r = result["roofline"]
        print(
            f"[dryrun] {name} ({mesh_name}) lower {result['lower_s']}s "
            f"compile {result['compile_s']}s | compute {r['compute_s']:.3e}s "
            f"memory {r['memory_s']:.3e}s collective {r['collective_s']:.3e}s "
            f"→ {r['dominant']}-bound",
            flush=True,
        )
        print(f"[dryrun] memory_analysis: {mem}", flush=True)
        print(f"[dryrun] wrote {path}", flush=True)
    return result


# ---------------------------------------------------------------------------
# ONN dry-run cells (the paper's contribution on the production mesh)
# ---------------------------------------------------------------------------


def _pack_bits(s: jax.Array) -> jax.Array:
    """±1 int8 spins → bit-packed uint8, 8 spins/byte (last dim ÷ 8)."""
    b, n = s.shape
    bits = (s > 0).astype(jnp.uint8).reshape(b, n // 8, 8)
    weights = jnp.array([1, 2, 4, 8, 16, 32, 64, 128], jnp.uint8)
    return jnp.sum(bits * weights, axis=-1, dtype=jnp.uint8)


def _unpack_bits(p: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`_pack_bits`: uint8 → ±1 int8 spins."""
    b = p.shape[0]
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (p[..., None] >> shifts) & 1
    return (2 * bits.astype(jnp.int8) - 1).reshape(b, n)


def run_onn_cell(
    cell_name: str,
    multi_pod: bool,
    *,
    tag: str = "",
    outdir: str = ARTIFACT_DIR,
    verbose: bool = True,
    variant: str = "baseline2d",
) -> Dict[str, Any]:
    """Lower the batched ONN retrieval sweep, W sharded on the mesh — the
    paper's deferred "multi-FPGA clustering" as a GSPMD program.

    Variants (§Perf hillclimb; baseline2d is the paper-faithful mapping):
      baseline2d      W P("model","data") 2-D sharded; spins replicated.
                      Each step: partial matvec + psum over "data" +
                      re-gather of spins over "model".
      rowpar          W row-sharded over ALL axes P(("data","model")); no
                      contraction psum — only the σ' all-gather.
      rowpar_bitpack  rowpar + spins bit-packed to 1 bit/osc for the gather
                      (the wire carries N/8 bytes instead of N).
      rowpar_bp_int4  + couplings stored 2/byte (int4), unpacked on-chip:
                      halves the W HBM stream (the dominant memory term).
    """
    spec = ONN_CELLS[cell_name]
    n, batch, cycles = spec["n"], spec["batch"], spec["cycles"]
    mesh = make_production_mesh(multi_pod=multi_pod)
    ndev = mesh_devices(mesh)
    all_axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    rep = NamedSharding(mesh, P(None, None))

    # The update rule is the shared functional core (repro.core.dynamics);
    # only the sharding annotations are variant-specific here.
    onn_cfg = dyn.ONNConfig(n=n, max_cycles=cycles, backend="parallel")
    sign_update = dyn.sign_update

    def matvec(w, s):
        return dyn.weighted_sum(onn_cfg, w, s)

    if variant == "baseline2d":
        # FPGA-scale cells (N=506 does not divide the mesh axes) keep W
        # replicated and parallelize over the request batch — the right
        # production layout for a network whose couplings fit one chip.
        # Pod-scale cells 2-D-shard W (the paper's multi-FPGA clustering).
        layout = "2d" if n % 16 == 0 else "replicated"
        w_sh = NamedSharding(mesh, shrules.onn_weight_spec(multi_pod, layout))
        w_sds = jax.ShapeDtypeStruct((n, n), jnp.int8)
        sig_rep = rep if n % 16 == 0 else NamedSharding(
            mesh, P(("pod", "data") if multi_pod else "data", None)
        )

        def onn_sweep(w, sigma):
            def body(s, _):
                s_new = sign_update(matvec(w, s), s)
                return jax.lax.with_sharding_constraint(s_new, sig_rep), None

            out, _ = jax.lax.scan(body, sigma, None, length=cycles, unroll=True)
            return out

    elif variant == "rowpar":
        w_sh = NamedSharding(mesh, shrules.onn_weight_spec(multi_pod, "row"))
        w_sds = jax.ShapeDtypeStruct((n, n), jnp.int8)

        def onn_sweep(w, sigma):
            def body(s, _):
                field = matvec(w, s)  # rows sharded → no contraction psum
                s_new = jax.lax.with_sharding_constraint(
                    sign_update(field, s), NamedSharding(mesh, P(None, all_axes))
                )
                return jax.lax.with_sharding_constraint(s_new, rep), None

            out, _ = jax.lax.scan(body, sigma, None, length=cycles, unroll=True)
            return out

    elif variant in ("rowpar_bitpack", "rowpar_bp_int4"):
        int4 = variant.endswith("int4")
        w_sh = NamedSharding(mesh, shrules.onn_weight_spec(multi_pod, "row"))
        w_sds = jax.ShapeDtypeStruct((n, n // 2 if int4 else n), jnp.int8 if not int4 else jnp.uint8)

        row_sharded = NamedSharding(mesh, P(None, all_axes))

        def onn_sweep(w, sigma):
            packed0 = _pack_bits(sigma)

            def body(pk, _):
                s = _unpack_bits(pk, n)  # replicated spins, decoded on-chip
                if int4:
                    from repro.core.quantization import unpack_int4

                    w_full = unpack_int4(w)
                else:
                    w_full = w
                # pin every intermediate to the row sharding so GSPMD never
                # falls back to gathering the int32 field (measured: without
                # these constraints it moves 4×int8 worth of field instead of
                # 1-bit packed spins — EXPERIMENTS.md §Perf H2 iteration 1)
                field = jax.lax.with_sharding_constraint(matvec(w_full, s), row_sharded)
                s_new = jax.lax.with_sharding_constraint(
                    sign_update(field, s), row_sharded
                )
                pk_new = jax.lax.with_sharding_constraint(
                    _pack_bits(s_new),
                    NamedSharding(mesh, P(None, all_axes)),
                )  # pack on the sharded value…
                # …so the gather back to replicated moves 1 bit/oscillator.
                return jax.lax.with_sharding_constraint(pk_new, rep), None

            out, _ = jax.lax.scan(body, packed0, None, length=cycles, unroll=True)
            return _unpack_bits(out, n)

    else:
        raise ValueError(f"unknown ONN variant {variant!r}")

    sig_sds = jax.ShapeDtypeStruct((batch, n), jnp.int8)
    sig_in = locals().get("sig_rep", rep)
    in_sh = (w_sh, sig_in)
    jitted = jax.jit(onn_sweep, in_shardings=in_sh)
    t0 = time.time()
    lowered = jitted.lower(w_sds, sig_sds)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    result = _analyze(
        compiled,
        mesh,
        name=f"onn:{cell_name}",
        kind="onn-sweep",
        tokens=batch * cycles,
        cfg=None,
        mesh_name="multi" if multi_pod else "single",
        timings=(t_lower, t_compile),
        tag=tag or (variant if variant != "baseline2d" else ""),
        outdir=outdir,
        verbose=verbose,
        extra={"n_oscillators": n, "batch": batch, "cycles": cycles,
               "variant": variant},
    )
    # Useful ops: 2·N²·B MACs per cycle (the coupling weighted sums).
    useful = 2.0 * n * n * batch * cycles
    result["model_flops_global"] = useful
    flops_global = result["cost_analysis"]["flops"] * ndev
    result["useful_flops_ratio"] = useful / flops_global if flops_global else 0.0
    return result


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


#: Placeholder host devices the dry-run meshes are built from.
DRYRUN_XLA_FLAGS = "--xla_force_host_platform_device_count=512"


def main() -> None:
    os.environ["XLA_FLAGS"] = DRYRUN_XLA_FLAGS
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--onn", type=str, default=None, choices=list(ONN_CELLS) + [None])
    ap.add_argument("--all", action="store_true", help="run every applicable cell")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--microbatches", type=int, default=0, help="0 = auto")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--opt", type=str, default=None)
    ap.add_argument("--rule", action="append", default=[],
                    help="sharding rule override key=axis ('' = replicate)")
    ap.add_argument("--tag", type=str, default="")
    ap.add_argument("--out", type=str, default=ARTIFACT_DIR)
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    overrides: Dict[str, Any] = {}
    for kv in args.rule:
        k, _, v = kv.partition("=")
        if v == "":
            overrides[k] = None
        elif "," in v:
            overrides[k] = tuple(v.split(","))
        else:
            overrides[k] = v

    jobs = []
    if args.onn:
        jobs = [("onn", args.onn, None)]
    elif args.all:
        jobs = [("lm", a, s) for a, s in configs.all_cells()]
        jobs += [("onn", c, None) for c in ONN_CELLS]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all or --onn required"
        jobs = [("lm", args.arch, args.shape)]

    failures = []
    for kind, a, s in jobs:
        for mp in meshes:
            try:
                if kind == "onn":
                    run_onn_cell(a, mp, tag=args.tag, outdir=args.out)
                else:
                    run_cell(
                        a, s, mp,
                        microbatches=args.microbatches,
                        remat=False if args.no_remat else None,
                        rule_overrides=overrides or None,
                        optimizer=args.opt,
                        tag=args.tag,
                        outdir=args.out,
                    )
            except Exception as e:  # noqa: BLE001 — surface per-cell failures
                failures.append((a, s, mp, repr(e)))
                print(f"[dryrun] FAILED {a} {s} multi_pod={mp}: {e!r}", flush=True)
    if failures:
        raise SystemExit(f"{len(failures)} dry-run cells failed: {failures}")


if __name__ == "__main__":
    main()
