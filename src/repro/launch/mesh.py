"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the smoke tests must keep seeing 1 CPU
device while the dry-run sees 512 placeholder devices.

Mesh layout (TPU v5e pods):
  single-pod:  (16, 16)        axes ("data", "model")   — 256 chips
  multi-pod:   (2, 16, 16)     axes ("pod", "data", "model") — 512 chips

"model" is the tensor-parallel axis (heads / mlp / vocab / experts), "data"
carries batch + FSDP weight sharding, "pod" composes with "data" for
cross-pod data parallelism (DESIGN.md §5).
"""

from __future__ import annotations


import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over however many (host) devices exist — tests only."""
    return jax.make_mesh((data, model), ("data", "model"), (AxisType.Auto,) * 2)


def mesh_devices(mesh: Mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n


def build_shard_plan(spec: str = "auto"):
    """Build the launcher-facing :class:`repro.distributed.ShardPlan`.

    ``spec``: ``"BxM"`` (data × model degrees) or ``"auto"``
    (``ft.propose_mesh`` over the local devices).  The single entry point
    behind every launcher's ``--mesh`` flag.
    """
    from repro.distributed import ShardPlan

    return ShardPlan.parse(spec)


def make_plan_mesh(plan) -> Mesh:
    """The local ``(batch, model)`` mesh for a ShardPlan."""
    return plan.make_mesh()
