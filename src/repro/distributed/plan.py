"""`ShardPlan`: the one description of how an ONN solve parallelizes.

Before this module the repo had three parallelism knobs that did not
compose: each launcher's ``--shard-batch`` recipe (lanes over every local
device), the rule-table layouts of :mod:`repro.distributed.sharding`
(``onn_weight_spec`` / ``constrain_onn``), and the fault-tolerance mesh
proposal in :mod:`repro.distributed.ft`.  A :class:`ShardPlan` unifies them:

* ``batch`` — data-parallel degree: request lanes split over the ``"data"``
  mesh axis (the old ``--shard-batch`` behaviour is ``ShardPlan(batch=ndev)``).
* ``model`` — model-parallel degree: the (N, N) coupling matrix is
  row-sharded over the ``"model"`` mesh axis and every ``weighted_sum``
  becomes a shard_map collective (local int8 MACs over the row block, then a
  psum combine) — see ``repro.core.dynamics._model_sharded_sum``.  This is
  what breaks the single-device N = 506 weight-residency wall.
* ``layout`` — coupling-matrix placement: ``"row"`` (sharded, the default)
  or ``"replicated"`` (W on every device; the model axis is declared but the
  collective is skipped — batch parallelism only).
* ``compressed`` — combine row-block partials over an int8 wire
  (``repro.optim.compress.compressed_psum_scatter``) instead of the exact
  int32 psum.  Exact whenever every local partial fits int8 (the quantizer's
  scale floors at 1); an opt-in approximation beyond that.

The plan is a frozen, hashable dataclass, so it rides the jit-cache
discriminator that the batched dynamics entry points already thread
(``dynamics._sharding_cache_key``): activating a plan forks executables
instead of silently reusing unsharded ones.

Usage::

    plan = ShardPlan.parse("2x4")          # or ShardPlan(batch=2, model=4)
    mesh = plan.make_mesh()
    params = jax.device_put(params, sharding.onn_param_shardings(mesh, plan=plan))
    with plan.context(mesh):
        result = dynamics.retrieve(cfg, params, sigma0)
"""

from __future__ import annotations

import contextlib
import dataclasses
import re
from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh

_LAYOUTS = ("row", "replicated")


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """How one solve spreads over a (batch × model) device mesh."""

    batch: int = 1  # data-parallel degree (request lanes over "data")
    model: int = 1  # model-parallel degree (W rows over "model")
    layout: str = "row"  # coupling-matrix placement: "row" | "replicated"
    compressed: bool = False  # int8 wire format for the row-block combine

    def __post_init__(self) -> None:
        if self.batch < 1 or self.model < 1:
            raise ValueError(
                f"ShardPlan axes must be >= 1, got batch={self.batch} "
                f"model={self.model}"
            )
        if self.layout not in _LAYOUTS:
            raise ValueError(
                f"unknown ShardPlan layout {self.layout!r}; expected one of "
                f"{_LAYOUTS}"
            )

    @property
    def devices(self) -> int:
        return self.batch * self.model

    @property
    def model_sharded(self) -> bool:
        """Whether the weighted-sum collective is active (W actually split)."""
        return self.model > 1 and self.layout == "row"

    @classmethod
    def parse(cls, spec: str, n_devices: Optional[int] = None) -> "ShardPlan":
        """Parse a ``--mesh`` spec: ``"BxM"`` (e.g. ``"2x4"``) or ``"auto"``.

        ``"auto"`` delegates to :func:`repro.distributed.ft.propose_mesh`
        over ``n_devices`` (default: every local device) — the same policy
        the fault-tolerant daemon uses to re-mesh after a device loss.
        """
        spec = spec.strip().lower()
        if spec == "auto":
            return cls.auto(n_devices)
        m = re.fullmatch(r"(\d+)x(\d+)", spec)
        if not m:
            raise ValueError(
                f"bad mesh spec {spec!r}: expected 'BxM' (e.g. '2x4') or 'auto'"
            )
        plan = cls(batch=int(m.group(1)), model=int(m.group(2)))
        avail = jax.device_count() if n_devices is None else n_devices
        if plan.devices > avail:
            raise ValueError(
                f"mesh {spec!r} needs {plan.devices} devices, "
                f"only {avail} available"
            )
        return plan

    @classmethod
    def auto(cls, n_devices: Optional[int] = None) -> "ShardPlan":
        """Propose a plan for the surviving device count (ft policy)."""
        from repro.distributed import ft

        avail = jax.device_count() if n_devices is None else n_devices
        data, model = ft.propose_mesh(avail, prefer_model=min(avail, 16))
        return cls(batch=data, model=model)

    def make_mesh(self) -> Mesh:
        """A local ``(batch, model)`` mesh with axes ``("data", "model")``.

        Axes are ``Auto``: the solve places its lanes and weight rows with
        sharding constraints and ``shard_map``, which explicit axes reject.
        """
        return jax.make_mesh(
            (self.batch, self.model), ("data", "model"), (AxisType.Auto,) * 2
        )

    @contextlib.contextmanager
    def context(self, mesh: Optional[Mesh] = None):
        """Activate this plan (and mesh) for every solve traced inside.

        Yields the mesh so call sites can ``with plan.context() as mesh:``.
        """
        from repro.distributed import sharding

        if mesh is None:
            mesh = self.make_mesh()
        shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        if shape.get("data", 1) < self.batch or shape.get("model", 1) < self.model:
            raise ValueError(
                f"mesh {shape} too small for plan (batch={self.batch}, "
                f"model={self.model})"
            )
        with sharding.use_plan(self, mesh):
            yield mesh


def plan_of_legacy_shard_batch(n_devices: Optional[int] = None) -> ShardPlan:
    """The plan equivalent of the retired per-launcher ``--shard-batch``."""
    avail = jax.device_count() if n_devices is None else n_devices
    return ShardPlan(batch=avail, model=1, layout="replicated")
