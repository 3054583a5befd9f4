"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import
and then calls it.

Single pod: (data=16, model=16) — 256 chips of TPU v5e.
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; ``pod`` is an outer
data axis (gradients reduce hierarchically: reduce-scatter on the fast
intra-pod ICI, then the small cross-pod hop on DCI).
"""

from __future__ import annotations

import jax


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]
               ) -> jax.sharding.Mesh:
    # Auto axes: the sharded programs here place arrays with explicit
    # PartitionSpecs and shard_map, not with jax.make_mesh's Explicit
    # default (which requires a jax.set_mesh context around every jit)
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over however many real devices exist (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return _auto_mesh((data, model), ("data", "model"))


def data_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """The axes a global batch shards over: ('pod','data') on multi-pod."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh: jax.sharding.Mesh) -> str:
    return "model"
