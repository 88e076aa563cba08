"""Production mesh construction (spec: MULTI-POD DRY-RUN item 1).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (v5e pod),
multi-pod: 2x16x16 = 512 chips with a leading "pod" data-parallel axis.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_smoke_mesh(model: int = 1):
    """1-device mesh for CPU smoke paths (same axis names)."""
    return jax.make_mesh((1, model), ("data", "model"))


def make_grid_mesh(p: int = 1, q: int = 1):
    """P x Q ("row", "col") process grid for the distributed linear
    algebra subsystem (repro.dist) — ScaLAPACK's 2D grid in mesh form.
    Runs on any device set: TPU slices, or CPU host devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the hermetic
    tier-1 path).  Both axes are Auto: the layer partitions with
    NamedSharding and shard_map, never by sharding-in-types."""
    auto = jax.sharding.AxisType.Auto
    return jax.make_mesh((p, q), ("row", "col"), axis_types=(auto, auto))


def dp_axes(mesh) -> tuple[str, ...]:
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def replicated(x):
    """A concrete array sharded over a mesh, as one replicated on that
    mesh; anything else unchanged.  Arrays on an Explicit-axis mesh (the
    default of ``jax.make_mesh`` since JAX 0.9) refuse reshapes that split
    or merge their sharded dimensions, which gathering a distributed
    matrix or flattening it for statistics needs."""
    sharding = getattr(x, "sharding", None)
    if isinstance(x, jax.core.Tracer) or not isinstance(
            sharding, jax.sharding.NamedSharding):
        return x
    return jax.device_put(x, jax.sharding.NamedSharding(
        sharding.mesh, jax.sharding.PartitionSpec()))
