"""Production + auction mesh builders (assignment-fixed shapes).

Functions, not module-level constants: importing this module never touches
jax device state (critical — device count locks on first use).

``make_auction_mesh`` is the entry point the sharded auction round uses
(``SchedulerConfig.mesh`` / the ``mesh=`` knob on ``clear_round`` /
``pipelined_clear_rounds``): a 1-axis mesh named ``"bids"`` over a
power-of-two device count, degrading gracefully — never raising — when the
requested shape exceeds what the platform actually has.  On CPU,
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set before the
first jax import) provides virtual devices for testing the sharded path
without hardware.
"""
from __future__ import annotations

from typing import Optional

import jax

__all__ = ["make_production_mesh", "make_auction_mesh", "mesh_chips",
           "AUCTION_AXIS"]

#: the single mesh axis the auction shards over: the pooled bid dim of the
#: scoring dispatch and the window dim of the batched WIS settle
AUCTION_AXIS = "bids"


def _pow2_floor(n: int) -> int:
    return 1 << (max(int(n), 1).bit_length() - 1)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:   (pod=2, data=16, model=16) = 512 chips.

    Falls back to a 1-axis ``("data",)`` mesh over every local device when
    the fixed shape exceeds what the platform has (CI boxes, virtual-device
    CPU runs) — callers get a working mesh, never an exception.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n_needed = 1
    for s in shape:
        n_needed *= s
    if jax.device_count() < n_needed:
        return jax.make_mesh((jax.local_device_count(),), ("data",))
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_auction_mesh(n_shards: Optional[int] = None):
    """A 1-axis auction mesh over ``n_shards`` devices (axis ``"bids"``).

    ``n_shards=None`` takes every local device.  The shard count is clamped
    to the largest power of two ≤ min(requested, available) so pow2-bucketed
    round shapes (kernels/jasda_score ``bucket_m``, core/wis row buckets)
    always divide evenly across shards — the zero-retrace contract needs
    one executable per bucket per MESH SHAPE, not per pool size.  With one
    device (or ``n_shards=1``) the mesh is valid but degenerate; every
    ``mesh=`` consumer falls back to the unsharded dispatch path.
    """
    avail = jax.local_device_count()
    n = avail if n_shards is None else min(int(n_shards), avail)
    n = _pow2_floor(max(n, 1))
    # Auto axes: shard_map places the round, and host-side slicing of its
    # (possibly unevenly trimmed) outputs needs no explicit out_sharding
    return jax.make_mesh((n,), (AUCTION_AXIS,),
                         axis_types=(jax.sharding.AxisType.Auto,))


def mesh_chips(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
