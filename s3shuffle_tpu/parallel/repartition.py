"""On-device shuffle repartition: sharded rows → owner devices over ICI.

The device analog of the map-side partitioner + reduce-side fetch: each device
holds a local batch of fixed-width records (uint8 rows) plus a target
partition id per row; one jitted ``shard_map`` step routes every row to the
device owning its partition using ``all_to_all`` — XLA schedules the collective
over ICI, no host round-trip, no object store.

Static-shape contract (XLA needs fixed shapes): each device sends exactly
``capacity`` rows to every peer, padding short buckets; row counts travel in a
tiny side all_to_all so receivers can mask padding. Overflow beyond capacity
raises at the call boundary (callers size capacity with :func:`plan_capacity`;
the store path remains the fallback for pathological skew).
"""

from __future__ import annotations

import functools


def plan_capacity(local_rows: int, n_devices: int, slack: float = 2.0) -> int:
    """Rows-per-peer capacity for a balanced-ish shuffle with ``slack``×
    headroom over perfectly uniform routing."""
    import math

    return max(1, math.ceil(local_rows / max(1, n_devices) * slack))


@functools.lru_cache(maxsize=32)
def _repartition_fn(axis: str, n_dev: int, capacity: int, row_bytes: int):
    import jax
    import jax.numpy as jnp

    def local_step(rows, part_ids):
        # rows: (N_local, row_bytes) uint8; part_ids: (N_local,) int32
        n_local = rows.shape[0]
        dest = part_ids % n_dev
        # stable sort by destination so each peer's rows are contiguous
        order = jnp.argsort(dest, stable=True)
        rows_sorted = jnp.take(rows, order, axis=0)
        dest_sorted = jnp.take(dest, order)
        ids_sorted = jnp.take(part_ids, order)
        # per-destination counts and bucket-local offsets
        counts = jnp.bincount(dest, length=n_dev)  # (n_dev,)
        starts = jnp.cumsum(counts) - counts
        within = jnp.arange(n_local) - jnp.take(starts, dest_sorted)
        # scatter into (n_dev, capacity, row_bytes); rows beyond capacity are
        # dropped by the scatter itself (mode="drop" on the out-of-bounds
        # `within` index) so they can never clobber an in-capacity slot
        send = jnp.zeros((n_dev, capacity, row_bytes), dtype=rows.dtype)
        send_ids = jnp.zeros((n_dev, capacity), dtype=part_ids.dtype)
        send = send.at[dest_sorted, within].set(rows_sorted, mode="drop")
        send_ids = send_ids.at[dest_sorted, within].set(ids_sorted, mode="drop")
        overflow = jnp.sum(jnp.maximum(counts - capacity, 0))
        send_counts = jnp.minimum(counts, capacity)
        return send, send_ids, send_counts, overflow

    def step(rows, part_ids):
        send, send_ids, send_counts, overflow = local_step(rows, part_ids)
        # exchange: concat-split semantics, one chunk per peer
        recv = jax.lax.all_to_all(
            send[None], axis, split_axis=1, concat_axis=0, tiled=False
        )[:, 0]
        recv_ids = jax.lax.all_to_all(
            send_ids[None], axis, split_axis=1, concat_axis=0, tiled=False
        )[:, 0]
        recv_counts = jax.lax.all_to_all(
            send_counts[None].reshape(1, n_dev, 1), axis, split_axis=1, concat_axis=0
        ).reshape(n_dev)
        # mask of valid received rows
        valid = (
            jax.lax.broadcasted_iota(jnp.int32, (n_dev, capacity), 1)
            < recv_counts[:, None]
        )
        return (
            recv.reshape(n_dev * capacity, row_bytes),
            recv_ids.reshape(n_dev * capacity),
            valid.reshape(n_dev * capacity),
            overflow.reshape(1),  # rank-1 so shard_map can concat over the axis
        )

    return step


def device_repartition(mesh, rows, part_ids, axis: str = "data", capacity: int | None = None):
    """Repartition sharded records across the mesh axis.

    ``rows``: (N, row_bytes) uint8 sharded over ``axis``; ``part_ids``: (N,)
    int32 target partition ids (owner device = id % axis size). Returns
    per-device (received_rows, received_ids, valid_mask) as a sharded tuple,
    plus the global overflow count (int — nonzero means capacity was too
    small and rows were dropped; callers must treat that as an error and
    retry via the store path or a larger capacity).
    """
    import jax.numpy as jnp

    n_dev = mesh.shape[axis]
    n, row_bytes = rows.shape
    if n % n_dev != 0:
        raise ValueError(f"row count {n} must divide evenly over {n_dev} devices")
    local_n = n // n_dev
    if capacity is None:
        capacity = plan_capacity(local_n, n_dev)

    step = sharded_step(mesh, axis, capacity, row_bytes)
    recv, recv_ids, valid, overflow = step(rows, part_ids)
    total_overflow = int(jnp.sum(overflow))
    if total_overflow:
        raise ValueError(
            f"repartition overflow: {total_overflow} rows exceeded capacity "
            f"{capacity}; increase capacity/slack or use the store path"
        )
    return recv, recv_ids, valid


def sharded_step(mesh, axis: str, capacity: int, row_bytes: int):
    """The jitted ``shard_map`` repartition step over ``mesh``: takes
    (rows [N, row_bytes] u8, part_ids [N] i32) sharded on ``axis`` and
    returns per-device (received_rows, received_ids, valid_mask, overflow).
    One ``all_to_all`` per payload; callers lower it against a described
    topology too (tests/test_chip_compile.py)."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    step = _repartition_fn(axis, mesh.shape[axis], capacity, row_bytes)
    return jax.jit(shard_map(
        step,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis)),
        out_specs=(P(axis, None), P(axis), P(axis), P(axis)),
    ))
