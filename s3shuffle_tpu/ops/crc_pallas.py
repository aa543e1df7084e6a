"""Pallas TPU kernel for batched CRC32/CRC32C — tiled systolic fold.

The first formulation of this kernel (and the XLA kernel in
:mod:`s3shuffle_tpu.ops.checksum` it mirrored) contracted the whole
right-aligned row against a monolithic ``(L*8, 32)`` weight table: one
weight column per (byte position, bit) of the FULL block, so the table grew
with L (8 MB of int8 weights at L = 256 KiB) and the chip probe clocked the
path at 40.5 MB/s — the weights, not the data, dominated HBM traffic.

This rework keeps the MXU formulation but makes the weights O(1) in L via
the same identity :func:`s3shuffle_tpu.ops.checksum.crc_combine` uses on the
host. Processing one TL-byte tile from CRC state ``s`` is affine over GF(2):

    state' = A_TL(state) ⊕ r(tile)

where ``r(tile)`` is the tile's zero-init raw remainder and ``A_TL`` is the
"advance by TL zero bytes" linear operator (``checksum._zero_op_power``).
So the kernel walks the row tile-by-tile, computing each tile remainder with
a FIXED ``(8, 32, TL)`` weight table (one (32, TL) plane per bit, 32 KiB
total regardless of L) and folding it into the running state with the
``(32, 32)`` GF(2) shift matrix — both steps int8 MXU matmuls with the
parity (&1) applied in-register:

    r      = Σ_k bits_k(tile) @ W[k]^T          # (TB, 32) counts
    state  = (state_bits @ A_TL  +  r) & 1      # fold, in the same grid step

Grid is (B/TB, L/TL) with the L axis minor; the (TB, 32) state block lives
in the output ref across the row's tiles (same revisiting idiom as an MXU
reduction), so per grid step HBM moves exactly one (TB, TL) data tile.
Front-aligned zero padding is a fixed point (A(0) ⊕ r(0) = 0), so the
right-aligned staging layout needs no masking.

Same contract as before: raw remainder with zero init over right-aligned
rows; callers apply the zero-run fixup table for true init/final-xor
semantics (checksum.crc32_batch).
"""

from __future__ import annotations

import functools

import numpy as np

# Tile sizes: TB rows of the batch, TL bytes of the block per grid step.
# (TB, TL) uint8 data tile = 16 KiB VMEM; weight table (8, 32, TL) int8 =
# 32 KiB and fold matrix (32, 32) int8 — both constant in L. TB shrinks to
# the largest power of two that divides a small batch (the chip probe times
# 8-row batches; sublane granularity keeps 8 the floor).
_TB = 128
_TL = 128


def _row_tile(b: int) -> int:
    for tb in (128, 64, 32, 16, 8):
        if b % tb == 0:
            return tb
    raise ValueError(f"batch of {b} rows not 8-row tileable")


def _jax():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    return jax, jnp, pl


def _crc_fold_kernel(data_ref, w_ref, m_ref, out_ref):
    """One grid step: fold tile j of TB rows into the running CRC state.

    ``out_ref`` (TB, 32) int32 carries the state as 0/1 parity bits across
    the row's tiles (j is the minor grid axis, so steps over one row tile
    sequence revisit the same block).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    j = pl.program_id(1)

    data = data_ref[:].astype(jnp.int32)  # (TB, TL)
    r = jnp.zeros_like(out_ref)
    for k in range(8):
        bits_k = ((data >> k) & 1).astype(jnp.int8)  # (TB, TL)
        # contract over TL: (TB, TL) x (32, TL) -> (TB, 32)
        r = r + jax.lax.dot_general(
            bits_k,
            w_ref[k],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

    @pl.when(j == 0)
    def _():
        out_ref[:] = r & 1

    @pl.when(j != 0)
    def _():
        # advance the previous state past this tile's TL bytes, then XOR the
        # tile remainder in — both mod-2, via counts & 1
        adv = jax.lax.dot_general(
            out_ref[:].astype(jnp.int8),
            m_ref[:],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        out_ref[:] = (adv + r) & 1


@functools.lru_cache(maxsize=8)
def _fold_pallas(b: int, length: int, interpret: bool):
    """The raw (unjitted) pallas_call for (b, length) rows — shared by the
    standalone jitted kernel and larger fused traces (the TLZ encode kernel
    embeds it so payload CRCs ride the encode launch, ops/tlz.py)."""
    jax, jnp, pl = _jax()
    from jax.experimental.pallas import tpu as pltpu

    tb = _row_tile(b)
    grid = (b // tb, length // _TL)
    return pl.pallas_call(
        _crc_fold_kernel,
        out_shape=jax.ShapeDtypeStruct((b, 32), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, _TL), lambda i, j: (i, j), memory_space=pltpu.VMEM),
            pl.BlockSpec((8, 32, _TL), lambda i, j: (0, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((32, 32), lambda i, j: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tb, 32), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
        interpret=interpret,
    )


def crc_raw_in_graph(data_u8, tables, interpret: bool = False):
    """Raw zero-init remainders of right-aligned rows as a TRACEABLE op:
    callable inside an enclosing jit (shapes are concrete at trace time), so
    a fused kernel gets its CRCs in the same launch as its other outputs.
    ``tables`` is the (weights, fold matrix) pair from :func:`plane_weights`
    + :func:`fold_matrix` (or the device-resident :func:`_device_tables`).
    B and L must satisfy :func:`supported`."""
    _jax_mod, jnp, _pl = _jax()
    w_planes, fold_m = tables
    b, length = int(data_u8.shape[0]), int(data_u8.shape[1])
    parity = _fold_pallas(b, length, interpret)(data_u8, w_planes, fold_m)
    parity = parity.astype(jnp.uint32)
    return jnp.sum(
        parity << jnp.arange(32, dtype=jnp.uint32)[None, :], axis=1, dtype=jnp.uint32
    )


@functools.lru_cache(maxsize=8)
def _fold_call(b: int, length: int, poly: int, interpret: bool):
    jax, _jnp, _pl = _jax()

    @jax.jit
    def kernel(data_u8, w_planes, fold_m):
        return crc_raw_in_graph(data_u8, (w_planes, fold_m), interpret)

    from s3shuffle_tpu.ops import rates

    return rates.timed_first_call("crc32c_pallas", kernel)


def supported(b: int, length: int) -> bool:
    """Shapes the kernel tiles cleanly (callers fall back to the XLA path
    otherwise)."""
    return b > 0 and b % 8 == 0 and length % _TL == 0 and length > 0


def plane_weights(poly: int) -> np.ndarray:
    """Per-TILE weight table in the kernel's (8, 32, TL) plane layout: the
    zero-init remainder contribution of each (bit, position) of ONE TL-byte
    tile. Constant in the row length — the fold matrix carries position."""
    from s3shuffle_tpu.ops.checksum import _weights

    w_bits, _zero = _weights.get(poly, _TL)
    # (TL*8, 32) with row j*8+k  ->  (TL, 8, 32) -> (8, 32, TL)
    return np.ascontiguousarray(w_bits.reshape(_TL, 8, 32).transpose(1, 2, 0))


def fold_matrix(poly: int) -> np.ndarray:
    """``A_TL`` — the "advance CRC state by TL zero bytes" GF(2) operator as
    a (32, 32) int8 bit matrix: ``new_bits = (state_bits @ M) & 1`` with
    ``M[i, c]`` = bit c of the operator applied to basis state ``1 << i``."""
    from s3shuffle_tpu.ops.checksum import _zero_op_power

    cols = _zero_op_power(poly, _TL)  # cols[i] = A(1 << i) as uint32
    m = np.zeros((32, 32), dtype=np.int8)
    for i, col in enumerate(cols):
        for c in range(32):
            m[i, c] = (col >> c) & 1
    return m


@functools.lru_cache(maxsize=32)
def _device_tables(poly: int, device=None):
    """The fold's constant tables resident on ``device`` (None: the default
    device), one copy per (poly, chip)."""
    jax, _jnp, _pl = _jax()
    return (
        jax.device_put(plane_weights(poly), device),
        jax.device_put(fold_matrix(poly), device),
    )


def crc_raw_batch(blocks_u8, poly: int, interpret: bool = False):
    """Raw zero-init CRC remainders of right-aligned (B, L) uint8 rows, via
    the tiled-fold Pallas kernel. B and L must satisfy :func:`supported`."""
    b, length = blocks_u8.shape
    if not supported(b, length):
        raise ValueError(f"unsupported shape ({b}, {length}) for pallas crc")
    if interpret:
        tables = (plane_weights(poly), fold_matrix(poly))
    else:
        tables = _device_tables(poly)
    return _fold_call(b, length, poly, interpret)(blocks_u8, *tables)
