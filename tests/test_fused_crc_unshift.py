"""The fused TLZ kernels checksum each literal plane where it lies: front-
aligned, zero from the row's last literal byte on, then each row's raw
remainder is unshifted by ``Z^-pad`` in-graph. The unshift op matches the
host remainder of every prefix length; both fused programs return the
literal CRCs the old right-aligned formulation returned, whatever the
staged plane holds past a row's literals; and neither lowered program
gathers one element per byte of the launch outside the pointer-jump loop.
"""

import numpy as np
import pytest

from s3shuffle_tpu.ops import checksum, tlz
from s3shuffle_tpu.ops.checksum import POLY_CRC32, POLY_CRC32C, host_crc
from test_encode_window_words import _collage, _gathers

WIDTH = tlz.MAX_BLOCK
N_GROUPS = WIDTH // tlz.GROUP
JUNK = 0xA5  # what a staged plane may hold past a row's literals


def _raw_remainder(data: bytes, poly: int) -> int:
    """Zero-init raw remainder from the host's full CRCs: the init and final
    xor of a full CRC contribute exactly the full CRC of as many zeros."""
    return host_crc(data, poly) ^ host_crc(bytes(len(data)), poly)


def _prefix_rows(length: int, n_valid, seed: int) -> np.ndarray:
    rows = np.random.default_rng(seed).integers(
        0, 256, (len(n_valid), length), dtype=np.uint8
    )
    for row, n in zip(rows, n_valid):
        row[n:] = 0
    return rows


@pytest.mark.parametrize("poly", [POLY_CRC32C, POLY_CRC32], ids=["crc32c", "crc32"])
@pytest.mark.parametrize("length,pads", [
    (4096, [0, 1, 7, 8, 1733, 4096 - 8, 4096]),
    (WIDTH, [0, 8, 99_999, WIDTH]),
], ids=["small", "256KiB"])
def test_unshifted_remainder_matches_host_prefix(poly, length, pads):
    import jax

    n_valid = np.asarray([length - p for p in pads], dtype=np.int32)
    rows = _prefix_rows(length, n_valid, seed=length + len(pads))
    fn, tables = checksum.raw_crc_graph_fn(poly, length)
    got = jax.jit(fn)(rows, n_valid, *tables(None))
    assert [int(c) for c in got] == [
        _raw_remainder(bytes(row[:n]), poly) for row, n in zip(rows, n_valid)
    ]


def test_unshift_steps_invert_the_zero_byte_operator():
    for poly in (POLY_CRC32C, POLY_CRC32):
        z = checksum._zero_op_matrix(poly)
        inv = checksum._mat_inverse(z)
        identity = tuple(1 << i for i in range(32))
        assert checksum._mat_mul(z, inv) == identity == checksum._mat_mul(inv, z)
        assert checksum._unshift_bits(poly, WIDTH).shape == (19, 32, 32)


# ---------------------------------------------------------------------------
# the fused programs: the old right-aligned literal CRC, with or without junk
# ---------------------------------------------------------------------------

BS = 4096
BLOCKS = [_collage(21), _collage(22, lo=0x80), _collage(23)]


def _right_aligned_literal_crcs(lits, n_lits, length):
    """The formulation the fused kernels had: each literal plane gathered to
    the right end of its row, then the raw remainder of the whole row."""
    rows = np.zeros((len(lits), length), dtype=np.uint8)
    for row, plane, n in zip(rows, lits, n_lits):
        nb = int(n) * tlz.GROUP
        if nb:
            row[length - nb:] = plane.reshape(-1)[:nb]
    w_bits = checksum._device_weights(POLY_CRC32C, length)
    return [int(c) for c in checksum._crc_kernel(length)(rows, w_bits)]


def _encoded():
    outs = [np.asarray(x) for x in tlz._encode_kernel(BS // tlz.GROUP)(np.stack(BLOCKS))]
    n_lits = BS // tlz.GROUP - outs[8].astype(np.int32) - outs[7].astype(np.int32)
    return outs, n_lits


@pytest.mark.parametrize("junk", [False, True], ids=["zero_tail", "junk_tail"])
def test_fused_encode_literal_crcs_equal_right_aligned(junk, monkeypatch):
    outs, n_lits = _encoded()
    want = _right_aligned_literal_crcs(outs[5], n_lits, BS)
    if junk:
        real = tlz._encode_math

        def junk_past_literals(blocks, n_groups):
            import jax.numpy as jnp

            planes = real(blocks, n_groups)
            n = n_groups - planes[8] - planes[7]
            pos = jnp.arange(n_groups)[None, :, None]
            lits = jnp.where(pos < n[:, None, None], planes[5], jnp.uint8(JUNK))
            return planes[:5] + (lits,) + planes[6:]

        monkeypatch.setattr(tlz, "_encode_math", junk_past_literals)
    import jax

    staged = np.concatenate([np.stack(BLOCKS), np.zeros((1, BS), np.uint8)])
    fused = [np.asarray(x) for x in tlz._batch_kernel.__wrapped__(
        len(staged), BS // tlz.GROUP, POLY_CRC32C
    )(jax.device_put(staged))]
    for got, plane in zip(fused[6:9], outs[6:9]):  # n_new, n_split, n_match
        assert np.array_equal(got[: len(BLOCKS)], plane)
    assert [int(c) for c in fused[10][: len(BLOCKS)]] == want
    assert [int(c) for c in fused[9][: len(BLOCKS)]] == [
        _raw_remainder(b.tobytes(), POLY_CRC32C) for b in BLOCKS
    ]


@pytest.mark.parametrize("junk", [False, True], ids=["zero_tail", "junk_tail"])
def test_fused_decode_literal_crcs_equal_right_aligned(junk):
    outs, n_lits = _encoded()
    bitmap, cont, split, offs, ks, lits = outs[:6]
    want = _right_aligned_literal_crcs(lits, n_lits, BS)
    n_groups = BS // tlz.GROUP
    unpack = lambda a: np.unpackbits(  # noqa: E731
        a, axis=1, count=n_groups, bitorder="little"
    ).astype(bool)
    staged = lits.copy()
    if junk:
        staged[np.arange(n_groups)[None, :] >= n_lits[:, None]] = JUNK
    import jax

    planes = (unpack(bitmap), unpack(cont), unpack(split),
              offs.astype(np.int32), ks.astype(np.int32), staged, n_lits)
    decoded, crcs, _rounds = tlz._decode_batch_kernel.__wrapped__(
        len(BLOCKS), n_groups, POLY_CRC32C
    )(*(jax.device_put(p) for p in planes))
    assert [np.asarray(d).tobytes() for d in decoded] == [b.tobytes() for b in BLOCKS]
    assert [int(c) for c in crcs] == want


# ---------------------------------------------------------------------------
# HLO guard: no per-byte gather outside the pointer-jump loop
# ---------------------------------------------------------------------------


def _lowered_fused(fused_math, *planes):
    import jax
    import jax.numpy as jnp

    crc_fn, _tables = checksum.raw_crc_graph_fn(POLY_CRC32C, WIDTH)
    tables = (
        jax.ShapeDtypeStruct((WIDTH * 8, 32), jnp.int8),
        jax.ShapeDtypeStruct((WIDTH.bit_length(), 32, 32), jnp.int8),
    )
    return jax.jit(
        lambda *args: fused_math(
            *args[:-2], lambda d, n: crc_fn(d, n, *args[-2:])
        )
    ).lower(*planes, *tables).as_text(dialect="hlo")


def test_fused_programs_gather_no_byte_plane_outside_the_jump_loop():
    """Lowered only: nothing compiles. ``_gathers`` does not enter the
    pointer-jump ``while`` body, whose per-round gather stays."""
    import functools

    import jax
    import jax.numpy as jnp

    rows = 64
    enc = _gathers(_lowered_fused(
        lambda blocks, crc: tlz._encode_fused_math(blocks, N_GROUPS, crc),
        jax.ShapeDtypeStruct((rows, WIDTH), jnp.uint8),
    ))
    assert [g for g in enc if np.prod(g[0]) == rows * WIDTH] == []
    assert [g for g in enc if g[0] == (rows, WIDTH - tlz.GROUP + 1)]
    window = [g for g in enc if len(g[1]) == 2 and g[1][1] > WIDTH]
    assert len(window) == 5 * 2

    rows = 32
    plane = functools.partial(jax.ShapeDtypeStruct, (rows, N_GROUPS))
    dec = _gathers(_lowered_fused(
        lambda *a: tlz._decode_fused_math(*a[:7], N_GROUPS, a[7]),
        plane(jnp.bool_), plane(jnp.bool_), plane(jnp.bool_),
        plane(jnp.int32), plane(jnp.int32),
        jax.ShapeDtypeStruct((rows, N_GROUPS, tlz.GROUP), jnp.uint8),
        jax.ShapeDtypeStruct((rows,), jnp.int32),
    ))
    assert [g for g in dec if g[0] == (rows, WIDTH)] == []
    assert [g for g in dec if g[0] == (rows, N_GROUPS, tlz.GROUP)]
