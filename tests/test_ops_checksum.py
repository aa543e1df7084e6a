"""Device checksum kernels vs the zlib/native ground truth."""

import os
import zlib

import numpy as np
import pytest

from s3shuffle_tpu.ops.checksum import (
    POLY_CRC32,
    POLY_CRC32C,
    adler32_batch,
    crc32_batch,
    crc_combine,
    stage_right_aligned,
)
from s3shuffle_tpu.utils.checksums import crc32c_py

BLOCK = 1024  # small weights for test speed


def _random_chunks(n, max_len=BLOCK, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        length = int(rng.integers(0, max_len + 1))
        out.append(rng.integers(0, 256, size=length, dtype=np.uint8).tobytes())
    return out


def test_crc32_batch_matches_zlib():
    chunks = _random_chunks(17)
    batch, lengths = stage_right_aligned(chunks, BLOCK)
    got = crc32_batch(batch, lengths, poly=POLY_CRC32)
    expected = [zlib.crc32(c) & 0xFFFFFFFF for c in chunks]
    assert got.tolist() == expected


def test_crc32c_batch_matches_reference_impl():
    chunks = _random_chunks(9, seed=1)
    batch, lengths = stage_right_aligned(chunks, BLOCK)
    got = crc32_batch(batch, lengths, poly=POLY_CRC32C)
    expected = [crc32c_py(c) for c in chunks]
    assert got.tolist() == expected


def test_crc32_edge_cases():
    chunks = [b"", b"\x00", b"\x00" * BLOCK, b"\xff" * BLOCK, b"a"]
    batch, lengths = stage_right_aligned(chunks, BLOCK)
    got = crc32_batch(batch, lengths, poly=POLY_CRC32)
    assert got.tolist() == [zlib.crc32(c) & 0xFFFFFFFF for c in chunks]


def test_adler32_batch_matches_zlib():
    chunks = _random_chunks(17, seed=2) + [b"", b"\x00" * BLOCK, b"\xff" * BLOCK]
    batch, lengths = stage_right_aligned(chunks, BLOCK)
    got = adler32_batch(batch, lengths)
    assert got.tolist() == [zlib.adler32(c) & 0xFFFFFFFF for c in chunks]


def test_adler32_non_chunk_multiple_width():
    chunks = [os.urandom(700) for _ in range(3)]
    batch, lengths = stage_right_aligned(chunks, 700)  # 700 % 2048 != 0
    got = adler32_batch(batch, lengths)
    assert got.tolist() == [zlib.adler32(c) & 0xFFFFFFFF for c in chunks]


@pytest.mark.parametrize("poly", [POLY_CRC32, POLY_CRC32C])
def test_crc_combine(poly):
    a, b = os.urandom(1000), os.urandom(3777)
    if poly == POLY_CRC32:
        crc = lambda d: zlib.crc32(d) & 0xFFFFFFFF
    else:
        crc = crc32c_py
    assert crc_combine(crc(a), crc(b), len(b), poly) == crc(a + b)
    # empty-side identities
    assert crc_combine(crc(a), crc(b""), 0, poly) == crc(a)
    assert crc_combine(crc(b""), crc(b), len(b), poly) == crc(b)


def test_combine_stitches_device_block_crcs():
    # partition = 5 blocks; per-block device CRCs + combine == whole-partition CRC
    blocks = [os.urandom(BLOCK) for _ in range(4)] + [os.urandom(137)]
    batch, lengths = stage_right_aligned(blocks, BLOCK)
    per_block = crc32_batch(batch, lengths, poly=POLY_CRC32)
    total = per_block[0]
    for i in range(1, len(blocks)):
        total = crc_combine(int(total), int(per_block[i]), len(blocks[i]), POLY_CRC32)
    assert total == (zlib.crc32(b"".join(blocks)) & 0xFFFFFFFF)
