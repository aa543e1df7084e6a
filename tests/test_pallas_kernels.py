"""Seeded property suites for the device codec kernels.

On the CPU test mesh the device kernels run as the same XLA programs the
chip runs, so CI proves byte-identity without a chip: the device TLZ
encode against the numpy encoder (which makes the device graph's match
decisions at every width; the host C encoder shares the wire format and
agrees only at small blocks, ROADMAP D4), the fused decode+CRC against the
host decode and native crc32c, the CRC32C bit-matmul against the host raw
remainder, and the GF(2^8) parity table kernel against the numpy table
encoder, plus mid-kernel failure falling back host-side without losing a
frame (and counted). tests/test_chip_compile.py compiles the same kernels
for a described v5e.
"""

import numpy as np
import pytest

import s3shuffle_tpu.codec.tpu as tpu_mod
from s3shuffle_tpu.codec.tpu import TpuCodec
from s3shuffle_tpu.metrics import registry as mreg
from s3shuffle_tpu.ops import tlz
from s3shuffle_tpu.ops.checksum import (
    POLY_CRC32C,
    _crc_raw_bytes,
    crc32_batch,
    raw_crc_graph_fn,
)
from s3shuffle_tpu.utils.checksums import crc32c_py


def _host_payload(data: bytes) -> bytes:
    """The device encode's host oracle: the numpy encoder."""
    return tlz._assemble_payload_numpy(data)


def _make_block(kind: str, size: int, rng) -> bytes:
    if kind == "text":
        return (b"the quick brown fox jumps over the lazy dog " * size)[:size]
    if kind == "zeros":
        return bytes(size)
    if kind == "random":
        return bytes(rng.integers(0, 256, size, dtype=np.uint8))
    # mixed: compressible run, then noise, then a repeat of the run
    run = (b"columnar shuffle row payload " * size)[: size // 3]
    noise = bytes(rng.integers(0, 256, size - 2 * len(run), dtype=np.uint8))
    return (run + noise + run)[:size]


# ---------------------------------------------------------------------------
# TLZ encode: device batch kernel byte-identical to the numpy encoder
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block_size", [512, 2048])
@pytest.mark.parametrize("n_blocks", [1, 3, 4])  # 3 = padded tail bucket
def test_device_encode_byte_identical_to_numpy_encoder(block_size, n_blocks):
    rng = np.random.default_rng(block_size * 31 + n_blocks)
    kinds = ["text", "random", "zeros", "mixed"]
    blocks = [
        _make_block(kinds[i % len(kinds)], block_size, rng)
        for i in range(n_blocks)
    ]
    blob = b"".join(blocks)
    payloads, _ = tlz.encode_batch_device(
        blob, n_blocks, block_size, batch_blocks=4
    )
    for data, payload in zip(blocks, payloads):
        assert bytes(payload) == _host_payload(data)
        assert bytes(tlz.decode_payload_numpy(bytes(payload),
                                              block_size)) == data
        # at these small widths the C encoder happens to choose the same
        # matches; at the codec's 256 KiB width it does not (below)
        native = tlz._encode_block_native(data)
        assert native is None or native == bytes(payload)


def test_device_encode_at_codec_width_matches_numpy_encoder():
    """At the codec's real width (256 KiB blocks of serialized terasort
    records) the device graph is byte-identical to the numpy encoder. The
    host C encoder picks other matches there (50 of 64 blocks in the chip
    smoke's Phase B data), so it is held only to decoding to the input
    (ROADMAP D4 tracks aligning it)."""
    import importlib.util
    import io
    import os

    from s3shuffle_tpu.batch import write_frame

    spec = importlib.util.spec_from_file_location(
        "terasort_example",
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "examples", "terasort.py"),
    )
    terasort = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(terasort)
    bs = 256 * 1024  # TpuCodec default block_size
    buf = io.BytesIO()
    for part in terasort.generate(3 * bs, 1, seed=3):
        write_frame(buf, part)
    raw = buf.getvalue()
    blocks = [raw[i * bs:(i + 1) * bs] for i in range(2)]
    payloads, _ = tlz.encode_batch_device(b"".join(blocks), 2, bs,
                                          batch_blocks=2)
    for data, payload in zip(blocks, payloads):
        assert bytes(payload) == tlz._assemble_payload_numpy(data)
        assert bytes(tlz.decode_payload_numpy(bytes(payload), bs)) == data
        native = tlz._encode_block_native(data)
        if native is not None:  # same wire format, its own matches
            assert bytes(tlz.decode_payload_numpy(native, bs)) == data


def test_device_fused_encode_crcs_match_host():
    """poly= routes through _encode_fused_math: payloads stay
    byte-identical AND the fused raw-block CRCs are true."""
    bs = 1024
    rng = np.random.default_rng(99)
    blocks = [_make_block(k, bs, rng) for k in ("text", "mixed")]
    blob = b"".join(blocks)
    payloads, crc_info = tlz.encode_batch_device(
        blob, 2, bs, batch_blocks=2, poly=POLY_CRC32C
    )
    assert crc_info is not None
    block_crcs, _lit_crcs, _lit_lens = crc_info
    for i, data in enumerate(blocks):
        assert bytes(payloads[i]) == _host_payload(data)
        assert int(block_crcs[i]) == crc32c_py(data)


# ---------------------------------------------------------------------------
# Fused decode: plane reconstruction + CRC in one launch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_blocks", [2, 4])
def test_device_fused_decode_roundtrip_and_crc(n_blocks):
    bs = 1024
    rng = np.random.default_rng(n_blocks * 7)
    kinds = ["text", "mixed", "zeros", "random"]
    blocks = [_make_block(kinds[i], bs, rng) for i in range(n_blocks)]
    payloads = [_host_payload(b) for b in blocks]
    dec, crcs = tlz.decode_batch_device(
        payloads, [bs] * n_blocks, bs, batch_rows=4, poly=POLY_CRC32C
    )
    for i in range(n_blocks):
        assert bytes(dec[i]) == blocks[i]
        assert crcs[i] is not None
        assert int(crcs[i]) == crc32c_py(payloads[i])


# ---------------------------------------------------------------------------
# CRC32C bit-matmul: every length/alignment, incl. right-aligned staging
# ---------------------------------------------------------------------------


def _full_rows(b, length):
    rng = np.random.default_rng(b * length)
    return rng.integers(0, 256, (b, length), dtype=np.uint8), [length] * b


def _right_aligned_rows(tail):
    """The literal-plane form: rows right-aligned with zero front padding,
    which a zero-init raw remainder passes through unchanged."""
    length = 512
    rng = np.random.default_rng(tail)
    rows = np.zeros((8, length), dtype=np.uint8)
    lens = [min(length, tail + i) for i in range(8)]
    for i, n in enumerate(lens):
        if n:
            rows[i, length - n:] = rng.integers(0, 256, n, dtype=np.uint8)
    return rows, lens


@pytest.mark.parametrize("staged", [
    *(pytest.param(lambda b=b, n=n: _full_rows(b, n), id=f"{b}-{n}")
      for b, n in [(8, 128), (8, 512), (16, 1280), (24, 256)]),
    *(pytest.param(lambda t=t: _right_aligned_rows(t), id=f"tail-{t}")
      for t in [0, 1, 37, 127, 128, 300]),
])
def test_pallas_crc_matches_host_remainder(staged):
    """The in-graph hook the fused kernels call returns each row's raw
    zero-init remainder; the standalone batch returns the true CRC32C."""
    import jax

    rows, lens = staged()
    length = rows.shape[1]
    suffixes = [bytes(row[length - n:]) for row, n in zip(rows, lens)]
    fn, tables = raw_crc_graph_fn(POLY_CRC32C, length)
    raw = jax.jit(fn)(rows, np.full(len(rows), length, np.int32), *tables(None))
    assert [int(c) for c in raw] == [
        _crc_raw_bytes(s, POLY_CRC32C, 0) & 0xFFFFFFFF for s in suffixes
    ]
    full = crc32_batch(rows, lens, POLY_CRC32C)
    assert [int(c) for c in full] == [crc32c_py(s) for s in suffixes]


# ---------------------------------------------------------------------------
# GF(2^8) parity table kernel vs the numpy table encoder, with recovery
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k", [(1, 2), (1, 16), (2, 4), (2, 8), (3, 5),
                                 (4, 16), (8, 64)])
def test_pallas_gf_matches_numpy(m, k):
    from s3shuffle_tpu.coding import gf

    rng = np.random.default_rng(m * 100 + k)
    chunks = rng.integers(0, 256, (3, k, 100), dtype=np.uint8)  # odd G and L
    coefs = gf.parity_coefficients(m, k)
    got = np.asarray(gf._device_kernel(m, k)(chunks, coefs))
    assert got.shape == (3, m, 100)
    assert np.array_equal(got, gf._encode_host(chunks, coefs))


def test_pallas_gf_parity_recovers_erased_chunks():
    from s3shuffle_tpu.coding import gf

    k, m, L = 4, 2, 256
    rng = np.random.default_rng(42)
    chunks = rng.integers(0, 256, (1, k, L), dtype=np.uint8)
    coefs = gf.parity_coefficients(m, k)
    parity = np.asarray(gf._device_kernel(m, k)(chunks, coefs))
    recovered = gf.recover_group(
        k, coefs,
        {0: chunks[0, 0], 2: chunks[0, 2]},
        {0: parity[0, 0], 1: parity[0, 1]},
        [1, 3],
    )
    assert recovered is not None
    assert np.array_equal(recovered[1], chunks[0, 1])
    assert np.array_equal(recovered[3], chunks[0, 3])


# ---------------------------------------------------------------------------
# Mid-kernel failure: host-side fallback without frame loss
# ---------------------------------------------------------------------------


def _device_counters():
    snap = mreg.REGISTRY.snapshot()
    batches = {
        (s["labels"]["op"], s["labels"]["outcome"]): s["value"]
        for s in snap["codec_device_batches_total"]["series"]
    }
    pins = {
        s["labels"]["op"]: s["value"]
        for s in snap["codec_host_pins_total"]["series"]
    }
    return batches, pins


@pytest.fixture
def counting():
    mreg.REGISTRY.reset_values()
    mreg.enable()
    yield
    mreg.disable()
    mreg.REGISTRY.reset_values()


def test_encode_kernel_failure_falls_back_without_frame_loss(
    monkeypatch, counting
):
    bs = 1024
    rng = np.random.default_rng(1)
    blocks = [_make_block(k, bs, rng) for k in ("text", "random")]
    codec = TpuCodec(block_size=bs, batch_blocks=4, use_device=True)

    def broken_kernel(*a, **kw):
        def boom(*aa, **kk):
            raise RuntimeError("mosaic lowering failed mid-kernel")

        return boom

    monkeypatch.setattr(tpu_mod.tlz, "_batch_kernel", broken_kernel)
    for _ in range(3):  # the third consecutive failure pins the host
        payloads, crcs = codec._encode_full_blocks(
            memoryview(b"".join(blocks)), 2, bs, None
        )
        assert crcs is None
        assert [bytes(p) for p in payloads] == [
            _host_payload(b) for b in blocks
        ]
    for data, payload in zip(blocks, payloads):
        assert bytes(tlz.decode_payload_numpy(bytes(payload), bs)) == data
    batches, pins = _device_counters()
    assert batches == {("encode", "failed"): 3.0}
    assert pins == {"encode": 1.0}


def test_decode_kernel_failure_falls_back_without_frame_loss(
    monkeypatch, counting
):
    bs = 1024
    rng = np.random.default_rng(2)
    blocks = [_make_block(k, bs, rng) for k in ("mixed", "zeros")]
    payloads = [_host_payload(b) for b in blocks]
    codec = TpuCodec(block_size=bs, batch_blocks=4, use_device=True)

    def broken_kernel(*a, **kw):
        def boom(*aa, **kk):
            raise RuntimeError("mosaic lowering failed mid-kernel")

        return boom

    monkeypatch.setattr(tpu_mod.tlz, "_decode_batch_kernel", broken_kernel)
    out, crcs = codec._decode_full_blocks(
        [(p, bs) for p in payloads], POLY_CRC32C
    )
    assert [bytes(o) for o in out] == blocks  # every frame recovered
    assert crcs == [None, None]  # caller certifies those from its own bytes
    batches, pins = _device_counters()
    assert batches == {("decode", "failed"): 1.0}
    assert pins == {}


def test_parity_kernel_failure_pins_host_and_counts(monkeypatch, counting):
    from s3shuffle_tpu.coding import gf

    def boom(*a, **kw):
        raise RuntimeError("mosaic lowering failed mid-kernel")

    monkeypatch.setenv("S3SHUFFLE_CODEC_RATE_GATE", "device")
    monkeypatch.setattr(gf, "_device_broken", False)
    monkeypatch.setattr(gf, "_DEVICE_MIN_BYTES", 0)
    monkeypatch.setattr(gf, "_mesh_dispatcher", lambda: None)
    monkeypatch.setattr(gf, "_device_kernel", lambda m, k: boom)
    rng = np.random.default_rng(3)
    chunks = rng.integers(0, 256, (2, 4, 128), dtype=np.uint8)
    coefs = gf.parity_coefficients(2, 4)
    for _ in range(2):  # the pin holds: the second batch never tries
        got = gf.encode_groups(chunks, coefs)
        assert np.array_equal(got, gf._encode_host(chunks, coefs))
    batches, pins = _device_counters()
    assert batches == {("parity", "failed"): 1.0}
    assert pins == {"parity": 1.0}
