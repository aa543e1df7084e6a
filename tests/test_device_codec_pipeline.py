"""Device-resident codec pipeline: batched fixed-shape launches, fused
CRC32C, async encode overlap, and the single-block
tail fix (PR 8)."""

import io
import os
import random
import threading

import numpy as np
import pytest

from s3shuffle_tpu.codec.framing import (
    CODEC_IDS,
    CodecInputStream,
    CodecOutputStream,
    FrameCodec,
)
from s3shuffle_tpu.codec.tpu import FusedChecksumAccumulator, TpuCodec
from s3shuffle_tpu.ops import tlz
from s3shuffle_tpu.ops.checksum import POLY_CRC32C
from s3shuffle_tpu.utils.checksums import crc32c_py

BS = 1024  # small block (multiple of 128) keeps XLA:CPU kernels fast


def _mixed_payload(rng: random.Random, n_bytes: int) -> bytes:
    """Semi-compressible + incompressible stretches, like real shuffle data."""
    out = bytearray()
    pool = [rng.randbytes(48) for _ in range(8)]
    while len(out) < n_bytes:
        if rng.random() < 0.5:
            out += pool[rng.randrange(8)]
        else:
            out += rng.randbytes(64)
    return bytes(out[:n_bytes])


def _stream_compress(codec, data: bytes, chunk: int = 700) -> bytes:
    sink = io.BytesIO()
    out = CodecOutputStream(codec, sink, close_sink=False)
    for ofs in range(0, len(data), chunk):
        out.write(data[ofs : ofs + chunk])
    out.close()
    return sink.getvalue()


# ---------------------------------------------------------------------------
# Satellite: single-block tail routes through compress_blocks, not the
# per-block host path
# ---------------------------------------------------------------------------


class _RecordingBatchCodec(FrameCodec):
    """Batch codec WITHOUT compress_framed: exercises the _pending path."""

    name = "recording"
    codec_id = CODEC_IDS["zlib"]

    def __init__(self, block_size, batch_blocks):
        super().__init__(block_size)
        self.batch_blocks = batch_blocks
        self.batch_calls = []  # block counts per compress_blocks call
        self.single_calls = 0

    def compress_block(self, data: bytes) -> bytes:
        import zlib

        self.single_calls += 1
        return zlib.compress(data, 1)

    def compress_blocks(self, blocks):
        import zlib

        self.batch_calls.append(len(blocks))
        return [zlib.compress(b, 1) for b in blocks]

    def decompress_block(self, data: bytes, ulen: int) -> bytes:
        import zlib

        return zlib.decompress(data)


def test_single_block_tail_goes_through_batch_hook():
    """A tail batch of exactly ONE full block used to take frame_block (the
    per-block host path), silently skipping the device for the last partial
    batch of every partition — it must route through compress_blocks."""
    codec = _RecordingBatchCodec(BS, batch_blocks=4)
    data = _mixed_payload(random.Random(0), BS * 5)  # 4-batch + 1-block tail
    framed = _stream_compress(codec, data)
    assert codec.batch_calls == [4, 1], codec.batch_calls
    assert codec.single_calls == 0  # never the per-block path
    assert CodecInputStream(codec, io.BytesIO(framed)).read() == data
    # frames are byte-identical to the per-block reference framing
    ref = b"".join(
        codec.frame_block(data[i * BS : (i + 1) * BS]) for i in range(5)
    )
    assert framed == ref


def test_tpu_frame_blocks_single_full_block_uses_device_batch(monkeypatch):
    """Same fix on the TPU codec: frame_blocks routes even a SINGLE full
    block through the device batch encoder (the old frame_block tail path
    silently took the per-block host encoder instead)."""
    calls = []
    real = tlz.encode_blocks_device

    def spy(blocks, block_size):
        calls.append(len(blocks))
        return real(blocks, block_size)

    monkeypatch.setattr(tlz, "encode_blocks_device", spy)
    codec = TpuCodec(block_size=BS, batch_blocks=2, use_device=True)
    block = _mixed_payload(random.Random(1), BS)
    framed = codec.frame_blocks([block])
    assert calls == [1], calls  # the single full block hit the device batch
    assert codec.decompress_bytes(framed) == block
    # a full-block tail on the FRAMED path stays on the device too (via
    # compress_framed); only the final SHORT block takes the host encoder
    data = _mixed_payload(random.Random(1), BS * 3)
    framed = _stream_compress(codec, data, chunk=BS)
    assert codec.decompress_bytes(framed) == data


# ---------------------------------------------------------------------------
# Backend discovery: one synchronous decision per process, errors raise
# ---------------------------------------------------------------------------


def test_backend_decided_synchronously_before_the_first_batch(monkeypatch):
    """No discovery thread and no pending window: the first batch already
    sees the process's one backend verdict (a CPU here, so the documented
    host fallback reroutes every frame of it to SLZ)."""
    import jax

    from s3shuffle_tpu.codec import tpu as tpu_mod
    from s3shuffle_tpu.codec.native import native_available

    if not native_available():
        pytest.skip("native SLZ library not built")
    calls = []
    real = jax.default_backend
    monkeypatch.setattr(jax, "default_backend",
                        lambda: calls.append(1) or real())
    monkeypatch.setattr(tpu_mod, "_BACKEND_VERDICT", None)
    monkeypatch.delenv("S3SHUFFLE_TPU_CODEC_DEVICE", raising=False)
    threads_before = threading.active_count()
    codec = TpuCodec(block_size=BS, batch_blocks=4, host_encode_fallback=True)
    data = _mixed_payload(random.Random(2), BS * 4)
    blocks = [data[i * BS : (i + 1) * BS] for i in range(4)]
    for _ in range(3):
        framed = codec.frame_blocks(blocks)
        assert CodecInputStream(codec, io.BytesIO(framed)).read() == data
        ofs = 0
        while ofs < len(framed):  # every frame SLZ (or a raw escape)
            assert framed[ofs] in (CODEC_IDS["raw"], CODEC_IDS["native-lz"])
            ofs += 9 + int(np.frombuffer(framed[ofs + 5 : ofs + 9], "<u4")[0])
    assert len(calls) == 1
    assert threading.active_count() == threads_before


def test_failed_backend_query_raises(monkeypatch):
    import jax

    from s3shuffle_tpu.codec import tpu as tpu_mod

    def broken():
        raise RuntimeError("backend init failed")

    monkeypatch.setattr(jax, "default_backend", broken)
    monkeypatch.setattr(tpu_mod, "_BACKEND_VERDICT", None)
    monkeypatch.delenv("S3SHUFFLE_TPU_CODEC_DEVICE", raising=False)
    codec = TpuCodec(block_size=BS, batch_blocks=4)
    with pytest.raises(RuntimeError, match="backend init failed"):
        codec.compress_blocks([bytes(BS)])


# ---------------------------------------------------------------------------
# Satellite: seeded device/host byte-identity property
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(5))
def test_device_host_frame_identity_property(seed):
    """Random block sizes × batch sizes × in-flight windows × tail lengths:
    the reworked device encoder's frames must be BYTE-IDENTICAL to the host
    C encoder's (same planes, same assembly, same framing) and decode back
    to the data."""
    rng = random.Random(100 + seed)
    bs = rng.choice([256, 512, 1024, 2048])
    batch = rng.choice([1, 2, 3, 5])
    inflight = rng.choice([0, 2, 3])
    n_full = rng.randrange(0, 7)
    tail = rng.randrange(0, bs) if rng.random() < 0.8 else 0
    data = _mixed_payload(rng, n_full * bs + tail)
    dev = TpuCodec(
        block_size=bs, batch_blocks=batch, use_device=True,
        encode_inflight_batches=inflight,
    )
    host = TpuCodec(block_size=bs, use_device=False)
    framed_dev = _stream_compress(dev, data, chunk=rng.randrange(1, 2 * bs))
    framed_host = host.compress_bytes(data)
    assert framed_dev == framed_host, (bs, batch, inflight, n_full, tail)
    assert dev.decompress_bytes(framed_dev) == data


def test_vectorized_assembly_matches_per_block_oracle():
    rng = random.Random(9)
    blocks = [_mixed_payload(rng, BS) for _ in range(5)]
    blob = b"".join(blocks)
    payloads, _ = tlz.encode_batch_device(blob, 5, BS, batch_blocks=2)
    # the per-row oracle over the same kernel outputs
    n_groups = BS // tlz.GROUP
    jax = pytest.importorskip("jax")  # noqa: F841
    staged = np.frombuffer(blob, dtype=np.uint8).reshape(5, BS)
    arrs = tuple(np.asarray(x) for x in tlz._encode_kernel(n_groups)(staged))
    ref = [tlz._assemble_from_device(*arrs, i, n_groups) for i in range(5)]
    assert payloads == ref


# ---------------------------------------------------------------------------
# Tentpole: async overlap — ordering, accounting, failure semantics
# ---------------------------------------------------------------------------


class _GatedAsyncCodec:
    """Duck-typed async batch codec whose encode blocks on an event —
    deterministic control over the in-flight window."""

    block_size = BS
    batch_blocks = 2
    encode_inflight_batches = 3

    def __init__(self):
        self.gate = threading.Event()
        self.calls = []

    def wants_async_encode(self):
        return True

    def compress_framed(self, buf, n_blocks, block_size):
        self.gate.wait(timeout=30)
        self.calls.append(n_blocks)
        out = bytearray()
        for i in range(n_blocks):
            raw = bytes(buf[i * block_size : (i + 1) * block_size])
            from s3shuffle_tpu.codec.framing import HEADER

            out += HEADER.pack(0, len(raw), len(raw)) + raw
        return bytes(out)

    def frame_block(self, raw: bytes) -> bytes:
        from s3shuffle_tpu.codec.framing import HEADER

        return HEADER.pack(0, len(raw), len(raw)) + raw


def test_async_pending_bytes_counts_inflight_and_order_is_preserved():
    codec = _GatedAsyncCodec()
    sink = io.BytesIO()
    out = CodecOutputStream(codec, sink, close_sink=False)
    data = _mixed_payload(random.Random(4), BS * 4 + 100)
    out.write(data[: BS * 2])  # batch 1 submitted (gated: stays in flight)
    out.write(data[BS * 2 : BS * 4])  # batch 2 submitted
    # both batches are in flight; the budget hook must see their raw bytes
    assert out.pending_bytes >= BS * 4
    assert sink.getvalue() == b""  # nothing emitted while gated
    codec.gate.set()
    out.write(data[BS * 4 :])
    out.close()
    got = CodecInputStream(None, io.BytesIO(sink.getvalue())).read()
    assert got == data  # order-preserving emission, tail included


def test_async_encode_failure_reraises_on_producer_close():
    class FailingCodec(_GatedAsyncCodec):
        def compress_framed(self, buf, n_blocks, block_size):
            raise RuntimeError("chip fell off")

    codec = FailingCodec()
    codec.gate.set()
    out = CodecOutputStream(codec, io.BytesIO(), close_sink=False)
    out.write(b"x" * BS * 2)  # submits the failing batch
    with pytest.raises(RuntimeError, match="chip fell off"):
        out.close()
    assert out.pending_bytes == 0  # window cleaned up after the failure


def test_async_encode_failure_reraises_on_producer_write():
    class FailingCodec(_GatedAsyncCodec):
        encode_inflight_batches = 2

        def compress_framed(self, buf, n_blocks, block_size):
            raise RuntimeError("chip fell off")

    codec = FailingCodec()
    codec.gate.set()
    out = CodecOutputStream(codec, io.BytesIO(), close_sink=False)
    with pytest.raises(RuntimeError, match="chip fell off"):
        for _ in range(4):  # window fills → harvest on a write() call
            out.write(b"x" * BS * 2)
    out.close()


def test_mid_batch_device_failure_falls_back_without_losing_blocks(
    monkeypatch, caplog
):
    """A device failure mid-shuffle host-encodes THAT batch: no queued block
    is lost, the stream decodes, and the event is logged loudly."""
    import logging

    boom = {"armed": True}
    real = tlz.encode_batch_device

    def flaky(buf, n_blocks, block_size, **kw):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected device loss")
        return real(buf, n_blocks, block_size, **kw)

    monkeypatch.setattr(tlz, "encode_batch_device", flaky)
    codec = TpuCodec(
        block_size=BS, batch_blocks=2, use_device=True,
        encode_inflight_batches=2,
    )
    data = _mixed_payload(random.Random(5), BS * 6 + 31)
    with caplog.at_level(logging.WARNING, logger="s3shuffle_tpu.codec.tpu"):
        framed = _stream_compress(codec, data, chunk=BS)
    assert any("host-encoding this batch" in r.message for r in caplog.records)
    assert codec.decompress_bytes(framed) == data
    # and the output still matches the pure host reference byte-for-byte
    assert framed == TpuCodec(block_size=BS, use_device=False).compress_bytes(data)


def test_repeated_device_failures_pin_codec_to_host(monkeypatch, caplog):
    import logging

    def always_fails(*a, **kw):
        raise RuntimeError("device lost")

    monkeypatch.setattr(tlz, "encode_batch_device", always_fails)
    codec = TpuCodec(block_size=BS, batch_blocks=2, use_device=True)
    data = _mixed_payload(random.Random(6), BS * 2)
    with caplog.at_level(logging.WARNING, logger="s3shuffle_tpu.codec.tpu"):
        for _ in range(3):
            codec.compress_framed(data, 2, BS)
    assert codec._use_device is False  # pinned off after 3 consecutive fails
    assert any("pinning this codec" in r.message for r in caplog.records)
    # pinned path no longer touches the (failing) device entry at all
    framed = codec.compress_framed(data, 2, BS)
    assert codec.decompress_bytes(framed) == data


# ---------------------------------------------------------------------------
# Tentpole: fused CRC32C — frame CRCs from the encode launch, byte-identical
# sidecar values
# ---------------------------------------------------------------------------


def test_compress_framed_fused_crcs_match_stored_bytes():
    codec = TpuCodec(block_size=BS, batch_blocks=2, use_device=True)
    rng = random.Random(7)
    # compressible + incompressible (raw escape) blocks: both CRC branches
    data = _mixed_payload(rng, BS * 2) + os.urandom(BS * 2)
    framed, crcs = codec.compress_framed_fused(data, 4, BS)
    assert crcs is not None and len(crcs) == 4
    assert framed == codec.compress_framed(data, 4, BS)  # byte-identical
    off = 0
    for crc, length in crcs:
        frame = framed[off : off + length]
        assert crc == crc32c_py(frame)  # full-algorithm CRC of stored bytes
        off += length
    assert off == len(framed)


def test_fused_compress_and_checksum_device_route_single_launch(monkeypatch):
    """The helper's device route returns frames split from ONE fused launch
    — byte-identical to the host (staged-CRC) route, with true CRCs."""
    from s3shuffle_tpu.codec.tpu import fused_compress_and_checksum

    rng = random.Random(12)
    blocks = [_mixed_payload(rng, BS) for _ in range(3)] + [os.urandom(BS)]
    monkeypatch.setenv("S3SHUFFLE_TPU_CODEC_DEVICE", "1")
    dev_codec = TpuCodec(block_size=BS, batch_blocks=2)
    frames, crcs = fused_compress_and_checksum(dev_codec, blocks)
    assert [crc32c_py(f) for f in frames] == crcs
    monkeypatch.setenv("S3SHUFFLE_TPU_CODEC_DEVICE", "0")
    host_codec = TpuCodec(block_size=BS, batch_blocks=2)
    frames_host, crcs_host = fused_compress_and_checksum(host_codec, blocks)
    assert frames == frames_host
    assert crcs == crcs_host


def test_fused_accumulator_add_stored_equals_byte_serial():
    rng = random.Random(8)
    acc = FusedChecksumAccumulator(POLY_CRC32C)
    stream = bytearray()
    for i in range(6):
        chunk = rng.randbytes(rng.randrange(1, 400))
        stream += chunk
        if i % 2:  # mix fused values with host byte-hashes
            acc.add_stored(crc32c_py(chunk), len(chunk))
        else:
            acc.add_bytes(chunk)
    assert acc.value == crc32c_py(bytes(stream))


def test_fused_checksum_stream_hook_matches_streaming_checksum(monkeypatch):
    """CodecOutputStream's checksum hook (fused CRCs when available, byte
    hashes otherwise) must equal a byte-serial CRC of everything emitted —
    across device batches, tails, and host-path batches."""
    monkeypatch.setenv("S3SHUFFLE_TPU_CODEC_DEVICE", "1")
    codec = TpuCodec(
        block_size=BS, batch_blocks=2, encode_inflight_batches=2
    )
    acc = FusedChecksumAccumulator(POLY_CRC32C)
    sink = io.BytesIO()
    out = CodecOutputStream(codec, sink, close_sink=False, checksum=acc)
    data = _mixed_payload(random.Random(10), BS * 5 + 333)
    for ofs in range(0, len(data), 777):
        out.write(data[ofs : ofs + 777])
    out.close()
    assert acc.value == crc32c_py(sink.getvalue())


def test_shuffle_checksum_sidecars_identical_fused_vs_streaming(
    tmp_path, monkeypatch
):
    """End-to-end: a codec=tpu CRC32C shuffle commits the SAME .checksum
    sidecar values whether the partition checksums came stitched from fused
    device CRCs or from the streaming byte-serial pass — and the read side
    (which validates against the sidecar) accepts both."""
    import collections

    from s3shuffle_tpu.config import ShuffleConfig
    from s3shuffle_tpu.shuffle import ShuffleContext
    from s3shuffle_tpu.storage.dispatcher import Dispatcher

    monkeypatch.setenv("S3SHUFFLE_TPU_CODEC_DEVICE", "1")
    rng = random.Random(11)
    parts = [[(rng.randrange(50), 1) for _ in range(1500)] for _ in range(2)]
    expected = collections.Counter()
    for p in parts:
        for k, v in p:
            expected[k] += v

    def run(label: str, fused_enabled: bool):
        Dispatcher.reset()
        if not fused_enabled:
            monkeypatch.setattr(TpuCodec, "supports_fused_checksum", False)
        cfg = ShuffleConfig(
            root_dir=f"file://{tmp_path}/{label}",
            app_id=f"fused-{label}",
            codec="tpu",
            codec_block_size=BS,
            tpu_host_fallback=False,
            checksum_algorithm="CRC32C",
            encode_inflight_batches=2,
            cleanup=False,  # the sidecars must survive context exit
        )
        with ShuffleContext(config=cfg, num_workers=2) as ctx:
            result = dict(
                ctx.fold_by_key(parts, 0, lambda a, b: a + b, num_partitions=3)
            )
        assert result == dict(expected)
        # collect the checksum sidecar objects (values must match exactly)
        root = tmp_path / label
        sidecars = {}
        for p in sorted(root.rglob("*.checksum.*")):
            sidecars[p.name] = p.read_bytes()
        assert sidecars, "no checksum sidecars written"
        return sidecars

    fused = run("fused", True)
    streaming = run("streaming", False)
    assert fused == streaming  # sidecar BYTES identical
    Dispatcher.reset()


def test_precomputed_checksum_skips_hashing_and_lands_in_commit(tmp_path):
    from s3shuffle_tpu.config import ShuffleConfig
    from s3shuffle_tpu.metadata.helper import ShuffleHelper
    from s3shuffle_tpu.storage.dispatcher import Dispatcher
    from s3shuffle_tpu.write.map_output_writer import MapOutputWriter

    Dispatcher.reset()
    cfg = ShuffleConfig(
        root_dir=f"file://{tmp_path}/pre", app_id="pre",
        checksum_algorithm="CRC32C",
    )
    d = Dispatcher(cfg)
    w = MapOutputWriter(d, ShuffleHelper(d), 1, 0, 2)
    pw = w.get_partition_writer(0, precomputed_checksum=0xDEADBEEF)
    assert pw._checksum is None  # no byte-serial hashing happens at all
    pw.write(b"payload-bytes")
    pw.close()
    pw2 = w.get_partition_writer(1)  # streaming path still available
    pw2.write(b"more")
    pw2.close()
    msg = w.commit_all_partitions()
    assert int(msg.checksums[0]) == 0xDEADBEEF
    assert int(msg.checksums[1]) == crc32c_py(b"more")
    Dispatcher.reset()
