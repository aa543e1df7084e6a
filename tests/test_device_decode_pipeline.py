"""Read decode pipeline: fixed-shape batched device decode, fused stored-byte
CRC validation, and the async GET/decode/deserialize window (PR 14)."""

import io
import random
import threading

import numpy as np
import pytest
from conftest import RecordingBackend

from s3shuffle_tpu.block_ids import ShuffleBlockId
from s3shuffle_tpu.codec.framing import (
    CODEC_IDS,
    HEADER,
    CodecInputStream,
    FrameCodec,
)
from s3shuffle_tpu.codec.tpu import TpuCodec
from s3shuffle_tpu.config import ShuffleConfig
from s3shuffle_tpu.ops import tlz
from s3shuffle_tpu.ops.checksum import POLY_CRC32C
from s3shuffle_tpu.read.checksum_stream import (
    ChecksumError,
    ChecksumValidationStream,
)
from s3shuffle_tpu.storage.dispatcher import Dispatcher
from s3shuffle_tpu.utils.checksums import crc32c_py

BS = 1024  # small block (multiple of 128) keeps XLA:CPU kernels fast


def _mixed_payload(rng: random.Random, n_bytes: int) -> bytes:
    out = bytearray()
    pool = [rng.randbytes(48) for _ in range(8)]
    while len(out) < n_bytes:
        if rng.random() < 0.5:
            out += pool[rng.randrange(8)]
        else:
            out += rng.randbytes(64)
    return bytes(out[:n_bytes])


def _v1_payload(data: bytes):
    """Hand-built legacy v1 TLZ payload (16-byte groups, all literals) —
    the decode fallback tier must keep serving these forever."""
    ng = (len(data) + 15) // 16
    padded = data + b"\x00" * (ng * 16 - len(data))
    bitmap = np.packbits(np.zeros(ng, np.uint8), bitorder="little").tobytes()
    return np.array([ng], dtype="<u2").tobytes() + bitmap + padded


# ---------------------------------------------------------------------------
# Tentpole: batched device decode — byte identity property
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(5))
def test_decode_batch_device_matches_numpy_property(seed):
    """Random block sizes × batch rows × tail lengths × legacy/v2 mixes: the
    reworked batched decoder must be BYTE-IDENTICAL to the validating numpy
    decoder on every payload, and fused payload CRCs must equal the host CRC
    of the payload bytes for every device-shaped row."""
    rng = random.Random(100 + seed)
    bs = rng.choice([256, 512, 1024, 2048])
    batch_rows = rng.choice([1, 2, 3, 5, 8])
    payloads, ulens = [], []
    for _ in range(rng.randrange(2, 9)):
        kind = rng.random()
        if kind < 0.6:  # full v2 block (device-shaped)
            data = _mixed_payload(rng, bs)
            payloads.append(tlz._assemble_payload_numpy(data))
            ulens.append(bs)
        elif kind < 0.85:  # short tail block (host fallback)
            n = rng.randrange(1, bs)
            data = _mixed_payload(rng, n)
            payloads.append(tlz._assemble_payload_numpy(data))
            ulens.append(n)
        else:  # legacy v1 frame (host fallback)
            n = rng.randrange(1, bs)
            data = _mixed_payload(rng, n)
            payloads.append(_v1_payload(data))
            ulens.append(n)
    expect = [
        tlz.decode_payload_numpy(p, u, use_native=False)
        for p, u in zip(payloads, ulens)
    ]
    out, crcs = tlz.decode_batch_device(
        payloads, ulens, bs, batch_rows=batch_rows, poly=POLY_CRC32C
    )
    assert out == expect, (bs, batch_rows)
    for p, u, crc in zip(payloads, ulens, crcs):
        if u == bs and len(p) >= 2 and p[1] & 0x80:  # device-shaped v2 row
            assert crc is not None and crc == crc32c_py(bytes(p))
        else:
            assert crc is None


@pytest.mark.parametrize("seed", range(4))
def test_stream_decode_identity_device_vs_host_property(seed):
    """Random decode_batch_frames × windows × read sizes over a framed
    stream mixing v2 tpu-lz frames, hand-built LEGACY v1 frames, and raw
    escapes: the device stream must serve bytes identical to the host
    stream's."""
    import os

    rng = random.Random(300 + seed)
    frames = []
    expected = bytearray()
    host = TpuCodec(block_size=BS, use_device=False)
    for _ in range(rng.randrange(3, 12)):
        kind = rng.random()
        if kind < 0.5:
            data = _mixed_payload(rng, BS)
            frames.append(host.frame_block(data))
        elif kind < 0.7:
            data = os.urandom(BS)  # raw escape
            frames.append(host.frame_block(data))
        elif kind < 0.85:
            data = _mixed_payload(rng, rng.randrange(1, BS))  # short tail
            frames.append(host.frame_block(data))
        else:
            data = _mixed_payload(rng, rng.randrange(1, BS))  # legacy v1
            payload = _v1_payload(data)
            frames.append(
                HEADER.pack(CODEC_IDS["tpu-lz"], len(data), len(payload))
                + payload
            )
        expected += data
    framed = b"".join(frames)
    batch_frames = rng.choice([1, 2, 3, 8])
    window = rng.choice([0, 2, 3])
    dev = TpuCodec(
        block_size=BS, batch_blocks=4, use_device=True,
        decode_batch_frames=batch_frames, decode_inflight_batches=window,
    )
    got = bytearray()
    stream = CodecInputStream(dev, io.BytesIO(framed))
    while True:
        chunk = stream.read(rng.randrange(1, 3 * BS))
        if not chunk:
            break
        got += chunk
    stream.close()
    assert bytes(got) == bytes(expected), (batch_frames, window)
    assert CodecInputStream(host, io.BytesIO(framed)).read() == bytes(expected)


# ---------------------------------------------------------------------------
# Fault matrix: device failure fallback, pinning, corruption classification
# ---------------------------------------------------------------------------


def test_mid_batch_device_failure_host_decodes_batch(monkeypatch, caplog):
    """A device failure mid-scan host-decodes THAT batch: no frame is lost,
    the stream serves identical bytes, and the event is logged loudly."""
    import logging

    data = _mixed_payload(random.Random(5), BS * 5 + 77)
    host = TpuCodec(block_size=BS, use_device=False)
    framed = host.compress_bytes(data)
    boom = {"armed": True}
    real = tlz.decode_batch_device

    def flaky(payloads, ulens, block_size, **kw):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected device loss")
        return real(payloads, ulens, block_size, **kw)

    monkeypatch.setattr(tlz, "decode_batch_device", flaky)
    dev = TpuCodec(block_size=BS, batch_blocks=2, use_device=True,
                   decode_batch_frames=2)
    with caplog.at_level(logging.WARNING, logger="s3shuffle_tpu.codec.tpu"):
        got = CodecInputStream(dev, io.BytesIO(framed)).read()
    assert got == data
    assert any("host-decoding this batch" in r.message for r in caplog.records)
    assert dev._use_device is not False  # ONE failure does not pin


def test_repeated_decode_failures_pin_codec_to_host(monkeypatch, caplog):
    import logging

    def always_fails(*a, **kw):
        raise RuntimeError("device lost")

    monkeypatch.setattr(tlz, "decode_batch_device", always_fails)
    data = _mixed_payload(random.Random(6), BS * 2)
    host = TpuCodec(block_size=BS, use_device=False)
    framed = host.compress_bytes(data)
    dev = TpuCodec(block_size=BS, batch_blocks=2, use_device=True,
                   decode_batch_frames=4)
    with caplog.at_level(logging.WARNING, logger="s3shuffle_tpu.codec.tpu"):
        for _ in range(3):
            assert CodecInputStream(dev, io.BytesIO(framed)).read() == data
    assert dev._use_device is False  # pinned off after 3 consecutive fails
    assert any("pinning this codec" in r.message for r in caplog.records)
    # pinned path no longer touches the (failing) device entry at all
    assert CodecInputStream(dev, io.BytesIO(framed)).read() == data


def test_corrupt_payload_same_error_device_vs_host():
    """checksum_enabled=False territory: TLZ corruption must classify
    identically (IOError, same message) through the batched device decoder
    and the host decoder — host fallback never masks corruption as loss."""
    data = _mixed_payload(random.Random(7), BS * 3)
    host = TpuCodec(block_size=BS, use_device=False)
    framed = bytearray(host.compress_bytes(data))
    # flip a byte in the SECOND frame's header count field (offset: frame 1
    # length + 9-byte header + 1) — a parse-level corruption
    first_len = 9 + int(np.frombuffer(bytes(framed[5:9]), "<u4")[0])
    framed[first_len + 9] ^= 0xFF
    framed = bytes(framed)

    def classify(codec):
        try:
            CodecInputStream(codec, io.BytesIO(framed)).read()
            return None
        except Exception as e:
            return type(e).__name__, str(e)

    dev = TpuCodec(block_size=BS, batch_blocks=2, use_device=True,
                   decode_batch_frames=4)
    host_err = classify(host)
    dev_err = classify(dev)
    assert host_err is not None and host_err[0] in ("IOError", "OSError")
    assert dev_err == host_err


def _checksum_stream(framed, n_parts, algorithm="CRC32C", serve=None):
    """A ChecksumValidationStream over ``framed`` split into ``n_parts``
    frame-aligned partitions with correct per-partition checksums of the
    CLEAN bytes; ``serve`` (default ``framed``) is what the source actually
    delivers — pass a corrupted copy to model storage corruption."""
    bounds = [0]
    off = 0
    while off < len(framed):
        clen = int(np.frombuffer(framed[off + 5 : off + 9], "<u4")[0])
        off += 9 + clen
        bounds.append(off)
    # group frames into n_parts contiguous partitions
    cuts = [0]
    per = max(1, (len(bounds) - 1) // n_parts)
    for i in range(1, n_parts):
        cuts.append(bounds[min(i * per, len(bounds) - 1)])
    cuts.append(len(framed))
    offsets = np.array(cuts, dtype=np.int64)
    checksums = np.array(
        [crc32c_py(framed[cuts[i] : cuts[i + 1]]) for i in range(n_parts)],
        dtype=np.int64,
    )
    return ChecksumValidationStream(
        ShuffleBlockId(0, 0, 0), io.BytesIO(serve if serve is not None else framed),
        offsets, checksums, 0, n_parts, algorithm,
    )


@pytest.mark.parametrize("n_parts", [1, 3])
@pytest.mark.parametrize("corrupt_at", [0.15, 0.5, 0.9])
def test_corruption_checksum_error_identical_fused_vs_streaming(
    n_parts, corrupt_at
):
    """The fused-validation contract: corrupting a stored byte raises a
    ChecksumError BYTE-FOR-BYTE identical to streaming validation's —
    same type, same message, same computed value — because the retry,
    degraded-read, and MapOutputLost paths all classify on it."""
    data = _mixed_payload(random.Random(11), BS * 6)
    host = TpuCodec(block_size=BS, use_device=False)
    framed = host.compress_bytes(data)
    corrupt = bytearray(framed)
    corrupt[int(len(framed) * corrupt_at)] ^= 0xFF
    corrupt = bytes(corrupt)

    def classify(codec):
        stream = CodecInputStream(
            codec, _checksum_stream(framed, n_parts, serve=corrupt)
        )
        try:
            stream.read()
            return None
        except Exception as e:
            return type(e).__name__, str(e)
        finally:
            stream.close()

    streaming = classify(host)
    dev = TpuCodec(block_size=BS, batch_blocks=2, use_device=True,
                   decode_batch_frames=4)
    fused = classify(dev)
    assert streaming is not None and streaming[0] == "ChecksumError"
    assert fused == streaming


def test_fused_validation_certifies_everything_and_skips_host_hashing():
    """Clean read under fused validation: every served byte gets certified
    (pending drains to zero), the fused counter ticks, and the streaming
    Checksum object is never consulted."""
    from s3shuffle_tpu.metrics import registry as mreg

    data = _mixed_payload(random.Random(12), BS * 5)
    host = TpuCodec(block_size=BS, use_device=False)
    framed = host.compress_bytes(data)
    cvs = _checksum_stream(framed, 2)
    calls = []
    real_update = cvs._checksum.update
    cvs._checksum.update = lambda b: (calls.append(len(b)), real_update(b))
    dev = TpuCodec(block_size=BS, batch_blocks=2, use_device=True,
                   decode_batch_frames=4)
    mreg.REGISTRY.reset_values()
    mreg.enable()
    try:
        stream = CodecInputStream(dev, cvs)
        assert stream._certify is cvs  # handshake armed
        assert stream.read() == data
        assert cvs.pending_uncertified == 0
        assert calls == []  # streaming hash never ran
        fused = mreg.read_counter_total("codec_fused_crc_validated_total")
        assert fused > 0
    finally:
        mreg.disable()
        mreg.REGISTRY.reset_values()


def test_fused_validation_not_armed_for_adler32():
    data = _mixed_payload(random.Random(13), BS * 2)
    host = TpuCodec(block_size=BS, use_device=False)
    framed = host.compress_bytes(data)
    offsets = np.array([0, len(framed)], dtype=np.int64)
    import zlib

    checksums = np.array([zlib.adler32(framed)], dtype=np.int64)
    cvs = ChecksumValidationStream(
        ShuffleBlockId(0, 0, 0), io.BytesIO(framed), offsets, checksums,
        0, 1, "ADLER32",
    )
    dev = TpuCodec(block_size=BS, batch_blocks=2, use_device=True)
    stream = CodecInputStream(dev, cvs)
    assert stream._certify is None  # streaming validation stays active
    assert stream.read() == data


def test_boundary_straddling_certificate_falls_back_to_hashing():
    """One combined CRC cannot be split across a partition boundary: the
    deferred validator must hash the retained bytes instead — same values,
    both partitions validated."""
    rng = random.Random(14)
    p0, p1 = rng.randbytes(700), rng.randbytes(500)
    blob = p0 + p1
    offsets = np.array([0, len(p0), len(blob)], dtype=np.int64)
    checksums = np.array([crc32c_py(p0), crc32c_py(p1)], dtype=np.int64)
    cvs = ChecksumValidationStream(
        ShuffleBlockId(0, 0, 0), io.BytesIO(blob), offsets, checksums,
        0, 2, "CRC32C",
    )
    assert cvs.defer_validation()
    while cvs.read(256):
        pass
    cvs.certify(len(blob), stored_crc=crc32c_py(blob))  # straddles boundary
    assert cvs.pending_uncertified == 0  # both partitions validated clean


def test_resolve_pending_raises_streaming_identical_checksum_error():
    rng = random.Random(15)
    p0 = rng.randbytes(700)
    bad = bytearray(p0)
    bad[100] ^= 0xFF
    offsets = np.array([0, len(p0)], dtype=np.int64)
    checksums = np.array([crc32c_py(p0)], dtype=np.int64)
    cvs = ChecksumValidationStream(
        ShuffleBlockId(0, 0, 0), io.BytesIO(bytes(bad)), offsets, checksums,
        0, 1, "CRC32C",
    )
    assert cvs.defer_validation()
    while cvs.read(256):
        pass
    with pytest.raises(ChecksumError, match="Invalid checksum"):
        cvs.resolve_pending()


# ---------------------------------------------------------------------------
# Tentpole: async decode window — ordering, budget, failure semantics
# ---------------------------------------------------------------------------


class _GatedDecodeCodec(FrameCodec):
    """Duck-typed batch codec whose decode blocks on an event —
    deterministic control over the in-flight decode window."""

    name = "gated"
    codec_id = CODEC_IDS["zlib"]
    decode_batch_frames = 2
    decode_inflight_batches = 3

    def __init__(self, block_size=BS):
        super().__init__(block_size)
        self.gate = threading.Event()
        self.calls = []

    def compress_block(self, data):
        import zlib

        return zlib.compress(data, 1)

    def decompress_block(self, data, ulen):
        import zlib

        self.gate.wait(timeout=30)
        return zlib.decompress(data)

    def decompress_blocks(self, blocks):
        self.calls.append(len(blocks))
        return [self.decompress_block(b, n) for b, n in blocks]


def test_async_decode_order_preserved_and_budget_accounted():
    codec = _GatedDecodeCodec()
    data = _mixed_payload(random.Random(20), BS * 8 + 99)
    framed = codec.compress_bytes(data)

    class Budget:
        def __init__(self):
            self.live = 0
            self.peak = 0

        def try_reserve(self, n):
            self.live += n
            self.peak = max(self.peak, self.live)
            return True

        def release_reserved(self, n):
            self.live -= n

    budget = Budget()
    codec.gate.set()
    stream = CodecInputStream(codec, io.BytesIO(framed), budget=budget)
    assert stream.read() == data  # order-preserving harvest
    stream.close()
    assert budget.live == 0  # every reservation released
    assert budget.peak > 0  # the window actually reserved


def test_async_decode_budget_denial_shrinks_window():
    """A full budget must shrink the window (stop reading ahead), never
    deadlock — and the stream still serves every byte."""
    codec = _GatedDecodeCodec()
    codec.gate.set()
    data = _mixed_payload(random.Random(21), BS * 8)
    framed = codec.compress_bytes(data)

    class DenyBudget:
        def __init__(self):
            self.denied = 0

        def try_reserve(self, n):
            self.denied += 1
            return False

        def release_reserved(self, n):
            raise AssertionError("nothing was reserved")

    budget = DenyBudget()
    stream = CodecInputStream(codec, io.BytesIO(framed), budget=budget)
    assert stream.read() == data
    stream.close()
    assert budget.denied > 0  # the window asked and was refused
    # with reservation denied beyond the first batch, decode calls happen
    # one-at-a-time (first-in-flight progress guarantee)
    assert max(codec.calls) <= codec.decode_batch_frames


def test_submit_failure_releases_fresh_reservation():
    """A source error raised while reading the NEXT run (after its budget
    reservation succeeded, before the job entered the window) must release
    that reservation — it lives in neither _inflight nor _decoded, so no
    other cleanup path would ever find it."""
    codec = _GatedDecodeCodec()
    codec.gate.set()
    data = _mixed_payload(random.Random(24), BS * 8)
    framed = codec.compress_bytes(data)

    class FailingTail(io.RawIOBase):
        """Serves the first two frames, then raises (storage_retries=0)."""

        def __init__(self, data, good):
            self._data = data
            self._pos = 0
            self._good = good

        def readable(self):
            return True

        def read(self, n=-1):
            if self._pos >= self._good:
                raise OSError("injected source loss")
            n = min(n, self._good - self._pos)
            out = self._data[self._pos : self._pos + n]
            self._pos += len(out)
            return out

    class Budget:
        def __init__(self):
            self.live = 0

        def try_reserve(self, n):
            self.live += n
            return True

        def release_reserved(self, n):
            self.live -= n

    # cut mid-stream at a frame boundary so batch 1 succeeds and the read
    # of batch 2+ raises from the source
    cut = 0
    for _ in range(2):
        clen = int(np.frombuffer(framed[cut + 5 : cut + 9], "<u4")[0])
        cut += 9 + clen
    budget = Budget()
    stream = CodecInputStream(codec, FailingTail(framed, cut), budget=budget)
    with pytest.raises(OSError, match="injected source loss"):
        stream.read()
    stream.close()
    assert budget.live == 0  # the fresh reservation was released


def test_async_decode_failure_reraises_on_consumer_read():
    class FailingCodec(_GatedDecodeCodec):
        def decompress_blocks(self, blocks):
            raise RuntimeError("chip fell off mid-scan")

    codec = FailingCodec()
    codec.gate.set()
    data = _mixed_payload(random.Random(22), BS * 4)
    framed = _GatedDecodeCodec().compress_bytes(data)
    stream = CodecInputStream(codec, io.BytesIO(framed))
    with pytest.raises(RuntimeError, match="chip fell off"):
        stream.read()
    stream.close()


def test_window_shrink_mid_stream_drains_in_order():
    """The window is a LIVE property: dropping it to 0 mid-stream drains
    in-flight futures in order and continues synchronously."""
    codec = _GatedDecodeCodec()
    codec.gate.set()
    data = _mixed_payload(random.Random(23), BS * 10)
    framed = codec.compress_bytes(data)
    stream = CodecInputStream(codec, io.BytesIO(framed))
    got = stream.read(BS)  # async fill starts
    codec.decode_inflight_batches = 0  # ScanTuner retune mid-stream
    rest = stream.read()
    stream.close()
    assert got + rest == data


def test_decode_executor_is_shared_and_bounded():
    import os

    from s3shuffle_tpu.codec import framing

    ex1 = framing._get_decode_executor()
    ex2 = framing._get_decode_executor()
    assert ex1 is ex2
    # NOT single-threaded (N concurrent reduce tasks must decode in
    # parallel — per-stream order comes from each stream's FIFO harvest),
    # but bounded so the pool never explodes with task count
    assert 1 <= ex1._max_workers <= min(4, os.cpu_count() or 2)


# ---------------------------------------------------------------------------
# op-for-op gate: knobs off reproduce the pre-pipeline read path
# ---------------------------------------------------------------------------


def _pipeline_roundtrip(tmp_path, tag, **cfg_extra):
    from s3shuffle_tpu.dependency import ShuffleDependency, HashPartitioner
    from s3shuffle_tpu.manager import ShuffleManager
    from s3shuffle_tpu.storage.local import LocalBackend

    Dispatcher.reset()
    cfg = ShuffleConfig(
        root_dir=f"file://{tmp_path}/{tag}", app_id=tag, codec="tpu",
        codec_block_size=BS, tpu_host_fallback=False,
        checksum_algorithm="CRC32C", cleanup=False, **cfg_extra,
    )
    d = Dispatcher(cfg)
    rec = RecordingBackend(LocalBackend())
    d.backend = rec
    manager = ShuffleManager(dispatcher=d)
    rng = random.Random(31)
    dep = ShuffleDependency(shuffle_id=0, partitioner=HashPartitioner(3))
    handle = manager.register_shuffle(0, dep)
    for map_id in range(2):
        w = manager.get_writer(handle, map_id)
        w.write([(rng.randrange(1000), rng.randbytes(40)) for _ in range(800)])
        w.stop(success=True)
    out = []
    for pid in range(3):
        out.append(sorted(manager.get_reader(handle, pid, pid + 1).read()))
    ops = sorted((op, p.rsplit("/", 1)[-1]) for op, p in rec.ops)
    return out, ops


def test_decode_knobs_off_op_for_op_and_byte_identical(tmp_path):
    """``decode_inflight_batches=0`` + ``decode_batch_frames=1`` must
    reproduce the pre-pipeline read path: identical record output AND an
    identical store-op multiset on the shared RecordingBackend (the
    gap=0/parity=0/columnar=0 contract)."""
    out_on, ops_on = _pipeline_roundtrip(tmp_path, "on")  # defaults: 32/2
    out_off, ops_off = _pipeline_roundtrip(
        tmp_path, "off", decode_batch_frames=1, decode_inflight_batches=0
    )
    assert out_on == out_off
    assert ops_on == ops_off  # the pipeline adds ZERO store ops


# ---------------------------------------------------------------------------
# e2e: async-window failure re-raise under storage_retries=0 and >0, and
# device-decode identity across coalesced-segment slice boundaries
# ---------------------------------------------------------------------------


def _run_shuffle_read(tmp_path, tag, retries, fault=False, **cfg_extra):
    from s3shuffle_tpu.shuffle import ShuffleContext
    from s3shuffle_tpu.storage.fault import FaultRule, FlakyBackend

    Dispatcher.reset()
    cfg = ShuffleConfig(
        root_dir=f"file://{tmp_path}/{tag}", app_id=tag, codec="tpu",
        codec_block_size=BS, tpu_host_fallback=False,
        checksum_algorithm="CRC32C", storage_retries=retries,
        decode_inflight_batches=3, decode_batch_frames=4, **cfg_extra,
    )
    rng = random.Random(41)
    parts = [
        [(rng.randrange(100), rng.randbytes(32)) for _ in range(1200)]
        for _ in range(2)
    ]
    with ShuffleContext(config=cfg, num_workers=2) as ctx:
        if fault:
            disp = ctx.manager.dispatcher
            flaky = FlakyBackend(disp.backend)
            from s3shuffle_tpu.storage.fault import transient_connection_reset

            flaky.add_rule(FaultRule(
                "read", match=".data", times=2,
                exc=transient_connection_reset,
            ))
            disp.backend = flaky
        # equal-key value order depends on block arrival order, which the
        # async read window does not fix: compare each key's value multiset
        return sorted(
            (k, sorted(vs))
            for k, vs in ctx.group_by_key(parts, num_partitions=3)
        )


def test_async_window_transient_fault_heals_with_retries(tmp_path):
    clean = _run_shuffle_read(tmp_path, "clean", retries=3)
    healed = _run_shuffle_read(tmp_path, "healed", retries=3, fault=True)
    assert healed == clean  # byte-identical through the retry layer


def test_async_window_fault_reraises_without_retries(tmp_path):
    with pytest.raises(ChecksumError):
        _run_shuffle_read(tmp_path, "hard", retries=0, fault=True)


def test_device_decode_identity_across_coalesced_slices(tmp_path, monkeypatch):
    """Full read plane with the coalescing planner ON and device decode
    forced: batch-fetched frames sliced out of merged segments must decode
    byte-identical to the host path (device off)."""
    host = _run_shuffle_read(tmp_path, "host", retries=0)
    monkeypatch.setenv("S3SHUFFLE_TPU_CODEC_DEVICE", "1")
    dev = _run_shuffle_read(tmp_path, "dev", retries=0)
    assert dev == host


# ---------------------------------------------------------------------------
# ScanTuner: decode knobs join the ladder as live codec attributes
# ---------------------------------------------------------------------------


def test_scan_tuner_owns_decode_knobs_and_retunes_bound_codec():
    from s3shuffle_tpu.tuning import ScanTuner

    cfg = ShuffleConfig(autotune=True, decode_batch_frames=32,
                        decode_inflight_batches=2)
    tuner = ScanTuner(cfg)
    fields = {k.field for k in tuner._knobs}
    assert "decode_batch_frames" in fields
    assert "decode_inflight_batches" in fields
    codec = TpuCodec(block_size=BS, use_device=False,
                     decode_batch_frames=32, decode_inflight_batches=2)
    tuner.bind_codec(codec)
    tuner._apply_decode_batch_frames(64)
    tuner._apply_decode_window(4)
    assert codec.decode_batch_frames == 64
    assert codec.decode_inflight_batches == 4
    # tuned() carries the rungs into the scan config too
    tuned = tuner.tuned(cfg)
    assert tuned.decode_batch_frames == 32  # static rung is the start point


def test_scan_tuner_never_overrules_plane_off_statics():
    from s3shuffle_tpu.tuning import ScanTuner

    cfg = ShuffleConfig(autotune=True, decode_batch_frames=1,
                        decode_inflight_batches=0)
    tuner = ScanTuner(cfg)
    fields = {k.field for k in tuner._knobs}
    assert "decode_batch_frames" not in fields
    assert "decode_inflight_batches" not in fields


def test_manager_binds_codec_to_scan_tuner(tmp_path):
    from s3shuffle_tpu.manager import ShuffleManager

    Dispatcher.reset()
    cfg = ShuffleConfig(
        root_dir=f"file://{tmp_path}/bind", app_id="bind", codec="tpu",
        tpu_host_fallback=False, autotune=True,
    )
    d = Dispatcher(cfg)
    manager = ShuffleManager(dispatcher=d)
    assert manager.codec in d.scan_tuner._codecs
    assert manager.codec.decode_batch_frames == cfg.decode_batch_frames
    assert manager.codec.decode_inflight_batches == cfg.decode_inflight_batches


def test_prefetcher_budget_reserve_release_cap():
    from s3shuffle_tpu.read.prefetch import BufferedPrefetchIterator

    pf = BufferedPrefetchIterator(iter(()), max_buffer_size=1000)
    assert pf.budget is pf
    assert pf.try_reserve(600)
    assert not pf.try_reserve(600)  # over the cap: denied, not blocked
    pf.release_reserved(600)
    assert pf.try_reserve(1000)
    pf.release_reserved(1000)
