"""Concatenatable block framing shared by every codec.

Wire format per block::

    [u8 codec_id][u32le uncompressed_len][u32le compressed_len][payload]

Properties the read plane relies on:

- **Self-delimiting** — a partition's compressed stream is a sequence of
  frames; the decoder never needs out-of-band lengths beyond the partition's
  byte range (which the index provides).
- **Concatenatable** — concatenating two partitions' streams yields a valid
  stream, which is what legalizes batch fetch (the reference requires a
  "concatenation of serialized streams" codec property —
  S3ShuffleReader.scala:55-75).
- **Incompressible-block escape** — if compression doesn't shrink a block, it
  is stored raw (codec_id=0) so worst-case expansion is 9 bytes per block.
"""

from __future__ import annotations

import io
import struct
import threading
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import BinaryIO, List, Optional, Tuple

from s3shuffle_tpu.metrics import registry as _metrics
from s3shuffle_tpu.utils import trace

HEADER = struct.Struct("<BII")
HEADER_SIZE = HEADER.size  # 9 bytes

#: Upper bound on a frame's claimed uncompressed length. Real frames never
#: exceed the writer's block_size (64 KiB default, a few MiB at most); the cap
#: stops a corrupt/hostile header from driving a multi-GiB allocation BEFORE
#: the decoded-length validation can reject it.
MAX_FRAME_ULEN = 1 << 28  # 256 MiB

CODEC_IDS = {
    "raw": 0,
    "zlib": 1,
    "zstd": 2,
    "native-lz": 3,
    "tpu-lz": 4,
    "lz4": 5,
}
_NAMES = {v: k for k, v in CODEC_IDS.items()}


class FrameCodec:
    """One compression algorithm behind the shared framing.

    Subclasses implement block-granular ``compress_block``/``decompress_block``;
    streaming, framing, and the raw-block escape live here. Batch codecs (TPU)
    additionally override :meth:`compress_blocks` to process many blocks per
    device round-trip.
    """

    name = "abstract"
    codec_id = 0
    #: read-plane knobs, stamped per instance from config by ``get_codec``
    #: (the class defaults reproduce the historical behavior): frames read
    #: ahead and decoded per batch (None → CodecInputStream.BATCH_FRAMES),
    #: and the bounded async decode window (<= 1 = synchronous decode on the
    #: consumer thread). CodecInputStream reads both LIVE per batch, so the
    #: ScanTuner's online retunes apply mid-stream.
    decode_batch_frames: int | None = None
    decode_inflight_batches: int = 0

    def __init__(self, block_size: int = 64 * 1024):
        if block_size <= 0:
            raise ValueError("block_size must be positive")
        if block_size > MAX_FRAME_ULEN:
            # keep write and read agreeing: the decoder rejects frames
            # claiming more than MAX_FRAME_ULEN, so refuse to write them
            raise ValueError(
                f"block_size {block_size} exceeds MAX_FRAME_ULEN {MAX_FRAME_ULEN}"
            )
        self.block_size = block_size

    # --- block granular (override) ---
    def compress_block(self, data: bytes) -> bytes:
        raise NotImplementedError

    def decompress_block(self, data: bytes, uncompressed_len: int) -> bytes:
        raise NotImplementedError

    # --- batch granular (TPU codecs override for device efficiency) ---
    def compress_blocks(self, blocks: List[bytes]) -> List[bytes]:
        return [self.compress_block(b) for b in blocks]

    def decompress_blocks(self, blocks: List[Tuple[bytes, int]]) -> List[bytes]:
        return [self.decompress_block(b, n) for b, n in blocks]

    def decompress_blocks_concat(self, blocks: List[Tuple[bytes, int]]) -> bytes:
        """Decompress a run of blocks into ONE contiguous bytes object.
        Batch codecs override this to skip per-block slicing entirely — the
        read plane serves big chunks, so bytes cross the stream stack in
        ~``BATCH_FRAMES * block_size`` pieces instead of per frame."""
        out = self.decompress_blocks(blocks)
        for (_, ulen), b in zip(blocks, out):
            if len(b) != ulen:
                raise IOError(f"Decompressed length {len(b)} != header {ulen}")
        return b"".join(out)

    # --- framing ---
    def frame_from(self, raw: bytes, compressed: bytes) -> bytes:
        """Frame a pre-compressed block, applying the raw escape — the single
        place the escape rule and header layout live."""
        if len(compressed) >= len(raw):
            return HEADER.pack(0, len(raw), len(raw)) + raw
        return HEADER.pack(self.codec_id, len(raw), len(compressed)) + compressed

    def frame_block(self, raw: bytes) -> bytes:
        return self.frame_from(raw, self.compress_block(raw))

    def frame_blocks(self, blocks: List[bytes]) -> bytes:
        """Frame a batch of raw blocks as ONE bytes blob. Compression routes
        through :meth:`compress_blocks` — so batch codecs keep their device
        path even for a single-block tail batch — and batch codecs override
        this to make the whole batch's framing decision ONCE (TpuCodec
        snapshots its fallback delegate per batch instead of re-reading
        shared routing state per frame)."""
        compressed = self.compress_blocks(blocks)
        return b"".join(
            self.frame_from(raw, comp) for raw, comp in zip(blocks, compressed)
        )

    def wants_async_decode(self) -> bool:
        """True when CodecInputStream should run this codec's batch decode on
        the shared decode thread (bounded by ``decode_inflight_batches``).
        Only batch-capable codecs qualify — per-frame codecs gain nothing
        from a one-frame window."""
        return (
            int(getattr(self, "decode_inflight_batches", 0)) > 1
            and type(self).decompress_blocks is not FrameCodec.decompress_blocks
        )

    def compress_stream(self, sink: BinaryIO) -> "CodecOutputStream":
        return CodecOutputStream(self, sink)

    def decompress_stream(self, source: BinaryIO) -> "CodecInputStream":
        return CodecInputStream(self, source)

    def compress_bytes(self, data: bytes) -> bytes:
        out = io.BytesIO()
        s = CodecOutputStream(self, out, close_sink=False)
        s.write(data)
        s.close()
        return out.getvalue()

    def decompress_bytes(self, data: bytes) -> bytes:
        return self.decompress_stream(io.BytesIO(data)).read()


_C_ENCODE_BYTES = _metrics.REGISTRY.counter(
    "codec_encode_bytes_total", "Raw bytes through batch compress+frame calls"
)
_G_ENCODE_INFLIGHT = _metrics.REGISTRY.gauge(
    "codec_encode_inflight",
    "Encode batches in flight between serializers and their sinks "
    "(async batch mode, summed across streams)",
)
_C_FUSED_CRC = _metrics.REGISTRY.counter(
    "codec_fused_crc_total",
    "Frames whose stored-byte CRC came fused from the encode launch",
)
_C_FRAMES = _metrics.REGISTRY.counter(
    "codec_frames_total", "Frames emitted by codec output streams"
)

_C_DECODE_BYTES = _metrics.REGISTRY.counter(
    "codec_decode_bytes_total",
    "Decoded (uncompressed) bytes out of batch decompress calls",
)
_G_DECODE_INFLIGHT = _metrics.REGISTRY.gauge(
    "codec_decode_inflight",
    "Decode batches in flight between sources and their consumers "
    "(async batch mode, summed across streams)",
)
_C_FUSED_VALIDATED = _metrics.REGISTRY.counter(
    "codec_fused_crc_validated_total",
    "Frames whose stored-byte CRC certificate came fused from the decode "
    "launch (the checksum stream's host hashing pass was skipped)",
)

#: process-wide single-thread encode executor: the device is one resource,
#: so batches from every stream serialize through one worker — which also
#: makes future completion order == submission order (the streams' ordered
#: emission leans on it) and lets the tlz staging buffers be reused
#: per-thread across every batch in the process.
_encode_executor_lock = threading.Lock()
_encode_executor: Optional[ThreadPoolExecutor] = None


def _get_encode_executor() -> ThreadPoolExecutor:
    global _encode_executor
    with _encode_executor_lock:
        if _encode_executor is None:
            # shuffle-lint: disable=THR01 reason=process-wide encode pool shared by every codec stream for the process lifetime (one worker serializing device access); concurrent.futures joins idle workers at interpreter exit
            _encode_executor = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="s3shuffle-encode"
            )
        return _encode_executor


#: process-wide DECODE executor — the read plane's mirror of the encode
#: worker. Unlike the encode side it is NOT single-threaded: N concurrent
#: reduce tasks each run their own stream, and funneling every CPU-codec
#: batch through one worker would cap aggregate decode throughput at one
#: core (the pre-pipeline path decoded on each consumer thread in
#: parallel). Per-stream ordering needs no single worker — each stream
#: harvests its own FIFO future deque in submission order — and the tlz
#: staging planes are per-thread, so a small pool just keeps a few staging
#: sets. Device launches serialize inside XLA regardless of pool width.
_decode_executor_lock = threading.Lock()
_decode_executor: Optional[ThreadPoolExecutor] = None


def _get_decode_executor() -> ThreadPoolExecutor:
    global _decode_executor
    with _decode_executor_lock:
        if _decode_executor is None:
            import os

            # shuffle-lint: disable=THR01 reason=process-wide decode pool shared by every codec input stream for the process lifetime; concurrent.futures joins idle workers at interpreter exit
            _decode_executor = ThreadPoolExecutor(
                max_workers=min(4, os.cpu_count() or 2),
                thread_name_prefix="s3shuffle-decode",
            )
        return _decode_executor


class CodecOutputStream(io.RawIOBase):
    """Buffers up to ``block_size`` bytes, then emits one frame. ``close``
    flushes the final short block and closes the sink.

    Batch codecs (``codec.batch_blocks > 1``, e.g. the TPU codec) have full
    blocks accumulated and compressed ``batch_blocks`` at a time — one device
    round-trip per batch — while emitting byte-identical framing.

    **Async batch mode** (``codec.encode_inflight_batches > 1`` and the codec
    answers ``wants_async_encode()``): batches are handed to the process-wide
    encode thread and a bounded window of encode futures rides between the
    producer and the sink — the serializer fills batch N+1 and the sink
    (PipelinedUploadStream) PUTs batch N−1 while the chip encodes batch N.
    Emission is order-preserving (single worker + FIFO harvest), encode
    failures re-raise on the producer's next ``write``/``flush``/``close``,
    and ``pending_bytes`` counts in-flight raw bytes so memory budgets see
    them. When the codec degrades to a delegate or the device probe fails,
    batches fall back to today's synchronous path mid-stream.

    ``checksum`` (optional FusedChecksumAccumulator-shaped object) receives
    every emitted byte: per-frame fused CRCs when the codec returns them
    with the batch (``compress_framed_fused``), byte hashes otherwise — so
    its final value always equals a byte-serial checksum of the emitted
    stream."""

    def __init__(self, codec: FrameCodec, sink: BinaryIO, close_sink: bool = True,
                 checksum=None):
        self._codec = codec
        self._sink = sink
        self._buf = bytearray()
        self._close_sink = close_sink
        self._pending: List[bytes] = []  # full blocks awaiting a batch flush
        self._batch_blocks = max(1, getattr(codec, "batch_blocks", 1))
        # native fast path: compress + frame straight from the accumulation
        # buffer in one call (no per-block slicing/joining/header packing)
        self._framed = getattr(codec, "compress_framed", None)
        self._framed_fused = getattr(codec, "compress_framed_fused", None)
        # batch framing hook; duck-typed codec stand-ins may only implement
        # frame_block — fall back to per-block framing for them
        self._frame_blocks = getattr(codec, "frame_blocks", None)
        self._checksum = checksum
        self._wants_async = getattr(codec, "wants_async_encode", None)
        self._inflight: deque = deque()  # (future, raw_byte_count)
        self._inflight_bytes = 0

    @property
    def _window(self) -> int:
        """Async window size, read LIVE from the codec at every batch
        submission (not cached at construction): the write-side CommitTuner
        retunes ``encode_inflight_batches`` online, and a retune applies to
        the next batch of every open stream — a shrink drains down through
        the harvest loop, a grow widens the window in place."""
        return max(0, int(getattr(self._codec, "encode_inflight_batches", 0)))

    def writable(self) -> bool:
        return True

    def write(self, b) -> int:
        # buffer-protocol append, NOT bytes(b): serializers hand memoryviews
        # of whole columns here, and an eager bytes() copy was a full extra
        # pass over every shuffled byte (r5 profile)
        before = len(self._buf)
        self._buf += b if isinstance(b, (bytes, bytearray, memoryview)) else memoryview(b)
        written = len(self._buf) - before
        bs = self._codec.block_size
        if self._framed is not None:
            if len(self._buf) >= bs * self._batch_blocks:
                self._emit_framed(len(self._buf) // bs)
            return written
        while len(self._buf) >= bs:
            self._pending.append(bytes(self._buf[:bs]))
            del self._buf[:bs]
            if len(self._pending) >= self._batch_blocks:
                self._emit_pending()
        return written

    # ------------------------------------------------------------------
    # batch emission (sync + async)
    # ------------------------------------------------------------------
    def _encode_batch(self, buf, n_blocks: int, bs: int):
        """Compress+frame one batch (producer thread in sync mode, the shared
        encode thread in async mode). Returns (framed_bytes, crcs|None)."""
        mv = memoryview(buf)[: n_blocks * bs]
        with trace.span("codec.encode.batch", blocks=n_blocks):
            if self._checksum is not None and self._framed_fused is not None:
                out, crcs = self._framed_fused(mv, n_blocks, bs)
            else:
                out, crcs = self._framed(mv, n_blocks, bs), None
        if _metrics.enabled():
            _C_ENCODE_BYTES.inc(n_blocks * bs)
        return out, crcs

    def _write_out(self, data, crcs, n_frames: int) -> None:
        self._sink.write(data)
        if _metrics.enabled():
            _C_FRAMES.inc(n_frames)
        if self._checksum is not None:
            if crcs is not None:
                for crc, length in crcs:
                    self._checksum.add_stored(crc, length)
                if _metrics.enabled():
                    _C_FUSED_CRC.inc(len(crcs))
            else:
                self._checksum.add_bytes(
                    data if isinstance(data, bytes) else bytes(data)
                )

    def _harvest_one(self) -> None:
        fut, nbytes = self._inflight.popleft()
        self._inflight_bytes -= nbytes
        if _metrics.enabled():
            _G_ENCODE_INFLIGHT.dec(1)
        try:
            with trace.span("codec.encode.harvest"):
                out, crcs, n_frames = fut.result()
        except BaseException:
            self._abort_inflight()
            raise
        self._write_out(out, crcs, n_frames)

    def _drain_inflight(self) -> None:
        while self._inflight:
            self._harvest_one()

    def _abort_inflight(self) -> None:
        """A batch failed: drop the rest of the window (the stream is broken
        — the producer is about to see the failure and abort the write)."""
        if _metrics.enabled():
            _G_ENCODE_INFLIGHT.dec(len(self._inflight))
        for fut, _nbytes in self._inflight:
            fut.cancel()
        self._inflight.clear()
        self._inflight_bytes = 0

    def _emit_framed(self, n_blocks: int) -> None:
        bs = self._codec.block_size
        cut = n_blocks * bs
        if (
            self._window > 1
            and self._wants_async is not None
            and self._wants_async()
        ):
            # hand the WHOLE buffer to the encode thread (it reads only the
            # first ``cut`` bytes and is never resized, so no copy of the
            # emitted region); keep the partial-block tail in a fresh buffer
            buf = self._buf
            self._buf = bytearray(memoryview(buf)[cut:])

            def job(b=buf, n=n_blocks, ctx=trace.current_context()):
                with trace.context(ctx):
                    out, crcs = self._encode_batch(b, n, bs)
                return out, crcs, n

            self._inflight.append((_get_encode_executor().submit(job), cut))
            self._inflight_bytes += cut
            if _metrics.enabled():
                _G_ENCODE_INFLIGHT.inc(1)
            while len(self._inflight) >= self._window:
                self._harvest_one()
            return
        # synchronous path (no window, delegate active, or device probe
        # failed): drain any in-flight batches first so emission order holds
        self._drain_inflight()
        out, crcs = self._encode_batch(self._buf, n_blocks, bs)
        self._write_out(out, crcs, n_blocks)
        try:
            del self._buf[:cut]
        except BufferError:
            # The device encode path stages H2D transfers asynchronously and
            # may still hold an export of the buffer after returning (jax
            # owns the view until the transfer lands). A pinned bytearray
            # cannot be resized — start a fresh buffer with the tail bytes
            # and let the old one die when the device releases it.
            self._buf = bytearray(memoryview(self._buf)[cut:])

    def _frame_batch(self, blocks: List[bytes]) -> bytes:
        if self._frame_blocks is not None:
            return self._frame_blocks(blocks)
        return b"".join(self._codec.frame_block(b) for b in blocks)

    def _emit_pending(self) -> None:
        if not self._pending:
            return
        # frame_blocks for ANY pending count — a single-block tail batch
        # used to take frame_block (the per-block HOST path), silently
        # skipping the device for the last partial batch of every partition
        out = self._frame_batch(self._pending)
        self._write_out(out, None, len(self._pending))
        self._pending.clear()

    @property
    def pending_bytes(self) -> int:
        """Raw bytes buffered but not yet framed (partial block + batch queue
        + async in-flight batches) — memory-budget accounting must count
        these."""
        return (
            len(self._buf)
            + sum(len(p) for p in self._pending)
            + self._inflight_bytes
        )

    def flush_block(self) -> None:
        """Force everything buffered out (used at partition boundaries so
        partitions never share a frame)."""
        if self._framed is not None:
            bs = self._codec.block_size
            full = len(self._buf) // bs
            if full:
                self._emit_framed(full)
            self._drain_inflight()
            if self._buf:
                # short tail: route through the codec's batch framing hook
                # (frame_blocks snapshots routing once and keeps batch
                # codecs' device/host decision in one place)
                tail = bytes(self._buf)
                self._write_out(self._frame_batch([tail]), None, 1)
                self._buf.clear()
            return
        if self._buf:
            self._pending.append(bytes(self._buf))
            self._buf.clear()
        self._emit_pending()

    def close(self) -> None:
        if not self.closed:
            try:
                self.flush_block()
            except BaseException:
                self._abort_inflight()
                raise
            if self._close_sink:
                self._sink.close()
            else:
                try:
                    self._sink.flush()
                except (AttributeError, ValueError):
                    pass
        super().close()


class CodecInputStream(io.RawIOBase):
    """Reads frames from ``source`` and serves decompressed bytes. Any codec's
    frames are accepted (the decoder dispatches on codec_id), so readers can
    decode data written by a different configured codec.

    **Async batch mode** (``codec.decode_inflight_batches > 1`` and the codec
    answers ``wants_async_decode()``): frame batches are handed to the
    process-wide decode thread and a bounded window of decode futures rides
    between the source and the consumer — the consumer deserializes chunk N
    and pulls chunk N+2's compressed frames (the next coalesced-segment GET's
    bytes) while the decode thread works on chunk N+1. Harvests are
    order-preserving (single worker + FIFO), decode failures re-raise on the
    consumer's next read, and each in-flight batch's decoded bytes are
    RESERVED against the scan's ``max_buffer_size_task`` budget (``budget``)
    so N concurrent reduce tasks never exceed their provisioned memory — the
    window shrinks instead of waiting when the budget is full. ≤ 1 keeps
    every decode synchronous on the consumer thread (today's behavior).

    **Fused validation**: when the codec can certify frames' stored-byte CRCs
    from its decode launch (``wants_fused_decode_validation``) and the source
    is a :class:`~s3shuffle_tpu.read.checksum_stream.ChecksumValidationStream`
    whose algorithm has a combinable CRC form, the stream arms the source's
    deferred mode and certifies each decoded frame itself — the checksum
    layer's host hashing pass is skipped for fused frames, with
    ``ChecksumError`` classification identical to streaming validation
    (decode errors resolve pending certification FIRST, so corruption still
    surfaces as the checksum mismatch it is)."""

    #: Frames read ahead and decoded per batch — one native/device call
    #: instead of one per frame. Bounds extra buffering to
    #: ``BATCH_FRAMES * block_size`` decoded bytes per stream. The
    #: ``decode_batch_frames`` codec attribute (config knob) overrides this,
    #: read LIVE per batch so online retunes apply mid-stream; <= 1
    #: reproduces the per-frame decode path exactly.
    BATCH_FRAMES = 32
    #: Source refill granularity: compressed bytes are pulled through the
    #: stream stack below (prefetch → checksum) in pieces this big instead of
    #: one read per frame header + payload — the checksum layer then hashes
    #: ~20x fewer, bigger chunks.
    SRC_CHUNK = 1 << 20

    def __init__(self, codec: FrameCodec | None, source: BinaryIO, budget=None):
        self._codec = codec
        self._source = source
        self._current = b""
        self._pos = 0
        self._eof = False
        self._decoded: deque = deque()  # (chunk, reserved_budget_bytes)
        self._rbuf = b""
        self._rpos = 0
        # Read-ahead only pays off for codecs with a batch decompress path.
        self._batch_capable = (
            codec is not None
            and type(codec).decompress_blocks is not FrameCodec.decompress_blocks
        )
        self._budget = budget  # try_reserve/release_reserved surface
        self._inflight: deque = deque()  # (future, reserved_budget_bytes)
        self._pending_frame = None  # codec-switch leftover seeding the next run
        self._src_eof = False
        self._wants_async = getattr(codec, "wants_async_decode", None)
        # fused-validation handshake: arm the source's deferred mode only
        # when the codec can actually hand back fused stored-byte CRCs
        self._certify = None
        self._fused_poly = None
        wants_fused = getattr(codec, "wants_fused_decode_validation", None)
        defer = getattr(source, "defer_validation", None)
        poly = getattr(source, "fused_poly", None)
        if wants_fused is not None and defer is not None and poly is not None:
            try:
                if wants_fused(poly) and defer():
                    self._certify = source
                    self._fused_poly = poly
            except Exception:
                import logging

                logging.getLogger("s3shuffle_tpu.codec").debug(
                    "fused-validation handshake failed; streaming validation "
                    "stays active", exc_info=True,
                )

    def readable(self) -> bool:
        return True

    @property
    def _batch_frames(self) -> int:
        """Live frames-per-batch: the codec's ``decode_batch_frames`` knob
        (ScanTuner retunes it online), falling back to BATCH_FRAMES."""
        if not self._batch_capable:
            return 1
        v = getattr(self._codec, "decode_batch_frames", None)
        if v is None:
            return self.BATCH_FRAMES
        return max(1, int(v))

    @property
    def _window(self) -> int:
        """Live async decode window, read at every batch boundary (the
        read-side mirror of CodecOutputStream._window): a retune shrinks or
        widens the in-flight future window mid-stream."""
        if self._codec is None:
            return 0
        return max(0, int(getattr(self._codec, "decode_inflight_batches", 0)))

    def _read_exact(self, n: int) -> bytes:
        """n bytes from the buffered source (may return fewer only at EOF).
        Refills in ``SRC_CHUNK`` pieces so the layers below see big reads."""
        avail = len(self._rbuf) - self._rpos
        if avail >= n:
            out = self._rbuf[self._rpos : self._rpos + n]
            self._rpos += n
            return out
        parts = [self._rbuf[self._rpos :]] if avail else []
        need = n - avail
        self._rbuf = b""
        self._rpos = 0
        while need > 0:
            chunk = self._source.read(max(need, self.SRC_CHUNK))
            if not chunk:
                break
            if len(chunk) > need:
                parts.append(chunk[:need])
                self._rbuf = chunk
                self._rpos = need
                need = 0
            else:
                parts.append(chunk)
                need -= len(chunk)
        return b"".join(parts) if len(parts) != 1 else parts[0]

    def _read_frame(self):
        """Returns (codec_id, payload, ulen) or None at EOF."""
        header = self._read_exact(HEADER_SIZE)
        if not header:
            return None
        if len(header) < HEADER_SIZE:
            raise IOError(f"Truncated frame header ({len(header)} bytes)")
        codec_id, ulen, clen = HEADER.unpack(header)
        if ulen > MAX_FRAME_ULEN or clen > MAX_FRAME_ULEN:
            raise IOError(
                f"Frame header claims {max(ulen, clen)} bytes "
                f"(> {MAX_FRAME_ULEN} cap) — corrupt stream"
            )
        payload = self._read_exact(clen)
        if len(payload) < clen:
            raise IOError(f"Truncated frame payload ({len(payload)}/{clen} bytes)")
        if codec_id == 0 and ulen != clen:
            raise IOError("Raw frame with mismatched lengths")
        return codec_id, payload, ulen

    def _read_run(self) -> list:
        """Pull the next in-order run of frames sharing one codec_id, up to
        the live batch size. A codec switch parks the switching frame to seed
        the NEXT run (frames are never reordered)."""
        run: list = []
        limit = self._batch_frames
        if self._pending_frame is not None:
            run.append(self._pending_frame)
            self._pending_frame = None
        while len(run) < limit:
            frame = self._read_frame()
            if frame is None:
                self._src_eof = True
                break
            if run and frame[0] != run[0][0]:
                self._pending_frame = frame
                break
            run.append(frame)
            if limit == 1:
                break
        return run

    def _decode_frames(self, frames, ctx=None):
        """One decode job under the ``codec.decode.batch`` span, adopting
        the submitting task's trace context ``ctx`` on a decode thread."""
        with trace.context(ctx), trace.span("codec.decode.batch"):
            return self._decode_run(frames)

    def _decode_run(self, frames):
        """Decode an in-order run of frames sharing one codec_id into ONE
        contiguous chunk (fewer, bigger pieces crossing the stream stack ⇒
        fewer per-chunk copy calls). Runs on the consumer thread in sync
        mode, the shared decode thread in async mode — it never touches the
        source. Returns ``(chunk, certs)`` where ``certs`` (fused validation
        armed) lists ``(frame_len, frame_crc_or_None)`` per frame in order."""
        codec_id = frames[0][0]
        certs = [] if self._certify is not None else None
        if codec_id == 0:
            out = (
                frames[0][1] if len(frames) == 1
                else b"".join(p for _c, p, _u in frames)
            )
            if certs is not None:
                certs.extend(
                    (HEADER_SIZE + len(p), None) for _c, p, _u in frames
                )
            if _metrics.enabled():
                _C_DECODE_BYTES.inc(len(out))
            return out, certs
        # route the whole run through its codec — the configured codec when
        # it matches, else the cached registry instance (a stream legally
        # mixes codec ids, e.g. SLZ frames written by the codec=tpu host
        # fallback read back under a TpuCodec hint)
        if self._codec is not None and codec_id == self._codec.codec_id:
            codec = self._codec
        else:
            codec = _codec_for_frame_id(codec_id)
        total = sum(u for _c, _p, u in frames)
        blocks = [(p, u) for _c, p, u in frames]
        crcs = None
        if certs is not None and getattr(codec, "decompress_blocks_fused", None):
            out, crcs = codec.decompress_blocks_fused(blocks, self._fused_poly)
        elif len(frames) == 1 and certs is None:
            out = decompress_frame_payload(
                codec_id, frames[0][1], frames[0][2], self._codec
            )
        else:
            out = codec.decompress_blocks_concat(blocks)
        if len(out) != total:
            raise IOError(
                f"Decompressed run length {len(out)} != headers {total}"
            )
        if certs is not None:
            from s3shuffle_tpu.ops.checksum import crc_combine, host_crc

            with trace.span("codec.decode.emit"):
                for i, (_c, p, u) in enumerate(frames):
                    crc = crcs[i] if crcs is not None else None
                    if crc is not None:
                        # frame = 9-byte header (host-hashed) + payload (fused)
                        header = HEADER.pack(codec_id, u, len(p))
                        crc = crc_combine(
                            host_crc(header, self._fused_poly), crc, len(p),
                            self._fused_poly,
                        )
                    certs.append((HEADER_SIZE + len(p), crc))
        if _metrics.enabled():
            _C_DECODE_BYTES.inc(len(out))
        return out, certs

    def _apply_certs(self, certs) -> None:
        """Feed a decoded run's certificates to the deferred checksum stream
        in order (consumer thread only — certification mutates the
        validator's cursor). Raises the validator's ChecksumError on a
        partition mismatch, exactly where streaming validation would."""
        if not certs:
            return
        fused = 0
        for length, crc in certs:
            self._certify.certify(length, stored_crc=crc)
            if crc is not None:
                fused += 1
        if fused and _metrics.enabled():
            _C_FUSED_VALIDATED.inc(fused)

    # ------------------------------------------------------------------
    # async window
    # ------------------------------------------------------------------
    def _submit_window(self) -> None:
        while not self._src_eof or self._pending_frame is not None:
            if len(self._inflight) >= self._window:
                break
            reserved = 0
            if self._inflight and self._budget is not None:
                # beyond the first in-flight batch the decoded bytes must fit
                # the task budget; a full budget SHRINKS the window instead
                # of blocking (the consumer holding this thread is the same
                # one whose closes release prefill budget)
                est = self._batch_frames * max(
                    1, int(getattr(self._codec, "block_size", 1 << 16))
                )
                if not self._budget.try_reserve(est):
                    break
                reserved = est
            try:
                run = self._read_run()
                if run:
                    fut = _get_decode_executor().submit(
                        self._decode_frames, run, trace.current_context()
                    )
            except BaseException:
                # the reservation is in neither _inflight nor _decoded yet —
                # release here or the scan budget stays inflated for good
                if reserved:
                    self._budget.release_reserved(reserved)
                raise
            if not run:
                if reserved:
                    self._budget.release_reserved(reserved)
                break
            self._inflight.append((fut, reserved))
            if _metrics.enabled():
                _G_DECODE_INFLIGHT.inc(1)

    def _harvest_one_decode(self) -> None:
        fut, reserved = self._inflight.popleft()
        if _metrics.enabled():
            _G_DECODE_INFLIGHT.dec(1)
        try:
            with trace.span("codec.decode.harvest"):
                chunk, certs = fut.result()
            self._apply_certs(certs)
        except BaseException:
            if reserved and self._budget is not None:
                self._budget.release_reserved(reserved)
            raise
        self._decoded.append((chunk, reserved))

    def _drain_decode_inflight(self) -> None:
        while self._inflight:
            self._harvest_one_decode()

    def _abort_decode_window(self) -> None:
        if _metrics.enabled() and self._inflight:
            _G_DECODE_INFLIGHT.dec(len(self._inflight))
        for fut, reserved in self._inflight:
            fut.cancel()
            if reserved and self._budget is not None:
                self._budget.release_reserved(reserved)
        self._inflight.clear()

    # ------------------------------------------------------------------
    def _fill(self) -> bool:
        if not self._decoded:
            try:
                if (
                    self._window > 1
                    and self._wants_async is not None
                    and self._wants_async()
                ):
                    while not self._decoded:
                        self._submit_window()
                        if not self._inflight:
                            break
                        self._harvest_one_decode()
                else:
                    # synchronous path (window off, or shrunk mid-stream:
                    # drain leftovers first so emission order holds)
                    self._drain_decode_inflight()
                    if not self._decoded:
                        run = self._read_run()
                        if run:
                            chunk, certs = self._decode_frames(run)
                            self._apply_certs(certs)
                            self._decoded.append((chunk, 0))
            except BaseException:
                self._abort_decode_window()
                if self._certify is not None:
                    # corruption must classify exactly as streaming
                    # validation classifies it: hash the served-but-
                    # uncertified bytes NOW — a checksum mismatch in a
                    # completed partition raises ChecksumError here, taking
                    # precedence over the decoder's parse error
                    self._certify.resolve_pending()
                raise
        if not self._decoded:
            self._eof = True
            return False
        chunk, reserved = self._decoded.popleft()
        if reserved and self._budget is not None:
            self._budget.release_reserved(reserved)
        self._current = chunk
        self._pos = 0
        return True

    def read(self, size: int = -1) -> bytes:
        if size is None or size < 0:
            chunks = []
            while True:
                chunk = self.read(1 << 20)
                if not chunk:
                    return b"".join(chunks)
                chunks.append(chunk)
        out = self.readview(size)
        return out if isinstance(out, bytes) else bytes(out)

    def readview(self, size: int):
        """Zero-copy variant of :meth:`read`: returns up to ``size`` bytes as
        a slice of the current decoded chunk WITHOUT converting to bytes —
        bytes, or a uint8 ndarray view for natively batch-decoded runs. The
        columnar frame parser reads through this (buffers feed np.frombuffer
        / struct.unpack_from directly), skipping one full copy of every
        decoded byte."""
        while self._pos >= len(self._current):
            if self._eof or not self._fill():
                return b""
        end = min(self._pos + size, len(self._current))
        out = self._current[self._pos : end]
        self._pos = end
        return out

    def close(self) -> None:
        if not self.closed:
            self._abort_decode_window()
            if self._budget is not None:
                for _chunk, reserved in self._decoded:
                    if reserved:
                        self._budget.release_reserved(reserved)
            self._decoded.clear()
            self._source.close()
        super().close()


import functools


@functools.lru_cache(maxsize=None)
def _codec_for_frame_id(codec_id: int) -> FrameCodec:
    """Registry codec for a frame's codec id, constructed once per process —
    cross-codec reads (frames whose id differs from the configured codec's)
    must not rebuild the codec (ctypes load + symbol lookups) per frame."""
    name = _NAMES.get(codec_id)
    if name is None:
        raise IOError(f"Unknown codec id in frame: {codec_id}")
    from s3shuffle_tpu.codec import get_codec

    # frame-name → registry-name: only two names are genuinely aliased;
    # every other codec registers under its frame name
    codec = get_codec({"native-lz": "native", "tpu-lz": "tpu"}.get(name, name))
    assert codec is not None
    return codec


def decompress_frame_payload(
    codec_id: int, payload: bytes, ulen: int, hint: FrameCodec | None
) -> bytes:
    """Dispatch on the frame's codec id; ``hint`` avoids a registry lookup when
    the configured codec matches (the common case)."""
    if hint is not None and codec_id == hint.codec_id:
        return hint.decompress_block(payload, ulen)
    return _codec_for_frame_id(codec_id).decompress_block(payload, ulen)


