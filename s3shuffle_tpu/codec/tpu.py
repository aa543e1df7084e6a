"""TPU codec: batched TLZ compression + fused CRC, behind the shared framing.

The north-star differentiator (BASELINE.json): shuffle partition bytes flow
through a batched device compressor instead of a JVM codec stream, with the
checksum pass fused onto the same staged batch. Host pipeline per batch:

    stage N blocks → H2D once → TLZ encode kernel (ops/tlz.py)
                              → CRC32C kernel on the same batch (ops/checksum.py)
    → D2H (compact arrays) → host frame assembly

``compress_blocks`` overrides the frame codec's batch hook, so the shared
:class:`CodecOutputStream` emits byte-identical framing while calling the
device once per ``batch_blocks`` blocks. Decompression of tpu-lz frames is a
parallel gather — served by vectorized numpy on the host read path
(decode_payload_numpy) or in batch on device (decode_blocks_device).

Fused checksum semantics: the partition checksum covers *stored* bytes
(reference semantics — S3ChecksumValidationStream.scala:41-66). Stored bytes
are frames = 9-byte headers + payloads; CRC is GF(2)-linear, so the device
computes per-payload CRCs in batch and the host stitches headers in with
:func:`crc_combine` — no byte-serial pass anywhere. See
FusedChecksumAccumulator.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import List

import numpy as np

from s3shuffle_tpu.codec.framing import CODEC_IDS, FrameCodec
from s3shuffle_tpu.ops import rates, tlz
from s3shuffle_tpu.ops.checksum import (
    POLY_CRC32,
    POLY_CRC32C,
    crc32_batch,
    crc_combine,
    stage_right_aligned,
)
from s3shuffle_tpu.utils import trace

logger = logging.getLogger("s3shuffle_tpu.codec.tpu")

#: host CRC32C for the small header/metadata slices stitched around fused
#: device CRCs (native C when built, Python table otherwise) — resolved once
_host_crc32c = None


def _crc32c_host(data: bytes) -> int:
    global _host_crc32c
    if _host_crc32c is None:
        from s3shuffle_tpu.utils.checksums import _crc32c_fn

        _host_crc32c = _crc32c_fn()
    return _host_crc32c(data)


#: process-wide backend verdict (None = not decided yet), decided once under
#: _BACKEND_LOCK by one synchronous ``jax.default_backend()`` query
_BACKEND_VERDICT: bool | None = None
_BACKEND_LOCK = threading.Lock()


def _env_device_override() -> bool | None:
    env = os.environ.get("S3SHUFFLE_TPU_CODEC_DEVICE")
    if env is None:
        return None
    return env.strip().lower() in ("1", "true", "yes", "on")


def _device_backend() -> bool:
    """True when an accelerator backend is attached. Decided once per
    process; ``S3SHUFFLE_TPU_CODEC_DEVICE`` overrides it on every call. A
    failing backend query raises: a chip that cannot be reached is an error,
    not a host run. The first device verdict arms the persistent compile
    cache before the first codec kernel compiles."""
    global _BACKEND_VERDICT
    env = _env_device_override()
    if env is not None:
        return env
    if _BACKEND_VERDICT is None:
        with _BACKEND_LOCK:
            if _BACKEND_VERDICT is None:
                import jax

                verdict = jax.default_backend() != "cpu"
                if verdict:
                    from s3shuffle_tpu.utils.compile_cache import (
                        default_compile_cache,
                    )

                    default_compile_cache()
                _BACKEND_VERDICT = verdict
    return _BACKEND_VERDICT


class TpuCodec(FrameCodec):
    name = "tpu-lz"
    codec_id = CODEC_IDS["tpu-lz"]

    def __init__(
        self,
        # 256 KiB default: TLZ's ratio improves with block length (per-block
        # first-occurrence literals amortize) while its match window is a
        # separate 64 KiB distance cap; CPU codecs keep 64 KiB blocks
        block_size: int = 256 * 1024,
        batch_blocks: int = 64,
        use_device: bool | None = None,
        host_encode_fallback: bool = False,
        # bounded window of encode batches allowed in flight between the
        # serializer and the sink (CodecOutputStream async batch mode);
        # <= 1 keeps every batch synchronous on the producer thread
        encode_inflight_batches: int = 0,
        # read-side mirrors (CodecInputStream async batch mode; see
        # FrameCodec class docs): frames decoded per batch (None = the
        # stream's BATCH_FRAMES default) and the bounded decode window
        decode_batch_frames: int | None = None,
        decode_inflight_batches: int = 0,
        # seconds a device-failure host pin lasts before ONE trial batch
        # re-probes the device (a transient device fault usually clears);
        # 0 = the legacy permanent pin. Config knob ``codec_repin_probe_s``.
        repin_probe_s: float = 300.0,
    ):
        if block_size % 128 != 0:
            raise ValueError("TPU codec block_size must be a multiple of 128")
        if block_size > tlz.MAX_BLOCK:
            raise ValueError("TPU codec block_size must be <= 256 KiB")
        super().__init__(block_size)
        self.batch_blocks = batch_blocks
        self.encode_inflight_batches = max(0, int(encode_inflight_batches))
        if decode_batch_frames is not None:
            self.decode_batch_frames = max(1, int(decode_batch_frames))
        self.decode_inflight_batches = max(0, int(decode_inflight_batches))
        self._device_failures = 0  # consecutive device batch-encode failures
        self._decode_failures = 0  # consecutive device batch-DECODE failures
        self._use_device = use_device
        #: the ctor's explicit choice, kept apart from the probe-cached
        #: verdict in ``_use_device``: an EXPLICIT device force bypasses the
        #: measured-rate gate, and a failure-pin re-probe restores it
        self._explicit_device = use_device
        self._repin_probe_s = max(0.0, float(repin_probe_s))
        self._host_pinned_at: float | None = None  # _clock() of the last pin
        self._reprobing = False  # current batch is a re-probe trial
        self._clock = time.monotonic  # patchable in the repin tests
        #: ``codec=tpu`` chosen but no accelerator attached: reroute ENCODE to
        #: SLZ frames (a different codec_id — readers dispatch per frame, so
        #: mixing is legal within a shuffle) instead of eating the ~5x-slower
        #: host C TLZ encoder. TLZ DECODE stays available for existing data.
        #: Deployment-level knob (config ``tpu_host_fallback``, default on);
        #: direct constructions default off so the host TLZ write path stays
        #: directly testable.
        self.host_encode_fallback = host_encode_fallback
        self._fallback_codec = None
        self._fallback_lock = threading.Lock()

    def _device_path(self) -> bool:
        """Batch work goes to the device only when an accelerator backend is
        actually attached — XLA:CPU runs the sort/gather kernels orders of
        magnitude slower than the vectorized numpy path, and readers of
        tpu-lz data are often plain CPU hosts. Overridable per instance
        (``use_device=``) or via S3SHUFFLE_TPU_CODEC_DEVICE=0/1. The backend
        is queried once per process (:func:`_device_backend`), synchronously
        and before the first batch, so no batch is host-encoded while a
        chip is being discovered.

        A failure pin (:meth:`_pin_host`) expires after ``repin_probe_s``
        seconds: ONE trial batch then goes back to the device — its first
        failure re-pins immediately, its first success clears the pin."""
        if self._use_device is not None:
            if (
                not self._use_device
                and self._host_pinned_at is not None
                and self._clock() - self._host_pinned_at >= self._repin_probe_s
            ):
                # shuffle-lint: disable=THR02 reason=pin/reprobe scalars are deliberately lock-free GIL-atomic writes; racing encoders converge (worst case one extra trial batch) and a lock here sits on the per-batch hot path
                self._reprobing = True
                # shuffle-lint: disable=THR02 reason=same lock-free pin state machine as _reprobing above
                self._host_pinned_at = None
                # shuffle-lint: disable=THR02 reason=same lock-free pin state machine as _reprobing above
                self._use_device = self._explicit_device
                if self._use_device is not None:
                    return self._use_device
            else:
                return self._use_device
        self._use_device = _device_backend()
        return self._use_device

    def _pin_host(self) -> None:
        """Pin this instance to the host path after device failures. With
        ``repin_probe_s`` > 0 the pin expires (see :meth:`_device_path`);
        0 keeps the legacy permanent pin."""
        self._use_device = False
        self._reprobing = False
        self._device_failures = 0
        # shuffle-lint: disable=THR02 reason=failure counters are best-effort lock-free tallies; a lost increment only delays the host pin by one failed batch
        self._decode_failures = 0
        self._host_pinned_at = (
            self._clock() if self._repin_probe_s > 0 else None
        )

    def _device_ok(self) -> None:
        """A device batch succeeded: clear any re-probe trial state."""
        if self._reprobing or self._host_pinned_at is not None:
            self._reprobing = False
            self._host_pinned_at = None
            logger.info(
                "device re-probe succeeded — codec back on the device path"
            )

    def _forced_verdict(self) -> bool:
        """True when the device side was EXPLICITLY forced (ctor
        ``use_device=True`` or S3SHUFFLE_TPU_CODEC_DEVICE truthy): the
        operator bypassed measurement, so the rate gate steps aside."""
        return self._explicit_device is True or _env_device_override() is True

    def _select_device(self, op: str) -> bool:
        """Availability (:meth:`_device_path`) AND the measured-rate gate
        (ops/rates.py): a chip runs ``op`` only when its cached probe rate
        beats the competing host rate — availability alone shipped a 120x
        encode regression (3.6 vs 435 MB/s) before this gate existed."""
        return self._device_path() and rates.select(
            op, forced=self._forced_verdict()
        )

    @property
    def supports_fused_checksum(self) -> bool:
        """The encode kernel can return each block's CRC32C with its payload
        planes in the same launch (ops/tlz.py encode_batch_device(poly=...));
        the write plane keys its fused-checksum wiring on this. True only
        when batches will actually route to the device encode — availability
        AND the measured-rate gate — since streaming host checksums win
        whenever the encode itself stays on the host."""
        return self._device_path() and rates.decide(
            "encode", forced=self._forced_verdict()
        )[0]

    def _encode_delegate(self):
        """The SLZ codec encode should reroute to, or None to encode TLZ.

        Decided once, at the first compress call: enabled fallback + host
        verdict activates the delegate forever (readers dispatch on each
        frame's codec_id, so a stream legally mixes SLZ frames after TLZ
        ones — but a stable choice keeps ratios predictable)."""
        if not self.host_encode_fallback:
            return None
        if self._fallback_codec is not None:  # sticky choice already made
            return self._fallback_codec
        verdict = (
            self._use_device if self._use_device is not None
            else _device_backend()
        )
        if verdict and rates.decide("encode", forced=self._forced_verdict())[0]:
            # chip attached AND measured worth using: TLZ on device. A chip
            # that is merely PRESENT but rate-gated to host behaves like no
            # chip — the SLZ reroute beats the host C TLZ encoder at write.
            self.host_encode_fallback = False
            return None
        with self._fallback_lock:
            if self._fallback_codec is not None:
                return self._fallback_codec
            try:
                from s3shuffle_tpu.codec.native import NativeLZCodec

                self._fallback_codec = NativeLZCodec(block_size=self.block_size)
            except Exception:
                # no native lib either — host TLZ is all we have
                logger.debug(
                    "codec=tpu: no native fallback codec", exc_info=True
                )
                self.host_encode_fallback = False
                return None
            logger.warning(
                "codec=tpu selected but no accelerator backend is attached "
                "(CPU-only host): rerouting shuffle WRITES to SLZ ('native') "
                "frames — the host C TLZ encoder would be ~5x slower at "
                "write. TLZ decode stays active for existing data. Set "
                "tpu_host_fallback=false (or S3SHUFFLE_TPU_CODEC_DEVICE=1 "
                "with a chip) to override."
            )
        return self._fallback_codec

    def frame_from(self, raw: bytes, compressed: bytes) -> bytes:
        # the delegate choice is sticky and made by the compress call that
        # produced ``compressed``, so frames stamp the codec that encoded it
        if self._fallback_codec is not None:
            return self._fallback_codec.frame_from(raw, compressed)
        return super().frame_from(raw, compressed)

    # --- single block (host path: C encoder, numpy fallback/oracle) ---
    def _compress_block_local(self, data: bytes) -> bytes:
        """TLZ host encode, NO delegate consultation — the device-failure
        fallback must not re-resolve routing mid-batch."""
        native = tlz._encode_block_native(data)
        if native is not None:
            return native
        return tlz._assemble_payload_numpy(data)

    def compress_block(self, data: bytes) -> bytes:
        delegate = self._encode_delegate()
        if delegate is not None:
            return delegate.compress_block(data)
        return self._compress_block_local(data)

    def decompress_block(self, data: bytes, uncompressed_len: int) -> bytes:
        return tlz.decode_payload_numpy(data, uncompressed_len)

    def _encode_full_blocks(self, mv, n_blocks: int, block_size: int, poly):
        """Device batch encode of ``n_blocks`` full blocks from a contiguous
        memoryview, with fused CRCs when ``poly`` is set. A device failure
        mid-shuffle host-encodes THIS
        batch — no queued block is ever lost — and after three consecutive
        failures pins the instance to the host path (each retry would eat an
        exception + fallback per batch forever)."""
        if self._select_device("encode"):
            try:
                payloads, crc_info = tlz.encode_batch_device(
                    mv, n_blocks, block_size,
                    batch_blocks=self.batch_blocks, poly=poly,
                )
                self._device_failures = 0
                self._device_ok()
                rates.count_device_batch("encode", True)
                return payloads, crc_info
            except Exception:
                rates.count_device_batch("encode", False)
                self._device_failures += 1
                if self._device_failures >= 3 or self._reprobing:
                    n = self._device_failures
                    trial = self._reprobing
                    self._pin_host()
                    rates.count_host_pin("encode")
                    logger.warning(
                        "device batch encode failed %s — pinning this codec "
                        "to the host TLZ encoder%s",
                        "on its re-probe trial" if trial
                        else f"{n} times in a row",
                        "" if self._repin_probe_s <= 0
                        else f" (re-probe in {self._repin_probe_s:g}s)",
                        exc_info=True,
                    )
                else:
                    logger.warning(
                        "device batch encode failed — host-encoding this "
                        "batch (no queued blocks lost)", exc_info=True,
                    )
        payloads = [
            self._compress_block_local(
                bytes(mv[i * block_size : (i + 1) * block_size])
            )
            for i in range(n_blocks)
        ]
        return payloads, None

    def _compress_framed_impl(self, buf, n_blocks: int, block_size: int,
                              want_crcs: bool):
        from s3shuffle_tpu.codec.framing import HEADER, HEADER_SIZE

        # routing snapshot: ONE delegate decision per batch — compression
        # and framing below both use it
        delegate = self._encode_delegate()
        if delegate is not None:
            return delegate.compress_framed(buf, n_blocks, block_size), None
        mv = memoryview(buf)
        payloads, crc_info = self._encode_full_blocks(
            mv, n_blocks, block_size, POLY_CRC32C if want_crcs else None
        )
        with trace.span("codec.encode.assemble"):
            out = bytearray()
            crcs: List | None = [] if crc_info is not None else None
            if crc_info is not None:
                block_crcs, lit_crcs, lit_lens = crc_info
            for i, pl in enumerate(payloads):
                if len(pl) >= block_size:  # framing raw escape
                    header = HEADER.pack(0, block_size, block_size)
                    out += header
                    out += mv[i * block_size : (i + 1) * block_size]
                    if crcs is not None:
                        # stored bytes = header + RAW block; the raw-block CRC
                        # came fused from the same launch as the encode planes
                        crcs.append((
                            crc_combine(
                                _crc32c_host(header), int(block_crcs[i]),
                                block_size, POLY_CRC32C,
                            ),
                            HEADER_SIZE + block_size,
                        ))
                else:
                    header = HEADER.pack(self.codec_id, block_size, len(pl))
                    out += header
                    out += pl
                    if crcs is not None:
                        # stored bytes = header + metadata prefix + literal
                        # plane; only the small prefix touches the host CRC
                        lit_len = int(lit_lens[i])
                        crcs.append((
                            crc_combine(
                                _crc32c_host(header + pl[: len(pl) - lit_len]),
                                int(lit_crcs[i]), lit_len, POLY_CRC32C,
                            ),
                            HEADER_SIZE + len(pl),
                        ))
            return bytes(out), crcs

    def compress_framed(self, buf, n_blocks: int, block_size: int) -> bytes:
        """Contiguous-buffer fast path (framing.CodecOutputStream hook): the
        accumulated write buffer IS the staging batch, so the device path
        never copies raw bytes on the host — ``np.frombuffer`` view straight
        into the H2D transfer, fixed-shape precompiled launches, vectorized
        whole-batch assembly (the bench's ``tpu_devwrite_host_mb_s`` fields
        time the assembly path)."""
        return self._compress_framed_impl(buf, n_blocks, block_size, False)[0]

    def compress_framed_fused(self, buf, n_blocks: int, block_size: int):
        """:meth:`compress_framed` + per-frame stored-byte CRC32C values from
        the SAME device launch. Returns ``(framed_bytes, crcs)`` where
        ``crcs`` is a list of ``(frame_crc, frame_len)`` in emission order —
        or None when the batch routed to a delegate or the host path (the
        caller then hashes the bytes itself). Framed bytes are byte-identical
        to :meth:`compress_framed`'s."""
        return self._compress_framed_impl(buf, n_blocks, block_size, True)

    def wants_async_encode(self) -> bool:
        """True when CodecOutputStream should run this codec's batch encode
        on the shared encode thread (bounded by ``encode_inflight_batches``).
        Async pays only when THIS codec runs the TLZ encoder itself (device
        kernels, or the host C encoder standing in for them): when encode is
        rerouted to the SLZ delegate (``host_encode_fallback`` with no chip)
        the stream stays synchronous — today's fallback behavior,
        unchanged."""
        if self.encode_inflight_batches <= 1:
            return False
        return self._encode_delegate() is None

    # --- batch (device, with a vectorized-numpy host fallback) ---
    def compress_blocks(self, blocks: List[bytes]) -> List[bytes]:
        delegate = self._encode_delegate()
        if delegate is not None:
            return delegate.compress_blocks(blocks)
        full = [b for b in blocks if len(b) == self.block_size]
        if not full or not self._select_device("encode"):
            return [self._compress_block_local(b) for b in blocks]
        return tlz.encode_blocks_device(blocks, self.block_size)

    def frame_blocks(self, blocks: List[bytes]) -> bytes:
        """Batch framing with ONE routing decision for the whole batch: the
        delegate is snapshotted here and used for both compression and
        framing."""
        delegate = self._encode_delegate()
        if delegate is not None:
            return delegate.frame_blocks(blocks)
        full = [b for b in blocks if len(b) == self.block_size]
        if full and self._select_device("encode"):
            payloads = tlz.encode_blocks_device(blocks, self.block_size)
        else:
            payloads = [self._compress_block_local(b) for b in blocks]
        # frame via the BASE rule with this codec's id
        return b"".join(
            FrameCodec.frame_from(self, raw, comp)
            for raw, comp in zip(blocks, payloads)
        )

    def decompress_blocks(self, blocks) -> List[bytes]:
        if not self._select_device("decode"):
            return [self.decompress_block(b, n) for b, n in blocks]
        return self._decode_full_blocks(blocks, None)[0]

    # --- read side: fused stored-byte CRC certification ---
    def wants_fused_decode_validation(self, poly: int) -> bool:
        """True when this codec's decode launches can hand back each frame's
        stored-byte CRC fused with the decoded planes — the read plane's
        checksum layer then defers its host hashing pass to those
        certificates. Only meaningful on the device path (host reads keep
        streaming validation: the native CRC is already cheap there), and
        only when the measured-rate table says the FUSED launch beats the
        effective rate of streaming (unfused device decode + host CRC) —
        the last probe clocked fused at 51 MB/s vs ~600 MB/s effective
        streaming, a collapse the old availability gate shipped."""
        if poly not in (POLY_CRC32, POLY_CRC32C):
            return False
        return self._select_device("decode") and rates.select_fused_decode(
            forced=self._forced_verdict()
        )

    def _decode_full_blocks(self, blocks, poly):
        """Device batch decode with fused payload CRCs when ``poly`` is set.
        A device failure mid-scan host-decodes THIS batch — no frame is ever lost — and after three
        consecutive failures pins the instance to the host decoder. A batch
        the HOST decoder also rejects is corruption, not device loss: the
        host path's precise error propagates and the failure counter is
        untouched (corrupt frames must not pin a healthy chip off)."""
        payloads = [b for b, _n in blocks]
        ulens = [n for _b, n in blocks]
        try:
            out, crcs = tlz.decode_batch_device(
                payloads, ulens, self.block_size,
                batch_rows=self.batch_blocks, poly=poly,
            )
            self._decode_failures = 0
            self._device_ok()
            rates.count_device_batch("decode", True)
            return out, crcs
        except Exception as device_err:
            try:
                host = [self.decompress_block(p, u) for p, u in blocks]
            except Exception:
                raise  # precise host classification (corruption) wins
            del device_err
            rates.count_device_batch("decode", False)
            self._decode_failures += 1
            if self._decode_failures >= 3 or self._reprobing:
                n = self._decode_failures
                trial = self._reprobing
                self._pin_host()
                rates.count_host_pin("decode")
                logger.warning(
                    "device batch decode failed %s — pinning this codec to "
                    "the host TLZ decoder%s",
                    "on its re-probe trial" if trial
                    else f"{n} times in a row",
                    "" if self._repin_probe_s <= 0
                    else f" (re-probe in {self._repin_probe_s:g}s)",
                    exc_info=True,
                )
            else:
                logger.warning(
                    "device batch decode failed — host-decoding this batch "
                    "(no frame lost)", exc_info=True,
                )
            return host, ([None] * len(blocks) if poly is not None else None)

    def decompress_blocks_fused(self, blocks, poly: int):
        """:meth:`decompress_blocks_concat` + per-frame PAYLOAD stored-byte
        CRCs from the SAME decode launch. Returns ``(concat_bytes, crcs)``
        where ``crcs[i]`` is the full-algorithm CRC of ``blocks[i]``'s
        payload bytes — or None per frame the launch didn't cover (host
        fallback, short/legacy frames); the caller certifies those from the
        bytes it holds. Decoded output is byte-identical to the unfused
        path's."""
        if not self._select_device("decode"):
            out = [self.decompress_block(b, n) for b, n in blocks]
            for (_, ulen), o in zip(blocks, out):
                if len(o) != ulen:
                    raise IOError(f"Decompressed length {len(o)} != header {ulen}")
            return b"".join(out), None
        out, crcs = self._decode_full_blocks(blocks, poly)
        for (_, ulen), o in zip(blocks, out):
            if len(o) != ulen:
                raise IOError(f"Decompressed length {len(o)} != header {ulen}")
        return b"".join(out), crcs


class FusedChecksumAccumulator:
    """Streaming checksum of *stored* frame bytes where payload CRCs come from
    the device in batch and only the 9-byte headers touch the host CPU.

    Usage per partition: ``add_frame(header, payload_crc, payload_len)`` per
    emitted frame (payload CRC from the fused device pass), then ``value``.
    Equals a byte-serial CRC over the concatenated stored bytes exactly.
    """

    def __init__(self, poly: int = POLY_CRC32C):
        self.poly = poly
        self._crc = 0
        self._empty = True

    def add_bytes(self, data: bytes) -> None:
        if self.poly == POLY_CRC32C:
            # native C when built — this path hashes whole frame batches
            # whenever the device didn't hand back fused CRCs (host/delegate
            # routes), so the Python table fallback must be a last resort
            part = _crc32c_host(data)
        else:
            import zlib

            part = zlib.crc32(data) & 0xFFFFFFFF
        self._crc = crc_combine(self._crc, part, len(data), self.poly)

    def add_frame(self, header: bytes, payload_crc: int, payload_len: int) -> None:
        self.add_bytes(header)
        self._crc = crc_combine(self._crc, payload_crc, payload_len, self.poly)

    def add_stored(self, crc: int, length: int) -> None:
        """Append ``length`` stored bytes whose full-algorithm CRC is
        ``crc`` — the form the fused encode launch hands back per frame
        (``compress_framed_fused``)."""
        self._crc = crc_combine(self._crc, crc, length, self.poly)

    @property
    def value(self) -> int:
        return self._crc


def fused_compress_and_checksum(
    codec: TpuCodec, blocks: List[bytes], poly: int = POLY_CRC32C
):
    """One batch through the device: compress every block AND produce each
    resulting frame's stored bytes + per-frame stored-byte CRC. On the
    device path the CRC is FUSED into the encode kernel itself — one launch
    returns payload planes and CRC values together (ops/tlz.py), with no
    second staging pass over the compressed bytes. Off-device (or for
    non-CRC32C polys / short blocks) the pre-fusion route runs: host frames
    plus one staged device CRC batch.

    Returns (frames: List[bytes], frame_crcs: List[int]) where
    ``crc(b"".join(frames))`` == stitching frame CRCs via
    :func:`crc_combine` — validated in tests.
    """
    if (
        poly == POLY_CRC32C
        and blocks
        and all(len(b) == codec.block_size for b in blocks)
        and codec._encode_delegate() is None
        and codec._select_device("encode")
    ):
        blob = b"".join(blocks)
        framed, crcs = codec.compress_framed_fused(
            blob, len(blocks), codec.block_size
        )
        if crcs is not None:
            frames = []
            off = 0
            for _crc, length in crcs:
                frames.append(framed[off : off + length])
                off += length
            return frames, [c for c, _len in crcs]
        # device flipped off mid-call — fall through to the staged route
    payloads = codec.compress_blocks(blocks)
    frames = [codec.frame_from(raw, comp) for raw, comp in zip(blocks, payloads)]
    batch, lengths = stage_right_aligned(frames)
    crcs = crc32_batch(batch, lengths, poly=poly) if frames else np.array([], np.uint32)
    return frames, [int(c) for c in crcs]
