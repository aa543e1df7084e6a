"""TLZ — a TPU-native block-parallel compression format (v2).

The reference compresses shuffle bytes with JVM LZ4/Snappy streams (Spark's
``spark.io.compression.*``; SURVEY.md §0). Byte-serial LZ parsing is hostile
to TPUs (data-dependent control flow, scalar loops), so TLZ is designed from
the hardware up instead of translating LZ4:

- a block is split into fixed **8-byte groups**; every group is a literal, a
  *match* — a copy of 8 bytes from ``group_start - distance`` for a u16
  DISTANCE (the same 64 KiB reach-back window as LZ4; block size is
  independent of it, up to 256 KiB) — or a *split literal* (below);
- a match whose distance equals the previous group's (what any repeated
  region longer than one group produces — the source advances in lockstep)
  is flagged in the cont bitmap and stores **no distance at all**, so long
  runs cost ~2 bits per 8 bytes;
- a group straddling two repeated regions fails as a whole match but its
  prefix matches at the LEFT neighbor's distance and its suffix at the
  RIGHT neighbor's: the split bitmap flags it and only the split point k
  (u8) is stored — both distances are reconstructed from the neighbors at
  decode, so a boundary group costs ~1 byte instead of 8;
- encoding hashes the 8-byte window at *every* byte position (8 shifted
  multiply-adds — pure VPU work), then finds each group's nearest previous
  identical window with one stable ``argsort`` per block: equal hashes land
  adjacent in sort order, so "nearest previous occurrence" is a shifted
  compare — no hash-table scatter, no sequential scan. Candidates are
  verified by exact compare, so hash collisions cost missed matches, never
  wrong output. A vectorized continuation-promotion pass then retries each
  group at the previous group's distance, aligning chains so the cont
  bitmap can elide them. The device compares every window (verification,
  promotion, the split tier's byte runs) as a pair of packed 32-bit words,
  so each test gathers two elements per group, not eight bytes;
- sources may overlap their destination (distance < 8), so runs of ANY
  period — classic LZ77 RLE — fall out free;
- decoding reconstructs elided distances with a rank gather (constant along
  a run), builds a per-byte source map (literal bytes are fixed points;
  match bytes point at ``pos - distance``; split-group bytes at
  ``pos - d_left`` before k and ``pos - d_right`` after) and resolves
  chains with **pointer jumping**: doubling rounds of one parallel gather
  each until converged, at most log2(block); the device carries each
  position's byte in the same gather, the host gathers once more from the
  literal plane. No sequential back-reference chasing — equally fast on
  TPU and in vectorized numpy on the host.

Wire format of one TLZ frame payload (fits the shared 9-byte frame header,
codec_id = ``tpu-lz``):

    [u16le flags+count] — bit 15 ⇒ v2; bit 14 ⇒ packed meta; low 14 bits =
                          n_groups mod 16384 (consistency only — the true
                          count derives from the frame's uncompressed len)
    [match bitmap ceil(n_groups/8) bytes — bit i ⇒ group i is a match]
    [cont  bitmap ceil(n_groups/8) bytes — bit i ⇒ dist[i] == dist[i-1]]
    [split bitmap ceil(n_groups/8) bytes — bit i ⇒ split literal]
    [u16le distance × n_new_matches — matches with cont bit 0, in order]
    [u8 split point k × n_splits — in order, 1..7]
    [literal groups × 8 bytes (last one zero-padded to 8)]

With bit 14 set, the five metadata planes (three bitmaps + distances +
split points) are stored as ``[u32le clen][zlib deflate of them]`` instead —
they are highly structured (long match runs ⇒ long bit runs, clustered
distances) and otherwise impose a ~3% floor on every block's size. Packing
is applied only when it shrinks. The metadata is parsed on the HOST in both
the numpy and device decode paths (the device kernel consumes unpacked
bitmaps either way), so the byte-plane decode remains pure parallel gathers.

Compatibility: v1 payloads (bit 15 clear; 16-byte groups, literal-group-
index sources, no cont/split bitmaps) remain decodable on the host path.
The v2 layout above is the FINAL v2 — in-development snapshots of v2 from
round 2 (absolute offsets, no split plane) are not readable, which is fine
because shuffle payloads are ephemeral job traffic, never an archival
format. Encoders always emit v2.

Ratio characteristics: catches aligned and unaligned repeats and runs of
any period; misses approximate redundancy (entropy coding beyond the packed
metadata is out of scope — the framing's raw escape bounds the worst case).
Measured on the terasort shuffle payload: 7.26x at 256 KiB blocks vs real
LZ4's 4.96x. Encoding cost is O(N log N) sort + O(N) VPU work per block
over N byte positions, fully batched over B blocks; the sequential C
encoder (native/src: tlz_encode_block) emits the same planes for CPU
writers at ~150 MB/s/core.
"""

from __future__ import annotations

import functools
import logging
import threading
import warnings
from typing import List, Optional, Tuple

import numpy as np

logger = logging.getLogger("s3shuffle_tpu.ops.tlz")

GROUP = 8
#: v1 used 16-byte groups; kept for decoding legacy payloads.
_V1_GROUP = 16
#: bit 15 of the leading u16 marks the v2 format.
V2_FLAG = 0x8000
#: bit 14 (v2 only) marks zlib-packed metadata planes.
PACKED_FLAG = 0x4000
#: u16 match DISTANCES bound the window a source can reach back (the same
#: 64 KiB window as LZ4); block size is independent of it.
MAX_DIST = (1 << 16) - 1
#: block-size cap: pointer-jump rounds, sort length, and decode map memory
#: scale with it. 256 KiB amortizes per-block first-occurrence literals 4x
#: vs 64 KiB at modest extra sort cost.
MAX_BLOCK = 1 << 18

#: deflate level for the packed metadata section. The knob trades write-side
#: HOST CPU (the offload pipeline's only non-trivial host work) for ratio —
#: measured on the terasort payload at 256 KiB blocks (framed, device-
#: algorithm encoder):
#:   level 6: assembly 476 MB/s/core,  ratio 7.28x
#:   level 1: assembly 1127 MB/s/core, ratio 7.06x   (default)
#:   level 0: plain meta, memcpy-bound assembly, ratio 5.54x
#: every level stays above real LZ4's 4.96x on the same payload.
META_PACK_LEVEL = 1


def _pack_meta(
    bitmap_b: bytes, cont_b: bytes, split_b: bytes, offs_b: bytes,
    ks_b: bytes, n_groups: int, level: int | None = None,
):
    """Assemble the header + metadata section (match/cont/split bitmaps,
    match distances, split points), deflating it when that shrinks (and
    ``level`` > 0). Returns the payload prefix (everything before the
    literal plane)."""
    import zlib

    if level is None:
        level = META_PACK_LEVEL
    meta = bitmap_b + cont_b + split_b + offs_b + ks_b
    ng_field = n_groups & 0x3FFF  # low 14 bits: consistency check only —
    # the true count derives from the frame's uncompressed length
    if level == 0:
        # exactly 0 ⇒ plain metadata; negative values (zlib's own
        # Z_DEFAULT_COMPRESSION sentinel) pass through to zlib below
        return np.array([ng_field | V2_FLAG], dtype="<u2").tobytes() + meta
    packed = zlib.compress(meta, level)
    if len(packed) + 4 < len(meta):
        return (
            np.array([ng_field | V2_FLAG | PACKED_FLAG], dtype="<u2").tobytes()
            + np.array([len(packed)], dtype="<u4").tobytes()
            + packed
        )
    return np.array([ng_field | V2_FLAG], dtype="<u2").tobytes() + meta


def _jax():
    import jax
    import jax.numpy as jnp

    return jax, jnp


# INDEPENDENT odd multipliers (xxhash/murmur-family constants). They must
# not be small multiples of one constant: with m_k = (2k+1)*C (the original
# choice) a collision needs only Σ Δb_k·(2k+1) == 0 — a small-coefficient
# relation that structured data satisfies constantly, and every collision
# shadows the true nearest match (candidates are verified by exact compare,
# so collisions cost missed matches, never wrong output — but on the
# terasort payload they cost ~10% of all matches and a third of the ratio).
_MULTS_I64 = np.array(
    [0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F,
     0x165667B1, 0xD3A2646D, 0xFD7046C5, 0xB55A4F09],
    dtype=np.int64,
)
_MULTS_I32 = _MULTS_I64.astype(np.uint32).astype(np.int32)  # wraparound view


def _jump_rounds(n_bytes: int) -> int:
    return int(np.ceil(np.log2(max(2, n_bytes))))


#: per-byte popcount — plane boundaries from packed bitmaps without
#: unpacking them to bools (32x less data touched)
_POP = np.array([bin(i).count("1") for i in range(256)], dtype=np.int64)


# ---------------------------------------------------------------------------
# Device encoder (batched)
# ---------------------------------------------------------------------------


def _candidate_math(blocks_u8, n_groups: int):
    """Hash + nearest-previous-identical-window candidate search — the
    front half of the encoder. Returns (B, G) int32 candidate source
    POSITIONS (-1 = none)."""
    jax, jnp = _jax()

    mults = jnp.asarray(_MULTS_I32)
    b = blocks_u8.shape[0]
    n_bytes = n_groups * GROUP
    n_pos = n_bytes - GROUP + 1  # every valid window start
    buf = blocks_u8.astype(jnp.int32)  # (B, n_bytes)
    rows = jnp.arange(b)[:, None]

    # hash of the window at every byte position: GROUP shifted MACs
    h = jnp.zeros((b, n_pos), dtype=jnp.int32)
    for k in range(GROUP):
        h = h + buf[:, k : k + n_pos] * mults[k]

    # nearest previous identical window via sort: stable-sort (h, pos);
    # an equal-hash neighbor to the left has the largest smaller position.
    order = jnp.argsort(h, axis=1, stable=True)  # (B, n_pos)
    h_sorted = jnp.take_along_axis(h, order, axis=1)
    prev_same = jnp.concatenate(
        [jnp.full((b, 1), False), h_sorted[:, 1:] == h_sorted[:, :-1]], axis=1
    )
    prev_pos = jnp.concatenate(
        [jnp.zeros((b, 1), dtype=order.dtype), order[:, :-1]], axis=1
    )
    cand_sorted = jnp.where(prev_same, prev_pos, -1)
    cand = jnp.zeros_like(cand_sorted).at[rows, order].set(cand_sorted)
    dest = jnp.arange(n_groups, dtype=jnp.int32) * GROUP
    return jnp.take(cand, dest, axis=1).astype(jnp.int32)  # (B, G)


def _plane_decisions_math(blocks_u8, cand_d, n_groups: int):
    """Match/continuation/split decisions from the candidate positions — the
    gather-heavy middle of the encoder. Returns FULL (uncompacted) planes:
    (is_match, is_cont, is_split, dists, ks).

    Every compare reads packed words, not bytes: ``words[:, GROUP + p]``
    holds bytes p..p+3 of the row (little-endian), so the 8-byte window at
    p is the pair at p and p + 4. Each window test gathers one pair per
    group — two elements where a per-byte gather fetched eight."""
    jax, jnp = _jax()
    b = blocks_u8.shape[0]
    n_bytes = n_groups * GROUP
    half = GROUP // 2
    # GROUP zero bytes in front: a suffix window may start up to GROUP bytes
    # before byte 0 (its bytes there are masked below), and no window starts
    # after byte n_bytes - GROUP, so no index needs a clip
    pad = GROUP
    padded = jnp.pad(blocks_u8.astype(jnp.uint32), ((0, 0), (pad, 0)))
    n_words = pad + n_bytes - half + 1
    words = padded[:, :n_words]
    for k in range(1, half):
        words = words | (padded[:, k : k + n_words] << (8 * k))
    # each group's own pair: a strided slice, no gather
    own_lo, own_hi = (
        jax.lax.slice(words, (0, pad + o), (b, n_words), (1, GROUP))
        for o in (0, half)
    )
    dest = jnp.arange(n_groups, dtype=jnp.int32) * GROUP

    def diff_at(start):
        # XOR of the window at each (B, G) start with the group's own: byte
        # k of the pair is zero iff window byte k equals group byte k
        idx = start + pad
        lo = jnp.take_along_axis(words, idx, axis=1)
        hi = jnp.take_along_axis(words, idx + half, axis=1)
        return lo ^ own_lo, hi ^ own_hi

    def same_at(start):
        lo, hi = diff_at(start)
        return (lo == 0) & (hi == 0)

    def byte_eq(diff, k):
        word = diff[k // half]  # unsigned: the shift brings in no sign bits
        return ((word >> (8 * (k % half))) & 0xFF) == 0

    # verify exact equality (hash collisions ⇒ missed match, never wrong);
    # matches are stored as DISTANCES (dest - src, 1..MAX_DIST) — constant
    # along a continued run and capped at the same 64 KiB window as LZ4,
    # which decouples block size from the u16 wire width
    safe = jnp.maximum(cand_d, 0)
    cand_dist = dest[None, :] - cand_d
    is_match = same_at(safe) & (cand_d >= 0) & (cand_dist <= MAX_DIST)
    dists = jnp.where(is_match, cand_dist, 0)

    # continuation promotion: retry each group at the previous group's
    # distance (same distance ⇒ source advanced by GROUP). This (a) aligns
    # equal-content candidates onto one chain so the cont bitmap can elide
    # their offsets, and (b) can add matches the hash search missed. Two
    # passes extend promotion chains far enough in practice; correctness
    # never depends on it.
    for _ in range(2):
        prev_dist = jnp.concatenate(
            [jnp.zeros((b, 1), jnp.int32), dists[:, :-1]], axis=1
        )
        prev_match = jnp.concatenate(
            [jnp.zeros((b, 1), bool), is_match[:, :-1]], axis=1
        )
        # source = dest - prev_dist >= 0 holds: prev_dist <= 8(g-1) < 8g
        c_ok = prev_match & (prev_dist > 0) & same_at(dest[None, :] - prev_dist)
        dists = jnp.where(c_ok, prev_dist, dists)
        is_match = is_match | c_ok

    prev_dist = jnp.concatenate(
        [jnp.zeros((b, 1), jnp.int32), dists[:, :-1]], axis=1
    )
    prev_match = jnp.concatenate([jnp.zeros((b, 1), bool), is_match[:, :-1]], axis=1)
    is_cont = is_match & prev_match & (dists == prev_dist)

    # split-literal tier: a group straddling two repeated regions fails as a
    # whole (its halves match at DIFFERENT distances — the previous group's
    # and the next group's). Store only the split point k: prefix bytes
    # [0,k) copy at the left neighbor's distance, suffix bytes [k,8) at the
    # right neighbor's — both distances are reconstructed from the
    # neighbors at decode, so a boundary group costs ~1 byte instead of 8.
    next_dist = jnp.concatenate(
        [dists[:, 1:], jnp.zeros((b, 1), jnp.int32)], axis=1
    )
    next_match = jnp.concatenate(
        [is_match[:, 1:], jnp.zeros((b, 1), bool)], axis=1
    )
    pre_diff = diff_at(dest[None, :] - prev_dist)  # starts ≥ 8 when prev matches
    suf_start = dest[None, :] - next_dist  # may be < 0 near the front
    suf_diff = diff_at(suf_start)
    # longest all-equal prefix, and first byte of the longest all-equal
    # suffix whose sources lie in the block: cumprod/sum, one byte at a time
    run = jnp.ones((b, n_groups), bool)
    prefix_run = jnp.zeros((b, n_groups), jnp.int32)
    for k in range(GROUP):
        run = run & byte_eq(pre_diff, k)
        prefix_run = prefix_run + run
    run = jnp.ones((b, n_groups), bool)
    ks = jnp.full((b, n_groups), GROUP, jnp.int32)
    for k in reversed(range(GROUP)):
        run = run & byte_eq(suf_diff, k) & (suf_start + k >= 0)
        ks = ks - run
    is_split = (
        ~is_match
        & prev_match
        & next_match
        & (prev_dist > 0)
        & (next_dist > 0)
        & (ks >= 1)
        & (ks <= GROUP - 1)
        & (ks <= prefix_run)
    )
    return is_match, is_cont, is_split, dists, ks


def _compact_pack_math(blocks_u8, is_match, is_cont, is_split, dists, ks,
                       n_groups: int):
    """Rank/scatter compaction + bitmap packing of the full decision planes
    into the 9-tuple wire layout — the back half of the encoder, always XLA
    (masked scatters have no Mosaic lowering)."""
    jax, jnp = _jax()
    b = blocks_u8.shape[0]
    rows = jnp.arange(b)[:, None]
    groups = blocks_u8.astype(jnp.int32).reshape(b, n_groups, GROUP)
    is_lit = ~is_match & ~is_split

    is_new = is_match & ~is_cont
    n_match = jnp.sum(is_match, axis=1, dtype=jnp.int32)
    n_new = jnp.sum(is_new, axis=1, dtype=jnp.int32)
    n_split = jnp.sum(is_split, axis=1, dtype=jnp.int32)

    # compact stored distances, split points, and literal groups via rank +
    # scatter. Group 0 can never match or split (no previous position), so
    # slot n_groups-1 is always free to absorb the masked writes.
    new_rank = jnp.cumsum(is_new, axis=1) - 1
    split_rank = jnp.cumsum(is_split, axis=1) - 1
    lit_rank = jnp.cumsum(is_lit, axis=1) - 1
    offs_compact = jnp.zeros((b, n_groups), dtype=jnp.int32)
    offs_compact = offs_compact.at[
        rows, jnp.where(is_new, new_rank, n_groups - 1)
    ].set(jnp.where(is_new, dists, 0), mode="drop")
    ks_compact = jnp.zeros((b, n_groups), dtype=jnp.int32)
    ks_compact = ks_compact.at[
        rows, jnp.where(is_split, split_rank, n_groups - 1)
    ].set(jnp.where(is_split, ks, 0), mode="drop")
    lits_compact = jnp.zeros((b, n_groups, GROUP), dtype=jnp.uint8)
    lits_compact = lits_compact.at[
        rows, jnp.where(is_lit, lit_rank, n_groups - 1)
    ].set(
        jnp.where(is_lit[:, :, None], groups, 0).astype(jnp.uint8), mode="drop"
    )

    # bitmaps packed to uint8 (little-endian bit order within the byte)
    bit_weights = jnp.asarray([1, 2, 4, 8, 16, 32, 64, 128], dtype=jnp.int32)

    def pack(bits):
        return jnp.sum(
            bits.reshape(b, n_groups // 8, 8).astype(jnp.int32)
            * bit_weights[None, None, :],
            axis=2,
            dtype=jnp.int32,
        ).astype(jnp.uint8)

    return (
        pack(is_match),
        pack(is_cont),
        pack(is_split),
        offs_compact.astype(jnp.uint16),
        ks_compact.astype(jnp.uint8),
        lits_compact,
        n_new,
        n_split,
        n_match,
    )


def _encode_math(blocks_u8, n_groups: int):
    """The raw (unjitted) encode computation — shared by the standalone
    jitted kernel and larger fused traces (see __graft_entry__). Composition
    of the three encoder stages (candidate search → plane decisions →
    compaction). Returns
    (match_bitmap, cont_bitmap, split_bitmap, dists_compact, ks_compact,
    lits_compact, n_new, n_split, n_match) where ``dists_compact[:, :n_new]``
    are the stored (non-continuation) match distances,
    ``ks_compact[:, :n_split]`` the split points, and
    ``lits_compact[:, :n_groups - n_match - n_split]`` the literal groups."""
    cand_d = _candidate_math(blocks_u8, n_groups)
    planes = _plane_decisions_math(blocks_u8, cand_d, n_groups)
    return _compact_pack_math(blocks_u8, *planes, n_groups)


@functools.lru_cache(maxsize=8)
def _encode_kernel(n_groups: int):
    jax, _jnp = _jax()
    return jax.jit(functools.partial(_encode_math, n_groups=n_groups))


def _encode_fused_math(blocks_u8, n_groups: int, crc_fn):
    """Encode + fused CRC in ONE trace: the planes of :func:`_encode_math`
    plus, from the same launch, raw zero-init CRC remainders of (a) each raw
    input block (the framing raw-escape branch checksums stored RAW bytes)
    and (b) each block's literal plane (the dominant slice of a TLZ payload
    — the host stitches the small header/metadata CRCs around it with
    :func:`ops.checksum.crc_combine`). Both remainder batches ride one
    (2B, L) CRC pass, so the separate checksum launch — and its second H2D
    staging of every compressed byte — disappears. ``crc_fn(rows, n_valid)``
    is the hook of :func:`ops.checksum.raw_crc_graph_fn`: the literal plane
    is checksummed where it lies, masked past its literals, and each row's
    remainder unshifted in-graph (a raw block's pad is 0)."""
    _jax_mod, jnp = _jax()
    outs = _encode_math(blocks_u8, n_groups)
    lits, n_split, n_match = outs[5], outs[7], outs[8]
    b = blocks_u8.shape[0]
    lit_bytes = (n_groups - n_match - n_split) * GROUP  # (B,)
    raw = crc_fn(
        jnp.concatenate([blocks_u8, _literal_plane(lits, lit_bytes)], axis=0),
        jnp.concatenate([jnp.full((b,), n_groups * GROUP, jnp.int32), lit_bytes]),
    )
    return outs + (raw[:b], raw[b:])


def _literal_plane(lits, lit_bytes):
    """``(B, n_groups, GROUP)`` literal groups in literal order as front-
    aligned ``(B, L)`` rows, zero from byte ``lit_bytes[b]`` on: an
    elementwise select, so a row's CRC never reads what lies past its
    literals."""
    _jax_mod, jnp = _jax()
    b = lits.shape[0]
    flat = lits.reshape(b, -1)
    pos = jnp.arange(flat.shape[1], dtype=jnp.int32)
    return jnp.where(pos[None, :] < lit_bytes[:, None], flat, 0).astype(jnp.uint8)


@functools.lru_cache(maxsize=16)
def _batch_kernel(batch_rows: int, n_groups: int, poly: Optional[int]):
    """Precompiled fixed-shape batched encode kernel — one trace per
    (batch rows, block shape, fused poly), never per call: a varying
    batch dim retraces per distinct size under jit (XLA compiles per shape),
    which taxed every tail batch on the old path. The staged batch is
    DONATED so XLA may reuse its device buffer for outputs. ``poly`` selects
    the fused CRC variant (None = encode planes only)."""
    jax, _jnp = _jax()
    from s3shuffle_tpu.ops import rates

    if poly is None:
        return rates.timed_first_call("tlz_encode", jax.jit(
            functools.partial(_encode_math, n_groups=n_groups),
            donate_argnums=(0,),
        ))
    from s3shuffle_tpu.ops.checksum import raw_crc_graph_fn

    crc_fn, tables = raw_crc_graph_fn(poly, n_groups * GROUP)
    kernel = jax.jit(
        lambda blocks, *tabs: _encode_fused_math(
            blocks, n_groups, lambda d, n: crc_fn(d, n, *tabs)
        ),
        donate_argnums=(0,),
    )
    return rates.timed_first_call(
        "tlz_encode_fused",
        lambda blocks: kernel(blocks, *tables(_device_of(blocks))),
    )


def _device_of(arr):
    """The one device a staged launch input lives on: the fused kernels'
    CRC tables must sit on the same chip, or every launch copies them."""
    (device,) = arr.devices()
    return device


def _bucket_rows(n: int, cap: int) -> int:
    """Launch-shape bucketing: a partial batch pads up to the next power of
    two (capped at the configured batch rows), so the compiled-shape count is
    log2(batch_blocks) — not one trace per distinct tail length."""
    if n >= cap:
        return cap
    rows = 1
    while rows < n:
        rows <<= 1
    return min(rows, cap)


class _EncodeStaging(threading.local):
    """Reusable per-thread host staging buffers, one per launch shape: the
    encode path stages every padded partial batch here instead of allocating
    a fresh (B, L) array per call. The async pipeline funnels every batch
    through ONE encode thread (codec/framing.py), so reuse hits every
    launch; zero-copy full batches bypass staging entirely."""

    def __init__(self) -> None:
        self.buffers: dict = {}

    def get(self, rows: int, block_size: int, slot: int = 0) -> np.ndarray:
        """``slot`` keys one buffer per in-flight dispatch lane: with the
        mesh dispatcher armed, up to n_devices launches of the same shape
        are outstanding at once, and each needs its own staging buffer
        (single-device callers always pass slot 0 — one buffer per shape,
        exactly the old behavior)."""
        buf = self.buffers.get((rows, block_size, slot))
        if buf is None:
            buf = np.zeros((rows, block_size), dtype=np.uint8)
            self.buffers[(rows, block_size, slot)] = buf
        return buf


_staging = _EncodeStaging()


def _mesh_dispatcher():
    """The armed multi-chip dispatcher (parallel/dispatch.py), or None for
    the single-device op pattern. Lazy import: the parallel package loads
    only when a device batch actually runs."""
    from s3shuffle_tpu.parallel import dispatch

    return dispatch.get_dispatcher()


def _assemble_from_device(bitmap, cont, split, offs, ks, lits, n_new, n_split,
                          n_match, i: int, n_groups: int) -> bytes:
    """Payload assembly for ONE row of a device encode batch — kept as the
    differential oracle for :func:`_assemble_batch` (the vectorized path must
    emit byte-identical payloads; regression-tested)."""
    nn, ns, nm = int(n_new[i]), int(n_split[i]), int(n_match[i])
    return _pack_meta(
        bitmap[i].tobytes(),
        cont[i].tobytes(),
        split[i].tobytes(),
        offs[i, :nn].astype("<u2").tobytes(),
        ks[i, :ns].tobytes(),
        n_groups,
    ) + lits[i, : n_groups - nm - ns].tobytes()


def _assemble_batch(arrs, n_blocks: int, n_groups: int) -> List[bytes]:
    """Whole-batch payload assembly — the host half of a device encode
    launch, reworked from the per-block path on two measured axes:

    - the bitmap planes convert to bytes ONCE for the batch (three small
      per-block ``tobytes`` calls each become a slice of one buffer);
    - the literal plane — the BULK of every payload — is copied exactly
      once: ``b"".join`` over a zero-copy row view builds each payload in a
      single pass, where ``prefix + lits[i].tobytes()`` copied every literal
      byte twice (once into the temp bytes, once into the concat).

    Byte-identical to mapping :func:`_assemble_from_device` over rows
    (regression-tested)."""
    bitmap, cont, split, offs, ks, lits, n_new, n_split, n_match = arrs
    b = n_blocks
    bm_len = bitmap.shape[1]
    bitmap_b = np.ascontiguousarray(bitmap[:b]).tobytes()
    cont_b = np.ascontiguousarray(cont[:b]).tobytes()
    split_b = np.ascontiguousarray(split[:b]).tobytes()
    offs_c = np.ascontiguousarray(offs[:b])
    ks_c = np.ascontiguousarray(ks[:b])
    row_bytes = n_groups * GROUP
    lits_mv = memoryview(
        np.ascontiguousarray(lits[:b]).reshape(b * row_bytes)
    )
    out: List[bytes] = []
    for i in range(b):
        nn, ns = int(n_new[i]), int(n_split[i])
        n_lits = n_groups - int(n_match[i]) - ns
        out.append(
            b"".join((
                _pack_meta(
                    bitmap_b[i * bm_len : (i + 1) * bm_len],
                    cont_b[i * bm_len : (i + 1) * bm_len],
                    split_b[i * bm_len : (i + 1) * bm_len],
                    offs_c[i, :nn].astype("<u2").tobytes(),
                    ks_c[i, :ns].tobytes(),
                    n_groups,
                ),
                lits_mv[i * row_bytes : i * row_bytes + n_lits * GROUP],
            ))
        )
    return out


def _check_block_size(block_size: int) -> None:
    if block_size % (8 * GROUP) != 0:
        raise ValueError("block_size must be a multiple of 64")
    if block_size > MAX_BLOCK:
        raise ValueError("block_size must be <= 256 KiB")


def encode_batch_device(
    buf,
    n_blocks: int,
    block_size: int,
    batch_blocks: Optional[int] = None,
    poly: Optional[int] = None,
):
    """Encode ``n_blocks`` FULL blocks held contiguously in ``buf`` on the
    device with FIXED-shape precompiled launches of ``batch_blocks`` rows
    (partial batches pad to a power-of-two bucket in reusable staging
    buffers — no per-call retrace) and vectorized whole-batch payload
    assembly. Full batches stage zero-copy (``np.frombuffer`` straight into
    the H2D transfer).

    With ``poly`` set, each block's CRC comes back FUSED from the same
    launch: returns ``(payloads, (block_crcs, lit_crcs, lit_lens))`` where
    ``block_crcs[i]`` is the full-algorithm CRC of raw block i (for the
    framing raw-escape branch) and ``lit_crcs[i]``/``lit_lens[i]`` cover
    payload i's literal-plane bytes — callers stitch the small
    header/metadata CRCs around them with ``crc_combine``. Without ``poly``:
    ``(payloads, None)``. Each launch is timed by the ``codec.encode.stage``
    / ``codec.encode.device`` / ``codec.encode.assemble`` spans."""
    from s3shuffle_tpu.ops import rates
    from s3shuffle_tpu.utils import trace

    _check_block_size(block_size)
    n_groups = block_size // GROUP
    cap = max(1, batch_blocks or n_blocks)
    mv = memoryview(buf)
    if mv.ndim != 1 or mv.itemsize != 1:
        mv = mv.cast("B")
    jax = _jax()[0]
    payloads: List[bytes] = []
    crc_parts: Optional[list] = [] if poly is not None else None
    import time as _time

    # Multi-chip placement (parallel/dispatch.py): with the dispatcher armed
    # each batch launches on the least-loaded device and up to n_devices
    # launches stay in flight (per-lane staging buffers keep their host
    # sources alive); disarmed, the window is 0 and every batch launches,
    # drains, and assembles synchronously on the default device — the exact
    # single-device op pattern this function always had.
    disp = _mesh_dispatcher()
    window = disp.max_inflight() if disp is not None else 0
    pending: List[tuple] = []  # (launch outputs, real rows, lane, device)

    def _drain_oldest(backpressure: bool) -> None:
        outs, n_real, slot, dev = pending.pop(0)
        t0 = _time.perf_counter()
        try:
            with trace.span("codec.encode.device"):
                arrs = tuple(np.asarray(x) for x in outs)
        finally:
            rates.launch_drained(dev)
        if disp is not None:
            disp.release(slot)
            if backpressure:
                disp.observe_wait(_time.perf_counter() - t0)
        with trace.span("codec.encode.assemble"):
            payloads.extend(_assemble_batch(arrs[:9], n_real, n_groups))
        if crc_parts is not None:
            crc_parts.append(
                (arrs[9][:n_real], arrs[10][:n_real],
                 arrs[8][:n_real], arrs[7][:n_real])
            )

    try:
        for s in range(0, n_blocks, cap):
            e = min(n_blocks, s + cap)
            rows = _bucket_rows(e - s, cap)
            slot = disp.acquire() if disp is not None else 0
            while any(p[2] == slot for p in pending):
                # the lane's previous launch may still be reading its
                # device_put-aliased staging plane — drain until the lane is
                # free before restaging on it
                _drain_oldest(True)
            with trace.span("codec.encode.stage"):
                if rows == e - s:
                    staged = np.frombuffer(
                        mv[s * block_size : e * block_size], dtype=np.uint8
                    ).reshape(rows, block_size)
                else:
                    staged = _staging.get(rows, block_size, slot)
                    flat = staged.reshape(-1)
                    used = (e - s) * block_size
                    flat[:used] = np.frombuffer(
                        mv[s * block_size : e * block_size], dtype=np.uint8
                    )
                    flat[used:] = 0  # deterministic pad rows (outputs discarded)
                dev = disp.device(slot) if disp is not None else None
                with warnings.catch_warnings():
                    # the donated staging buffer may not be aliasable on every
                    # backend (XLA:CPU uint8 staging) — jax warns per
                    # compilation; an expected no-op for OUR launch, suppressed
                    # only around it so the host application's own donation
                    # warnings stay visible
                    warnings.filterwarnings(
                        "ignore", message="Some donated buffers were not usable"
                    )
                    outs = _batch_kernel(rows, n_groups, poly)(
                        jax.device_put(staged, dev)
                        if dev is not None
                        else jax.device_put(staged)
                    )
                rates.launch_dispatched("encode", dev, e - s, rows - (e - s))
            pending.append((outs, e - s, slot, dev))
            while len(pending) > window:
                _drain_oldest(True)
        while pending:
            _drain_oldest(False)
    except BaseException:
        for _outs, _n, slot, dev in pending:
            rates.launch_drained(dev)
            if disp is not None:
                disp.release(slot)
        raise
    if crc_parts is None:
        return payloads, None
    from s3shuffle_tpu.ops.checksum import zero_run_crcs

    zero = zero_run_crcs(poly, n_groups * GROUP)
    block_crcs = (
        np.concatenate([p[0] for p in crc_parts]).astype(np.uint32)
        ^ zero[n_groups * GROUP]
    )
    lit_lens = np.concatenate(
        [
            (n_groups - p[2].astype(np.int64) - p[3].astype(np.int64)) * GROUP
            for p in crc_parts
        ]
    )
    lit_crcs = (
        np.concatenate([p[1] for p in crc_parts]).astype(np.uint32)
        ^ zero[lit_lens]
    )
    return payloads, (block_crcs, lit_crcs, lit_lens)


def encode_buffer_device(buf, n_blocks: int, block_size: int) -> List[bytes]:
    """Encode ``n_blocks`` FULL blocks held contiguously in ``buf`` (bytes,
    bytearray, or memoryview) on the device. Staging is a zero-copy
    ``np.frombuffer`` view — the write plane accumulates blocks contiguously
    (framing.CodecOutputStream), so the host never copies raw bytes before
    the H2D transfer. Returns the TLZ payload per block."""
    return encode_batch_device(buf, n_blocks, block_size)[0]


def encode_blocks_device(blocks: List[bytes], block_size: int) -> List[bytes]:
    """Encode a batch of ≤block_size byte blocks on the device. Returns the
    TLZ payload per block (caller applies the framing raw-escape when a
    payload fails to shrink)."""
    _check_block_size(block_size)
    n_groups = block_size // GROUP
    b = len(blocks)
    staged = np.zeros((b, block_size), dtype=np.uint8)
    for i, blk in enumerate(blocks):
        arr = np.frombuffer(blk, dtype=np.uint8)
        staged[i, : len(arr)] = arr
    full_payloads, _crcs = encode_batch_device(staged, b, block_size)
    out: List[bytes] = []
    for i, blk in enumerate(blocks):
        used_groups = (len(blk) + GROUP - 1) // GROUP
        if used_groups < n_groups:
            # Short (final) block: encode host-side over just the used groups.
            out.append(_assemble_payload_numpy(blk))
        else:
            out.append(full_payloads[i])
    return out


# ---------------------------------------------------------------------------
# Host (numpy) encoder/decoder — used for short tail blocks, for CPU-side
# reads of tpu-lz frames, and as the differential-testing oracle.
# ---------------------------------------------------------------------------


def _group_view(data: bytes, group: int = GROUP) -> Tuple[np.ndarray, int]:
    n_groups = (len(data) + group - 1) // group
    padded = np.zeros(n_groups * group, dtype=np.uint8)
    padded[: len(data)] = np.frombuffer(data, dtype=np.uint8)
    return padded.reshape(n_groups, group), n_groups


def _encode_planes_numpy(data: bytes):
    """Host encode producing the DEVICE-SHAPED wire planes — byte-identical
    match decisions to the batched device kernel (sort-based nearest-previous
    with continuation promotion and the split-literal tier). Returns
    ``(bitmap_b, cont_b, split_b, offs_b, ks_b, lits_b, n_groups)`` — exactly
    the inputs :func:`_pack_meta` + literal-plane concatenation turn into a
    payload; the bench's host-work-only mode times that assembly on these
    outputs to isolate the host-CPU cost of a chip-active write
    (VERDICT r2 next-#2). Returns None for empty input."""
    groups, n_groups = _group_view(data)
    if n_groups == 0:
        return None
    flat = groups.reshape(-1)
    windows = np.lib.stride_tricks.sliding_window_view(flat, GROUP)  # view
    n_bytes = n_groups * GROUP
    n_pos = n_bytes - GROUP + 1
    flat64 = flat.astype(np.int64)
    h = np.zeros(n_pos, dtype=np.int64)
    for k in range(GROUP):
        h += flat64[k : k + n_pos] * _MULTS_I64[k]
    order = np.argsort(h, kind="stable")
    h_sorted = h[order]
    prev_same = np.concatenate([[False], h_sorted[1:] == h_sorted[:-1]])
    prev_pos = np.concatenate([[0], order[:-1]])
    cand_sorted = np.where(prev_same, prev_pos, -1)
    cand = np.zeros(n_pos, dtype=np.int64)
    cand[order] = cand_sorted
    dest = np.arange(n_groups) * GROUP
    cand_d = cand[dest]
    safe = np.maximum(cand_d, 0)
    cand_dist = dest - cand_d
    is_match = (
        (windows[safe] == groups).all(axis=1)
        & (cand_d >= 0)
        & (cand_dist <= MAX_DIST)
    )
    dists = np.where(is_match, cand_dist, 0)
    for _ in range(2):  # continuation promotion (see _encode_math)
        prev_dist = np.concatenate([[0], dists[:-1]])
        prev_match = np.concatenate([[False], is_match[:-1]])
        c_src = dest - prev_dist
        c_ok = (
            prev_match
            & (prev_dist > 0)
            & (windows[np.maximum(c_src, 0)] == groups).all(axis=1)
        )
        dists = np.where(c_ok, prev_dist, dists)
        is_match = is_match | c_ok
    prev_dist = np.concatenate([[0], dists[:-1]])
    prev_match = np.concatenate([[False], is_match[:-1]])
    is_cont = is_match & prev_match & (dists == prev_dist)
    is_new = is_match & ~is_cont
    # split-literal tier (see _encode_math): boundary groups store only the
    # split point; both copy distances come from the neighbors at decode
    next_dist = np.concatenate([dists[1:], [0]])
    next_match = np.concatenate([is_match[1:], [False]])
    byte_pos = dest[:, None] + np.arange(GROUP)
    flat_i = groups.reshape(-1).astype(np.int64)
    pre_src = byte_pos - prev_dist[:, None]
    suf_src = byte_pos - next_dist[:, None]
    n_bytes_total = n_groups * GROUP
    take = lambda idx: flat_i[np.clip(idx, 0, n_bytes_total - 1)]  # noqa: E731
    pre_eq = take(pre_src) == groups
    suf_eq = (take(suf_src) == groups) & (suf_src >= 0)
    prefix_run = np.cumprod(pre_eq, axis=1).sum(axis=1)
    ks = (GROUP - np.cumprod(suf_eq[:, ::-1], axis=1).sum(axis=1)).astype(np.int64)
    is_split = (
        ~is_match
        & prev_match
        & next_match
        & (prev_dist > 0)
        & (next_dist > 0)
        & (ks >= 1)
        & (ks <= GROUP - 1)
        & (ks <= prefix_run)
    )
    is_lit = ~is_match & ~is_split
    return (
        np.packbits(is_match.astype(np.uint8), bitorder="little").tobytes(),
        np.packbits(is_cont.astype(np.uint8), bitorder="little").tobytes(),
        np.packbits(is_split.astype(np.uint8), bitorder="little").tobytes(),
        dists[is_new].astype("<u2").tobytes(),
        ks[is_split].astype(np.uint8).tobytes(),
        groups[is_lit].tobytes(),
        n_groups,
    )


def _assemble_payload_numpy(data: bytes) -> bytes:
    planes = _encode_planes_numpy(data)
    if planes is None:
        return np.array([V2_FLAG], dtype="<u2").tobytes()
    bitmap_b, cont_b, split_b, offs_b, ks_b, lits_b, n_groups = planes
    return _pack_meta(bitmap_b, cont_b, split_b, offs_b, ks_b, n_groups) + lits_b


def _parse_payload(payload: bytes, uncompressed_len: int):
    """Split a TLZ payload into (version, n_groups, is_match, is_cont,
    is_split, dists, ks, lits). v1 has no cont/split bitmaps (both None),
    16-byte groups, and literal-group-index sources. For v2 the group count
    derives from the frame's uncompressed length; the header's low 14 bits
    are a consistency check (the count can exceed 14 bits at 256 KiB
    blocks)."""
    if len(payload) < 2:
        raise IOError("TLZ payload too short")
    field = int(np.frombuffer(payload[:2], dtype="<u2")[0])
    version = 2 if field & V2_FLAG else 1
    packed = bool(field & PACKED_FLAG) and version == 2
    if version == 2:
        n_groups = (uncompressed_len + GROUP - 1) // GROUP
        # A legacy v1 payload from a >=512 KiB block has bit 15 set in its
        # 16-byte-group count and would otherwise be misread as v2 — the
        # count consistency check and the size cap both refuse loudly.
        if n_groups > MAX_BLOCK // GROUP:
            raise IOError(
                "ambiguous TLZ header: v2 flag set with out-of-range group "
                "count (legacy v1 payload from an oversized block?)"
            )
        if (field & 0x3FFF) != (n_groups & 0x3FFF):
            raise IOError(
                f"TLZ v2 header count {field & 0x3FFF} inconsistent with "
                f"frame length ({n_groups} groups) — corrupt or legacy header"
            )
    else:
        n_groups = field
    bm_len = (n_groups + 7) // 8
    group = GROUP if version == 2 else _V1_GROUP
    off = 2
    if packed:
        import zlib

        if len(payload) < 6:
            raise IOError("TLZ packed metadata length truncated")
        clen = int(np.frombuffer(payload[2:6], dtype="<u4")[0])
        if 6 + clen > len(payload):
            raise IOError("TLZ packed metadata truncated")
        # the deflated section can never legitimately exceed the plain
        # metadata planes (3 bitmaps + u16 distances + u8 split points); cap
        # the inflation so a crafted deflate bomb in a corrupt frame cannot
        # allocate unbounded memory (clen is untrusted)
        max_meta = 3 * ((n_groups + 7) // 8) + 3 * n_groups
        try:
            d = zlib.decompressobj()
            meta = d.decompress(payload[6 : 6 + clen], max_meta + 1)
        except zlib.error as e:
            raise IOError(f"TLZ packed metadata corrupt: {e}") from e
        if len(meta) > max_meta or d.unconsumed_tail:
            raise IOError("TLZ packed metadata inflates beyond any valid size")
        off = 6 + clen
        src = meta
        moff = 0
    else:
        src = payload
        moff = off
    bitmap = np.frombuffer(src[moff : moff + bm_len], dtype=np.uint8)
    moff += bm_len
    if len(bitmap) < bm_len:
        raise IOError("TLZ bitmap truncated")
    is_match = np.unpackbits(bitmap, count=n_groups, bitorder="little").astype(bool)
    is_cont = is_split = ks = None
    if version == 2:
        cont_b = np.frombuffer(src[moff : moff + bm_len], dtype=np.uint8)
        moff += bm_len
        if len(cont_b) < bm_len:
            raise IOError("TLZ cont bitmap truncated")
        is_cont = np.unpackbits(cont_b, count=n_groups, bitorder="little").astype(bool)
        if (is_cont & ~is_match).any():
            raise IOError("TLZ cont flag on non-match group")
        split_b = np.frombuffer(src[moff : moff + bm_len], dtype=np.uint8)
        moff += bm_len
        if len(split_b) < bm_len:
            raise IOError("TLZ split bitmap truncated")
        is_split = np.unpackbits(
            split_b, count=n_groups, bitorder="little"
        ).astype(bool)
        if (is_split & is_match).any():
            raise IOError("TLZ split flag on match group")
        n_offs = int((is_match & ~is_cont).sum())
        n_split = int(is_split.sum())
    else:
        n_offs = int(is_match.sum())
        n_split = 0
    offs_raw = src[moff : moff + 2 * n_offs]
    if len(offs_raw) < 2 * n_offs:  # before frombuffer: an odd-length slice
        raise IOError("TLZ sources truncated")  # would raise ValueError there
    offs = np.frombuffer(offs_raw, dtype="<u2")
    moff += 2 * n_offs
    if version == 2:
        ks = np.frombuffer(src[moff : moff + n_split], dtype=np.uint8)
        moff += n_split
        if len(ks) < n_split:
            raise IOError("TLZ split points truncated")
    if packed:
        if moff != len(meta):
            raise IOError(
                f"TLZ packed metadata has {len(meta) - moff} trailing bytes"
            )
    else:
        off = moff
    n_lits = n_groups - int(is_match.sum()) - n_split
    lits = np.frombuffer(payload[off : off + n_lits * group], dtype=np.uint8)
    if len(lits) < n_lits * group:
        raise IOError("TLZ literals truncated")
    # v2 payloads are exactly their declared fields — trailing bytes mean the
    # header was misread (e.g. a legacy v1 payload from a 512-640 KiB block
    # whose group count happens to alias a small v2 count + the flag bit)
    if version == 2 and off + n_lits * group != len(payload):
        raise IOError(
            f"TLZ v2 payload has {len(payload) - off - n_lits * group} "
            "trailing bytes — misread header (legacy v1 block?)"
        )
    return (
        version, n_groups, is_match, is_cont, is_split,
        offs.astype(np.int64), ks, lits,
    )


def _expand_dists_numpy(is_match, is_cont, dists, n_groups):
    """Reconstruct each match group's source DISTANCE: continuation groups
    share their run leader's stored distance (source advances in lockstep
    with the destination, so the distance is constant along a run)."""
    is_new = is_match & ~is_cont
    idx = np.arange(n_groups, dtype=np.int64)
    if not is_match.any():
        return np.zeros(n_groups, dtype=np.int64)
    leader = np.maximum.accumulate(np.where(is_new, idx, -1))
    if (leader[is_match] < 0).any() or len(dists) == 0:
        raise IOError("TLZ continuation run has no leader")
    new_rank = np.cumsum(is_new) - 1
    safe_rank = np.clip(new_rank, 0, len(dists) - 1)
    return dists[safe_rank]


def _validate_planes_v2(n_groups, is_match, is_cont, is_split, dists, ks):
    """Vectorized structural validation of parsed v2 planes; raises
    :class:`IOError` on out-of-range match distances or malformed split
    groups. Shared by the numpy decoder and the device staging path
    (:func:`decode_blocks_device`) so corruption fails loudly on EVERY
    decode path even with ``checksum_enabled=False`` — the in-graph kernel
    clamps offsets (an out-of-bounds gather is undefined under XLA) and
    would otherwise decode corrupt frames to silently wrong bytes.

    Returns ``(dist_full, group_start, split_idx, kvals, d_prev, d_next)``
    so the numpy decoder can reuse the intermediates."""
    dist_full = _expand_dists_numpy(is_match, is_cont, dists, n_groups)
    group_start = np.arange(n_groups, dtype=np.int64) * GROUP
    off_full = group_start - dist_full
    bad = is_match & ((dist_full < 1) | (off_full < 0))
    if bad.any():
        raise IOError("TLZ v2 source distance out of range")
    # split groups copy their prefix at the LEFT neighbor's distance and
    # their suffix at the RIGHT neighbor's — both neighbors must be matches
    split_idx = np.flatnonzero(is_split)
    kvals = d_prev = d_next = None
    if len(split_idx):
        if split_idx[0] == 0 or split_idx[-1] == n_groups - 1:
            raise IOError("TLZ split group at block edge")
        if (~is_match[split_idx - 1]).any() or (~is_match[split_idx + 1]).any():
            raise IOError("TLZ split group without match neighbors")
        kvals = ks.astype(np.int64)
        if ((kvals < 1) | (kvals > GROUP - 1)).any():
            raise IOError("TLZ split point out of range")
        d_prev = dist_full[split_idx - 1]
        d_next = dist_full[split_idx + 1]
        if ((group_start[split_idx] + kvals - d_next) < 0).any():
            raise IOError("TLZ split suffix distance out of range")
    return dist_full, group_start, split_idx, kvals, d_prev, d_next


def decode_payload_numpy(
    payload: bytes, uncompressed_len: int, use_native: bool | None = None
) -> bytes:
    """Host decode of one TLZ payload. v2 payloads go through the C
    single-pass block decoder (``libs3shuffle_native`` — header + inflate +
    popcount plane-splitting in Python, everything else sequential backward
    copies in C) when the library loads; otherwise — and whenever the C
    decoder rejects the payload — the vectorized numpy path parses,
    validates with precise errors, and pointer-jumps. ``use_native=False``
    forces the numpy path (the differential-testing oracle)."""
    if use_native is not False:
        fast = _decode_block_native_fast(payload, uncompressed_len)
        if fast is not None:
            return fast
        if use_native:  # explicitly forced: do not silently fall back
            raise RuntimeError(
                "native TLZ decoder unavailable or rejected the payload"
            )
        # fall through: the numpy path raises precise errors on corruption
    version, n_groups, is_match, is_cont, is_split, dists, ks, lits = (
        _parse_payload(payload, uncompressed_len)
    )
    if version == 1:
        # legacy format: 16-byte groups, sources are literal *group indices*
        n_lits = n_groups - int(is_match.sum())
        out = np.zeros((n_groups, _V1_GROUP), dtype=np.uint8)
        out[~is_match] = lits.reshape(n_lits, _V1_GROUP)
        if len(dists):
            if (dists >= n_groups).any() or is_match[dists].any():
                raise IOError("TLZ match source is not a literal group")
            out[is_match] = out[dists]
        return out.reshape(-1)[:uncompressed_len].tobytes()

    n_bytes = n_groups * GROUP
    if n_groups == 0:
        return b""
    n_lits = n_groups - int(is_match.sum()) - int(is_split.sum())
    dist_full, group_start, split_idx, kvals, d_prev, d_next = (
        _validate_planes_v2(n_groups, is_match, is_cont, is_split, dists, ks)
    )
    off_full = group_start - dist_full
    # literal plane, placed sparsely at each literal group's position
    is_lit = ~is_match & ~is_split
    sparse = np.zeros((n_groups, GROUP), dtype=np.uint8)
    sparse[is_lit] = lits.reshape(n_lits, GROUP)
    sparse = sparse.reshape(-1)
    # per-byte source map: literal bytes are fixed points; match bytes point
    # at offset + lane. Pointer jumping (src = src[src] — the DOUBLING update;
    # following a fixed map would advance one hop per round and never resolve
    # long periodic chains) reaches literal bytes in log2 rounds; the host
    # loop exits early once converged — terasort's column-framed values
    # need 9 doubling rounds at 256 KiB blocks.
    out = sparse
    match_groups = np.flatnonzero(is_match)
    if len(match_groups) or len(split_idx):
        lanes = np.arange(GROUP, dtype=np.int64)
        src = np.arange(n_bytes, dtype=np.int64)
        if len(match_groups):
            src_match = (off_full[match_groups][:, None] + lanes[None, :]).reshape(-1)
            dst_match = (group_start[match_groups][:, None] + lanes[None, :]).reshape(-1)
            src[dst_match] = src_match
        if len(split_idx):
            pos = group_start[split_idx][:, None] + lanes[None, :]
            d = np.where(lanes[None, :] < kvals[:, None], d_prev[:, None], d_next[:, None])
            src[pos.reshape(-1)] = (pos - d).reshape(-1)
        # whole-array pointer doubling with an early convergence exit.
        # (An active-set variant — updating only unresolved positions — was
        # measured 2.5x SLOWER here: numpy's contiguous whole-array gather
        # beats scattered fancy-index updates even at more total elements.)
        for _ in range(_jump_rounds(n_bytes)):
            nxt = src[src]
            if np.array_equal(nxt, src):
                break
            src = nxt
        out = sparse[src]
    return out[:uncompressed_len].tobytes()


def _unpack_bits_math(bitmap_u8, n_groups: int):
    """In-graph little-endian bit unpack: (B, G/8) uint8 → (B, G) bool."""
    _jax_mod, jnp = _jax()
    shifts = jnp.arange(8, dtype=jnp.int32)
    bits = (bitmap_u8[:, :, None].astype(jnp.int32) >> shifts[None, None, :]) & 1
    return bits.reshape(bitmap_u8.shape[0], n_groups).astype(bool)


def _decode_counted_math(
    is_match, is_cont, is_split, offs_padded, ks_padded, lits_padded,
    n_groups: int,
):
    """Decode, returning ``(decoded, rounds)``: the (B, n_bytes) uint8 rows
    and the int32 number of pointer-jump rounds the launch ran.

    is_match/is_cont/is_split: (B, G) bool; offs_padded: (B, G) int32
    (stored match DISTANCES in order); ks_padded: (B, G) int32 (stored
    split points in order); lits_padded: (B, G, GROUP) uint8 (literal slots
    in literal order) — exactly the (unpacked) shapes :func:`_encode_math`
    emits. Continuation groups share their run leader's distance, so the
    absolute source is ``group_start - distance``; split groups copy their
    prefix at the left neighbor's distance and suffix at the right
    neighbor's.
    """
    jax, jnp = _jax()
    n_bytes = n_groups * GROUP
    b = is_match.shape[0]
    idx = jnp.arange(n_groups, dtype=jnp.int32)
    is_new = is_match & ~is_cont
    new_rank = jnp.cumsum(is_new, axis=1) - 1
    dist_of = jnp.take_along_axis(offs_padded, jnp.maximum(new_rank, 0), axis=1)
    off_of = GROUP * idx[None, :] - dist_of
    split_rank = jnp.cumsum(is_split, axis=1) - 1
    k_of = jnp.take_along_axis(ks_padded, jnp.maximum(split_rank, 0), axis=1)
    # neighbors' distances for split groups (edge groups can't split)
    d_prev = jnp.concatenate([jnp.zeros((b, 1), jnp.int32), dist_of[:, :-1]], axis=1)
    d_next = jnp.concatenate([dist_of[:, 1:], jnp.zeros((b, 1), jnp.int32)], axis=1)
    is_lit = ~is_match & ~is_split
    lit_rank = jnp.cumsum(is_lit, axis=1) - 1
    lit_vals = jnp.take_along_axis(
        lits_padded, jnp.maximum(lit_rank, 0)[:, :, None], axis=1
    )
    sparse = jnp.where(is_lit[:, :, None], lit_vals, 0).reshape(b, n_bytes)
    # per-byte source map + pointer jumping
    lanes = jnp.arange(GROUP, dtype=jnp.int32)
    pos = jnp.arange(n_bytes, dtype=jnp.int32)
    off_b = (off_of[:, :, None] + lanes[None, None, :]).reshape(b, n_bytes)
    split_d = jnp.where(
        lanes[None, None, :] < k_of[:, :, None],
        d_prev[:, :, None],
        d_next[:, :, None],
    )
    split_src = (
        GROUP * idx[None, :, None] + lanes[None, None, :] - split_d
    ).reshape(b, n_bytes)
    match_b = jnp.repeat(is_match, GROUP, axis=1)
    split_b = jnp.repeat(is_split, GROUP, axis=1)
    # clamp corrupt offsets into range; wrong bytes are caught by the
    # checksum layer, unlike an out-of-bounds gather
    src = jnp.where(match_b, jnp.clip(off_b, 0, n_bytes - 1), pos[None, :])
    src = jnp.where(split_b, jnp.clip(split_src, 0, n_bytes - 1), src)
    # Pointer jumping over packed words ``pointer << 8 | byte``: one gather
    # per round doubles every pointer's reach (src = src[src]) and carries
    # the byte its old pointer held, so after round k a word holds the byte
    # 2**k - 1 hops back. Valid sources point strictly backward, so every
    # chain ends at a literal within n_bytes - 1 <= 2**cap - 1 hops; the
    # loop stops at the first round that moves no pointer of the launch
    # (the chains were resolved a round earlier, so the bytes now are
    # too), else at the cap.
    if n_bytes > 1 << 23:
        raise ValueError(f"{n_bytes}-byte blocks overflow the packed pointers")
    cap = _jump_rounds(n_bytes)

    def jump(state):
        words, _moved, rounds = state
        nxt = jnp.take_along_axis(words, words >> 8, axis=1)
        return nxt, jnp.any((nxt >> 8) != (words >> 8)), rounds + 1

    words, _moved, rounds = jax.lax.while_loop(
        lambda state: state[1] & (state[2] < cap),
        jump,
        ((src << 8) | sparse.astype(jnp.int32), jnp.array(True), jnp.int32(0)),
    )
    return (words & 0xFF).astype(jnp.uint8), rounds


class _NativeEncodeScratch(threading.local):
    """Per-thread reusable output buffers + pre-built ctypes pointers for
    the C block encoder. The host encode path runs once per 256 KiB block
    on every chipless writer, so per-call numpy allocation and ctypes
    pointer construction were a measured ~25% of wall (276 → ~420 MB/s
    with reuse); buffers are sized for MAX_BLOCK once and sliced."""

    def __init__(self):
        import ctypes

        ng = MAX_BLOCK // GROUP
        bm = (ng + 7) // 8
        self.match_b = np.empty(bm, dtype=np.uint8)
        self.cont_b = np.empty(bm, dtype=np.uint8)
        self.split_b = np.empty(bm, dtype=np.uint8)
        self.dists = np.empty(ng, dtype="<u2")
        self.ks = np.empty(ng, dtype=np.uint8)
        self.lits = np.empty(ng * GROUP, dtype=np.uint8)
        self.n_d = ctypes.c_int64()
        self.n_k = ctypes.c_int64()
        self.n_l = ctypes.c_int64()
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u16p = ctypes.POINTER(ctypes.c_uint16)
        self.ptrs = (
            self.match_b.ctypes.data_as(u8p),
            self.cont_b.ctypes.data_as(u8p),
            self.split_b.ctypes.data_as(u8p),
            self.dists.ctypes.data_as(u16p),
            ctypes.byref(self.n_d),
            self.ks.ctypes.data_as(u8p),
            ctypes.byref(self.n_k),
            self.lits.ctypes.data_as(u8p),
            ctypes.byref(self.n_l),
        )
        self.u8p = u8p


_native_scratch = _NativeEncodeScratch()


def _encode_block_native(data: bytes):
    """Whole-block host encode through the C sequential encoder, emitting
    the same wire planes as the device kernel (packed via _pack_meta).
    Returns the payload bytes, or None when the native library is
    unavailable (callers fall back to the numpy encoder)."""
    try:
        from s3shuffle_tpu.codec.native import _load

        lib = _load()
    except Exception:
        logger.debug("native tlz encoder unavailable", exc_info=True)
        return None
    n_groups = (len(data) + GROUP - 1) // GROUP
    if n_groups == 0 or n_groups > MAX_BLOCK // GROUP:
        return None
    if len(data) % GROUP:
        src = np.zeros(n_groups * GROUP, dtype=np.uint8)
        src[: len(data)] = np.frombuffer(data, dtype=np.uint8)
    else:
        src = np.frombuffer(data, dtype=np.uint8)  # zero-copy (C reads only)
    s = _native_scratch
    rc = lib.tlz_encode_block(
        src.ctypes.data_as(s.u8p), n_groups, *s.ptrs
    )
    if rc != 0:
        return None
    bm = (n_groups + 7) // 8
    return _pack_meta(
        s.match_b[:bm].tobytes(),
        s.cont_b[:bm].tobytes(),
        s.split_b[:bm].tobytes(),
        s.dists[: s.n_d.value].tobytes(),
        s.ks[: s.n_k.value].tobytes(),
        n_groups,
    ) + s.lits[: s.n_l.value * GROUP].tobytes()


def _decode_block_native_fast(payload: bytes, ulen: int):
    """Whole-block host decode through the C single-pass decoder, straight
    from the packed payload: header + (optional) inflate + popcount plane
    splitting in Python, everything else in C. Returns the decoded bytes, or
    None when the native library is unavailable or the payload doesn't parse
    cleanly — the caller then falls through to the validating numpy path,
    which raises precise errors (the C decoder enforces the same invariants
    but reports only accept/reject)."""
    try:
        import ctypes

        from s3shuffle_tpu.codec.native import _load

        lib = _load()
    except Exception:
        logger.debug("native tlz decoder unavailable", exc_info=True)
        return None
    if len(payload) < 2:
        return None
    field = int(np.frombuffer(payload[:2], dtype="<u2")[0])
    if not field & V2_FLAG:
        return None
    if ulen <= 0:
        return b"" if field == V2_FLAG and len(payload) == 2 else None
    n_groups = (ulen + GROUP - 1) // GROUP
    if n_groups == 0 or n_groups > MAX_BLOCK // GROUP:
        return None
    if (field & 0x3FFF) != (n_groups & 0x3FFF):
        return None
    bm = (n_groups + 7) // 8
    if field & PACKED_FLAG:
        import zlib

        if len(payload) < 6:
            return None
        clen = int(np.frombuffer(payload[2:6], dtype="<u4")[0])
        if 6 + clen > len(payload):
            return None
        max_meta = 3 * bm + 3 * n_groups
        try:
            d = zlib.decompressobj()
            meta = d.decompress(payload[6 : 6 + clen], max_meta + 1)
        except zlib.error:
            return None
        if len(meta) > max_meta or d.unconsumed_tail:
            return None
        lit_off = 6 + clen
        src, soff = meta, 0
    else:
        src, soff = payload, 2
        lit_off = None
    if len(src) - soff < 3 * bm:
        return None
    mb = np.frombuffer(src[soff : soff + bm], dtype=np.uint8)
    cb = np.frombuffer(src[soff + bm : soff + 2 * bm], dtype=np.uint8)
    sb = np.frombuffer(src[soff + 2 * bm : soff + 3 * bm], dtype=np.uint8)
    n_new = int(_POP[mb & ~cb].sum())
    n_split = int(_POP[sb].sum())
    n_lits = n_groups - int(_POP[mb].sum()) - n_split
    if n_lits < 0:
        return None
    meta_len = 3 * bm + 2 * n_new + n_split
    if len(src) - soff < meta_len:
        return None
    dists = np.frombuffer(
        src[soff + 3 * bm : soff + 3 * bm + 2 * n_new], dtype="<u2"
    ).copy()  # copy: frombuffer slices may be misaligned for u16
    ks = np.frombuffer(
        src[soff + 3 * bm + 2 * n_new : soff + meta_len], dtype=np.uint8
    )
    if lit_off is None:
        lit_off = 2 + meta_len
    elif len(meta) != meta_len:
        return None
    if len(payload) != lit_off + n_lits * GROUP:
        return None
    lits = np.frombuffer(payload[lit_off:], dtype=np.uint8)
    out = np.empty(n_groups * GROUP, dtype=np.uint8)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    u16p = ctypes.POINTER(ctypes.c_uint16)
    rc = lib.tlz_decode_block(
        np.ascontiguousarray(mb).ctypes.data_as(u8p),
        np.ascontiguousarray(cb).ctypes.data_as(u8p),
        np.ascontiguousarray(sb).ctypes.data_as(u8p),
        dists.ctypes.data_as(u16p),
        n_new,
        np.ascontiguousarray(ks).ctypes.data_as(u8p),
        n_split,
        np.ascontiguousarray(lits).ctypes.data_as(u8p),
        n_lits,
        n_groups,
        out.ctypes.data_as(u8p),
    )
    if rc != n_groups * GROUP:
        return None
    return out[:ulen].tobytes()


def _decode_fused_math(
    is_match, is_cont, is_split, offs_padded, ks_padded, lits_padded,
    n_lits, n_groups: int, crc_fn,
):
    """Decode + fused CRC in ONE trace: the decoded rows of
    :func:`_decode_counted_math` plus, from the same launch, the raw zero-init CRC
    remainder of each row's literal plane — the dominant slice of every
    stored TLZ payload. The host stitches the small header/metadata prefix
    CRCs around it with :func:`ops.checksum.crc_combine`, so the read plane
    certifies each frame's STORED bytes without a separate host hashing
    pass over the payload bulk (the read-side mirror of
    :func:`_encode_fused_math`, with the same masked, unshifted CRC).
    ``n_lits``: (B,) int32 literal-group counts (the staged literal plane is
    front-aligned in literal order; what lies past a row's literals is never
    read). Returns ``(decoded, crcs, rounds)``, ``rounds`` as
    :func:`_decode_counted_math`."""
    decoded, rounds = _decode_counted_math(
        is_match, is_cont, is_split, offs_padded, ks_padded, lits_padded,
        n_groups,
    )
    lit_bytes = n_lits * GROUP
    return decoded, crc_fn(_literal_plane(lits_padded, lit_bytes), lit_bytes), rounds


@functools.lru_cache(maxsize=16)
def _decode_batch_kernel(batch_rows: int, n_groups: int, poly: Optional[int]):
    """Precompiled fixed-shape batched decode kernel — one trace per
    (batch rows, block shape, fused poly), never per call: the old
    path jitted over whatever batch size arrived, so XLA recompiled per
    distinct frame-run length (every tail run of every partition). Staged
    plane arrays are DONATED so XLA may reuse their device buffers. ``poly``
    selects the fused CRC variant (None = decode only). Either variant's
    last output is the pointer-jump rounds the launch ran."""
    jax, _jnp = _jax()
    from s3shuffle_tpu.ops import rates

    if poly is None:
        return rates.timed_first_call("tlz_decode", jax.jit(
            functools.partial(_decode_counted_math, n_groups=n_groups),
            donate_argnums=(0, 1, 2, 3, 4, 5),
        ))
    from s3shuffle_tpu.ops.checksum import raw_crc_graph_fn

    crc_fn, tables = raw_crc_graph_fn(poly, n_groups * GROUP)
    kernel = jax.jit(
        lambda m, c, s, o, k, lits, n_lits, *tabs: _decode_fused_math(
            m, c, s, o, k, lits, n_lits, n_groups,
            lambda d, n: crc_fn(d, n, *tabs),
        ),
        donate_argnums=(0, 1, 2, 3, 4, 5),
    )
    return rates.timed_first_call(
        "tlz_decode_fused",
        lambda *planes: kernel(*planes, *tables(_device_of(planes[0]))),
    )


class _DecodeStaging(threading.local):
    """Reusable per-thread host staging planes, one set per launch shape: the
    decode path used to allocate six fresh (B, …) arrays per call. The async
    read pipeline funnels every batch through ONE decode thread
    (codec/framing.py), so reuse hits every launch."""

    def __init__(self) -> None:
        self.buffers: dict = {}

    def get(self, rows: int, n_groups: int, slot: int = 0) -> tuple:
        """``slot`` keys one plane set per in-flight dispatch lane (see
        :meth:`_EncodeStaging.get`); single-device callers pass slot 0 and
        keep exactly one set per shape."""
        arrs = self.buffers.get((rows, n_groups, slot))
        if arrs is None:
            arrs = (
                np.zeros((rows, n_groups), dtype=bool),
                np.zeros((rows, n_groups), dtype=bool),
                np.zeros((rows, n_groups), dtype=bool),
                np.zeros((rows, n_groups), dtype=np.int32),
                np.zeros((rows, n_groups), dtype=np.int32),
                np.zeros((rows, n_groups, GROUP), dtype=np.uint8),
                np.zeros(rows, dtype=np.int32),  # n_lits per row
            )
            self.buffers[(rows, n_groups, slot)] = arrs
        return arrs


_decode_staging = _DecodeStaging()


def _parse_batch_v2(payloads: List[bytes], ulens: List[int], n_groups: int):
    """Single vectorized batch parse of the v2 plane tables.

    Splits every device-shaped payload's metadata planes in ONE pass over the
    batch: the three bitmap planes of all rows stack into one (k, bm) array
    per plane (one ``np.unpackbits`` each instead of three per payload), the
    per-row plane counts come from one table-popcount pass, and the
    cross-plane consistency checks (cont ⊆ match, split ∩ match = ∅) run as
    whole-batch boolean reductions. Packed-metadata payloads inflate per row
    (zlib is inherently sequential) and join the same stacked pass.

    Returns ``(rows, fallback)`` where ``rows[i]`` is
    ``(is_match, is_cont, is_split, dists, ks, lits, n_lits, lit_off)`` for
    device-shaped rows and None for ``fallback`` members (legacy v1 frames,
    short blocks, foreign block sizes — the numpy decoder serves those).
    Corruption raises :class:`IOError` with the same classification as
    :func:`_parse_payload`; structural validation (`_validate_planes_v2`)
    stays on every device-shaped row."""
    import zlib

    b = len(payloads)
    bm = (n_groups + 7) // 8
    fallback = set()
    metas: List = [None] * b  # (meta_buffer, meta_off, lit_off) per v2 row
    for i, payload in enumerate(payloads):
        if len(payload) < 2:
            raise IOError("TLZ payload too short")
        field = int(np.frombuffer(payload[:2], dtype="<u2")[0])
        ng = (ulens[i] + GROUP - 1) // GROUP
        if not field & V2_FLAG or ng != n_groups:
            fallback.add(i)
            continue
        if (field & 0x3FFF) != (n_groups & 0x3FFF):
            raise IOError(
                f"TLZ v2 header count {field & 0x3FFF} inconsistent with "
                f"frame length ({n_groups} groups) — corrupt or legacy header"
            )
        if field & PACKED_FLAG:
            if len(payload) < 6:
                raise IOError("TLZ packed metadata length truncated")
            clen = int(np.frombuffer(payload[2:6], dtype="<u4")[0])
            if 6 + clen > len(payload):
                raise IOError("TLZ packed metadata truncated")
            max_meta = 3 * bm + 3 * n_groups
            try:
                d = zlib.decompressobj()
                meta = d.decompress(payload[6 : 6 + clen], max_meta + 1)
            except zlib.error as e:
                raise IOError(f"TLZ packed metadata corrupt: {e}") from e
            if len(meta) > max_meta or d.unconsumed_tail:
                raise IOError("TLZ packed metadata inflates beyond any valid size")
            metas[i] = (meta, 0, 6 + clen)
        else:
            metas[i] = (payload, 2, None)
        meta, moff, _lo = metas[i]
        if len(meta) - moff < 3 * bm:
            raise IOError("TLZ bitmap truncated")
    live = [i for i in range(b) if i not in fallback]
    if not live:
        return [None] * b, fallback
    # ONE stacked pass over every row's three bitmap planes
    stacked = np.empty((len(live), 3 * bm), dtype=np.uint8)
    for j, i in enumerate(live):
        meta, moff, _lo = metas[i]
        stacked[j] = np.frombuffer(meta, dtype=np.uint8, count=3 * bm, offset=moff)
    match_b = np.unpackbits(
        stacked[:, :bm], axis=1, count=n_groups, bitorder="little"
    ).astype(bool)
    cont_b = np.unpackbits(
        stacked[:, bm : 2 * bm], axis=1, count=n_groups, bitorder="little"
    ).astype(bool)
    split_b = np.unpackbits(
        stacked[:, 2 * bm :], axis=1, count=n_groups, bitorder="little"
    ).astype(bool)
    if (cont_b & ~match_b).any():
        raise IOError("TLZ cont flag on non-match group")
    if (split_b & match_b).any():
        raise IOError("TLZ split flag on match group")
    # counts from the TRUNCATED unpacked planes, never a raw byte popcount:
    # bits past n_groups in the final bitmap byte are padding the scalar
    # parser ignores, and counting them would misread a frame the host
    # decoder accepts (misclassifying it as a device failure downstream)
    n_match = match_b.sum(axis=1)
    n_new = (match_b & ~cont_b).sum(axis=1)
    n_split = split_b.sum(axis=1)
    n_lits = n_groups - n_match - n_split
    rows: List = [None] * b
    for j, i in enumerate(live):
        meta, moff, lit_off = metas[i]
        payload = payloads[i]
        nn, ns, nl = int(n_new[j]), int(n_split[j]), int(n_lits[j])
        meta_len = 3 * bm + 2 * nn + ns
        if len(meta) - moff < meta_len:
            raise IOError(
                "TLZ sources truncated" if len(meta) - moff < 3 * bm + 2 * nn
                else "TLZ split points truncated"
            )
        offs = np.frombuffer(meta, dtype=np.uint8,
                             count=2 * nn, offset=moff + 3 * bm)
        dists = offs.view()  # raw little-endian u16 pairs; staged via copy
        ks = np.frombuffer(meta, dtype=np.uint8, count=ns,
                           offset=moff + 3 * bm + 2 * nn)
        if lit_off is None:
            lit_off = 2 + meta_len
        elif len(meta) != meta_len:
            raise IOError(
                f"TLZ packed metadata has {len(meta) - meta_len} trailing bytes"
            )
        if len(payload) < lit_off + nl * GROUP:
            raise IOError("TLZ literals truncated")
        if len(payload) != lit_off + nl * GROUP:
            raise IOError(
                f"TLZ v2 payload has {len(payload) - lit_off - nl * GROUP} "
                "trailing bytes — misread header (legacy v1 block?)"
            )
        lits = np.frombuffer(payload, dtype=np.uint8,
                             count=nl * GROUP, offset=lit_off)
        # unaligned-safe u16 view: pair the bytes back up on the host
        dist_vals = (
            dists[0::2].astype(np.int64) | (dists[1::2].astype(np.int64) << 8)
        )
        # structural validation stays on EVERY device-shaped row: the
        # in-graph kernel clamps offsets (out-of-bounds gathers are
        # undefined under XLA) and would decode corrupt frames to silently
        # wrong bytes with checksum_enabled=False
        _validate_planes_v2(
            n_groups, match_b[j], cont_b[j], split_b[j], dist_vals,
            ks.astype(np.int64),
        )
        rows[i] = (
            match_b[j], cont_b[j], split_b[j], dist_vals, ks, lits, nl,
            lit_off,
        )
    return rows, fallback


def decode_batch_device(
    payloads: List[bytes],
    ulens: List[int],
    block_size: int,
    batch_rows: Optional[int] = None,
    poly: Optional[int] = None,
):
    """Batched device decode of v2 TLZ payloads with FIXED-shape precompiled
    launches of ``batch_rows`` rows (partial batches pad to a power-of-two
    bucket in reusable per-thread staging planes — no per-call retrace),
    fed by :func:`_parse_batch_v2`'s single vectorized batch parse. Short or
    legacy payloads fall back to the numpy decoder per row.

    With ``poly`` set, each device-shaped payload's full-algorithm CRC of its
    STORED bytes comes back FUSED from the same launch (the literal plane —
    the payload bulk — is CRC'd in-graph; the host stitches the small
    header/metadata prefix with ``crc_combine``): returns
    ``(blocks, payload_crcs)`` where ``payload_crcs[i]`` is the CRC of
    ``payloads[i]`` or None for fallback rows (callers hash those on the
    host). Without ``poly``: ``(blocks, None)``. Each launch is timed by the
    ``codec.decode.parse`` / ``codec.decode.device`` / ``codec.decode.emit``
    spans."""
    import time as _time

    from s3shuffle_tpu.ops import rates
    from s3shuffle_tpu.utils import trace

    n_groups = block_size // GROUP
    b = len(payloads)
    cap = max(1, batch_rows or b)
    out: List[Optional[bytes]] = [None] * b
    crcs: Optional[List[Optional[int]]] = [None] * b if poly is not None else None
    if poly is not None:
        from s3shuffle_tpu.ops.checksum import (
            crc_combine,
            host_crc,
            zero_run_crcs,
        )

        zero = zero_run_crcs(poly, n_groups * GROUP)
    jax = _jax()[0]
    # Multi-chip placement mirror of encode_batch_device: armed, each parsed
    # chunk launches on the least-loaded device with per-lane staging planes
    # and up to n_devices launches in flight; disarmed, window 0 keeps the
    # launch→drain→emit sequence synchronous on the default device.
    disp = _mesh_dispatcher()
    window = disp.max_inflight() if disp is not None else 0
    pending: List[tuple] = []  # (launch outputs, parsed rows, start, lane, device)

    def _drain_oldest(backpressure: bool) -> None:
        outs, prows, s0, slot, dev = pending.pop(0)
        t0 = _time.perf_counter()
        try:
            with trace.span("codec.decode.device"):
                decoded = np.asarray(outs[0])
                raw_crcs = None if poly is None else np.asarray(outs[1])
                rounds = int(outs[-1])
        finally:
            rates.launch_drained(dev)
        rates.decode_jump_rounds(rounds)
        if disp is not None:
            disp.release(slot)
            if backpressure:
                disp.observe_wait(_time.perf_counter() - t0)
        with trace.span("codec.decode.emit"):
            for j, row in enumerate(prows):
                if row is None:
                    continue
                out[s0 + j] = decoded[j, : ulens[s0 + j]].tobytes()
                if raw_crcs is not None:
                    nl, lit_off = row[6], row[7]
                    lit_len = nl * GROUP
                    payload = payloads[s0 + j]
                    # stored payload = prefix (host-hashed, small) + literal
                    # plane (CRC'd in the launch, fixed up for length)
                    lit_crc = int(raw_crcs[j]) ^ int(zero[lit_len])
                    crcs[s0 + j] = crc_combine(
                        host_crc(payload[: len(payload) - lit_len], poly),
                        lit_crc, lit_len, poly,
                    )

    try:
        for s in range(0, b, cap):
            e = min(b, s + cap)
            with trace.span("codec.decode.parse"):
                rows, fallback = _parse_batch_v2(
                    payloads[s:e], ulens[s:e], n_groups
                )
                for j in sorted(fallback):
                    out[s + j] = decode_payload_numpy(payloads[s + j], ulens[s + j])
                if len(fallback) == e - s:  # nothing device-shaped (e.g. a
                    # reader whose block_size differs from the writer's) —
                    # skip the kernel
                    continue
                launch_rows = _bucket_rows(e - s, cap)
                slot = disp.acquire() if disp is not None else 0
                while any(p[3] == slot for p in pending):
                    # the lane's previous launch may still be reading its
                    # device_put-aliased staging planes — drain until the
                    # lane is free before zeroing/refilling them
                    _drain_oldest(True)
                staging = _decode_staging.get(launch_rows, n_groups, slot)
                is_match, is_cont, is_split, offs, ks, lits, nlits = staging
                for arr in staging:
                    arr[...] = 0  # deterministic pad + fallback rows
                for j in range(e - s):
                    row = rows[j]
                    if row is None:
                        continue
                    m, c, sp, dist_vals, kv, l, nl, _lit_off = row
                    is_match[j] = m
                    is_cont[j] = c
                    is_split[j] = sp
                    offs[j, : len(dist_vals)] = dist_vals
                    ks[j, : len(kv)] = kv
                    lits[j, :nl] = l.reshape(nl, GROUP)
                    nlits[j] = nl
                dev = disp.device(slot) if disp is not None else None

                def _put(arr, dev=dev):
                    return (
                        jax.device_put(arr, dev)
                        if dev is not None
                        else jax.device_put(arr)
                    )

                with warnings.catch_warnings():
                    # donated staging may not be aliasable on every backend
                    # (XLA:CPU bool/uint8 staging) — an expected no-op for OUR
                    # launch; suppressed only around it (see encode_batch_device)
                    warnings.filterwarnings(
                        "ignore", message="Some donated buffers were not usable"
                    )
                    if poly is None:
                        outs = _decode_batch_kernel(launch_rows, n_groups, None)(
                            _put(is_match), _put(is_cont), _put(is_split),
                            _put(offs), _put(ks), _put(lits),
                        )
                    else:
                        outs = _decode_batch_kernel(launch_rows, n_groups, poly)(
                            _put(is_match), _put(is_cont), _put(is_split),
                            _put(offs), _put(ks), _put(lits), _put(nlits),
                        )
                real = e - s - len(fallback)
                rates.launch_dispatched("decode", dev, real, launch_rows - real)
            pending.append((outs, rows[: e - s], s, slot, dev))
            while len(pending) > window:
                _drain_oldest(True)
        while pending:
            _drain_oldest(False)
    except BaseException:
        for _outs, _r, _s, slot, dev in pending:
            rates.launch_drained(dev)
            if disp is not None:
                disp.release(slot)
        raise
    return out, crcs


def decode_blocks_device(payloads: List[bytes], ulens: List[int], block_size: int) -> List[bytes]:
    """Batched device decode of full-size v2 TLZ payloads; short or legacy
    blocks fall back to the numpy decoder. Thin wrapper over
    :func:`decode_batch_device` (one launch sized to the whole list)."""
    return decode_batch_device(payloads, ulens, block_size)[0]
