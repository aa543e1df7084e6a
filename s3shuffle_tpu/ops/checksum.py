"""Fully-parallel CRC32 / CRC32C / Adler32 on TPU.

The reference computes checksums byte-serially on the JVM
(java.util.zip.{CRC32, Adler32} — S3ShuffleHelper.scala:94-103,
S3ChecksumValidationStream.scala:41-66). A byte-serial scan is hostile to TPU;
instead this module exploits linearity:

**CRC (reflected, e.g. 0xEDB88320 / Castagnoli 0x82F63B78).** Over GF(2) the
CRC state update is linear in (state, data bits), so with zero initial state
the raw remainder of a message is the XOR of fixed per-(position, bit)
patterns: ``X = ⊕ bit[i,k] · W[i,k]``. XOR of selected 32-bit patterns is a
*bit-parity of a popcount*, i.e. ``X[j] = (Σ bit[i,k] · Wbits[i,k,j]) mod 2``
— which is an **int8 matmul with int32 accumulation, a native MXU operation**:
``(B, L·8) @ (L·8, 32) mod 2``. Two boundary tricks make the weight table
batch-shape-static:

- *front alignment*: leading zero bytes with zero state leave the state at
  zero, so blocks are staged right-aligned in the (B, L) buffer and one weight
  table serves every block length;
- *unshift*: trailing zero bytes move the remainder by the invertible
  one-zero-byte operator Z, so a row's first ``n`` bytes can also be
  checksummed where they lie, front-aligned and zero-masked behind: the
  whole-row remainder times ``Z^-(L-n)``, applied as one table step per set
  bit of ``L - n`` (the fused TLZ kernels' literal planes);
- *init/final fixup*: the 0xFFFFFFFF init + final XOR contribute exactly
  ``crc(0^n)``, so ``crc(block) = X ⊕ zero_crc[len(block)]`` with a host-side
  table of CRCs of zero runs.

**Adler32.** A = 1 + Σb, B = n + Σ (distance-from-end_i) · b_i (mod 65521) —
plain sums and weighted sums. Front-padding zeros contribute nothing because
weights are distances from the *end*. Weighted sums are chunked so int32
accumulation never overflows; chunks combine in int64 on the host.

Throughput is MXU/HBM-bound instead of byte-loop-bound: the bit expansion is
8 int8 per byte, so the matmul streams 8x the payload — still orders of
magnitude above the JVM's table-walk.
"""

from __future__ import annotations

import functools
import threading
from typing import Dict, Tuple

import numpy as np


POLY_CRC32 = 0xEDB88320  # java.util.zip.CRC32 (the reference's CRC32)
POLY_CRC32C = 0x82F63B78  # Castagnoli (our extension / native+TPU codec)

_ADLER_MOD = 65521
_ADLER_CHUNK = 2048  # max chunk so Σ (K-k)·255 stays far below int32


# ---------------------------------------------------------------------------
# Host-side GF(2) machinery
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _crc_table(poly: int) -> np.ndarray:
    table = np.zeros(256, dtype=np.uint32)
    for i in range(256):
        crc = i
        for _ in range(8):
            crc = (crc >> 1) ^ poly if crc & 1 else crc >> 1
        table[i] = crc
    return table


def _crc_raw_bytes(data: bytes, poly: int, state: int = 0) -> int:
    """Raw CRC register (init given, NO final xor) — reference semantics for
    weight construction."""
    table = _crc_table(poly)
    crc = state
    for b in data:
        crc = int(table[(crc ^ b) & 0xFF]) ^ (crc >> 8)
    return crc


class _WeightCache:
    """Per (poly, L): Wbits (L*8, 32) int8 and zero-run CRC table (L+1,)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._cache: Dict[Tuple[int, int], Tuple[np.ndarray, np.ndarray]] = {}

    def get(self, poly: int, length: int) -> Tuple[np.ndarray, np.ndarray]:
        key = (poly, length)
        with self._lock:
            hit = self._cache.get(key)
            if hit is not None:
                return hit
        table = _crc_table(poly).astype(np.uint32)
        # vec[k] = contribution pattern of bit k of the byte at distance d
        # from the end; start at d=0 (last byte) and step the zero-byte
        # transition A(s) = (s >> 8) ^ table[s & 0xFF] backwards through
        # positions.
        vec = table[(1 << np.arange(8)).astype(np.int64)].astype(np.uint32)
        W = np.zeros((length, 8), dtype=np.uint32)
        for d in range(length):
            W[length - 1 - d] = vec
            vec = (vec >> np.uint32(8)) ^ table[(vec & np.uint32(0xFF)).astype(np.int64)]
        bit_idx = np.arange(32, dtype=np.uint32)
        w_bits = ((W[:, :, None] >> bit_idx[None, None, :]) & np.uint32(1)).astype(np.int8)
        w_bits = w_bits.reshape(length * 8, 32)
        # crc of n zero bytes (full algorithm: init 0xFFFFFFFF + final xor)
        zero_crc = np.zeros(length + 1, dtype=np.uint32)
        state = 0xFFFFFFFF
        zero_crc[0] = state ^ 0xFFFFFFFF
        for n in range(1, length + 1):
            state = int(table[state & 0xFF]) ^ (state >> 8)
            zero_crc[n] = state ^ 0xFFFFFFFF
        entry = (w_bits, zero_crc)
        with self._lock:
            self._cache[key] = entry
        return entry


_weights = _WeightCache()


def crc_combine(crc1: int, crc2: int, len2: int, poly: int = POLY_CRC32) -> int:
    """crc(A || B) from crc(A), crc(B), len(B).

    Because init == final-xor == 0xFFFFFFFF, the init terms cancel and the
    identity collapses to ``crc(A||B) = Z^{len2}(crc1) ⊕ crc2`` where Z is the
    process-one-zero-byte linear operator (applied via O(log len2) GF(2)
    matrix squaring). Used to stitch per-block device CRCs back into one
    partition checksum."""
    return _mat_apply(_zero_op_power(poly, len2), crc1) ^ crc2


@functools.lru_cache(maxsize=None)
def _zero_op_matrix(poly: int) -> tuple:
    """The 'process one zero byte' linear operator as 32 uint32 columns."""
    table = _crc_table(poly)
    cols = []
    for bit in range(32):
        s = 1 << bit
        cols.append(int(table[s & 0xFF]) ^ (s >> 8))
    return tuple(cols)


def _mat_mul(a: tuple, b: tuple) -> tuple:
    return tuple(_mat_apply(a, col) for col in b)


def _mat_apply(mat: tuple, value: int) -> int:
    out = 0
    bit = 0
    while value:
        if value & 1:
            out ^= mat[bit]
        value >>= 1
        bit += 1
    return out


@functools.lru_cache(maxsize=4096)
def _zero_op_power_cached(poly: int, n: int) -> tuple:
    return _mat_power(_zero_op_matrix(poly), n)


def _zero_op_power(poly: int, n: int) -> tuple:
    return _zero_op_power_cached(poly, n)


def _mat_power(mat: tuple, n: int) -> tuple:
    result = tuple(1 << i for i in range(32))  # identity
    base = mat
    while n:
        if n & 1:
            result = _mat_mul(base, result)
        base = _mat_mul(base, base)
        n >>= 1
    return result


def _mat_inverse(mat: tuple) -> tuple:
    """Inverse of a 32 x 32 GF(2) matrix (columns as uint32) by Gauss-Jordan
    elimination over its rows; raises if it is singular."""
    rows = [sum(((col >> i) & 1) << j for j, col in enumerate(mat)) for i in range(32)]
    inv = [1 << i for i in range(32)]
    for c in range(32):
        p = next((r for r in range(c, 32) if (rows[r] >> c) & 1), None)
        if p is None:
            raise ValueError("singular GF(2) matrix")
        rows[c], rows[p] = rows[p], rows[c]
        inv[c], inv[p] = inv[p], inv[c]
        for r in range(32):
            if r != c and (rows[r] >> c) & 1:
                rows[r] ^= rows[c]
                inv[r] ^= inv[c]
    return tuple(sum(((inv[i] >> j) & 1) << i for i in range(32)) for j in range(32))


@functools.lru_cache(maxsize=None)
def _unshift_bits(poly: int, length: int) -> np.ndarray:
    """``(S, 32, 32)`` int8, ``S = length.bit_length()``: entry ``[i, j, k]``
    is bit k of ``Z^-(2^i)`` applied to bit j, Z the one-zero-byte operator.
    Steps 0..S-1 undo any trailing zero run up to ``length`` bytes."""
    step = _mat_inverse(_zero_op_matrix(poly))
    out = np.zeros((length.bit_length(), 32, 32), dtype=np.int8)
    for i in range(out.shape[0]):
        out[i] = (np.asarray(step, dtype=np.uint32)[:, None]
                  >> np.arange(32, dtype=np.uint32)[None, :]) & 1
        step = _mat_mul(step, step)
    return out


# ---------------------------------------------------------------------------
# Device kernels (XLA; jitted). The batch kernels take right-aligned
# (front-padded) rows; the in-graph hook takes front-aligned ones.
# ---------------------------------------------------------------------------


def _jax():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def _crc_math(data_u8, w_bits, length: int):
    """Raw (unjitted) CRC computation — shared by the standalone kernel and
    larger fused traces (see __graft_entry__)."""
    jax, jnp = _jax()
    # data_u8: (B, L) uint8, remainder of each whole row. w_bits: (L*8, 32) int8.
    b = data_u8.shape[0]
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (data_u8[:, :, None] >> shifts[None, None, :]) & jnp.uint8(1)
    bits = bits.reshape(b, length * 8).astype(jnp.int8)
    counts = jax.lax.dot_general(
        bits,
        w_bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (B, 32) — MXU int8 matmul, exact int32 accumulation
    return _parity_word(counts)


def _parity_word(counts):
    """(B, 32) int32 popcounts -> (B,) uint32 of their parities, bit j from
    column j."""
    _jax_mod, jnp = _jax()
    parity = (counts & 1).astype(jnp.uint32)
    return jnp.sum(parity << jnp.arange(32, dtype=jnp.uint32)[None, :], axis=1, dtype=jnp.uint32)


def _unshift_math(raw, pad, unshift_bits):
    """Each raw remainder ``raw[b]`` times ``Z^-pad[b]``: the remainder of a
    row with its ``pad[b]`` trailing zero bytes taken off. One step per bit
    of ``pad`` (``unshift_bits``: :func:`_unshift_bits`), each a (B, 32) x
    (32, 32) parity matmul kept where that bit is set; pad 0 passes ``raw``
    through."""
    jax, jnp = _jax()
    bit_idx = jnp.arange(32, dtype=jnp.uint32)
    for i in range(unshift_bits.shape[0]):
        bits = ((raw[:, None] >> bit_idx[None, :]) & jnp.uint32(1)).astype(jnp.int8)
        counts = jax.lax.dot_general(
            bits,
            unshift_bits[i],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        raw = jnp.where(((pad >> i) & 1) == 1, _parity_word(counts), raw)
    return raw


@functools.lru_cache(maxsize=8)
def _crc_kernel(length: int):
    jax, _jnp = _jax()
    return jax.jit(functools.partial(_crc_math, length=length))


def crc32_batch(blocks, lengths, poly: int = POLY_CRC32C, block_len: int | None = None) -> np.ndarray:
    """CRC of each block in a batch, on device.

    ``blocks``: (B, L) uint8, each row right-aligned (front-padded with
    zeros); ``lengths``: (B,) true byte counts. Returns (B,) uint32 CRCs with
    standard init/final-xor semantics (matches zlib.crc32 for POLY_CRC32).
    """
    blocks = np.ascontiguousarray(blocks, dtype=np.uint8)
    lengths = np.asarray(lengths, dtype=np.int64)
    length = blocks.shape[1]
    if block_len is not None and block_len != length:
        raise ValueError(f"block_len {block_len} != staged width {length}")
    _, zero_crc = _weights.get(poly, length)
    w_bits = _device_weights(poly, length)  # cached on-device, shipped once
    x = np.asarray(_crc_kernel(length)(blocks, w_bits))  # raw remainders, zero-init
    return (x ^ zero_crc[lengths]).astype(np.uint32)


_host_crc32c_fn = None


def host_crc(data, poly: int) -> int:
    """Full-algorithm HOST CRC for the two supported reflected polynomials —
    the small-slice companion of the fused device kernels (frame headers and
    TLZ metadata prefixes get hashed here and stitched around the device
    remainders with :func:`crc_combine`)."""
    global _host_crc32c_fn
    if poly == POLY_CRC32:
        import zlib

        return zlib.crc32(bytes(data)) & 0xFFFFFFFF
    if poly == POLY_CRC32C:
        if _host_crc32c_fn is None:
            from s3shuffle_tpu.utils.checksums import _crc32c_fn

            _host_crc32c_fn = _crc32c_fn()
        return _host_crc32c_fn(bytes(data)) & 0xFFFFFFFF
    raise ValueError(f"no host CRC for poly {poly:#x}")


def zero_run_crcs(poly: int, length: int) -> np.ndarray:
    """Host-side fixup table: ``crc(0^n)`` for ``n in [0, length]`` (full
    init/final-xor semantics). Raw zero-init remainders from the device
    kernels become true CRCs via ``raw ^ zero_run_crcs(poly, L)[n]`` — the
    front-alignment trick documented in the module header. Public because
    the fused TLZ encode kernel (ops/tlz.py) applies the fixup host-side to
    the remainders it gets back with the encode planes."""
    _w, zero_crc = _weights.get(poly, length)
    return zero_crc


def raw_crc_graph_fn(poly: int, length: int):
    """``(fn, tables)``: a traceable ``fn(data_u8, n_valid, w_bits, unshift)
    -> (B,) uint32``, the raw zero-init remainder of each front-aligned
    ``(B, length)`` row's first ``n_valid[b]`` bytes (the bytes behind them
    must be zero), safe to call INSIDE a larger jit trace — the hook the
    fused TLZ kernels use to fold the CRC pass into their own launch — and
    ``tables(device)``, its ``(w_bits, unshift)`` tables resident on
    ``device``, shipped once per (poly, device). ``fn`` is the MXU
    bit-matmul of each whole row, then :func:`_unshift_math` by
    ``length - n_valid``; a right-aligned row with ``n_valid = length``
    gives its suffix's remainder. Callers pass the tables as jit ARGUMENTS
    placed on the launch's own device: closed over, the (8L, 32) weight
    table (64 MiB at 256 KiB blocks) is constant-folded into every program,
    ~45 s of TPU compile per launch shape; placed on another chip, it is
    copied over on every launch."""
    def fn(data_u8, n_valid, w_bits, unshift):
        return _unshift_math(_crc_math(data_u8, w_bits, length), length - n_valid, unshift)

    return fn, lambda device: (
        _device_weights(poly, length, device),
        _device_unshift(poly, length, device),
    )


@functools.lru_cache(maxsize=32)
def _device_weights(poly: int, length: int, device=None):
    """Weight table as a jax array resident on ``device`` (None: the default
    device) — avoids re-shipping L*8*32 bytes over the host link on every
    batch; one copy per chip under the mesh dispatcher."""
    jax, _jnp = _jax()
    w_bits, _zero = _weights.get(poly, length)
    return jax.device_put(w_bits, device)


@functools.lru_cache(maxsize=32)
def _device_unshift(poly: int, length: int, device=None):
    """:func:`_unshift_bits` as a jax array resident on ``device``."""
    jax, _jnp = _jax()
    return jax.device_put(_unshift_bits(poly, length), device)


@functools.lru_cache(maxsize=8)
def _adler_kernel(length: int):
    jax, jnp = _jax()
    n_chunks = (length + _ADLER_CHUNK - 1) // _ADLER_CHUNK
    padded = n_chunks * _ADLER_CHUNK

    @jax.jit
    def kernel(data_u8):
        b = data_u8.shape[0]
        data = data_u8.astype(jnp.int32)
        if padded != length:
            data = jnp.pad(data, ((0, 0), (padded - length, 0)))  # front-pad
        chunks = data.reshape(b, n_chunks, _ADLER_CHUNK)
        s_c = jnp.sum(chunks, axis=2, dtype=jnp.int32)  # (B, C)
        w = jnp.arange(_ADLER_CHUNK, 0, -1, dtype=jnp.int32)  # K..1 (dist from chunk end)
        t_c = jnp.sum(chunks * w[None, None, :], axis=2, dtype=jnp.int32)
        return s_c, t_c

    return kernel


def adler32_batch(blocks, lengths) -> np.ndarray:
    """Adler32 of each right-aligned block; matches zlib.adler32."""
    blocks = np.ascontiguousarray(blocks, dtype=np.uint8)
    lengths = np.asarray(lengths, dtype=np.int64)
    b, length = blocks.shape
    s_c, t_c = (np.asarray(x, dtype=np.int64) for x in _adler_kernel(length)(blocks))
    n_chunks = s_c.shape[1]
    # distance (bytes) from each chunk's end to the message end, per chunk
    padded = n_chunks * _ADLER_CHUNK
    dist_after = padded - _ADLER_CHUNK * (np.arange(n_chunks, dtype=np.int64) + 1)
    total_s = s_c.sum(axis=1)
    total_t = (t_c + s_c * dist_after[None, :]).sum(axis=1)
    a = (1 + total_s) % _ADLER_MOD
    bb = (lengths + total_t) % _ADLER_MOD
    return ((bb << 16) | a).astype(np.uint32)


def stage_right_aligned(chunks, block_len: int | None = None):
    """Stage a list of byte strings into a right-aligned (B, L) uint8 batch
    (the layout both kernels expect). Returns (batch, lengths)."""
    lengths = np.array([len(c) for c in chunks], dtype=np.int64)
    length = block_len or (int(lengths.max()) if len(chunks) else 0)
    if len(lengths) and int(lengths.max()) > length:
        raise ValueError("chunk longer than block_len")
    batch = np.zeros((len(chunks), length), dtype=np.uint8)
    for i, c in enumerate(chunks):
        if len(c):
            batch[i, length - len(c):] = np.frombuffer(c, dtype=np.uint8)
    return batch, lengths
