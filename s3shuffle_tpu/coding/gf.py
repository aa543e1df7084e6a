"""GF(2^8) arithmetic for the coded shuffle plane.

Coded TeraSort / Coded MapReduce (PAPERS.md) trade cheap encode-side
redundancy for shuffle-time robustness; the arithmetic that makes the trade
cheap is byte-wise GF(2^8): parity segment *i* over the k data chunks of one
stripe group is ``P_i = XOR_j gfmul(C[i][j], D_j)``, and any k of the
``k + m`` segments reconstruct the group by solving a small linear system
over the field.

Coefficients are the classic Vandermonde rows ``C[i][j] = alpha^(i*j)``:
row 0 is all ones — **plain XOR**, the RAID-5 P parity and the m=1 fast
path — and row 1 is the RAID-6 Q polynomial, so the m<=2 configurations are
provably MDS. Higher m keeps the same rows; the decoder guards against the
(rare, large-k) singular survivor subsets by trying the other parity
combinations before giving up — reconstruction is best-effort by contract
(the caller falls back to today's logged-EOF/ChecksumError behavior).

Encode is **batched**: one call takes every pending stripe group as a
``[groups, k, chunk]`` uint8 array. The host path is vectorized numpy table
lookups; when JAX imports (the PR-8 device codec toolchain) and the batch is
big enough to amortize a dispatch, the same math runs as a jitted
table-gather kernel — with the host path as the always-correct fallback,
pinned after the first device failure (the device-codec pipeline's policy).
"""

from __future__ import annotations

import functools
import logging
import threading
from itertools import combinations
from typing import Dict, List, Optional, Sequence

import numpy as np

logger = logging.getLogger("s3shuffle_tpu.coding")

#: AES-ish primitive polynomial x^8+x^4+x^3+x^2+1 — the standard RS choice.
_POLY = 0x11D

# exp table doubled so exp[log a + log b] never needs a mod in multiply
_EXP = np.zeros(512, dtype=np.uint8)
_LOG = np.zeros(256, dtype=np.int32)
_x = 1
for _i in range(255):
    _EXP[_i] = _x
    _LOG[_x] = _i
    _x <<= 1
    if _x & 0x100:
        _x ^= _POLY
_EXP[255:510] = _EXP[:255]
del _x, _i


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(_EXP[int(_LOG[a]) + int(_LOG[b])])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("GF(256) inverse of 0")
    return int(_EXP[255 - int(_LOG[a])])


def gf_mul_bytes(coef: int, data: np.ndarray) -> np.ndarray:
    """``gfmul(coef, byte)`` over a uint8 array (any shape), vectorized."""
    if coef == 0:
        return np.zeros_like(data)
    if coef == 1:
        return data.copy()
    out = _EXP[_LOG[data] + int(_LOG[coef])]
    out[data == 0] = 0
    return out


def parity_coefficients(segments: int, stripe_k: int) -> np.ndarray:
    """The ``[m, k]`` Vandermonde coefficient matrix ``alpha^(i*j)``.
    Row 0 is all ones (XOR parity)."""
    if segments < 1 or stripe_k < 1:
        raise ValueError("parity needs m >= 1, k >= 1")
    if segments + stripe_k > 255:
        raise ValueError("GF(256) coding supports k + m <= 255")
    i = np.arange(segments).reshape(-1, 1)
    j = np.arange(stripe_k).reshape(1, -1)
    return _EXP[(i * j) % 255].astype(np.uint8)


# ---------------------------------------------------------------------------
# Batched encode: host numpy, optional JAX kernel
# ---------------------------------------------------------------------------

#: below this many payload bytes per batch the dispatch overhead of the
#: device kernel outweighs the math — stay on the host path
_DEVICE_MIN_BYTES = 1 << 20

_device_lock = threading.Lock()
_device_broken = False


def _mesh_dispatcher():
    """The armed multi-chip dispatcher (parallel/dispatch.py), or None for
    single-device placement. Import kept lazy and failure-proof: the host
    parity path must never pull accelerator plumbing in."""
    try:
        from s3shuffle_tpu.parallel import dispatch

        return dispatch.get_dispatcher()
    except Exception:  # noqa: BLE001 — any import/arming failure = off
        logger.debug("mesh dispatcher unavailable; striping disabled",
                     exc_info=True)
        return None


def _encode_host(chunks: np.ndarray, coefs: np.ndarray) -> np.ndarray:
    """``[G, k, L] x [m, k] -> [G, m, L]`` on the host: one vectorized
    table-lookup multiply + XOR accumulate per (i, j) coefficient."""
    groups, k, length = chunks.shape
    m = coefs.shape[0]
    out = np.zeros((groups, m, length), dtype=np.uint8)
    for i in range(m):
        if (coefs[i] == 1).all():
            # XOR fast path (row 0 always; any all-ones row)
            out[:, i, :] = np.bitwise_xor.reduce(chunks, axis=1)
            continue
        acc = np.zeros((groups, length), dtype=np.uint8)
        for j in range(k):
            acc ^= gf_mul_bytes(int(coefs[i, j]), chunks[:, j, :])
        out[:, i, :] = acc
    return out


@functools.lru_cache(maxsize=8)
def _device_kernel(m: int, k: int):
    """Jitted batched GF multiply-accumulate over the log/exp tables —
    compiled once per (m, k) shape family."""
    import jax
    import jax.numpy as jnp

    exp = jnp.asarray(_EXP)
    log = jnp.asarray(_LOG)

    def kernel(chunks, coefs):  # [G, k, L] u8, [m, k] u8 -> [G, m, L] u8
        logs = log[chunks]  # [G, k, L] i32
        zero = chunks == 0
        outs = []
        for i in range(m):
            acc = None
            for j in range(k):
                c = coefs[i, j]
                term = jnp.where(
                    (c == 0) | zero[:, j, :],
                    jnp.uint8(0),
                    exp[logs[:, j, :] + log[c]],
                )
                acc = term if acc is None else acc ^ term
            outs.append(acc)
        return jnp.stack(outs, axis=1)

    return jax.jit(kernel)


def _encode_striped(
    chunks: np.ndarray, coefs: np.ndarray, disp
) -> np.ndarray:
    """Cross-chip parity placement: split the group axis into one slice per
    dispatcher lane and encode each slice on the least-loaded device, so
    every chip encodes parity for its neighbors' stripe groups (the Coded
    MapReduce placement) instead of device 0 encoding everything. A
    single-group batch still rides the dispatcher — concurrent degraded /
    hot-fanout reconstructions then spread across all chips. Byte-identical
    to the unstriped kernel (pure per-group math)."""
    import jax

    m, k = coefs.shape
    groups = chunks.shape[0]
    n_lanes = max(1, min(disp.n_devices, groups))
    bounds = np.linspace(0, groups, n_lanes + 1).astype(np.int64)
    outs = []
    slots = []
    try:
        for i in range(n_lanes):
            lo, hi = int(bounds[i]), int(bounds[i + 1])
            if lo == hi:
                continue
            slot = disp.acquire()
            slots.append(slot)
            dev = disp.device(slot)
            outs.append(
                _device_kernel(m, k)(
                    jax.device_put(chunks[lo:hi], dev),
                    jax.device_put(coefs, dev),
                )
            )
        # materialize AFTER every lane launched: the table-kernel slices run
        # concurrently across their devices and drain in order
        parts = [np.asarray(o) for o in outs]
    finally:
        for slot in slots:
            disp.release(slot)
    return np.concatenate(parts, axis=0)


def _encode_device(chunks: np.ndarray, coefs: np.ndarray) -> Optional[np.ndarray]:
    global _device_broken
    from s3shuffle_tpu.ops import rates

    if _device_broken:
        return None
    try:
        m, k = coefs.shape
        disp = _mesh_dispatcher()
        if disp is not None:
            out = _encode_striped(chunks, coefs, disp)
        else:
            out = np.asarray(_device_kernel(m, k)(chunks, coefs))
        rates.count_device_batch("parity", True)
        return out
    except Exception as e:  # noqa: BLE001 — any device/toolchain failure
        rates.count_device_batch("parity", False)
        with _device_lock:
            if not _device_broken:
                _device_broken = True
                rates.count_host_pin("parity")
                logger.warning(
                    "parity device kernel unavailable, pinning host encode: %s", e
                )
        return None


def encode_groups(chunks: np.ndarray, coefs: np.ndarray) -> np.ndarray:
    """Encode a batch of stripe groups: ``chunks[G, k, L]`` uint8 ->
    ``parity[G, m, L]`` uint8. The device kernel runs only when the batch is
    big enough to amortize a dispatch AND the measured-rate gate says the
    chip has proven faster than the host table encode (ops/rates.py — no
    probe data means host); host numpy otherwise (byte-identical by the unit
    property test)."""
    from s3shuffle_tpu.ops import rates

    chunks = np.ascontiguousarray(chunks, dtype=np.uint8)
    if chunks.nbytes >= _DEVICE_MIN_BYTES and rates.select("gf_encode"):
        out = _encode_device(chunks, coefs)
        if out is not None:
            return out
    return _encode_host(chunks, coefs)


# ---------------------------------------------------------------------------
# Decode: recover erased data chunks of one stripe group
# ---------------------------------------------------------------------------


def _gauss_solve(
    matrix: List[List[int]], rhs: List[np.ndarray]
) -> Optional[List[np.ndarray]]:
    """Solve ``A x = b`` over GF(256); A is a small list-of-ints matrix, b a
    list of equal-length uint8 arrays. Returns the solution arrays or None
    when A is singular."""
    n = len(matrix)
    a = [row[:] for row in matrix]
    b = [v.copy() for v in rhs]
    for col in range(n):
        pivot = next((r for r in range(col, n) if a[r][col] != 0), None)
        if pivot is None:
            return None
        if pivot != col:
            a[col], a[pivot] = a[pivot], a[col]
            b[col], b[pivot] = b[pivot], b[col]
        inv = gf_inv(a[col][col])
        if inv != 1:
            a[col] = [gf_mul(inv, v) for v in a[col]]
            b[col] = gf_mul_bytes(inv, b[col])
        for r in range(n):
            if r == col or a[r][col] == 0:
                continue
            f = a[r][col]
            a[r] = [a[r][c] ^ gf_mul(f, a[col][c]) for c in range(n)]
            b[r] = b[r] ^ gf_mul_bytes(f, b[col])
    return b


def recover_group(
    stripe_k: int,
    coefs: np.ndarray,
    data_present: Dict[int, np.ndarray],
    parity_present: Dict[int, np.ndarray],
    want: Sequence[int],
) -> Optional[Dict[int, np.ndarray]]:
    """Recover the ``want`` data chunks of one stripe group from any
    sufficient subset of surviving segments.

    ``data_present`` maps data-chunk position -> uint8 array (all the same
    length L, already zero-padded); ``parity_present`` maps parity index ->
    its group chunk. Returns ``{position: chunk}`` for every requested
    position, or None when the survivors cannot determine them (fewer than
    k segments, or — for m >= 3 Vandermonde — every parity subset singular).
    """
    unknown = sorted(set(range(stripe_k)) - set(data_present))
    missing_wanted = [w for w in want if w not in data_present]
    if not missing_wanted:
        return {w: data_present[w] for w in want}
    need = len(unknown)
    if need > len(parity_present):
        return None
    present_pos = sorted(data_present)
    stacked = (
        np.stack([data_present[j] for j in present_pos])
        if present_pos
        else None
    )
    for combo in combinations(sorted(parity_present), need):
        a = [[int(coefs[i][j]) for j in unknown] for i in combo]
        if stacked is None:
            b = [parity_present[i].copy() for i in combo]
        else:
            # the survivors' contribution to each combo parity is itself a
            # batched GF encode over the present chunks — routed through
            # encode_groups so big degraded reads ride the same rate-gated,
            # dispatcher-striped kernel as the write-side parity plane
            sub = np.array(
                [[int(coefs[i][j]) for j in present_pos] for i in combo],
                dtype=np.uint8,
            )
            contrib = encode_groups(stacked[None, :, :], sub)[0]
            b = [parity_present[i] ^ contrib[r] for r, i in enumerate(combo)]
        sol = _gauss_solve(a, b)
        if sol is not None:
            solved = dict(zip(unknown, sol))
            solved.update(data_present)
            return {w: solved[w] for w in want}
    return None
