"""What a shuffle left in the store, read back with no code of the program.

Objects follow the reference's layout (S3ShuffleHelper.scala:44-59): per map,
``shuffle_<s>_<m>_0.data`` holds every reduce partition back to back as
frames ``[u8 codec_id][u32le ulen][u32le clen][payload]``,
``shuffle_<s>_<m>_0.index`` the cumulative partition offsets and
``shuffle_<s>_<m>_0.checksum.<ALG>`` one checksum per partition, both as
big-endian int64. The checksum is the ``java.util.zip`` value of the
partition's stored bytes for ADLER32 and CRC32 (the reference's two), or
CRC32C.

The program adds two optional trailers to the index, each four int64 words
that start with a magic no cumulative offset can reach: a skew trailer
``[SKEW_MAGIC, flags, split_bytes, 0]`` and after it, always last, a
stripe-geometry trailer ``[GEOMETRY_MAGIC, m, k, chunk_bytes]``. With the
geometry trailer, ``m`` parity sidecars ``shuffle_<s>_<m>_par<i>.parity``
hold a 64-byte header ``[PARITY_MAGIC, 1, shuffle_id, i, m, k, chunk_bytes,
payload_len]`` (big-endian int64) and then, per stripe group of ``k``
chunks of the data object, one slice ``XOR_j alpha^(i*j) * chunk_j`` over
GF(2^8) with polynomial 0x11D; the final group is zero-padded to ``k`` full
chunks and its slice cut to the group's first chunk.
"""

from __future__ import annotations

import os
import re
import struct
import zlib

import numpy as np

HEADER = struct.Struct("<BII")
TLZ_CODEC = 4  # framing.CODEC_IDS["tpu-lz"]
_NAME = re.compile(
    r"^shuffle_(\d+)_(\d+)_(?:0\.(data|index|checksum\.\w+)|(par\d+)\.parity)$")

# the program's wire constants (coding/parity.py:65,75; skew.py:55)
PARITY_MAGIC = 0x5333504152495459
PARITY_VERSION = 1
PARITY_HEADER_BYTES = 64
GEOMETRY_MAGIC = 0x5333504152474D54
SKEW_MAGIC = 0x53335348534B4557
TRAILER_WORDS = 4
GF_POLY = 0x11D


def shuffle_objects(root: str, shuffle_id: int) -> dict:
    """{map_id: {"data": path, "index": path, "checksum.<ALG>": path,
    "par<i>": path}}."""
    maps: dict = {}
    for dirpath, _dirs, files in os.walk(root):
        for name in files:
            m = _NAME.match(name)
            if m and int(m.group(1)) == shuffle_id:
                key = m.group(3) or m.group(4)
                maps.setdefault(int(m.group(2)), {})[key] = os.path.join(dirpath, name)
    return maps


def read_index(path: str) -> tuple:
    """(partition offsets, geometry) of an index object. The geometry
    trailer is peeled first, then the skew trailer before it, each only
    where its magic stands at word -4 and two offsets remain. ``geometry``
    is ``(m, k, chunk_bytes)``, or None without the trailer."""
    words = np.fromfile(path, dtype=">i8").astype(np.int64)
    geometry = None
    if len(words) >= TRAILER_WORDS + 2 and int(words[-TRAILER_WORDS]) == GEOMETRY_MAGIC:
        geometry = tuple(int(w) for w in words[-3:])
        words = words[:-TRAILER_WORDS]
    if len(words) >= TRAILER_WORDS + 2 and int(words[-TRAILER_WORDS]) == SKEW_MAGIC:
        words = words[:-TRAILER_WORDS]
    return words, geometry


def frame_stats(path: str, block_size: int) -> dict:
    """Walk a data object's frame headers. A frame of a full block went
    through the device encode (the forced gate sends every full block
    there; a partition's short tail is host-encoded by design), and a full
    TLZ frame goes through the device decode."""
    st = {"bytes": 0, "frames": 0, "full_frames": 0, "full_tlz_frames": 0,
          "partial_frames": 0, "encode_work_bytes": 0, "decode_work_bytes": 0}
    with open(path, "rb") as f:
        size = os.fstat(f.fileno()).st_size
        pos = 0
        while pos < size:
            f.seek(pos)
            head = f.read(HEADER.size)
            if len(head) < HEADER.size:
                raise IOError(f"{path}: truncated frame header at {pos}")
            codec_id, ulen, clen = HEADER.unpack(head)
            pos += HEADER.size + clen
            st["frames"] += 1
            if ulen == block_size:
                st["full_frames"] += 1
                st["encode_work_bytes"] += ulen + clen
                if codec_id == TLZ_CODEC:
                    st["full_tlz_frames"] += 1
                    st["decode_work_bytes"] += clen + ulen
            else:
                st["partial_frames"] += 1
        if pos != size:
            raise IOError(f"{path}: frames overrun the object ({pos} > {size})")
        st["bytes"] = size
    return st


def shuffle_stats(root: str, shuffle_id: int, block_size: int) -> dict:
    """Frame counts and bytes of the data objects; ``parity_bytes`` is what
    the parity sidecars hold, headers included."""
    total: dict = {"parity_bytes": 0}
    for objs in shuffle_objects(root, shuffle_id).values():
        for k, v in frame_stats(objs["data"], block_size).items():
            total[k] = total.get(k, 0) + v
        total["parity_bytes"] += sum(os.path.getsize(p) for key, p in objs.items()
                                     if key.startswith("par"))
    return total


def _checksum(algorithm: str):
    if algorithm == "CRC32C":
        import google_crc32c

        return google_crc32c.value
    if algorithm == "CRC32":
        return zlib.crc32
    if algorithm == "ADLER32":
        return zlib.adler32
    raise ValueError(f"no checksum {algorithm!r}: ADLER32, CRC32 or CRC32C")


def checksum_mismatches(root: str, shuffle_id: int, algorithm: str) -> tuple:
    """(partitions checked, partitions whose stored checksum differs from
    ``algorithm`` over the partition's stored bytes: CRC32C by
    ``google_crc32c``, CRC32 and ADLER32 by ``zlib``). A missing checksum
    object of that algorithm counts every partition of its map."""
    value = _checksum(algorithm)
    checked = wrong = 0
    for objs in shuffle_objects(root, shuffle_id).values():
        offsets, _geometry = read_index(objs["index"])
        n_parts = len(offsets) - 1
        checked += n_parts
        path = objs.get(f"checksum.{algorithm}")
        if path is None:
            wrong += n_parts
            continue
        stored = np.fromfile(path, dtype=">i8").astype(np.int64)
        with open(objs["data"], "rb") as f:
            data = f.read()
        for p in range(n_parts):
            if p >= len(stored) or int(stored[p]) != value(data[offsets[p]:offsets[p + 1]]):
                wrong += 1
    return checked, wrong


def gf_mul(a: int, b: int) -> int:
    """a * b in GF(2^8) over ``GF_POLY``, by shift and xor."""
    out = 0
    while b:
        if b & 1:
            out ^= a
        a <<= 1
        if a & 0x100:
            a ^= GF_POLY
        b >>= 1
    return out


def gf_scale(c: int, x: np.ndarray) -> np.ndarray:
    """c * x for every byte of ``x``, by shift and xor over the bits of c."""
    out = np.zeros_like(x)
    x = x.copy()
    while c:
        if c & 1:
            out ^= x
        high = x & 0x80
        x <<= 1
        x[high != 0] ^= GF_POLY & 0xFF
        c >>= 1
    return out


def parity_coefficient(i: int, j: int) -> int:
    """alpha^(i*j), alpha = 2."""
    out = 1
    for _ in range(i * j):
        out = gf_mul(out, 2)
    return out


def encode_parity(data: bytes, m: int, k: int, chunk: int) -> list:
    """The ``m`` parity payloads of ``data`` (headers excluded), one slice
    per stripe group, as the sidecars hold them."""
    n_groups = -(-len(data) // (k * chunk))
    plane = np.zeros(n_groups * k * chunk, dtype=np.uint8)
    plane[:len(data)] = np.frombuffer(data, dtype=np.uint8)
    plane = plane.reshape(n_groups, k, chunk)
    last = min(chunk, len(data) - (n_groups - 1) * k * chunk) if n_groups else 0
    out = []
    for i in range(m):
        par = np.zeros((n_groups, chunk), dtype=np.uint8)
        for j in range(k):
            par ^= gf_scale(parity_coefficient(i, j), plane[:, j])
        out.append(par.reshape(-1)[:max(0, (n_groups - 1) * chunk + last)].tobytes())
    return out


def parity_mismatches(root: str, shuffle_id: int, stated=None) -> tuple:
    """(stripe groups checked, groups whose parity differs from a plain
    encode of the data object). The geometry is the index trailer's;
    ``stated`` is the configuration's ``(m, k, chunk_bytes)`` or None. A map
    whose trailer differs from what is stated counts all of its groups, and
    so does a sidecar that is missing or carries another header or length.
    A store with no trailer and nothing stated checks nothing."""
    checked = wrong = 0
    for objs in shuffle_objects(root, shuffle_id).values():
        _offsets, geometry = read_index(objs["index"])
        want_geometry = stated or geometry
        if want_geometry is None:
            continue
        m, k, chunk = want_geometry
        with open(objs["data"], "rb") as f:
            data = f.read()
        n_groups = -(-len(data) // (k * chunk))
        checked += n_groups
        if geometry != want_geometry:
            wrong += n_groups
            continue
        bad = np.zeros(n_groups, dtype=bool)
        for i, want in enumerate(encode_parity(data, m, k, chunk)):
            got = b""
            if f"par{i}" in objs:
                with open(objs[f"par{i}"], "rb") as f:
                    got = f.read()
            header, got = got[:PARITY_HEADER_BYTES], got[PARITY_HEADER_BYTES:]
            if len(header) == PARITY_HEADER_BYTES:
                header = [int(w) for w in np.frombuffer(header, dtype=">i8")]
            if header != [PARITY_MAGIC, PARITY_VERSION, shuffle_id, i, m, k, chunk,
                          len(data)] or len(got) != len(want):
                bad[:] = True
                continue
            pad = n_groups * chunk - len(want)
            diff = (np.frombuffer(got + bytes(pad), dtype=np.uint8)
                    != np.frombuffer(want + bytes(pad), dtype=np.uint8))
            bad |= diff.reshape(n_groups, chunk).any(axis=1)
        wrong += int(bad.sum())
    return checked, wrong
