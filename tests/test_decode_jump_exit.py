"""Device TLZ decode stops pointer jumping once every source chain of the
launch has resolved: byte identity with the numpy decoder, and the rounds
each launch runs, on blocks whose chains need few, some and all rounds."""

import io

import numpy as np
import pytest

from s3shuffle_tpu.batch import RecordBatch
from s3shuffle_tpu.metrics import registry as mreg
from s3shuffle_tpu.ops import tlz
from s3shuffle_tpu.ops.checksum import POLY_CRC32C
from s3shuffle_tpu.serializer import ColumnarKVSerializer
from s3shuffle_tpu.utils.checksums import crc32c_py

BS = tlz.MAX_BLOCK  # the worst-case chain needs the full-size block
CAP = tlz._jump_rounds(BS)
HEX = np.frombuffer(b"0123456789ABCDEF", dtype=np.uint8)


def _gensort_block() -> bytes:
    """The first block of a column-framed stream of gensort rows (10 B random
    key; a value of fixed breaks, a hex row id and a hex filler written four
    times), as a terasort map task writes them."""
    n = 3000
    rand = np.random.default_rng(0).integers(0, 256, (n, 16), dtype=np.uint8)
    rows = np.empty((n, 100), dtype=np.uint8)
    rows[:, :10] = rand[:, :10]
    rows[:, 10:12] = (0x00, 0x11)
    rows[:, 12:28] = ord("0")
    shifts = np.arange(60, -4, -4, dtype=np.uint64)
    ids = np.arange(n, dtype=np.uint64)
    rows[:, 28:44] = HEX[((ids[:, None] >> shifts) & np.uint64(0xF)).astype(np.intp)]
    rows[:, 44:48] = (0x88, 0x99, 0xAA, 0xBB)
    nibbles = np.stack([rand[:, 10:] >> 4, rand[:, 10:] & 0xF], axis=2).reshape(n, 12)
    rows[:, 48:96] = np.repeat(HEX[nibbles], 4, axis=1)
    rows[:, 96:] = (0xCC, 0xDD, 0xEE, 0xFF)
    sink = io.BytesIO()
    writer = ColumnarKVSerializer(column_frames=True).new_write_stream(sink)
    for s in range(0, n, 1000):
        chunk = rows[s : s + 1000]
        writer.write_batch(RecordBatch.from_fixed(
            len(chunk), 10, 90,
            np.ascontiguousarray(chunk[:, :10]).reshape(-1),
            np.ascontiguousarray(chunk[:, 10:]).reshape(-1),
        ))
    writer.flush()
    return sink.getvalue()[:BS]


def _rounds_needed(payload: bytes) -> int:
    """Rounds a launch of this block alone runs, from a sequential walk of
    its source chains: k doublings reach 2**k hops, and the round after the
    last that moves a pointer shows nothing moved, up to the cap."""
    _v, n_groups, is_match, is_cont, is_split, dists, ks, _lits = (
        tlz._parse_payload(payload, BS)
    )
    dist, _start, split_idx, kvals, d_prev, d_next = tlz._validate_planes_v2(
        n_groups, is_match, is_cont, is_split, dists, ks
    )
    lanes = np.arange(tlz.GROUP)
    back = np.zeros((n_groups, tlz.GROUP), dtype=np.int64)
    back[is_match] = dist[is_match][:, None]
    if len(split_idx):
        back[split_idx] = np.where(lanes < kvals[:, None], d_prev[:, None], d_next[:, None])
    depth = [0] * BS
    for pos, d in enumerate(back.reshape(-1).tolist()):
        if d:
            depth[pos] = depth[pos - d] + 1
    hops = max(depth)
    return 1 if hops == 0 else min((hops - 1).bit_length() + 1, CAP)


@pytest.fixture(scope="module")
def blocks():
    literal = np.random.default_rng(1).integers(0, 256, BS, dtype=np.uint8).tobytes()
    raw = {"gensort": _gensort_block(), "run": b"\x07" * BS, "literal": literal}
    payloads = {k: tlz._assemble_payload_numpy(v) for k, v in raw.items()}
    return {k: (raw[k], p, _rounds_needed(p)) for k, p in payloads.items()}


@pytest.fixture
def metrics_on():
    mreg.REGISTRY.reset_values()
    mreg.enable()
    yield
    mreg.disable()
    mreg.REGISTRY.reset_values()


def _counter(name: str, **labels) -> float:
    series = mreg.REGISTRY.snapshot().get(name, {}).get("series", [])
    return sum(s["value"] for s in series
               if all(s.get("labels", {}).get(k) == v for k, v in labels.items()))


@pytest.mark.parametrize("poly", [None, POLY_CRC32C], ids=["decode", "fused_crc"])
@pytest.mark.parametrize("kinds,batch_rows", [
    (("gensort",), 1),
    (("run",), 1),
    (("literal",), 1),
    (("gensort", "run", "literal"), 3),  # one launch: its deepest row decides
    (("literal", "gensort", "run"), 1),  # separate launches, each its own
], ids=["gensort", "run", "literal", "one_launch", "separate_launches"])
def test_decode_stops_jumping_once_the_launch_has_resolved(
    blocks, metrics_on, kinds, batch_rows, poly
):
    raws, payloads, needs = zip(*(blocks[k] for k in kinds))
    # gensort values stop early; a one-byte run is a distance-1 chain
    # across the block, which runs the cap; literals resolve at once
    assert list(needs) == [{"gensort": 10, "run": CAP, "literal": 1}[k] for k in kinds]
    launches = [needs[s : s + batch_rows] for s in range(0, len(needs), batch_rows)]

    out, crcs = tlz.decode_batch_device(list(payloads), [BS] * len(payloads), BS,
                                        batch_rows=batch_rows, poly=poly)

    assert out == list(raws)
    assert out == [tlz.decode_payload_numpy(p, BS, use_native=False) for p in payloads]
    if poly is not None:
        assert crcs == [crc32c_py(p) for p in payloads]
    assert _counter("codec_launches_total", op="decode") == len(launches)
    assert _counter("codec_decode_jump_rounds_total") == sum(max(l) for l in launches)
    assert all(max(l) <= CAP for l in launches)
