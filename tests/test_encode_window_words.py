"""The device TLZ encode compares 8-byte windows as pairs of packed 32-bit
words: every plane and payload stays byte-identical to the numpy encoder on
the cases a packed compare could get wrong (bytes with the word's sign bit
set, each split point, suffix sources before byte 0, overlapping and
longest distances, windows at the block's end, small launches), and the
lowered program gathers one word pair per group, never one element per
byte."""

import functools
import re

import numpy as np
import pytest

from s3shuffle_tpu.ops import tlz

BS = 4096
ROWS, WIDTH = 64, tlz.MAX_BLOCK  # the codec's launch: 64 rows of 256 KiB


def _copy(buf: np.ndarray, src: int, dst: int, n: int) -> None:
    """LZ77 copy, byte by byte, so a source may overlap its destination."""
    for i in range(n):
        buf[dst + i] = buf[src + i]


def _random(seed: int, size: int = BS, lo: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(lo, 256, size, dtype=np.uint8)


def _collage(seed: int, size: int = BS, lo: int = 0) -> np.ndarray:
    """Random bytes in [lo, 256), then back-to-back copies of earlier spans
    at assorted distances and lengths that are not multiples of a group:
    matches, continuations and splits at every lane."""
    rng = np.random.default_rng(seed)
    buf = _random(seed, size, lo)
    pos = 256
    while pos < size - 128:
        n = int(rng.integers(9, 120))
        d = int(rng.integers(1, pos))
        _copy(buf, pos - d, pos, n)
        pos += n
    return buf


def _split_at(k: int) -> np.ndarray:
    """Group 131 straddles two copies: its first k bytes continue a copy at
    distance 824, the rest start one at distance 448 + k."""
    buf = _random(10 + k)
    dest = 1024
    _copy(buf, 200, dest, 24 + k)
    _copy(buf, 600, dest + 24 + k, 40 - k)
    return buf


def _suffix_before_start() -> np.ndarray:
    """Group 2's right neighbour copies from byte 2 (distance 22), so the
    suffix sources of group 2's first six bytes lie before byte 0. Those
    bytes are zero, as the padding in front of the row is: only the mask
    keeps them from counting as equal, and group 2 splits at k = 6."""
    buf = _random(3, lo=1)
    buf[5:22] = 0  # groups 1 and 2 continue a zero run at distance 1
    _copy(buf, 0, 22, 2)
    _copy(buf, 2, 24, 8)
    return buf


def _overlapping() -> np.ndarray:
    """Runs of period 1..7 (sources overlap their destinations)."""
    buf = _random(4)
    for p in range(1, 8):
        start = 256 * p + 3
        _copy(buf, start - p, start, 96)
    return buf


def _longest_distances() -> np.ndarray:
    """A 128 KiB block with a copy at MAX_DIST and one at MAX_DIST + 1."""
    size = 1 << 17
    buf = _random(5, size)
    _copy(buf, 70000 - tlz.MAX_DIST, 70000, 32)
    _copy(buf, 100000 - (tlz.MAX_DIST + 1), 100000, 32)
    return buf


def _ends_on_last_byte() -> np.ndarray:
    """The block's last 29 bytes (all >= 0x80) copy an earlier span."""
    buf = _random(6, lo=0x80)
    _copy(buf, 777, BS - 29, 29)
    return buf


def _full_planes(payload: bytes, size: int):
    """(is_match, is_split, per-group distance, per-group split point) of
    the numpy encoder's payload."""
    _v, n_groups, is_match, is_cont, is_split, dists, ks, _lits = (
        tlz._parse_payload(payload, size)
    )
    dist = tlz._validate_planes_v2(n_groups, is_match, is_cont, is_split,
                                   dists, ks)[0]
    k_full = np.zeros(n_groups, dtype=np.int64)
    k_full[is_split] = ks
    return is_match, is_split, dist, k_full


def _splits_k(k):
    return lambda m, s, d, ks: s[131] and ks[131] == k


# name: (blocks, rows of a launch, what the numpy encoder's decisions on
# the first block must show for the case to test what it is named for)
CASES = {
    "sign_bytes": (
        [_collage(1, lo=0x80), _collage(2, lo=0x80)], 2,
        lambda m, s, d, ks: m.sum() > 100 and set(ks[s]) == set(range(1, 8)),
    ),
    **{
        f"split_k{k}": ([_split_at(k)], 1, _splits_k(k))
        for k in range(1, tlz.GROUP)
    },
    "suffix_before_start": (
        [_suffix_before_start()], 1,
        lambda m, s, d, ks: m[1] and s[2] and ks[2] == 6 and d[3] == 22,
    ),
    "overlapping_distances": (
        [_overlapping()], 1,
        lambda m, s, d, ks: set(range(1, 8)) <= set(d[m].tolist()),
    ),
    "longest_distances": (
        [_longest_distances()], 1,
        lambda m, s, d, ks: d[70000 // 8] == tlz.MAX_DIST
        and not m[100000 // 8] and not s[100000 // 8],
    ),
    "ends_on_last_byte": (
        [_ends_on_last_byte()], 1, lambda m, s, d, ks: m[-1] and m[-3],
    ),
    "one_row_launch": ([_collage(7)], 1, lambda m, s, d, ks: s.sum() > 5),
    "three_rows_padded_bucket": (
        [_collage(8), _collage(9, lo=0x80), _split_at(3)], 4,
        lambda m, s, d, ks: s.sum() > 5,
    ),
}


@pytest.mark.parametrize("case", list(CASES))
def test_packed_window_compare_matches_numpy_encoder(case):
    blocks, rows, covers = CASES[case]
    size = len(blocks[0])
    n_groups = size // tlz.GROUP
    expected = [tlz._encode_planes_numpy(b.tobytes()) for b in blocks]
    payloads = [tlz._assemble_payload_numpy(b.tobytes()) for b in blocks]
    assert covers(*_full_planes(payloads[0], size))

    outs = [np.asarray(x) for x in
            tlz._encode_kernel(n_groups)(np.stack(blocks))]
    bitmap, cont, split, offs, ks, lits, n_new, n_split, n_match = outs
    for i, (bm, cb, sb, ob, kb, lb, _n) in enumerate(expected):
        n_lits = n_groups - int(n_match[i]) - int(n_split[i])
        assert bitmap[i].tobytes() == bm
        assert cont[i].tobytes() == cb
        assert split[i].tobytes() == sb
        assert offs[i, : int(n_new[i])].astype("<u2").tobytes() == ob
        assert ks[i, : int(n_split[i])].tobytes() == kb
        assert lits[i, :n_lits].tobytes() == lb

    got, _ = tlz.encode_batch_device(
        np.concatenate(blocks).tobytes(), len(blocks), size, batch_blocks=rows
    )
    assert [bytes(p) for p in got] == payloads


_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?(\S+)\s*=\s*(\w+)\[([\d,]*)\]\S*\s+([\w-]+)\(([^)]*)\)(.*)$"
)


def _gathers(hlo: str):
    """Every gather of an HLO module, once per call site, as (result shape,
    operand shape)."""
    comps, name, entry = {}, None, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?(\S+)\s*\{$", line.strip())
        if head:
            name = head.group(1)
            comps[name] = {"shapes": {}, "gathers": [], "calls": []}
            if line.startswith("ENTRY"):
                entry = name
            continue
        m = _INSTR.match(line)
        if name is None or not m:
            continue
        comp = comps[name]
        out, _dtype, dims, op, args, attrs = m.groups()
        shape = tuple(int(d) for d in dims.split(",") if d)
        comp["shapes"][out] = shape
        if op == "gather":
            comp["gathers"].append((shape, args.split(",")[0].strip()))
        elif op == "call":
            comp["calls"].append(re.search(r"to_apply=(\S+?)(?:,|$)", attrs).group(1))

    def walk(c):
        own = [(shape, comps[c]["shapes"][src]) for shape, src in comps[c]["gathers"]]
        return own + [g for callee in comps[c]["calls"] for g in walk(callee)]

    return walk(entry)


def test_encode_window_gathers_fetch_one_word_pair_per_group():
    """At the codec's launch shape no gather of the encode program fetches
    one element per byte of the launch, and the five window tests
    (candidate verification, two continuation passes, the split prefix and
    suffix) each gather one pair of words per group from the padded word
    plane. Lowered only: nothing compiles."""
    import jax
    import jax.numpy as jnp

    n_groups = WIDTH // tlz.GROUP
    hlo = jax.jit(functools.partial(tlz._encode_math, n_groups=n_groups)).lower(
        jax.ShapeDtypeStruct((ROWS, WIDTH), jnp.uint8)
    ).as_text(dialect="hlo")
    gathers = _gathers(hlo)
    assert gathers, "no gather found: the HLO parse is stale"
    assert [g for g in gathers if np.prod(g[0]) == ROWS * WIDTH] == []
    window = [g for g in gathers if len(g[1]) == 2 and g[1][1] > WIDTH]
    assert len(window) == 5 * 2
    assert {g[0] for g in window} == {(ROWS, n_groups)}
