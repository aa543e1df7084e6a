"""The store check reads every layout the program writes for a per-map
shuffle: index trailers, parity sidecars, and checksums by algorithm.

Runs go through ``harness.run_cell`` with the host codec at the tiny size
of ``conftest.tiny_cell``; the configuration states what the program is
set to, as a configuration file would.
"""

import json
import os
import zlib

import numpy as np
import pytest

import harness
import store
from conftest import tiny_cell

CODED = {"parity_segments": 2, "parity_stripe_k": 2, "parity_chunk_bytes": 4096}
SEED = 2**31 + 3030


def _run(shuffle=None, overrides=None, capsys=None):
    """One tiny run with ``shuffle`` stated in the configuration; returns the
    result and the run's ``checked`` observation."""
    bench, cell, config, traffic = tiny_cell("terasort.r8")
    config["shuffle"].update(shuffle or {})
    res = harness.run_cell(bench, cell, config, traffic, SEED, 0.3, False,
                           shuffle_overrides={"codec": "native", **(overrides or {})})
    checked = None
    if capsys is not None:
        for line in capsys.readouterr().err.splitlines():
            if line.startswith("observation checked: "):
                checked = json.loads(line.split(": ", 1)[1])
    return res, checked


@pytest.fixture
def env(monkeypatch):
    monkeypatch.setenv("S3SHUFFLE_CODEC_RATE_GATE", "device")
    return monkeypatch


@pytest.fixture
def data_lost(env):
    """Every open of a data object fails as missing once reduce tasks start."""
    from s3shuffle_tpu.manager import ShuffleManager
    from s3shuffle_tpu.storage.fault import FaultRule, FlakyBackend

    orig = ShuffleManager.get_reader

    def get_reader(self, *a, **kw):
        d = self.dispatcher
        if not isinstance(d.backend, FlakyBackend):
            d.backend = FlakyBackend(d.backend, rules=[FaultRule(
                "open", ".data", times=None, exc=FileNotFoundError)])
            d.clear_status_cache()
        return orig(self, *a, **kw)

    env.setattr(ShuffleManager, "get_reader", get_reader)


def _loss_reconstructions() -> int:
    """Byte ranges the window's reads rebuilt from parity for a lost object
    (a straggler may also be raced against parity, under another label)."""
    from s3shuffle_tpu.metrics import registry

    series = registry.REGISTRY.snapshot().get(
        "shuffle_parity_reconstructions_total", {}).get("series", [])
    return int(sum(s["value"] for s in series if s["labels"].get("reason") == "loss"))


@pytest.mark.parametrize("layout", ["coded", "coded_data_lost", "adler32", "crc32"])
def test_a_stated_layout_is_correct_with_every_check_zero(env, request, capsys, layout):
    shuffle = {"coded": CODED, "coded_data_lost": CODED,
               "adler32": {"checksum_algorithm": "ADLER32"},
               "crc32": {"checksum_algorithm": "CRC32"}}[layout]
    if layout == "coded_data_lost":
        request.getfixturevalue("data_lost")
    res, checked = _run(shuffle, capsys=capsys)
    assert res["correct"], res["checks"]
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert checked["partitions_crc_checked"] == 8 * res["attempted"]
    if layout.startswith("coded"):
        assert checked["parity_groups_checked"] > 0
    else:
        assert checked["parity_groups_checked"] == 0
    assert (_loss_reconstructions() > 0) == (layout == "coded_data_lost")


def _after_each_shuffle(monkeypatch, act):
    """Call ``act(root, shuffle_id)`` on the store once each shuffle's reduce
    tasks have read it: the program's answer stays right."""
    from executor import Executor

    orig = Executor.run_shuffle

    def run_shuffle(self, *a, **kw):
        out = orig(self, *a, **kw)
        act(self.manager.dispatcher.config.root_dir[len("file://"):],
            self.open_shuffles[-1])
        return out

    monkeypatch.setattr(Executor, "run_shuffle", run_shuffle)


def _flip(path, pos=100):
    with open(path, "r+b") as f:
        f.seek(pos)
        b = f.read(1)
        f.seek(pos)
        f.write(bytes([b[0] ^ 0x01]))


def test_a_flipped_parity_byte_is_counted(env):
    import s3shuffle_tpu.coding.parity as parity

    orig = parity.put_parity_objects

    def put(dispatcher, block, geometry, payloads):
        payloads = list(payloads)
        payloads[1] = bytes([payloads[1][0] ^ 0x01]) + payloads[1][1:]
        return orig(dispatcher, block, geometry, payloads)

    env.setattr(parity, "put_parity_objects", put)
    # no straggler race against parity: the wrong slice stays unread
    res, _ = _run(CODED, overrides={"speculative_read_quantile": 0.0})
    assert not res["correct"]
    assert res["checks"]["parity_groups_wrong"]["value"] >= 1
    assert res["checks"]["checksum_mismatches"]["value"] == 0


def test_a_flipped_data_byte_is_counted(env):
    def flip(root, sid):
        _flip(store.shuffle_objects(root, sid)[0]["data"])

    _after_each_shuffle(env, flip)
    res, _ = _run(CODED)
    assert not res["correct"]
    assert res["checks"]["checksum_mismatches"]["value"] >= 1
    assert res["checks"]["parity_groups_wrong"]["value"] >= 1
    assert res["checks"]["rows_wrong"]["value"] == 0


@pytest.mark.parametrize("keep", [None, 13])
def test_a_missing_or_cut_parity_sidecar_counts_all_of_its_groups(env, capsys, keep):
    def cut(root, sid):
        path = store.shuffle_objects(root, sid)[0]["par1"]
        if keep is None:
            os.remove(path)
        else:
            os.truncate(path, keep)

    _after_each_shuffle(env, cut)
    res, checked = _run(CODED, capsys=capsys)
    assert not res["correct"]
    assert res["checks"]["parity_groups_wrong"]["value"] == checked["parity_groups_checked"] > 0


def test_parity_stated_but_not_written_counts_every_group(env, capsys):
    res, checked = _run(CODED, overrides={"parity_segments": 0}, capsys=capsys)
    assert not res["correct"]
    assert res["checks"]["parity_groups_wrong"]["value"] == checked["parity_groups_checked"] > 0


def test_the_control_counts_every_partition(env, capsys):
    """The control of ``calibrate.py``: the program writes ADLER32 where the
    configuration states CRC32C, so no partition has the stated checksum."""
    res, checked = _run(overrides={"checksum_algorithm": "ADLER32"}, capsys=capsys)
    assert not res["correct"]
    assert res["checks"]["checksum_mismatches"]["value"] == 8 * res["attempted"]
    assert checked["partitions_crc_checked"] == 8 * res["attempted"]


def _words(*w):
    return np.array(w, dtype=">i8").tobytes()


@pytest.mark.parametrize("trailers,geometry", [
    ([], None),
    ([[store.SKEW_MAGIC, 1, 65536, 0]], None),
    ([[store.GEOMETRY_MAGIC, 2, 2, 4096]], (2, 2, 4096)),
    ([[store.SKEW_MAGIC, 0, 65536, 0], [store.GEOMETRY_MAGIC, 1, 4, 1 << 20]], (1, 4, 1 << 20)),
])
def test_index_trailers_are_peeled(tmp_path, trailers, geometry):
    offsets = [0, 10, 10, 250]
    path = tmp_path / "shuffle_1_0_0.index"
    path.write_bytes(_words(*offsets, *[w for t in trailers for w in t]))
    got, geom = store.read_index(str(path))
    assert got.tolist() == offsets and geom == geometry


def test_the_plain_field_arithmetic():
    assert store.gf_mul(0x80, 2) == 0x1D and store.parity_coefficient(1, 8) == 0x1D
    assert len({store.parity_coefficient(1, e) for e in range(255)}) == 255  # 2 is primitive
    assert store.parity_coefficient(1, 255) == 1 and store.parity_coefficient(2, 1) == 4
    assert all(store.parity_coefficient(0, j) == 1 for j in range(5))
    x = np.arange(256, dtype=np.uint8)
    for c in (0, 1, 2, 0x1D, 0x80, 0xFF):
        assert store.gf_scale(c, x).tolist() == [store.gf_mul(c, int(v)) for v in x]
    data = bytes(np.random.default_rng(5).integers(0, 256, 3 * 8 + 5, dtype=np.uint8))
    p0, p1 = store.encode_parity(data, 2, 3, 4)
    assert len(p0) == len(p1) == 2 * 4 + 4  # three groups, the last cut to its first chunk
    pad = np.frombuffer(data + bytes(36 - len(data)), dtype=np.uint8).reshape(3, 3, 4)
    assert p0 == np.bitwise_xor.reduce(pad, axis=1).reshape(-1)[:12].tobytes()


def _fixture_store(root):
    """Three maps of a parity-less CRC32C store, written once: map 0 right,
    map 1 with its second checksum off by one, map 2 with no checksum
    object."""
    rng = np.random.default_rng(30)
    for mid in range(3):
        parts = [bytes(rng.integers(0, 256, n, dtype=np.uint8)) for n in (40, 0, 73)]
        base = os.path.join(root, f"shuffle_7_{mid}_0")
        with open(base + ".data", "wb") as f:
            for p in parts:  # one raw frame per partition
                f.write(store.HEADER.pack(0, len(p), len(p)) + p)
        lens = [store.HEADER.size + len(p) for p in parts]
        with open(base + ".index", "wb") as f:
            f.write(_words(0, *np.cumsum(lens)))
        if mid < 2:
            import google_crc32c

            crcs = [google_crc32c.value(store.HEADER.pack(0, len(p), len(p)) + p)
                    for p in parts]
            crcs[1] += mid
            with open(base + ".checksum.CRC32C", "wb") as f:
                f.write(_words(*crcs))


def test_a_parity_less_store_counts_as_before(tmp_path):
    """The counts the reader gave before it knew of trailers, parity and
    other algorithms, on the same store."""
    _fixture_store(str(tmp_path))
    assert store.checksum_mismatches(str(tmp_path), 7, "CRC32C") == (9, 4)
    assert store.parity_mismatches(str(tmp_path), 7) == (0, 0)
    assert store.shuffle_stats(str(tmp_path), 7, 40) == {
        "bytes": 3 * (3 * 9 + 113), "frames": 9, "full_frames": 3, "full_tlz_frames": 0,
        "partial_frames": 6, "encode_work_bytes": 3 * 80, "decode_work_bytes": 0,
        "parity_bytes": 0}
    with pytest.raises(ValueError):
        store.checksum_mismatches(str(tmp_path), 7, "MD5")


@pytest.mark.parametrize("algorithm,fn", [("CRC32", zlib.crc32), ("ADLER32", zlib.adler32)])
def test_zip_checksums_are_held_against_the_stored_longs(tmp_path, algorithm, fn):
    _fixture_store(str(tmp_path))
    for mid in range(3):
        base = os.path.join(str(tmp_path), f"shuffle_7_{mid}_0")
        offsets, _g = store.read_index(base + ".index")
        with open(base + ".data", "rb") as f:
            data = f.read()
        vals = [fn(data[offsets[p]:offsets[p + 1]]) for p in range(3)]
        vals[2] ^= int(mid == 1)
        with open(f"{base}.checksum.{algorithm}", "wb") as f:
            f.write(_words(*vals))
    assert store.checksum_mismatches(str(tmp_path), 7, algorithm) == (9, 1)
