"""Multi-chip shuffle plane (ISSUE 20): per-chip partition ownership over
ICI, the chip-aware codec dispatcher, and the ``mesh_devices`` arming
contract.

Layers:

- **byte-identity property suite** — seeded mesh-vs-host comparisons across
  mesh widths × partition counts × batch-size mixes (the conftest rig pins
  8 emulated CPU devices, so every width up to 8 is real placement);
- **fallback contract** — ragged key/value widths must decline the mesh
  route explicitly and still produce the right answer via the host path;
- **op-for-op regression gate** — ``mesh_devices=0`` on the shared
  RecordingBackend must reproduce the pre-plane host pattern exactly: the
  same op multiset AND byte-identical blobs;
- **dispatcher units** — least-outstanding-work placement and slot
  accounting, run under the PR-19 race witness with ``watch_shared`` on the
  per-device queue state;
- **codec executors under the dispatcher** — encode/decode payload bytes at
  width 8 equal the disarmed single-device bytes.
"""

import random
import threading

import numpy as np
import pytest

from conftest import RecordingBackend, racewitness

from s3shuffle_tpu.batch import RecordBatch
from s3shuffle_tpu.config import ShuffleConfig
from s3shuffle_tpu.dependency import HashPartitioner, ShuffleDependency
from s3shuffle_tpu.manager import ShuffleManager
from s3shuffle_tpu.metrics import registry as mreg
from s3shuffle_tpu.parallel import dispatch
from s3shuffle_tpu.shuffle import ShuffleContext
from s3shuffle_tpu.storage.dispatcher import Dispatcher
from s3shuffle_tpu.storage.local import LocalBackend


@pytest.fixture(autouse=True)
def _mesh_reset(monkeypatch):
    monkeypatch.delenv("S3SHUFFLE_MESH_DEVICES", raising=False)
    dispatch.reset_for_testing()
    yield
    dispatch.reset_for_testing()


@pytest.fixture
def metrics_on():
    mreg.REGISTRY.reset_values()
    mreg.enable()
    yield mreg.REGISTRY
    mreg.disable()
    mreg.REGISTRY.reset_values()


def _fixed_batch(rng, n, kb=8, vb=16):
    keys = rng.integers(0, 256, size=n * kb, dtype=np.uint8).astype(np.uint8)
    vals = rng.integers(0, 256, size=n * vb, dtype=np.uint8).astype(np.uint8)
    return RecordBatch.from_fixed(n, kb, vb, keys, vals)


def _ctx(tmp_path, tag, **cfg_kwargs):
    Dispatcher.reset()
    cfg = ShuffleConfig(
        root_dir=f"file://{tmp_path}/{tag}", app_id=tag, **cfg_kwargs
    )
    return ShuffleContext(cfg)


# ---------------------------------------------------------------------------
# Byte-identity property suite: mesh path vs host/store path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "width,n_parts,sizes",
    [
        (2, 3, (50, 17)),
        (4, 8, (100, 37, 250, 0, 64)),
        (5, 7, (33, 1, 0, 90)),
        (8, 16, (40,) * 8),
        (8, 2, (301,)),
    ],
)
def test_mesh_matches_host_across_shapes(tmp_path, width, n_parts, sizes):
    """Seeded property: the mesh route must deliver record-identical
    partitions to the host/store path for every (mesh width × partition
    count × batch-size mix) — the partition owner moved chips, the answer
    did not."""
    rng = np.random.default_rng(width * 1000 + n_parts)
    batches = [_fixed_batch(rng, n) for n in sizes]

    with _ctx(tmp_path, f"mesh{width}", mesh_devices=width) as ctx:
        mesh_parts, used_mesh = ctx.mesh_shuffle(batches, n_parts)
    assert used_mesh, "uniform widths at width >= 2 must ride the mesh"

    with _ctx(tmp_path, "host") as ctx:
        host_parts, used_host = ctx.mesh_shuffle(batches, n_parts)
    assert not used_host

    assert len(mesh_parts) == len(host_parts) == n_parts
    for p, (mp, hp) in enumerate(zip(mesh_parts, host_parts)):
        assert sorted(mp) == sorted(hp), f"partition {p} diverged"
    total = sum(s for s in sizes)
    assert sum(len(p) for p in mesh_parts) == total


def test_mesh_route_rows_metric_counts_real_rows(tmp_path, metrics_on):
    rng = np.random.default_rng(3)
    batches = [_fixed_batch(rng, n) for n in (64, 21)]
    with _ctx(tmp_path, "routed", mesh_devices=4) as ctx:
        _, used = ctx.mesh_shuffle(batches, 4)
    assert used
    series = metrics_on.snapshot()["mesh_route_rows_total"]["series"]
    assert sum(s["value"] for s in series) == 85


# ---------------------------------------------------------------------------
# Ragged fallback contract
# ---------------------------------------------------------------------------


def test_ragged_input_falls_back_to_host_path(tmp_path):
    """Variable-width records break the fixed-shape contract: the mesh
    route must decline EXPLICITLY (used_mesh=False, host-path commit), not
    crash and not silently truncate."""
    prng = random.Random(5)
    ragged = [
        RecordBatch.from_records(
            [(prng.randbytes(prng.randint(2, 12)), prng.randbytes(6))
             for _ in range(80)]
        ),
        RecordBatch.from_records([(b"solo-key", b"v")]),
    ]
    expected = sorted(kv for b in ragged for kv in b.iter_records())
    with _ctx(tmp_path, "ragged", mesh_devices=8) as ctx:
        parts, used_mesh = ctx.mesh_shuffle(ragged, 3)
    assert used_mesh is False
    assert sorted(kv for p in parts for kv in p) == expected


def test_mesh_shuffle_or_fallback_wrapper_contract(tmp_path):
    """The ici_shuffle-level wrapper: ragged widths raised inside the mesh
    leg fall back to one-writer-per-batch host commits (used_mesh=False);
    unrelated ValueErrors still propagate."""
    import jax

    from s3shuffle_tpu.parallel.ici_shuffle import mesh_shuffle_or_fallback
    from s3shuffle_tpu.parallel.mesh import make_mesh

    Dispatcher.reset()
    cfg = ShuffleConfig(root_dir=f"file://{tmp_path}/wrap", app_id="wrap")
    manager = ShuffleManager(cfg)
    mesh = make_mesh({"data": 2}, devices=jax.local_devices()[:2])
    prng = random.Random(7)
    ragged = [
        RecordBatch.from_records(
            [(prng.randbytes(prng.randint(2, 9)), prng.randbytes(4))
             for _ in range(30)]
        )
        for _ in range(2)
    ]
    handle, per_map, used_mesh = mesh_shuffle_or_fallback(
        mesh, ragged, manager, HashPartitioner(4), key_bytes=8, value_bytes=4
    )
    assert used_mesh is False
    assert per_map == [30, 30]
    got = sorted(
        kv for p in range(4) for kv in manager.get_reader(handle, p, p + 1).read()
    )
    assert got == sorted(kv for b in ragged for kv in b.iter_records())
    manager.unregister_shuffle(handle.shuffle_id)

    # a batch-count mismatch is a CALLER bug, not a fallback trigger
    one = [_fixed_batch(np.random.default_rng(0), 8)]
    with pytest.raises(ValueError, match="one batch per device"):
        mesh_shuffle_or_fallback(
            mesh, one, manager, HashPartitioner(4), key_bytes=8, value_bytes=16
        )
    manager.stop()


# ---------------------------------------------------------------------------
# mesh_devices=0 op-for-op regression gate (shared RecordingBackend)
# ---------------------------------------------------------------------------


def _recorded_run(tmp_path, tag, drive, **cfg_kwargs):
    """Run ``drive(manager)`` over a RecordingBackend; returns the op
    multiset (basenames) and every blob written, keyed by basename."""
    Dispatcher.reset()
    cfg = ShuffleConfig(
        root_dir=f"file://{tmp_path}/{tag}", app_id=tag, cleanup=False,
        **cfg_kwargs,
    )
    d = Dispatcher(cfg)
    rec = RecordingBackend(LocalBackend())
    d.backend = rec
    manager = ShuffleManager(dispatcher=d)
    out = drive(manager)
    ops = sorted((op, p.rsplit("/", 1)[-1]) for op, p in rec.ops)
    blobs = {}
    for op, p in rec.ops:
        if op in ("write", "create"):
            blobs[p.rsplit("/", 1)[-1]] = d.backend.read_all(p)
    return out, ops, blobs


def test_mesh_devices_zero_is_op_for_op_and_byte_identical(tmp_path):
    """``mesh_devices=0`` (and 1) must reproduce today's host pattern
    exactly: the same store-op multiset and byte-identical blobs as the
    pre-plane map-task sequence issued directly against the manager."""
    rng = np.random.default_rng(11)
    batches = [_fixed_batch(rng, n) for n in (120, 45, 0, 77)]
    n_parts = 5

    def via_mesh_entry(manager):
        ctx = ShuffleContext(manager=manager)
        parts, used_mesh = ctx.mesh_shuffle(batches, n_parts, cleanup=False)
        assert used_mesh is False
        return parts

    def via_legacy_pattern(manager):
        dep = ShuffleDependency(
            shuffle_id=0, partitioner=HashPartitioner(n_parts)
        )
        handle = manager.register_shuffle(0, dep)
        for map_id, b in enumerate(batches):
            w = manager.get_writer(handle, map_id)
            w.write(b)
            w.stop(success=True)
        return [
            list(manager.get_reader(handle, p, p + 1).read())
            for p in range(n_parts)
        ]

    for width in (0, 1):
        out_a, ops_a, blobs_a = _recorded_run(
            tmp_path, f"zero{width}", via_mesh_entry, mesh_devices=width
        )
        out_b, ops_b, blobs_b = _recorded_run(
            tmp_path, f"legacy{width}", via_legacy_pattern
        )
        # per-partition multisets: within-partition order is the read
        # prefetcher's completion order, not part of the contract
        assert [sorted(p) for p in out_a] == [sorted(p) for p in out_b]
        assert ops_a == ops_b, f"width {width}: op multiset diverged"
        assert blobs_a == blobs_b, f"width {width}: wire bytes diverged"


# ---------------------------------------------------------------------------
# Dispatcher units (under the race witness)
# ---------------------------------------------------------------------------


class _FakeDev:
    def __init__(self, i):
        self.id = i
        self.platform = "fake"


def test_dispatcher_least_outstanding_placement():
    disp = dispatch.DeviceDispatcher([_FakeDev(i) for i in range(4)])
    assert disp.n_devices == 4
    assert disp.max_inflight() == 4
    # empty dispatcher walks devices round-robin (ties -> lowest index)
    slots = [disp.acquire() for _ in range(4)]
    assert slots == [0, 1, 2, 3]
    assert disp.outstanding_snapshot() == [1, 1, 1, 1]
    # releasing device 2 makes it the unique least-loaded target
    disp.release(2)
    assert disp.acquire() == 2
    for i in range(4):
        disp.release(i)
    assert disp.outstanding_snapshot() == [0] * 4
    assert disp.label(0) == "fake:0"


def test_dispatcher_queue_state_race_clean_under_witness():
    """Concurrent acquire/release storms over watch_shared'd per-device
    queue state: the dispatcher's lock discipline must leave the PR-19
    happens-before witness with zero reports."""
    with racewitness.quarantine() as q:
        disp = dispatch.DeviceDispatcher([_FakeDev(i) for i in range(3)])
        disp = racewitness.watch_shared(disp, ("_outstanding",))

        def storm():
            for _ in range(60):
                idx = disp.acquire()
                disp.release(idx)

        threads = [threading.Thread(target=storm) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert disp.outstanding_snapshot() == [0, 0, 0]
        assert not q.new_reports(), "\n".join(q.new_reports())


# ---------------------------------------------------------------------------
# Arming plumbing
# ---------------------------------------------------------------------------


def test_get_dispatcher_disarmed_and_armed(tmp_path):
    assert dispatch.get_dispatcher() is None  # width 0
    dispatch.configure(1)
    assert dispatch.get_dispatcher() is None  # width 1 = op-for-op
    dispatch.configure(3)
    disp = dispatch.get_dispatcher()
    assert disp is not None and disp.n_devices == 3
    assert dispatch.get_dispatcher() is disp  # cached singleton
    dispatch.configure(0)
    assert dispatch.get_dispatcher() is None  # re-disarm drops it


def test_env_override_wins_over_config(monkeypatch):
    dispatch.configure(0)
    monkeypatch.setenv("S3SHUFFLE_MESH_DEVICES", "2")
    assert dispatch.requested_devices() == 2
    disp = dispatch.get_dispatcher()
    assert disp is not None and disp.n_devices == 2
    monkeypatch.setenv("S3SHUFFLE_MESH_DEVICES", "bogus")
    assert dispatch.requested_devices() == 0


def test_manager_arms_dispatcher_from_config(tmp_path):
    Dispatcher.reset()
    cfg = ShuffleConfig(
        root_dir=f"file://{tmp_path}/arm", app_id="arm", mesh_devices=6
    )
    manager = ShuffleManager(cfg)
    try:
        assert dispatch.requested_devices() == 6
        disp = dispatch.get_dispatcher()
        assert disp is not None and disp.n_devices == 6
    finally:
        manager.stop()


def test_config_rejects_negative_mesh_devices():
    with pytest.raises(ValueError, match="mesh_devices"):
        ShuffleConfig(mesh_devices=-1)


# ---------------------------------------------------------------------------
# Codec executors under the dispatcher: byte identity at width 8
# ---------------------------------------------------------------------------


def test_encode_decode_bytes_identical_armed_vs_disarmed():
    from s3shuffle_tpu.ops import tlz
    from s3shuffle_tpu.ops.checksum import POLY_CRC32C

    block, blocks, batch = 2048, 13, 4
    rng = np.random.default_rng(8)
    data = np.where(
        rng.random((blocks, block)) < 0.5,
        rng.integers(0, 256, (blocks, block)),
        np.tile(rng.integers(0, 256, (1, tlz.GROUP)),
                (blocks, block // tlz.GROUP)),
    ).astype(np.uint8)
    buf = data.tobytes()

    def run():
        payloads, _crc = tlz.encode_batch_device(
            buf, blocks, block, batch_blocks=batch, poly=POLY_CRC32C
        )
        decoded, _pc = tlz.decode_batch_device(
            payloads, [block] * blocks, block, batch_rows=batch,
            poly=POLY_CRC32C,
        )
        return payloads, [bytes(b) for b in decoded]

    dispatch.reset_for_testing()
    ref_payloads, ref_blocks = run()
    dispatch.configure(8)
    disp = dispatch.get_dispatcher()
    assert disp is not None and disp.n_devices == 8
    mesh_payloads, mesh_blocks = run()
    assert mesh_payloads == ref_payloads
    assert mesh_blocks == ref_blocks
    assert ref_blocks == [data[i].tobytes() for i in range(blocks)]
    assert disp.outstanding_snapshot() == [0] * 8


@pytest.mark.parametrize("op", ["encode", "decode"])
def test_fused_crc_tables_live_on_the_launch_device(monkeypatch, op):
    """Under the armed dispatcher each fused launch takes the CRC tables
    resident on its own staging device — never the default device's copy,
    which would be copied to the launch's chip on every call."""
    from s3shuffle_tpu.ops import checksum, tlz
    from s3shuffle_tpu.ops.checksum import POLY_CRC32C

    block, blocks, batch = 2048, 16, 4
    data = np.tile(np.arange(block, dtype=np.uint8) % 7, (blocks, 1))
    payloads, _ = tlz.encode_batch_device(
        data.tobytes(), blocks, block, batch_blocks=batch, poly=POLY_CRC32C
    )
    tlz._batch_kernel.cache_clear()
    tlz._decode_batch_kernel.cache_clear()
    dispatch.configure(4)
    assert dispatch.get_dispatcher().n_devices == 4

    launches = []
    real_of = tlz._device_of

    def spy_of(arr):
        dev = real_of(arr)
        launches.append(dev)
        return dev

    placed = []
    real_weights = checksum._device_weights

    def spy_weights(poly, length, device=None):
        tables = real_weights(poly, length, device)
        placed.append((device, tables.devices()))
        return tables

    monkeypatch.setattr(tlz, "_device_of", spy_of)
    monkeypatch.setattr(checksum, "_device_weights", spy_weights)
    try:
        if op == "encode":
            tlz.encode_batch_device(data.tobytes(), blocks, block,
                                    batch_blocks=batch, poly=POLY_CRC32C)
        else:
            tlz.decode_batch_device(payloads, [block] * blocks, block,
                                    batch_rows=batch, poly=POLY_CRC32C)
    finally:
        tlz._batch_kernel.cache_clear()
        tlz._decode_batch_kernel.cache_clear()
    assert len(launches) == blocks // batch
    assert len(set(launches)) > 1  # the dispatcher spread the launches
    assert [dev for dev, _ in placed] == launches
    assert all(devs == {dev} for dev, devs in placed)
