"""Tests for the distributed control plane (metadata service + multi-process
execution) — the analog of the reference's driver-RPC block enumeration
(S3ShuffleReader.scala:169-176) and its executor-independence property
(S3ShuffleWriter.scala:7-21; tests run with dynamic allocation on,
S3ShuffleManagerTest.scala:217)."""

import random

import numpy as np
import pytest

from s3shuffle_tpu.config import ShuffleConfig
from s3shuffle_tpu.metadata.map_output import MapOutputTracker, MapStatus, STORE_LOCATION
from s3shuffle_tpu.metadata.service import MetadataServer, RemoteMapOutputTracker


@pytest.fixture
def service():
    server = MetadataServer().start()
    client = RemoteMapOutputTracker(server.address)
    yield server, client
    client.close()
    server.stop()


def test_service_roundtrip(service):
    server, client = service
    assert client.ping()
    client.register_shuffle(3, 4)
    assert client.contains(3)
    assert not client.contains(99)
    assert client.num_partitions(3) == 4
    client.register_map_output(
        3, MapStatus(map_id=0, location=STORE_LOCATION, sizes=np.array([10, 0, 5, 7]))
    )
    client.register_map_output(
        3, MapStatus(map_id=2, location=STORE_LOCATION, sizes=np.array([1, 2, 3, 4]))
    )
    out = client.get_map_sizes_by_range(3, 0, None, 1, 3)
    assert out == [(0, [(1, 0), (2, 5)]), (2, [(1, 2), (2, 3)])]
    assert client.shuffle_ids() == [3]
    client.unregister_shuffle(3)
    assert not client.contains(3)


def test_service_errors_propagate(service):
    _server, client = service
    with pytest.raises(KeyError):
        client.get_map_sizes_by_range(42, 0, None, 0, 1)
    with pytest.raises(KeyError):
        client.register_map_output(
            42, MapStatus(map_id=0, location=STORE_LOCATION, sizes=np.zeros(1))
        )
    # the connection must survive errors
    assert client.ping()


def test_service_concurrent_clients(service):
    import threading

    server, _ = service
    server.tracker.register_shuffle(1, 8)
    errors = []

    def hammer(worker: int):
        try:
            c = RemoteMapOutputTracker(server.address)
            for i in range(20):
                c.register_map_output(
                    1,
                    MapStatus(
                        map_id=worker * 100 + i,
                        location=STORE_LOCATION,
                        sizes=np.arange(8),
                    ),
                )
            c.close()
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(w,)) for w in range(4)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert not errors
    assert len(server.tracker.get_map_sizes_by_range(1, 0, None, 0, 8)) == 80


def test_remote_tracker_reconnects(service):
    server, client = service
    client.register_shuffle(7, 2)
    # kill the client's socket behind its back; next call must reconnect
    client._sock.close()
    assert client.contains(7)


def test_local_tracker_remote_tracker_same_interface():
    local = MapOutputTracker()
    for name in (
        "register_shuffle", "register_map_output", "get_map_sizes_by_range",
        "contains", "num_partitions", "unregister_shuffle", "shuffle_ids",
    ):
        assert hasattr(local, name) and hasattr(RemoteMapOutputTracker, name)


# ---------------------------------------------------------------------------
# Multi-process end-to-end: map workers die before reducers start
# ---------------------------------------------------------------------------


def _make_sort_dep(shuffle_id: int):
    from s3shuffle_tpu.dependency import RangePartitioner, ShuffleDependency, natural_key
    from s3shuffle_tpu.serializer import ColumnarKVSerializer

    bounds = [bytes([b]) for b in (64, 128, 192)]
    return ShuffleDependency(
        shuffle_id=shuffle_id,
        partitioner=RangePartitioner(bounds),
        serializer=ColumnarKVSerializer(),
        key_ordering=natural_key,
    )


@pytest.mark.slow
def test_multiprocess_shuffle_survives_worker_death(tmp_path):
    from s3shuffle_tpu.cluster import LocalCluster

    cfg = ShuffleConfig(
        root_dir=f"file://{tmp_path}/store", app_id="cluster-test", codec="zlib"
    )
    rng = random.Random(0)
    parts = [
        [(rng.randbytes(6), rng.randbytes(20)) for _ in range(500)] for _ in range(3)
    ]
    cluster = LocalCluster(cfg, num_workers=2)
    try:
        out = cluster.run_shuffle(parts, _make_sort_dep)
        got = [kv for p in out for kv in p]
        assert len(got) == 1500
        flat = [k for p in out for k, _v in p]
        assert flat == sorted(k for p in parts for k, _v in p)
    finally:
        cluster.shutdown()


def _agent_main(coordinator, cfg_dict, worker_id, heartbeat_s=5.0):
    # module-level so it pickles under spawn
    from s3shuffle_tpu.config import ShuffleConfig
    from s3shuffle_tpu.storage.dispatcher import Dispatcher
    from s3shuffle_tpu.worker import WorkerAgent

    Dispatcher.reset()
    agent = WorkerAgent(tuple(coordinator), config=ShuffleConfig(**cfg_dict), worker_id=worker_id)
    agent.run_forever(poll_interval=0.01, heartbeat_s=heartbeat_s)


@pytest.mark.slow
def test_distributed_driver_with_worker_agents(tmp_path):
    # The multi-host topology on one host: a DistributedDriver (metadata
    # service + task queue) and two standalone WorkerAgent processes that
    # share nothing with the driver but the store and the coordinator address.
    import dataclasses
    import multiprocessing as mp

    from s3shuffle_tpu.batch import RecordBatch
    from s3shuffle_tpu.cluster import DistributedDriver
    from s3shuffle_tpu.storage.dispatcher import Dispatcher

    Dispatcher.reset()
    cfg = ShuffleConfig(
        root_dir=f"file://{tmp_path}/store", app_id="dist-test", codec="zlib"
    )
    rng = random.Random(1)
    recs = [(rng.randbytes(8), rng.randbytes(24)) for _ in range(4000)]
    batches = [RecordBatch.from_records(recs[i::4]) for i in range(4)]

    driver = DistributedDriver(cfg)
    ctx = mp.get_context("spawn")
    workers = [
        ctx.Process(
            target=_agent_main,
            args=(list(driver.coordinator_address), dataclasses.asdict(cfg), f"w{i}"),
            daemon=True,
        )
        for i in range(2)
    ]
    for w in workers:
        w.start()
    try:
        out = driver.run_sort_shuffle(batches, num_partitions=3)
        total = sum(b.n for b in out)
        assert total == 4000
        prev = None
        got = []
        for b in out:
            if b.n == 0:
                continue
            sk = b.key_strings(width=8)
            assert (sk[:-1] <= sk[1:]).all()
            if prev is not None:
                assert prev <= sk[0]
            prev = sk[-1]
            got.extend(b.to_records())
        assert sorted(got) == sorted(recs)
    finally:
        driver.shutdown()
        for w in workers:
            w.join(timeout=10)
            if w.is_alive():
                w.terminate()
    # stop_workers drained the fleet: agents exited by themselves
    assert all(not w.is_alive() for w in workers)


def test_task_queue_semantics():
    from s3shuffle_tpu.metadata.service import TaskQueue

    q = TaskQueue()
    q.submit_stage("s1", [{"task_id": i, "kind": "noop"} for i in range(3)])
    with pytest.raises(RuntimeError):
        q.submit_stage("s1", [])  # duplicate stage
    with pytest.raises(RuntimeError):
        q.submit_stage("s2", [{"task_id": 0}, {"task_id": 0}])  # dup task ids
    t0 = q.take_task("w0")
    assert t0["action"] == "run" and t0["task"]["task_id"] == 0  # FIFO
    q.complete_task("s1", 0, {"ok": 1})
    t1 = q.take_task("w1")
    q.fail_task("s1", t1["task"]["task_id"], "boom")
    st = q.stage_status("s1")
    assert st["pending"] == 1 and st["done"] == {0: {"ok": 1}} and "boom" in st["failed"][1]
    q.stop_workers()
    assert q.take_task("w0")["action"] == "stop"


def test_task_queue_lease_reap_and_attempt_cap():
    """§5.3: a crashed/hung worker's running task is re-queued once its
    lease expires (idempotent re-execution), and a task that keeps dying is
    failed after MAX_ATTEMPTS so the stage errors instead of looping."""
    from s3shuffle_tpu.metadata.service import TaskQueue

    q = TaskQueue()
    q.submit_stage("s", [{"task_id": 0, "kind": "noop"}])
    for attempt in range(TaskQueue.MAX_ATTEMPTS):
        t = q.take_task(f"w{attempt}")
        assert t["action"] == "run"
        # fresh lease: nothing reaped
        assert q.reap_expired("s", lease_s=60.0) == 0
        # expired lease: requeued, except on the final attempt -> failed
        reaped = q.reap_expired("s", lease_s=0.0)
        st = q.stage_status("s")
        if attempt < TaskQueue.MAX_ATTEMPTS - 1:
            assert reaped == 1 and st["pending"] == 1 and not st["failed"]
        else:
            assert reaped == 0 and "attempts" in st["failed"][0]
    # requeue_lost returns the task itself to pending (explicit variant)
    q.submit_stage("s2", [{"task_id": 7, "kind": "noop"}])
    q.take_task("dead-worker")
    assert q.requeue_lost("s2", "dead-worker") == 1
    t = q.take_task("w9")
    assert t["task"]["task_id"] == 7


def test_task_queue_refuses_zombie_reports():
    """A reaped-but-alive attempt must be unable to release the stage
    barrier or crash on a dropped stage: completion/failure reports are
    accepted only from the current lease holder."""
    from s3shuffle_tpu.metadata.service import TaskQueue

    q = TaskQueue()
    q.submit_stage("s", [{"task_id": 0, "kind": "noop"}])
    q.take_task("zombie")
    assert q.reap_expired("s", lease_s=0.0) == 1  # zombie presumed dead
    t2 = q.take_task("live")  # replacement attempt
    assert t2["action"] == "run"
    # the zombie comes back: its report must be ignored, not crash
    assert q.complete_task("s", 0, {"stale": True}, worker_id="zombie") is False
    st = q.stage_status("s")
    assert st["running"] == 1 and not st["done"]  # barrier still held
    # the live holder's report lands
    assert q.complete_task("s", 0, {"ok": True}, worker_id="live") is True
    assert q.stage_status("s")["done"] == {0: {"ok": True}}
    # reports for a dropped stage are quietly refused (no KeyError)
    q.drop_stage("s")
    assert q.complete_task("s", 0, {"late": True}, worker_id="live") is False
    assert q.fail_task("s", 0, "late", worker_id="live") is False
    # heartbeat keeps a long task alive: fresh beat -> nothing reaped
    q.submit_stage("s3", [{"task_id": 1, "kind": "noop"}])
    q.take_task("slowpoke")
    q.heartbeat("slowpoke")
    assert q.reap_expired("s3", lease_s=10.0) == 0


def test_commit_fence_and_atomic_registration(tmp_path):
    """can_commit (OutputCommitCoordinator analog): only the current lease
    holder is authorized; and map-output registration rides completion
    ATOMICALLY — a refused (zombie) completion registers nothing, so
    reducers can never see two attempts of one logical map."""
    from s3shuffle_tpu.metadata.map_output import MapOutputTracker, MapStatus, STORE_LOCATION
    from s3shuffle_tpu.metadata.service import TaskQueue

    q = TaskQueue()
    q.submit_stage("s", [{"task_id": 0, "kind": "map", "map_id": 0}])
    t1 = q.take_task("zombie")
    assert t1["task"]["_attempt"] == 1
    q.reap_expired("s", lease_s=0.0)
    t2 = q.take_task("live")
    assert t2["task"]["_attempt"] == 2
    assert q.can_commit("s", 0, "zombie") is False
    assert q.can_commit("s", 0, "live") is True
    assert q.can_commit("dropped-stage", 0, "live") is False

    # atomic accept+register: the zombie's on_accept must never run
    tracker = MapOutputTracker()
    tracker.register_shuffle(9, 2)

    def register(mid):
        return lambda: tracker.register_map_output(
            9, MapStatus(map_id=mid, location=STORE_LOCATION, sizes=np.array([1, 2]))
        )

    assert q.complete_task("s", 0, {}, worker_id="zombie", on_accept=register(0)) is False
    assert q.complete_task("s", 0, {}, worker_id="live", on_accept=register(1)) is True
    registered = [m for m, _sizes in tracker.get_map_sizes_by_range(9, 0, None, 0, 2)]
    assert registered == [1]  # only the winning attempt's output exists


def test_distributed_driver_recovers_from_hung_worker(tmp_path):
    """A worker takes a task and never completes it (hang/crash): the
    driver's stage-wait loop reaps the expired lease and a live agent
    re-runs the task — the shuffle completes with full results."""
    import dataclasses
    import multiprocessing as mp

    from s3shuffle_tpu.batch import RecordBatch
    from s3shuffle_tpu.cluster import DistributedDriver
    from s3shuffle_tpu.metadata.service import RemoteMapOutputTracker
    from s3shuffle_tpu.storage.dispatcher import Dispatcher

    Dispatcher.reset()
    cfg = ShuffleConfig(
        root_dir=f"file://{tmp_path}/store", app_id="reap-test", codec="zlib"
    )
    rng = random.Random(4)
    recs = [(rng.randbytes(8), rng.randbytes(24)) for _ in range(2000)]
    batches = [RecordBatch.from_records(recs[i::2]) for i in range(2)]

    driver = DistributedDriver(cfg)
    # fast reap, with the live worker heartbeating at lease/6 so a loaded CI
    # machine cannot falsely reap a healthy worker (invariant: heartbeat
    # interval << lease)
    driver.task_lease_s = 3.0
    # the "hung worker": steals the first map task and never finishes it
    thief = RemoteMapOutputTracker(driver.coordinator_address)
    stolen = {"n": 0}

    def steal_once():
        import time as _t

        for _ in range(200):
            t = thief.take_task("hung-worker")
            if t["action"] == "run":
                stolen["n"] += 1
                return  # never complete/fail it — simulate a hang
            _t.sleep(0.02)

    import threading

    stealer = threading.Thread(target=steal_once, daemon=True)
    stealer.start()

    ctx = mp.get_context("spawn")
    worker = ctx.Process(
        target=_agent_main,
        args=(list(driver.coordinator_address), dataclasses.asdict(cfg), "live", 0.5),
        daemon=True,
    )
    worker.start()
    try:
        out = driver.run_sort_shuffle(batches, num_partitions=3)
        assert sum(b.n for b in out) == 2000
        got = [kv for b in out for kv in b.to_records()]
        assert sorted(got) == sorted(recs)
        stealer.join(timeout=5)
        assert stolen["n"] == 1  # the hang actually happened and was recovered
    finally:
        thief.close()
        driver.shutdown()
        worker.join(timeout=10)
        if worker.is_alive():
            worker.terminate()


def test_dep_descriptor_roundtrip():
    from s3shuffle_tpu.dependency import HashPartitioner, RangePartitioner, ShuffleDependency, natural_key
    from s3shuffle_tpu.serializer import ColumnarKVSerializer
    from s3shuffle_tpu.worker import dep_from_descriptor, dep_to_descriptor

    dep = ShuffleDependency(
        7, RangePartitioner([b"b", b"m\x00x"]), serializer=ColumnarKVSerializer(),
        key_ordering=natural_key,
    )
    back = dep_from_descriptor(7, dep_to_descriptor(dep))
    assert back.partitioner.bounds == [b"b", b"m\x00x"]
    assert back.num_partitions == 3 and back.key_ordering is natural_key
    dep2 = ShuffleDependency(8, HashPartitioner(5), serializer=ColumnarKVSerializer())
    back2 = dep_from_descriptor(8, dep_to_descriptor(dep2))
    assert back2.num_partitions == 5 and back2.key_ordering is None


def test_worker_metrics_endpoint(tmp_path):
    """The deploy templates annotate prometheus scrape ports — the worker must
    actually answer /metrics with text-format counters."""
    import urllib.request

    from s3shuffle_tpu.worker import MetricsServer, WorkerAgent

    svc = MetadataServer(host="127.0.0.1", port=0).start()
    try:
        cfg = ShuffleConfig(root_dir=f"file://{tmp_path}", app_id="metrics")
        agent = WorkerAgent(svc.address, config=cfg, worker_id="w-metrics")
        metrics = MetricsServer(agent, host="127.0.0.1", port=0).start()
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{metrics.port}/metrics", timeout=5
            ) as resp:
                body = resp.read().decode()
            assert 's3shuffle_tasks_run_total{worker="w-metrics"} 0' in body
            with urllib.request.urlopen(
                f"http://127.0.0.1:{metrics.port}/healthz", timeout=5
            ) as resp:
                assert resp.status == 200
        finally:
            metrics.stop()
            agent.close()
    finally:
        svc.stop()


def test_worker_metrics_colliding_counter_names_dedup(tmp_path):
    """Counter names that sanitize to the same metric name must merge into one
    series — duplicate # TYPE lines make Prometheus reject the scrape."""
    from s3shuffle_tpu.utils import trace
    from s3shuffle_tpu.worker import MetricsServer, WorkerAgent

    svc = MetadataServer(host="127.0.0.1", port=0).start()
    try:
        cfg = ShuffleConfig(root_dir=f"file://{tmp_path}", app_id="metrics2")
        agent = WorkerAgent(svc.address, config=cfg, worker_id="w-dedup")
        metrics = MetricsServer(agent, host="127.0.0.1", port=0)
        trace.enable(str(tmp_path / "trace.json"))
        try:
            trace.count("dedup.check", 3)
            trace.count("dedup/check", 4)
            body = metrics.render()
        finally:
            trace.disable()
            metrics.stop()  # never started, but its listening socket is bound
            agent.close()
        assert body.count("# TYPE s3shuffle_dedup_check counter") == 1
        assert 's3shuffle_dedup_check{worker="w-dedup"} 7.0' in body
    finally:
        svc.stop()


def test_orphan_sweep_reclaims_dead_attempt_objects(tmp_path):
    """VERDICT r4 ask #7: a map worker that dies MID-WRITE leaks its
    attempt-unique store objects (it never registers, so only the final
    prefix delete would reclaim them). The driver's post-map-stage orphan
    sweep must remove every non-winner object while the stage's winners'
    objects stay intact — asserted BEFORE unregister/shutdown."""
    import dataclasses
    import multiprocessing as mp

    from s3shuffle_tpu.batch import RecordBatch
    from s3shuffle_tpu.block_ids import parse_shuffle_object_name
    from s3shuffle_tpu.cluster import DistributedDriver
    from s3shuffle_tpu.metadata.service import RemoteMapOutputTracker
    from s3shuffle_tpu.storage.dispatcher import Dispatcher
    from s3shuffle_tpu.worker import WorkerAgent

    Dispatcher.reset()
    cfg = ShuffleConfig(
        root_dir=f"file://{tmp_path}/store", app_id="orphan-test", codec="zlib"
    )
    rng = random.Random(6)
    recs = [(rng.randbytes(8), rng.randbytes(24)) for _ in range(2000)]
    batches = [RecordBatch.from_records(recs[i::2]) for i in range(2)]

    driver = DistributedDriver(cfg)
    driver.task_lease_s = 3.0
    sid = driver._next_shuffle_id
    thief = RemoteMapOutputTracker(driver.coordinator_address)
    leaked = {}

    def die_mid_write():
        import time as _t

        for _ in range(200):
            t = thief.take_task("doomed-worker")
            if t["action"] == "run":
                task = t["task"]
                # the attempt's data object lands in the store, then the
                # worker "dies": no index, no commit, no fail report
                map_id = (
                    int(task["map_id"]) * WorkerAgent.ATTEMPT_STRIDE
                    + int(task.get("_attempt", 1)) - 1
                )
                from s3shuffle_tpu.block_ids import ShuffleDataBlockId

                path = driver.dispatcher.get_path(ShuffleDataBlockId(sid, map_id))
                with driver.dispatcher.backend.create(path) as sink:
                    sink.write(b"partial bytes of a dead attempt")
                leaked["map_id"] = map_id
                leaked["path"] = path
                return
            _t.sleep(0.02)

    import threading

    t = threading.Thread(target=die_mid_write, daemon=True)
    t.start()

    ctx = mp.get_context("spawn")
    worker = ctx.Process(
        target=_agent_main,
        args=(list(driver.coordinator_address), dataclasses.asdict(cfg), "live", 0.5),
        daemon=True,
    )
    worker.start()
    try:
        out = driver.run_sort_shuffle(batches, num_partitions=3)
        assert sum(b.n for b in out) == 2000
        t.join(timeout=5)
        assert "map_id" in leaked, "the doomed worker never got a task"
        # the sweep ran inside run_sort_shuffle after the map stage: only
        # winner objects may remain in the store
        winners = set(driver.server.tracker.registered_map_ids(sid))
        assert leaked["map_id"] not in winners
        assert not driver.dispatcher.backend.exists(leaked["path"])
        survivors = []
        for prefix in driver.dispatcher.root_prefixes():
            for st in driver.dispatcher.backend.list_prefix(
                f"{prefix}/{driver.dispatcher.app_id}/{sid}"
            ):
                parsed = parse_shuffle_object_name(st.path)
                if parsed is not None and parsed[0] == sid:
                    survivors.append(parsed[1])
        assert survivors and set(survivors) <= winners
    finally:
        thief.close()
        driver.shutdown()
        worker.join(timeout=10)
        if worker.is_alive():
            worker.terminate()


def _make_narrow_agg_dep(shuffle_id: int):
    # module-level so the whole dependency (aggregator included) pickles to
    # the spawn workers — the regression this guards: ColumnarAggregator
    # once built its combine hooks from __init__ lambdas, which don't pickle
    from s3shuffle_tpu.colagg import ColumnarAggregator
    from s3shuffle_tpu.dependency import HashPartitioner, ShuffleDependency
    from s3shuffle_tpu.serializer import BytesKVSerializer

    return ShuffleDependency(
        shuffle_id=shuffle_id,
        partitioner=HashPartitioner(4),
        serializer=BytesKVSerializer(),
        aggregator=ColumnarAggregator(("sum", "sum"), val_dtypes=("i2", "i1")),
        map_side_combine=True,
    )


def test_multiprocess_narrow_schema_aggregation(tmp_path):
    """Narrow-schema typed aggregation ACROSS PROCESS BOUNDARIES: the
    dependency (with its widen-before-reduce aggregator) pickles to spawn
    workers, map-side combine runs in the worker processes, and the reduce
    output is exact."""
    from s3shuffle_tpu.cluster import LocalCluster
    from s3shuffle_tpu.structured import pack_values

    cfg = ShuffleConfig(
        root_dir=f"file://{tmp_path}/store", app_id="cluster-narrow", codec="zlib"
    )
    rng = np.random.default_rng(3)
    ref = {}
    parts = []
    for _p in range(3):
        recs = []
        keys = rng.integers(0, 40, 400)
        vals = rng.integers(-100, 101, 400)
        for k, v in zip(keys.tolist(), vals.tolist()):
            kb = int(k).to_bytes(2, "big")
            recs.append(
                (kb, pack_values(np.array([v]), np.array([1]),
                                 dtypes=("i2", "i1")).tobytes())
            )
            s, c = ref.get(kb, (0, 0))
            ref[kb] = (s + v, c + 1)
        parts.append(recs)
    cluster = LocalCluster(cfg, num_workers=2)
    try:
        out = cluster.run_shuffle(parts, _make_narrow_agg_dep)
    finally:
        cluster.shutdown()
    got = {}
    for p in out:
        for k, v in p:
            assert k not in got, f"duplicate key {k!r} across partitions"
            w = np.frombuffer(v, dtype="<i8")
            got[k] = (int(w[0]), int(w[1]))
    assert got == ref
