"""Distributed trace plane: causal spans, cross-process assembly, the
flight recorder, fleet telemetry, and the $/shuffle cost digest.

The acceptance gate (ISSUE 16) is the spawned-fleet test at the bottom:
a 2-worker :class:`DistributedDriver` job with tracing on must produce ONE
merged Chrome-trace file whose worker/storage spans link into the driver's
tree by trace_id/parent_id across real process boundaries (flow events on
the causal edges), whose critical-path digest covers >= 90% of the job
wall, and whose fleet view prices the run through the rate card. The
converse gate: with tracing fully off the shuffle is byte- AND op-identical
(RecordingBackend multiset) — observability must never cost a store request.
"""

import json
import os

import pytest

from s3shuffle_tpu.config import ShuffleConfig
from s3shuffle_tpu.metrics import registry as mreg
from s3shuffle_tpu.storage.dispatcher import Dispatcher
from s3shuffle_tpu.utils import trace

from conftest import RecordingBackend  # noqa: E402  (test-local import path)


@pytest.fixture
def trace_sandbox(tmp_path):
    """Isolated tracing state: enabled onto a tmp file, fully torn down
    after (the conftest strictness would surface any leak as a failure in
    an unrelated test)."""
    trace.reset()
    path = str(tmp_path / "trace.json")
    trace.enable(path)
    yield path
    trace.disable()
    trace.reset()


@pytest.fixture
def flight_sandbox(tmp_path):
    """Isolated flight-recorder state (module-global ring + dump dir)."""
    trace.configure_flight(dir="", ring=trace.FLIGHT_RING_DEFAULT, worker_id="")
    trace._flight.clear()
    trace._flight_error = False
    yield str(tmp_path / "flight")
    trace.configure_flight(dir="", ring=trace.FLIGHT_RING_DEFAULT, worker_id="")
    trace._flight.clear()
    trace._flight_error = False


# ---------------------------------------------------------------------------
# Causal spans and context propagation
# ---------------------------------------------------------------------------


def test_span_records_ids_and_wall_clock(trace_sandbox):
    import time as _time

    before = _time.time() * 1e6
    with trace.span("driver.job", app="t"):
        pass
    after = _time.time() * 1e6
    (event,) = trace.events_snapshot()
    assert event["ph"] == "X"
    assert event["name"] == "driver.job"
    args = event["args"]
    assert args["trace_id"] and args["span_id"]
    assert "parent_id" not in args  # a root span has no parent
    # wall-anchored timestamps: mergeable across processes without skew math
    assert before - 1e6 <= event["ts"] <= after + 1e6


def test_nested_spans_share_trace_and_chain_parents(trace_sandbox):
    with trace.span("driver.job"):
        with trace.span("driver.map_stage"):
            pass
    stage, job = sorted(trace.events_snapshot(), key=lambda e: e["ts"], reverse=True)
    assert {job["name"], stage["name"]} == {"driver.job", "driver.map_stage"}
    if job["name"] != "driver.job":
        job, stage = stage, job
    assert stage["args"]["trace_id"] == job["args"]["trace_id"]
    assert stage["args"]["parent_id"] == job["args"]["span_id"]


def test_current_context_is_none_outside_any_span(trace_sandbox):
    assert trace.current_context() is None


def test_context_adoption_links_remote_child(trace_sandbox):
    """The driver→worker hop: current_context() stamped into a task
    descriptor, adopted with trace.context() on the far side — the remote
    span must join the same tree."""
    with trace.span("driver.job"):
        ctx = trace.current_context()
    assert set(ctx) == {"trace_id", "parent_id"}
    trace.reset()  # the "worker process" starts with an empty buffer
    with trace.context(ctx):
        with trace.span("worker.task", task_id="0"):
            pass
    (task,) = trace.events_snapshot()
    assert task["args"]["trace_id"] == ctx["trace_id"]
    assert task["args"]["parent_id"] == ctx["parent_id"]


def test_context_with_falsy_or_partial_ctx_is_noop(trace_sandbox):
    for ctx in (None, {}, {"trace_id": "abc"}, "garbage"):
        with trace.context(ctx):
            with trace.span("worker.task"):
                pass
    for event in trace.events_snapshot():
        assert "parent_id" not in event["args"]


def test_drain_spans_pops_the_buffer(trace_sandbox):
    with trace.span("read.prefetch"):
        pass
    assert len(trace.drain_spans()) == 1
    assert trace.drain_spans() == []
    assert trace.events_snapshot() == []


def test_disabled_tracing_records_nothing(trace_sandbox):
    trace.disable()
    with trace.span("driver.job"):
        trace.count("read.tasks")
    assert trace.events_snapshot() == []
    assert trace.counters() == {}
    assert trace.current_context() is None  # no frame leaked either


# ---------------------------------------------------------------------------
# Assembly: one merged doc, flow events only on cross-process edges
# ---------------------------------------------------------------------------


def _evt(name, span_id, parent_id=None, pid=1, ts=0.0, dur=10.0, trace_id="t1"):
    args = {"trace_id": trace_id, "span_id": span_id}
    if parent_id:
        args["parent_id"] = parent_id
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": pid,
            "tid": 7, "args": args}


def test_assemble_emits_flows_only_for_cross_pid_edges():
    root = _evt("driver.job", "a", pid=1)
    local = _evt("driver.map_stage", "b", parent_id="a", pid=1)
    remote = _evt("worker.task", "c", parent_id="a", pid=2)
    doc = trace.assemble([[root, local], [remote]], counters={"read.tasks": 3})
    flows = [e for e in doc["traceEvents"] if e["ph"] in ("s", "f")]
    # exactly one source (at the driver span) + one finish (at the worker)
    assert [e["ph"] for e in sorted(flows, key=lambda e: e["ph"])] == ["f", "s"]
    src = next(e for e in flows if e["ph"] == "s")
    fin = next(e for e in flows if e["ph"] == "f")
    assert src["pid"] == 1 and fin["pid"] == 2
    assert src["id"] == fin["id"] == "a"
    assert doc["otherData"]["counters"] == {"read.tasks": 3}
    # the complete events all survive the merge
    assert [e for e in doc["traceEvents"] if e["ph"] == "X"] == [root, local, remote]


def test_assemble_orphan_parent_produces_no_flow():
    remote = _evt("worker.task", "c", parent_id="missing", pid=2)
    doc = trace.assemble([[remote]])
    assert [e for e in doc["traceEvents"] if e["ph"] in ("s", "f")] == []


def test_write_trace_doc_is_atomic_and_leaves_no_tmp(tmp_path):
    target = str(tmp_path / "out.json")
    written = trace.write_trace_doc(target, {"traceEvents": []})
    assert written == target
    with open(target) as f:
        assert json.load(f) == {"traceEvents": []}
    assert os.listdir(tmp_path) == ["out.json"]  # tmp sibling renamed away


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


def test_flight_ring_is_bounded_and_dump_is_parseable(flight_sandbox):
    trace.configure_flight(dir=flight_sandbox, ring=4, worker_id="w9")
    for i in range(10):
        trace.flight_record("worker.task", "B", task_id=i)
    path = trace.flight_dump("task_failure")
    assert path is not None and os.path.exists(path)
    assert os.path.basename(path).startswith("flight-w9-")
    assert path.endswith("-task_failure.jsonl")
    with open(path) as f:
        lines = [json.loads(line) for line in f]
    header, records = lines[0], lines[1:]
    assert header["flight_recorder"] == 1
    assert header["reason"] == "task_failure"
    assert header["worker"] == "w9"
    assert header["pid"] == os.getpid()
    assert header["events"] == len(records) == 4  # ring kept only the last 4
    assert [r["args"]["task_id"] for r in records] == [6, 7, 8, 9]
    assert not any(n.endswith(".tmp") for n in os.listdir(flight_sandbox))


def test_flight_dump_without_dir_returns_none(flight_sandbox):
    trace.flight_record("worker.task", "B")
    assert trace.flight_dump("drain") is None


def test_flight_ring_zero_disables_recording(flight_sandbox):
    trace.configure_flight(dir=flight_sandbox, ring=0)
    trace.flight_record("worker.task", "B")
    with trace.span("read.prefetch"):  # span-exit ring mirror also gated
        pass
    trace.configure_flight(ring=8)  # re-enable: ring starts empty
    path = trace.flight_dump("drain")
    with open(path) as f:
        header = json.loads(f.readline())
    assert header["events"] == 0


def test_flight_record_stamps_causal_context(trace_sandbox, flight_sandbox):
    trace.configure_flight(dir=flight_sandbox, ring=16)
    with trace.span("worker.task"):
        trace.flight_record("write.commit", "i")
        ctx = trace.current_context()
    path = trace.flight_dump("drain")
    with open(path) as f:
        records = [json.loads(line) for line in f][1:]
    commit = next(r for r in records if r["name"] == "write.commit")
    assert commit["args"]["trace_id"] == ctx["trace_id"]
    assert commit["args"]["parent_id"] == ctx["parent_id"]


def test_flight_atexit_hook_dumps_only_after_error(flight_sandbox):
    trace.configure_flight(dir=flight_sandbox, ring=8)
    trace.flight_record("worker.task", "B")
    trace._atexit_hook()  # no error noted: no dump
    assert not os.path.exists(flight_sandbox)
    trace.flight_note_error()
    trace._atexit_hook()
    dumps = os.listdir(flight_sandbox)
    assert len(dumps) == 1 and dumps[0].endswith("-atexit_after_error.jsonl")
    trace._atexit_hook()  # a successful dump clears the error flag
    assert len(os.listdir(flight_sandbox)) == 1


def test_flight_dump_counts_metric_by_reason(flight_sandbox):
    mreg.REGISTRY.reset_values()
    mreg.enable()
    try:
        trace.configure_flight(dir=flight_sandbox, ring=8)
        trace.flight_dump("drain")
        snap = mreg.REGISTRY.snapshot(compact=True)
        series = snap["flight_dumps_total"]["series"]
        assert [(s["labels"], s["value"]) for s in series] == [
            ({"reason": "drain"}, 1.0)
        ]
    finally:
        mreg.disable()
        mreg.REGISTRY.reset_values()


# ---------------------------------------------------------------------------
# Coordinator-side stores: span shards and the fleet merge
# ---------------------------------------------------------------------------


def test_trace_shard_store_accepts_and_drains():
    from s3shuffle_tpu.metadata.service import TraceShardStore

    store = TraceShardStore()
    assert store.report([]) == 0
    assert store.report([_evt("worker.task", "a")]) == 1
    assert store.report([_evt("storage.op", "b", parent_id="a")]) == 1
    spans = store.drain()
    assert [e["name"] for e in spans] == ["worker.task", "storage.op"]
    assert store.drain() == []


def test_trace_shard_store_refuses_whole_shard_at_cap():
    from s3shuffle_tpu.metadata.service import TraceShardStore

    store = TraceShardStore(bytes_max=256)
    big = [_evt("worker.task", f"s{i}") for i in range(50)]
    mreg.REGISTRY.reset_values()
    mreg.enable()
    try:
        assert store.report(big) == 0  # refused whole, not truncated
        snap = mreg.REGISTRY.snapshot(compact=True)
        (series,) = snap["trace_shard_drops_total"]["series"]
        assert series["labels"] == {"reason": "capacity"}
        assert series["value"] == 1.0
    finally:
        mreg.disable()
        mreg.REGISTRY.reset_values()
    assert store.drain() == []
    assert store.report([_evt("worker.task", "ok")]) == 1  # cap freed by drain


def _counter_snap(name, series):
    return {name: {"kind": "counter", "series": series}}


def test_merge_registry_snapshots_sums_counters_and_maxes_gauges():
    from s3shuffle_tpu.metadata.service import merge_registry_snapshots

    a = {
        "storage_read_bytes_total": {
            "kind": "counter",
            "series": [{"labels": {}, "value": 100.0}],
        },
        "task_queue_depth": {
            "kind": "gauge",
            "series": [{"labels": {}, "value": 3.0}],
        },
        "storage_op_seconds": {
            "kind": "histogram",
            "labelnames": ["scheme", "op"],
            "series": [{"labels": {"scheme": "file", "op": "read"},
                        "buckets": [1, 2], "sum": 0.5, "count": 3}],
        },
    }
    b = {
        "storage_read_bytes_total": {
            "kind": "counter",
            "series": [{"labels": {}, "value": 11.0}],
        },
        "task_queue_depth": {
            "kind": "gauge",
            "series": [{"labels": {}, "value": 2.0}],
        },
        "storage_op_seconds": {
            "kind": "histogram",
            "labelnames": ["scheme", "op"],
            "series": [{"labels": {"scheme": "file", "op": "read"},
                        "buckets": [4, 1], "sum": 1.5, "count": 5},
                       {"labels": {"scheme": "file", "op": "open"},
                        "buckets": [1, 0], "sum": 0.1, "count": 1}],
        },
    }
    merged = merge_registry_snapshots([a, b, "not-a-snapshot"])
    assert merged["storage_read_bytes_total"]["series"][0]["value"] == 111.0
    assert merged["task_queue_depth"]["series"][0]["value"] == 3.0  # MAX
    hist = merged["storage_op_seconds"]
    assert hist["labelnames"] == ["scheme", "op"]
    by_op = {s["labels"]["op"]: s for s in hist["series"]}
    assert by_op["read"]["buckets"] == [5, 3]
    assert by_op["read"]["sum"] == 2.0 and by_op["read"]["count"] == 8
    assert by_op["open"]["count"] == 1  # disjoint series carried through


def test_merge_registry_snapshots_never_aliases_inputs():
    from s3shuffle_tpu.metadata.service import merge_registry_snapshots

    snap = _counter_snap("x_total", [{"labels": {}, "value": 1.0}])
    merged = merge_registry_snapshots([snap])
    merged["x_total"]["series"][0]["value"] = 999.0
    assert snap["x_total"]["series"][0]["value"] == 1.0


def test_fleet_telemetry_merges_peaks_and_ages():
    from s3shuffle_tpu.metadata.service import FleetTelemetry

    fleet = FleetTelemetry()
    fleet.report("w0", _counter_snap("x_total", [{"labels": {}, "value": 1.0}]),
                 {"a/p1.data": 5, "a/p2.data": 2})
    fleet.report("w1", _counter_snap("x_total", [{"labels": {}, "value": 2.0}]),
                 {"a/p1.data": 9})
    view = fleet.view()
    assert sorted(view["workers"]) == ["w0", "w1"]
    for worker in view["workers"].values():
        assert worker["age_seconds"] >= 0.0
    # cross-worker OBJECT_GETS peaks: MAX per key
    assert view["object_gets_peaks"] == {"a/p1.data": 9, "a/p2.data": 2}
    assert view["metrics"]["x_total"]["series"][0]["value"] == 3.0
    # latest-sample-wins per worker: the table is bounded by fleet size
    fleet.report("w1", {}, {"a/p1.data": 1})
    view = fleet.view()
    assert view["workers"]["w1"]["peaks"] == {"a/p1.data": 1}
    assert view["object_gets_peaks"]["a/p1.data"] == 5  # w0 still holds 5


# ---------------------------------------------------------------------------
# Storage economics: rate card and cost digest
# ---------------------------------------------------------------------------


def test_parse_rate_card_defaults_and_overrides():
    from s3shuffle_tpu.costs import DEFAULT_RATE_CARD, parse_rate_card

    assert parse_rate_card("") == DEFAULT_RATE_CARD
    card = parse_rate_card("get=4e-7, put=1e-5")
    assert card["get"] == 4e-7 and card["put"] == 1e-5
    assert card["list"] == DEFAULT_RATE_CARD["list"]  # unnamed keep defaults


@pytest.mark.parametrize("spec", ["bogus=1", "get", "get=-1", "get=abc"])
def test_parse_rate_card_rejects_bad_specs(spec):
    from s3shuffle_tpu.costs import parse_rate_card

    with pytest.raises(ValueError):
        parse_rate_card(spec)


def test_config_validates_rate_card_up_front(tmp_path):
    with pytest.raises(ValueError):
        ShuffleConfig(root_dir=f"file://{tmp_path}", cost_rate_card="bogus=1")


def test_cost_digest_prices_a_snapshot():
    from s3shuffle_tpu.costs import GiB, cost_digest

    snapshot = {
        "storage_op_seconds": {
            "kind": "histogram",
            "series": [
                {"labels": {"scheme": "file", "op": "read"}, "count": 1000},
                {"labels": {"scheme": "file", "op": "open"}, "count": 500},
                {"labels": {"scheme": "file", "op": "create"}, "count": 10},
                {"labels": {"scheme": "file", "op": "write_close"}, "count": 10},
                # stream writes are NOT store requests (the commit is)
                {"labels": {"scheme": "file", "op": "write"}, "count": 9999},
            ],
        },
        "storage_read_bytes_total": {
            "kind": "counter", "series": [{"labels": {}, "value": 2 * GiB}],
        },
    }
    digest = cost_digest(
        snapshot, {"get": 1e-6, "put": 1e-5, "gb_read": 0.01}, shuffles=2
    )
    assert digest["ops"] == {"get": 1500.0, "put": 20.0}
    assert digest["dollars"]["get"] == pytest.approx(1.5e-3)
    assert digest["dollars"]["put"] == pytest.approx(2e-4)
    assert digest["dollars"]["gb_read"] == pytest.approx(0.02)
    assert digest["dollars_total"] == pytest.approx(1.5e-3 + 2e-4 + 0.02)
    assert digest["dollars_per_shuffle"] == pytest.approx(digest["dollars_total"] / 2)
    assert digest["read_bytes"] == 2 * GiB


def test_record_cost_metrics_mirrors_into_registry():
    from s3shuffle_tpu.costs import record_cost_metrics

    mreg.REGISTRY.reset_values()
    mreg.enable()
    try:
        record_cost_metrics({"dollars": {"get": 0.5, "put": 0.25, "delete": 0.0}})
        snap = mreg.REGISTRY.snapshot(compact=True)
        by_class = {
            s["labels"]["op_class"]: s["value"]
            for s in snap["cost_dollars_total"]["series"]
        }
        assert by_class == {"get": 0.5, "put": 0.25}  # zero classes skipped
    finally:
        mreg.disable()
        mreg.REGISTRY.reset_values()


# ---------------------------------------------------------------------------
# Critical-path analyzer
# ---------------------------------------------------------------------------


def test_critical_path_attributes_blame_and_covers_wall():
    from tools.critical_path import analyze

    job = _evt("driver.job", "j", pid=1, ts=0, dur=100.0)
    stage = _evt("driver.map_stage", "m", parent_id="j", pid=1, ts=2, dur=90.0)
    task = _evt("worker.task", "t", parent_id="m", pid=2, ts=5, dur=80.0)
    get = _evt("storage.op", "g", parent_id="t", pid=2, ts=6, dur=50.0)
    get["args"]["op"] = "read"
    put = _evt("storage.op", "p", parent_id="t", pid=2, ts=60, dur=20.0)
    put["args"]["op"] = "write_close"
    doc = trace.assemble([[job, stage], [task, get, put]])
    digest = analyze(doc, top=5)
    assert digest["trace_id"] == "t1"
    assert digest["job_wall_us"] == 100.0
    assert digest["coverage"] >= 0.9  # the stage covers 90% of the job wall
    blame = {row["bucket"]: row["work_us"] for row in digest["blame"]}
    assert blame["get_wait"] == 50.0
    assert blame["commit"] == 20.0
    assert blame["worker"] == pytest.approx(10.0)  # task exclusive time
    # heaviest-child chain: job -> map_stage -> task -> the 50us GET
    names = [entry["name"] for entry in digest["critical_path"]]
    assert names == ["driver.job", "driver.map_stage", "worker.task", "storage.op"]
    assert digest["critical_path"][-1]["args"] == {"op": "read"}


def test_critical_path_returns_none_without_spans():
    from tools.critical_path import analyze

    assert analyze({"traceEvents": []}) is None
    assert analyze({"traceEvents": [{"ph": "M", "name": "meta"}]}) is None


def test_critical_path_cli_renders_digest(tmp_path, capsys):
    from tools.critical_path import main

    job = _evt("driver.job", "j", pid=1, ts=0, dur=100.0)
    task = _evt("worker.task", "t", parent_id="j", pid=2, ts=5, dur=80.0)
    path = str(tmp_path / "t.json")
    trace.write_trace_doc(path, trace.assemble([[job, task]]))
    assert main([path]) == 0
    out = capsys.readouterr().out
    assert "driver.job" in out and "worker.task" in out
    assert main([path, "--json"]) == 0
    digest = json.loads(capsys.readouterr().out)
    assert digest["job_wall_us"] == 100.0


# ---------------------------------------------------------------------------
# The acceptance gate: spawned 2-worker fleet, one merged trace
# ---------------------------------------------------------------------------


def _traced_agent_main(coordinator, cfg_dict, worker_id):
    """Module-level worker main (spawn-picklable). Tracing + metrics arm
    via the inherited S3SHUFFLE_TRACE / S3SHUFFLE_METRICS environment."""
    from s3shuffle_tpu.config import ShuffleConfig as _Cfg
    from s3shuffle_tpu.storage.dispatcher import Dispatcher as _Disp
    from s3shuffle_tpu.worker import WorkerAgent as _Agent

    _Disp.reset()
    agent = _Agent(
        tuple(coordinator), config=_Cfg(**cfg_dict), worker_id=worker_id
    )
    agent.run_forever(poll_interval=0.01, heartbeat_s=0.3)


def _chain_to_root(event, by_id):
    """Walk parent_id links to the root; returns the list of names."""
    names = [event["name"]]
    seen = set()
    parent_id = event["args"].get("parent_id")
    while parent_id and parent_id not in seen:
        seen.add(parent_id)
        parent = by_id.get(parent_id)
        if parent is None:
            break
        names.append(parent["name"])
        parent_id = parent["args"].get("parent_id")
    return names


def test_distributed_job_produces_one_merged_linked_trace(tmp_path, monkeypatch):
    """ISSUE 16 acceptance: 2 spawned worker processes + a traced driver
    job -> ONE merged trace file where driver -> worker.task -> storage.op
    link by trace_id/parent chains across pids, flow events mark the causal
    edges, the critical-path digest explains >= 90% of the job wall, and
    ``trace_report --fleet`` prices the run in $/shuffle."""
    import dataclasses
    import multiprocessing as mp
    import random

    from s3shuffle_tpu.batch import RecordBatch
    from s3shuffle_tpu.cluster import DistributedDriver
    from tools.critical_path import analyze
    from tools.trace_report import render

    mreg.REGISTRY.reset_values()
    mreg.enable()
    Dispatcher.reset()
    trace.reset()
    trace_file = str(tmp_path / "merged_trace.json")
    trace.enable(trace_file)
    # children inherit the env at spawn: worker-side tracing ships span
    # shards to the coordinator; any worker-local residue flushes into tmp
    monkeypatch.setenv("S3SHUFFLE_TRACE", str(tmp_path / "worker_residue.json"))
    monkeypatch.setenv("S3SHUFFLE_METRICS", "1")

    cfg = ShuffleConfig(
        root_dir=f"file://{tmp_path}/store", app_id="traced", codec="zlib",
    )
    rng = random.Random(77)
    records = [(rng.randbytes(8), rng.randbytes(24)) for _ in range(3000)]
    batches = [RecordBatch.from_records(records[i::3]) for i in range(3)]
    driver = DistributedDriver(cfg)
    ctx = mp.get_context("spawn")
    workers = {}
    try:
        for wid in ("w0", "w1"):
            p = ctx.Process(
                target=_traced_agent_main,
                args=(list(driver.coordinator_address),
                      dataclasses.asdict(cfg), wid),
                daemon=True,
            )
            p.start()
            workers[wid] = p
        out = driver.run_sort_shuffle(batches, num_partitions=4)
        assert sorted(r for b in out for r in b.to_records()) == sorted(records)

        # drain the fleet so every span shard lands before assembly
        driver.drain_workers(["w0", "w1"])
        for wid, p in workers.items():
            p.join(timeout=15)
            assert p.exitcode == 0, f"worker {wid} exited {p.exitcode}"

        # --- ONE merged trace file -----------------------------------
        written = driver.dump_trace()
        assert written == trace_file
        with open(trace_file) as f:
            doc = json.load(f)
        events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        by_id = {e["args"]["span_id"]: e for e in events if e["args"].get("span_id")}
        root = next(e for e in events if e["name"] == "driver.job")
        assert root["pid"] == os.getpid()
        trace_id = root["args"]["trace_id"]

        tasks = [e for e in events if e["name"] == "worker.task"]
        assert tasks, "no worker.task spans reached the coordinator"
        worker_pids = {e["pid"] for e in tasks}
        assert os.getpid() not in worker_pids  # spans from REAL remote pids
        for task in tasks:
            assert task["args"]["trace_id"] == trace_id
            assert _chain_to_root(task, by_id)[-1] == "driver.job"

        # storage ops issued INSIDE tasks join the job's tree; drain-path
        # ops legitimately root their own worker-local traces
        storage_ops = [
            e for e in events
            if e["name"] == "storage.op" and e["pid"] in worker_pids
            and e["args"]["trace_id"] == trace_id
        ]
        assert storage_ops, "no worker storage.op spans linked to the job"
        for op in storage_ops:
            chain = _chain_to_root(op, by_id)
            assert "worker.task" in chain and chain[-1] == "driver.job"

        # causal edges across the process boundary render as flow events
        flows = [e for e in doc["traceEvents"] if e.get("ph") in ("s", "f")]
        assert any(e["ph"] == "s" for e in flows)
        assert any(e["ph"] == "f" for e in flows)

        # --- critical path explains the job wall ----------------------
        digest = analyze(doc)
        assert digest is not None
        assert digest["trace_id"] == trace_id
        assert digest["job_wall_us"] == pytest.approx(root["dur"])
        assert digest["coverage"] >= 0.9
        assert sum(row["work_us"] for row in digest["blame"]) > 0

        # --- fleet view: $/shuffle from a live run --------------------
        fleet_file = str(tmp_path / "fleet.json")
        driver.dump_fleet(fleet_file)
        with open(fleet_file) as f:
            fleet_doc = json.load(f)
        assert fleet_doc["fleet_workers"], "no worker pushed a fleet sample"
        cost = fleet_doc["cost"]
        assert cost["dollars_total"] > 0
        assert cost["dollars_per_shuffle"] == pytest.approx(
            cost["dollars_total"] / cost["shuffles"]
        )
        rendered = render(fleet_doc)
        assert "Fleet:" in rendered
        assert "/shuffle" in rendered
    finally:
        driver.shutdown()
        for p in workers.values():
            p.join(timeout=10)
            if p.is_alive():
                p.terminate()
        trace.disable()
        trace.reset()
        mreg.disable()
        mreg.REGISTRY.reset_values()


# ---------------------------------------------------------------------------
# The converse gate: tracing off is byte- and op-identical
# ---------------------------------------------------------------------------


def _recorded_roundtrip(tmp_path, tag, trace_on):
    from s3shuffle_tpu.dependency import HashPartitioner, ShuffleDependency
    from s3shuffle_tpu.shuffle import ShuffleManager
    from s3shuffle_tpu.storage.backend import _maybe_instrument
    from s3shuffle_tpu.storage.local import LocalBackend
    import random

    Dispatcher.reset()
    trace.reset()
    if trace_on:
        trace.enable(str(tmp_path / f"{tag}.json"))
    else:
        trace.disable()
    try:
        cfg = ShuffleConfig(
            root_dir=f"file://{tmp_path}/{tag}", app_id=tag, codec="zlib",
            cleanup=False,
        )
        d = Dispatcher(cfg)
        rec = RecordingBackend(LocalBackend())
        # the production wrap decision: instrumented iff metrics/trace on
        d.backend = _maybe_instrument(rec)
        manager = ShuffleManager(dispatcher=d)
        rng = random.Random(31)
        dep = ShuffleDependency(shuffle_id=0, partitioner=HashPartitioner(3))
        handle = manager.register_shuffle(0, dep)
        for map_id in range(2):
            w = manager.get_writer(handle, map_id)
            w.write([(rng.randrange(1000), rng.randbytes(40))
                     for _ in range(800)])
            w.stop(success=True)
        out = []
        for pid in range(3):
            out.append(sorted(manager.get_reader(handle, pid, pid + 1).read()))
        ops = sorted((op, p.rsplit("/", 1)[-1]) for op, p in rec.ops)
        return out, ops
    finally:
        trace.disable()
        trace.reset()


def test_tracing_off_is_byte_and_op_identical(tmp_path):
    """Observability must be free when off AND request-free when on: the
    traced run may time ops but must issue the exact same store-op multiset
    and produce byte-identical output."""
    out_on, ops_on = _recorded_roundtrip(tmp_path, "on", trace_on=True)
    out_off, ops_off = _recorded_roundtrip(tmp_path, "off", trace_on=False)
    assert out_on == out_off
    assert ops_on == ops_off  # tracing adds ZERO store requests
