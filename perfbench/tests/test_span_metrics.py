"""The per-layer metrics read from the program's span self times and launch
counters: arithmetic on a synthetic registry snapshot, silence on a program
that has none of them, and the launch-shape count of a CPU job with the
device path forced."""

import pytest

import harness

SPAN_METRICS = (
    "encode_device_s_per_launch", "decode_device_s_per_launch", "decode_launches_ahead",
    "codec_host_s_per_launch", "launch_pad_share", "encode_harvest_s_per_map_task",
    "map_host_s_per_task", "decode_harvest_s_per_reduce_task",
    "deserialize_s_per_reduce_task", "sort_s_per_reduce_task", "task_unattributed_share",
)

SELF_US = {
    "task.map": 400_000, "task.reduce": 600_000,
    "record.partition": 1_000_000, "record.serialize": 500_000, "write.commit": 250_000,
    "codec.encode.harvest": 16_000_000, "codec.encode.stage": 80_000,
    "codec.encode.device": 18_400_000, "codec.encode.assemble": 320_000,
    "codec.decode.parse": 240_000, "codec.decode.device": 30_000_000,
    "codec.decode.emit": 360_000, "codec.decode.harvest": 48_000_000,
    "record.deserialize": 800_000, "agg.sort": 1_600_000, "storage.op": 9_000,
}


def _snapshot():
    def series(name, rows):
        return {name: {"series": [{"labels": labels, "value": float(v)} for labels, v in rows]}}

    snap = {}
    snap.update(series("trace_span_self_us_total",
                       [({"span": k}, v) for k, v in SELF_US.items()]))
    snap.update(series("codec_launches_total",
                       [({"op": "encode"}, 8), ({"op": "decode"}, 16)]))
    snap.update(series("codec_launch_rows_total", [
        ({"op": "encode", "row": "real"}, 512), ({"op": "encode", "row": "pad"}, 0),
        ({"op": "decode", "row": "real"}, 500), ({"op": "decode", "row": "pad"}, 12)]))
    snap.update(series("codec_launches_ahead_total",
                       [({"op": "encode"}, 3), ({"op": "decode"}, 40)]))
    return snap


TASKS = ([{"kind": "map", "t0": 0.0, "t1": 20.0}]
         + [{"kind": "reduce", "t0": 20.0, "t1": 30.0} for _ in range(8)])


def _read(name, snap, tasks=TASKS):
    w = harness._window_record(snap, tasks=tasks)
    return harness.load_module(f"metrics/{name}.py").read(w)


def test_span_metrics_on_a_synthetic_snapshot():
    snap = _snapshot()
    got = {m: _read(m, snap) for m in SPAN_METRICS}
    assert got["encode_device_s_per_launch"] == pytest.approx(18.4 / 8)
    assert got["decode_device_s_per_launch"] == pytest.approx(30.0 / 16)
    assert got["decode_launches_ahead"] == pytest.approx(40 / 16)
    assert got["codec_host_s_per_launch"] == pytest.approx((0.08 + 0.32 + 0.24 + 0.36) / 24)
    assert got["launch_pad_share"] == pytest.approx(100.0 * 12 / 1024)
    assert got["encode_harvest_s_per_map_task"] == pytest.approx(16.0)
    assert got["map_host_s_per_task"] == pytest.approx(1.75)
    assert got["decode_harvest_s_per_reduce_task"] == pytest.approx(48.0 / 8)
    assert got["deserialize_s_per_reduce_task"] == pytest.approx(0.8 / 8)
    assert got["sort_s_per_reduce_task"] == pytest.approx(1.6 / 8)
    # 1.0 s of root self time over 20 + 8 x 10 s of task walls
    assert got["task_unattributed_share"] == pytest.approx(1.0)


def test_span_metrics_are_silent_without_the_program_counters():
    parent = {"codec_device_batches_total": {"series": [
        {"labels": {"op": "encode", "outcome": "ok"}, "value": 8.0}]}}
    assert {m: _read(m, parent) for m in SPAN_METRICS} == {m: None for m in SPAN_METRICS}


def test_a_queue_that_never_forms_reads_zero_not_silence():
    snap = _snapshot()
    snap["codec_launches_ahead_total"]["series"] = []
    assert _read("decode_launches_ahead", snap) == 0.0


def test_launch_pad_share_counts_segment_shapes_on_the_cpu(monkeypatch):
    """The 128 MiB split at 200 reducers (Spark's default shuffle
    partitions) leaves 2 full blocks and a host-encoded tail per segment;
    at 2 such segments each encodes in a 2-row launch and decodes its 3
    frames in a 4-row launch whose tail row and bucket row are pad, so 4 of
    every 12 launched rows are pad."""
    bench, cell, config, traffic = harness.load_cell("terasort.r8")
    per_segment = config["records_per_job"] // 200
    traffic["reducers"] = 2
    config["records_per_job"] = 2 * per_segment
    for k, v in {**config.get("env", {}), "S3SHUFFLE_TPU_CODEC_DEVICE": "1"}.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(harness, "cell_metrics", lambda b, c, t: b["per_layer"])
    res = harness.run_cell(bench, cell, config, traffic, 2**31 + 77, 0.3, False)
    assert res["correct"], res["checks"]
    assert res["metrics"]["launch_pad_share"]["value"] == pytest.approx(100.0 / 3)
    assert res["metrics"]["decode_launches_ahead"]["value"] >= 0.0
    for name in SPAN_METRICS:
        assert name in res["metrics"], name
