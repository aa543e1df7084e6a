"""Span self time in the metrics registry, the profiler annotation of every
span, and the device codec's launch-shape and launch-queue counters."""

import glob
import os
import random
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from s3shuffle_tpu.metrics import registry as mreg
from s3shuffle_tpu.ops import rates, tlz
from s3shuffle_tpu.utils import trace

BS = 1024  # small block keeps the XLA:CPU launches fast


@pytest.fixture
def metrics_on():
    mreg.REGISTRY.reset_values()
    mreg.enable()
    yield mreg.REGISTRY
    mreg.disable()
    mreg.REGISTRY.reset_values()


@pytest.fixture
def fake_clock(monkeypatch):
    """A perf_counter the test advances by hand (shared by every thread)."""
    clock = SimpleNamespace(ns=10**9)
    fake = SimpleNamespace(perf_counter_ns=lambda: clock.ns, time=time.time)
    monkeypatch.setattr(trace, "time", fake)
    return clock


def _counter(name: str, **labels) -> float:
    series = mreg.REGISTRY.snapshot().get(name, {}).get("series", [])
    return sum(s["value"] for s in series
               if all(s.get("labels", {}).get(k) == v for k, v in labels.items()))


def _self_us(span: str) -> float:
    return _counter("trace_span_self_us_total", span=span)


def test_nested_spans_report_duration_minus_children(metrics_on, fake_clock):
    with trace.span("task.map"):
        fake_clock.ns += 5_000_000
        with trace.span("record.partition"):
            fake_clock.ns += 7_000_000
            with trace.span("codec.compile"):
                fake_clock.ns += 2_000_000
        fake_clock.ns += 3_000_000
        with trace.span("record.partition"):
            fake_clock.ns += 1_000_000
    assert _self_us("task.map") == 8_000  # 18 ms minus 7+2 and 1 ms of children
    assert _self_us("record.partition") == 8_000  # 9 - 2 ms, then 1 ms
    assert _self_us("codec.compile") == 2_000


def _histogram(name: str, **labels) -> tuple:
    series = mreg.REGISTRY.snapshot()[name]["series"]
    (s,) = [s for s in series
            if all(s.get("labels", {}).get(k) == v for k, v in labels.items())]
    return s["count"], s["sum"]


def test_span_feeds_its_histogram_its_whole_duration(metrics_on, fake_clock):
    with trace.span("record.partition"):
        fake_clock.ns += 3_000_000
        with trace.span("codec.compile", kernel="tlz_encode"):
            fake_clock.ns += 2_000_000
    with trace.span("write.commit") as commit:
        fake_clock.ns += 4_000_000
    # the histogram takes the duration, children included; self time does not
    assert _histogram("record_partition_seconds") == (1, pytest.approx(0.005))
    assert _self_us("record.partition") == 3_000
    assert _histogram("codec_kernel_compile_seconds", kernel="tlz_encode") == (
        1, pytest.approx(0.002))
    assert commit.seconds == pytest.approx(0.004)
    assert _histogram("write_commit_seconds") == (1, pytest.approx(0.004))


def test_abandoned_reader_leaves_no_span_open(metrics_on, tmp_path):
    from s3shuffle_tpu.config import ShuffleConfig
    from s3shuffle_tpu.dependency import HashPartitioner, ShuffleDependency
    from s3shuffle_tpu.manager import ShuffleManager
    from s3shuffle_tpu.serializer import ColumnarKVSerializer

    manager = ShuffleManager(ShuffleConfig(root_dir=f"file://{tmp_path}", app_id="abandon"))
    try:
        dep = ShuffleDependency(0, HashPartitioner(1), serializer=ColumnarKVSerializer())
        handle = manager.register_shuffle(0, dep)
        writer = manager.get_writer(handle, 0)
        writer.write([(f"k{i}".encode(), b"v" * 64) for i in range(5000)])
        writer.stop(success=True)
        for it in (manager.get_reader(handle, 0, 1).read(),
                   manager.get_reader(handle, 0, 1).read_batches()):
            next(it)
            assert trace._frames() == []  # nothing held open across the yield
    finally:
        manager.stop()
    assert trace._frames() == []


def test_cross_thread_child_does_not_subtract(metrics_on, fake_clock, tmp_path):
    trace.reset()
    trace.enable(str(tmp_path / "trace.json"))
    try:
        with trace.span("task.reduce"):
            ctx = trace.current_context()
            fake_clock.ns += 4_000_000

            def job():
                with trace.context(ctx), trace.span("codec.decode.batch"):
                    fake_clock.ns += 10_000_000

            t = threading.Thread(target=job)
            t.start()
            t.join()
            fake_clock.ns += 1_000_000
        events = {e["name"]: e for e in trace.events_snapshot()}
    finally:
        trace.disable()
        trace.reset()
    # the parent covers the other thread's 10 ms and keeps them as self time
    assert _self_us("task.reduce") == 15_000
    assert _self_us("codec.decode.batch") == 10_000
    parent, child = events["task.reduce"], events["codec.decode.batch"]
    assert child["args"]["trace_id"] == parent["args"]["trace_id"]
    assert child["args"]["parent_id"] == parent["args"]["span_id"]


def test_span_is_the_shared_noop_with_registry_and_tracing_off():
    assert not mreg.enabled() and not trace.enabled()
    assert trace.span("task.map") is trace._NOOP
    mreg.enable()
    try:
        assert trace.span("task.map") is not trace._NOOP
    finally:
        mreg.disable()
        mreg.REGISTRY.reset_values()


def test_registry_only_spans_record_no_chrome_events(metrics_on):
    trace.reset()
    with trace.span("agg.sort"):
        assert trace.current_context() is None  # no causal ids without tracing
    assert trace.events_snapshot() == []
    assert "trace_span_self_us_total" in mreg.REGISTRY.snapshot()


def test_span_name_lands_in_the_profiler_host_plane(metrics_on, tmp_path):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("task.reduce"):
            jnp.arange(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    host = [ev.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]
    assert "task.reduce" in host


def test_encode_launch_counts_real_and_pad_rows(metrics_on):
    rng = random.Random(3)
    blob = bytes(rng.randrange(256) for _ in range(3 * BS))
    payloads, _ = tlz.encode_batch_device(blob, 3, BS, batch_blocks=4)
    assert len(payloads) == 3
    assert _counter("codec_launches_total", op="encode") == 1
    assert _counter("codec_launch_rows_total", op="encode", row="real") == 3
    assert _counter("codec_launch_rows_total", op="encode", row="pad") == 1
    assert _counter("codec_launches_ahead_total", op="encode") == 0
    for name in ("codec.encode.stage", "codec.encode.device", "codec.encode.assemble"):
        assert name in {s["labels"]["span"] for s in mreg.REGISTRY.snapshot()
                        ["trace_span_self_us_total"]["series"]}


def test_decode_launch_counts_host_fallback_rows_as_pad(metrics_on):
    rng = random.Random(4)
    raw = [bytes(rng.randrange(4) for _ in range(BS)) for _ in range(3)]
    raw.append(b"tail" * 40)  # a short block: the host decoder serves it
    payloads = [tlz._assemble_payload_numpy(r) for r in raw]
    out, _ = tlz.decode_batch_device(payloads, [len(r) for r in raw], BS, batch_rows=4)
    assert out == raw
    assert _counter("codec_launches_total", op="decode") == 1
    assert _counter("codec_launch_rows_total", op="decode", row="real") == 3
    assert _counter("codec_launch_rows_total", op="decode", row="pad") == 1


def test_launches_ahead_counts_undrained_launches_on_the_same_device(metrics_on):
    dev_a, dev_b = object(), object()
    rates.launch_dispatched("decode", dev_a, 3, 1)
    rates.launch_dispatched("decode", dev_a, 2, 0)  # one ahead on dev_a
    rates.launch_dispatched("encode", dev_b, 2, 0)  # none ahead on dev_b
    rates.launch_drained(dev_a)
    rates.launch_dispatched("decode", dev_a, 1, 0)  # one still ahead
    rates.launch_drained(dev_a)
    rates.launch_drained(dev_a)
    rates.launch_drained(dev_b)
    assert _counter("codec_launches_ahead_total", op="decode") == 2
    assert _counter("codec_launches_ahead_total", op="encode") == 0
    assert _counter("codec_launches_total", op="decode") == 3
    assert _counter("codec_launch_rows_total", op="decode", row="pad") == 1
    assert not any(rates._queued.values())


def test_task_spans_cover_a_local_shuffle(metrics_on, tmp_path):
    from s3shuffle_tpu.config import ShuffleConfig
    from s3shuffle_tpu.shuffle import ShuffleContext

    cfg = ShuffleConfig(root_dir=f"file://{tmp_path}", app_id="spans")
    ctx = ShuffleContext(config=cfg, num_workers=2)
    try:
        parts = [[(f"k{i}".encode(), np.int64(i).tobytes()) for i in range(m, 400, 3)]
                 for m in range(3)]
        out = ctx.run_shuffle(parts, 4)
    finally:
        ctx.stop()
    assert sum(len(o) for o in out) == 400
    spans = {s["labels"]["span"] for s in mreg.REGISTRY.snapshot()
             ["trace_span_self_us_total"]["series"]}
    assert {"task.map", "task.reduce", "write.commit", "read.prefetch_wait",
            "record.deserialize"} <= spans


def test_launch_queue_survives_racing_threads(metrics_on):
    """More dispatching threads than cores, switching every microsecond: a
    lost update on the process-wide queue would leave it off zero."""
    import sys

    dev = object()
    n_threads, per_thread = 16, 300
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def worker():
            for _ in range(per_thread):
                rates.launch_dispatched("decode", dev, 1, 0)
                rates.launch_drained(dev)

        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert rates._queued[dev] == 0
    assert _counter("codec_launches_total", op="decode") == n_threads * per_thread
    # a launch can have at most every other thread's launch ahead of it
    ahead = _counter("codec_launches_ahead_total", op="decode")
    assert 0 <= ahead <= (n_threads - 1) * n_threads * per_thread


def test_trace_report_digests_the_launch_queue_of_both_ops(metrics_on):
    import tools.trace_report as trace_report

    dev = object()
    rates.launch_dispatched("encode", dev, 2, 0)
    rates.launch_dispatched("encode", dev, 2, 0)  # one ahead
    rates.launch_drained(dev)
    rates.launch_drained(dev)
    for _ in range(3):  # 0, 1, 2 ahead
        rates.launch_dispatched("decode", dev, 3, 1)
    for _ in range(3):
        rates.launch_drained(dev)
    snap = mreg.REGISTRY.snapshot()
    snap["codec_encode_bytes_total"] = {"series": [{"value": 1 << 20}]}
    snap["codec_encode_batch_seconds"] = {"series": [{"sum": 1.0, "count": 2}]}
    snap["codec_decode_bytes_total"] = {"series": [{"value": 1 << 20}]}
    snap["codec_decode_batch_seconds"] = {"series": [{"sum": 1.0, "count": 3}]}
    assert trace_report._codec_line(snap).endswith(
        "; 2 device launches, 0.50 queued ahead of each")
    assert trace_report._codec_read_line(snap).endswith(
        "; 3 device launches, 1.00 queued ahead of each")
