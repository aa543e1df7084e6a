"""One run of one cell: set-up, the measured window, the check, the metrics.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the cell,
its configuration file and its traffic mix; the configuration names its
pipeline (``pipelines/<pipeline>.py``: inputs from the seed and one job
through the executor stand-in) and has its plain reference beside it
(``configs/<name>_reference.py``); each metric is read by
``metrics/<metric>.py`` from the window record built here.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import random
import shutil
import sys
import tempfile
import time
import traceback
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

_modules: dict = {}


def load_module(rel: str):
    """A file of the benchmark, imported by its path under ``perfbench/``."""
    if rel not in _modules:
        name = "perfbench_" + rel.replace("/", "_").replace(".", "_")
        spec = importlib.util.spec_from_file_location(name, os.path.join(HERE, rel))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _modules[rel] = mod
    return _modules[rel]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str) -> tuple:
    bench = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; cells: {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(REPO, entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    return bench, cell, config, traffic


def cell_metrics(bench: dict, cell: dict, trace: bool) -> list:
    metrics = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in metrics if cell["name"] in m.get("workloads", [cell["name"]])]


def log(line: str) -> None:
    print(line, file=sys.stderr, flush=True)


def observe(name: str, value) -> None:
    """An observation of this run, on an earlier line: not a metric."""
    log(f"observation {name}: {json.dumps(value, sort_keys=True, default=str)}")


class _CompileCounter:
    """JAX compile and trace events while ``active``: the window should have
    none. One listener per process (JAX keeps every listener registered)."""

    _instance = None

    def __init__(self):
        import jax

        self.active = True
        self.events: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @classmethod
    def get(cls) -> "_CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        cls._instance.active, cls._instance.events = True, {}
        return cls._instance

    def _on(self, event: str, duration: float, **_kw) -> None:
        if self.active and ("compile" in event or "trace" in event):
            self.events[event] = self.events.get(event, 0) + 1


def _series(snap: dict, name: str) -> list:
    return snap.get(name, {}).get("series", [])


def _window_record(snap: dict, **fields) -> SimpleNamespace:
    def counter(name, **labels):
        return int(sum(s["value"] for s in _series(snap, name)
                       if all(s.get("labels", {}).get(k) == v for k, v in labels.items())))

    def hist_sum(name):
        return sum(s["sum"] for s in _series(snap, name))

    return SimpleNamespace(counter=counter, hist_sum=hist_sum, **fields)


def _add(total: dict, part: dict) -> None:
    for k, v in part.items():
        total[k] = total.get(k, 0) + v


def run_cell(bench, cell, config, traffic, seed: int, seconds: float, trace: bool,
             shuffle_overrides=None, warmup: bool = True, keep_trace=None) -> dict:
    """One run. Returns the result line's object; observations go to stderr.
    ``shuffle_overrides`` changes the program's settings (the control runs
    of ``calibrate.py``); the check still holds it to the configuration.
    ``warmup=False`` skips the warm-up job, for a process whose kernels are
    already compiled (``calibrate.py``). ``keep_trace(trace)`` receives the
    loaded trace before it is reduced."""
    t_setup = time.perf_counter()
    import jax

    from executor import Executor
    from s3shuffle_tpu.codec.native import native_available
    from s3shuffle_tpu.config import ShuffleConfig
    from s3shuffle_tpu.metrics import registry
    from s3shuffle_tpu.storage.dispatcher import Dispatcher
    from s3shuffle_tpu.utils.compile_cache import enable_compile_cache

    import store
    import trace_reduce

    observe("compile_cache_dir", enable_compile_cache())
    if not native_available():  # builds the native library when it is missing
        raise RuntimeError("the native library cannot be built or loaded")
    registry.enable()
    compiles = _CompileCounter.get()
    pipeline = load_module(f"pipelines/{config['pipeline']}.py")
    reference = load_module(f"configs/{config['name']}_reference.py")
    inputs = pipeline.generate(config, traffic, seed)
    job_bytes = pipeline.user_bytes(config, traffic, inputs)
    block = config["shuffle"]["codec_block_size"]
    root = tempfile.mkdtemp(prefix="perfbench-store-")
    Dispatcher.reset()
    shuffle_kw = {**config["shuffle"], **(shuffle_overrides or {})}
    scfg = ShuffleConfig(root_dir=f"file://{root}", app_id=f"perfbench-{cell['name']}",
                         **shuffle_kw)
    ex = Executor(scfg, slots=config["executor_cores"], annotate=trace)
    trace_dir = None
    try:
        # warm-up: the pipeline's warm-up job (or one whole job) compiles, or
        # loads from the persistent cache, exactly the launch shapes this
        # cell's segments produce
        if warmup:
            t0 = time.perf_counter()
            getattr(pipeline, "warmup_job", pipeline.run_job)(ex, inputs, config, traffic)
            observe("warmup_job_s", time.perf_counter() - t0)
            ex.unregister(list(ex.open_shuffles))
        observe("compiles_in_setup", compiles.events)
        setup_s = time.perf_counter() - t_setup

        registry.REGISTRY.reset_values()
        ex.tasks.clear()
        rng = random.Random(seed)
        keep = traffic["retained_jobs"]
        retained: list = []  # [(job index, output)], a reservoir drawn from the seed
        window_sids: list = []  # every shuffle of the window, kept until it closes
        jobs: list = []
        failed = 0
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        compiles.events = {}
        cpu0 = time.process_time()
        t_start = time.perf_counter()
        while time.perf_counter() - t_start < seconds:
            idx = len(jobs)
            t0 = time.perf_counter()
            before = len(ex.open_shuffles)
            try:
                with (jax.profiler.TraceAnnotation("job") if trace
                      else contextlib.nullcontext()):
                    out = pipeline.run_job(ex, inputs, config, traffic)
            except Exception:
                failed += 1
                log(traceback.format_exc())
                jobs.append({"t0": t0, "t1": time.perf_counter(), "user_bytes": 0})
                break
            window_sids.extend(ex.open_shuffles[before:])
            if len(retained) < keep:
                retained.append((idx, out))
            else:
                j = rng.randrange(idx + 1)
                if j < keep:
                    retained[j] = (idx, out)
            jobs.append({"t0": t0, "t1": time.perf_counter(), "user_bytes": job_bytes})
        t_end = time.perf_counter()
        cpu_s = time.process_time() - cpu0
        compiles.active = False  # the window's compile events are counted
        if trace:
            jax.profiler.stop_trace()
        window_s = t_end - t_start
        snap = registry.REGISTRY.snapshot()
        dev = jax.devices()[0]
        memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                           for d in jax.devices())
        observe("window", {"jobs": len(jobs), "span_s": jobs[-1]["t1"] - jobs[0]["t0"],
                           "overrun_s": t_end - t_start - seconds, "seconds": seconds,
                           "reduce_tasks": sum(t["kind"] == "reduce" for t in ex.tasks),
                           "map_tasks": sum(t["kind"] == "map" for t in ex.tasks)})
        observe("task_walls_s", {
            kind: [sorted(round(t["t1"] - t["t0"], 4) for t in ex.tasks
                          if t["kind"] == kind and t["shuffle_id"] == sid)
                   for sid in window_sids]
            for kind in ("map", "reduce")})
        observe("compiles_in_window", compiles.events)
        # what the window's shuffles stored, read once the window has closed
        store_total: dict = {}
        for sid in window_sids:
            _add(store_total, store.shuffle_stats(root, sid, block))
        observe("store", store_total)
        for name in ("codec_encode_batch_seconds", "codec_decode_batch_seconds",
                     "codec_kernel_compile_seconds"):
            observe(name, {"count": sum(s["count"] for s in _series(snap, name)),
                           "sum_s": sum(s["sum"] for s in _series(snap, name))})

        # the device path the configuration states, from the program's counters
        w = _window_record(snap)
        checks = {
            "jobs_failed": failed,
            "device_batches_failed": w.counter("codec_device_batches_total", outcome="failed"),
            "host_pins": w.counter("codec_host_pins_total"),
            "host_selections": w.counter("codec_path_selected_total", path="host")
            + w.counter("codec_path_selected_total", path="streaming"),
            "full_blocks_not_device_encoded":
                store_total.get("full_frames", 0) - w.counter("codec_fused_crc_total"),
            "full_tlz_frames_not_device_decoded":
                store_total.get("full_tlz_frames", 0)
                - w.counter("codec_fused_crc_validated_total"),
        }
        observe("device_path", {"encode_frames_fused": w.counter("codec_fused_crc_total"),
                                "decode_frames_fused":
                                    w.counter("codec_fused_crc_validated_total"),
                                "partial_frames_host_encoded":
                                    store_total.get("partial_frames", 0)})
        crc_checked = crc_wrong = 0
        for sid in window_sids:
            c, bad = store.checksum_mismatches(
                root, sid, config["shuffle"]["checksum_algorithm"])
            crc_checked += c
            crc_wrong += bad
        checks["checksum_mismatches"] = crc_wrong
        stated = _stated_geometry(config["shuffle"])
        par_checked = par_wrong = 0
        for sid in window_sids:
            c, bad = store.parity_mismatches(root, sid, stated)
            par_checked += c
            par_wrong += bad
        checks["parity_groups_wrong"] = par_wrong
        ex.unregister(window_sids)
        observe("checked", {"jobs": [i for i, _o in retained],
                            "partitions_crc_checked": crc_checked,
                            "parity_groups_checked": par_checked})
    finally:
        ex.stop()
        shutil.rmtree(root, ignore_errors=True)

    reduced = None
    if trace:
        t0 = time.perf_counter()
        loaded = trace_reduce.load(trace_dir)
        if keep_trace is not None:
            keep_trace(loaded)
        reduced = trace_reduce.reduce(loaded)
        shutil.rmtree(trace_dir, ignore_errors=True)
        observe("trace_reduce_s", time.perf_counter() - t0)
        observe("trace_modules", trace_reduce.top(reduced["modules"], 20))

    t0 = time.perf_counter()
    checks.update(reference.check(config, inputs, [o for _i, o in retained]))
    observe("reference_s", time.perf_counter() - t0)

    kind = dev.device_kind
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    record = _window_record(
        snap, setup_s=setup_s, jobs=jobs, tasks=ex.tasks, cpu_s=cpu_s,
        user_bytes=sum(j["user_bytes"] for j in jobs),
        span_s=jobs[-1]["t1"] - jobs[0]["t0"], window_s=window_s, store=store_total,
        trace=reduced, peaks=peaks.get(kind), kernels=_kernels(),
    )
    correct = all(v == 0 for v in checks.values())
    metrics = {}
    if correct:
        if trace and kind not in peaks and dev.platform != "cpu":
            raise RuntimeError(f"no peaks for device kind {kind!r} in peaks.json")
        for m in cell_metrics(bench, cell, trace):
            value = load_module(f"metrics/{m['name']}.py").read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": kind, "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(jobs), "failed": failed,
              "metrics": metrics, "device": device}
    if trace and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = window_s
        result["breakdown"] = {"device_ops": trace_reduce.top(reduced["ops"]),
                               "idle_gaps": trace_reduce.top(reduced["idle_gaps"])}
    result["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    return result


def _stated_geometry(shuffle: dict):
    """The parity geometry ``(m, k, chunk_bytes)`` a configuration states,
    or None where it states no parity."""
    if not shuffle.get("parity_segments"):
        return None
    return (shuffle["parity_segments"], shuffle["parity_stripe_k"],
            shuffle["parity_chunk_bytes"])


def _kernels() -> dict:
    d = os.path.join(HERE, "kernels")
    return {f[:-5]: load_json(os.path.join(d, f)) for f in sorted(os.listdir(d))
            if f.endswith(".json")}
