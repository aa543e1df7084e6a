#!/usr/bin/env python
"""Trace / ShuffleStats report analyzer.

The reference's answer to "where did the time go" is an external Grafana
dashboard over jvm-profiler samples (examples/README.md:54-101). This CLI is
the in-repo equivalent: point it at either

- a **Chrome trace JSON** written by :mod:`s3shuffle_tpu.utils.trace`
  (``S3SHUFFLE_TRACE=<path>``), or
- a **ShuffleStats report** written by the metrics subsystem
  (``S3SHUFFLE_STATS=<path>``, or ``ShuffleStatsCollector.dump``),

and it prints per-span / per-histogram p50/p95/p99 latencies, the top time
consumers, and bytes/throughput tables.

Usage:
    python -m tools.trace_report s3shuffle_trace.json
    python -m tools.trace_report shuffle_stats.json --top 15
    python -m tools.trace_report --selftest   # fast smoke check (CI tier-1)
"""

from __future__ import annotations

import argparse
import json
from typing import Dict, List, Optional, Sequence, Tuple

QUANTILES = (0.5, 0.95, 0.99)


# ---------------------------------------------------------------------------
# Shared formatting
# ---------------------------------------------------------------------------


def _fmt_seconds(s: float) -> str:
    if s >= 1.0:
        return f"{s:.2f}s"
    if s >= 1e-3:
        return f"{s * 1e3:.2f}ms"
    return f"{s * 1e6:.0f}µs"


def _fmt_bytes(n: float) -> str:
    for unit, scale in (("GiB", 1 << 30), ("MiB", 1 << 20), ("KiB", 1 << 10)):
        if n >= scale:
            return f"{n / scale:.2f} {unit}"
    return f"{int(n)} B"


def _table(headers: Sequence[str], rows: List[Sequence[str]]) -> str:
    widths = [
        max(len(str(h)), *(len(str(r[i])) for r in rows)) if rows else len(str(h))
        for i, h in enumerate(headers)
    ]
    def line(cells):
        return "  ".join(str(c).ljust(w) for c, w in zip(cells, widths)).rstrip()
    sep = "  ".join("-" * w for w in widths)
    return "\n".join([line(headers), sep, *(line(r) for r in rows)])


def _exact_quantile(sorted_values: List[float], q: float) -> float:
    if not sorted_values:
        return 0.0
    idx = min(len(sorted_values) - 1, max(0, round(q * (len(sorted_values) - 1))))
    return sorted_values[idx]


def _import_quantile():
    """The bucket math lives in ONE place — the metric registry's
    snapshot/percentile API (the tuning controllers read the same function).
    Direct-script invocation (``python tools/trace_report.py``) has tools/ as
    sys.path[0], so bootstrap the repo root like ``-m`` would."""
    try:
        from s3shuffle_tpu.metrics.registry import quantile_from_buckets
    except ModuleNotFoundError:
        import os
        import sys

        sys.path.insert(
            0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        from s3shuffle_tpu.metrics.registry import quantile_from_buckets
    return quantile_from_buckets


quantile_from_buckets = _import_quantile()


def histogram_quantile(
    bounds: Sequence[float], counts: Sequence[int], q: float
) -> float:
    """Historical CLI-local name; delegates to
    :func:`s3shuffle_tpu.metrics.registry.quantile_from_buckets` (linear
    interpolation within the winning bin; the overflow bin answers the last
    finite bound, a lower bound on the true value)."""
    return quantile_from_buckets(bounds, counts, q)


# ---------------------------------------------------------------------------
# Chrome trace rendering
# ---------------------------------------------------------------------------


def render_trace(doc: dict, top: int = 10) -> str:
    events = [e for e in doc.get("traceEvents", []) if e.get("ph") == "X"]
    by_name: Dict[str, List[float]] = {}
    for e in events:
        by_name.setdefault(e.get("name", "?"), []).append(float(e.get("dur", 0.0)) / 1e6)
    out: List[str] = []
    total_all = sum(sum(v) for v in by_name.values())
    if by_name:
        rows = []
        for name, durs in sorted(
            by_name.items(), key=lambda kv: -sum(kv[1])
        )[:top]:
            durs.sort()
            total = sum(durs)
            rows.append(
                (
                    name,
                    len(durs),
                    _fmt_seconds(total),
                    f"{100.0 * total / total_all:.1f}%" if total_all else "-",
                    _fmt_seconds(_exact_quantile(durs, 0.5)),
                    _fmt_seconds(_exact_quantile(durs, 0.95)),
                    _fmt_seconds(_exact_quantile(durs, 0.99)),
                )
            )
        out.append(f"Spans (top {min(top, len(by_name))} by total time):")
        out.append(
            _table(("span", "count", "total", "share", "p50", "p95", "p99"), rows)
        )
    else:
        out.append("No complete ('X') span events in trace.")
    counters = doc.get("otherData", {}).get("counters", {})
    if counters:
        wall_s = 0.0
        if events:
            t0 = min(float(e["ts"]) for e in events)
            t1 = max(float(e["ts"]) + float(e.get("dur", 0.0)) for e in events)
            wall_s = (t1 - t0) / 1e6
        rows = []
        for name, value in sorted(counters.items()):
            if "bytes" in name.lower():
                thr = _fmt_bytes(value / wall_s) + "/s" if wall_s else "-"
                rows.append((name, _fmt_bytes(value), thr))
            else:
                rows.append((name, f"{value:g}", "-"))
        out.append("")
        out.append(f"Counters (trace wall {_fmt_seconds(wall_s)}):")
        out.append(_table(("counter", "value", "throughput"), rows))
    return "\n".join(out)


# ---------------------------------------------------------------------------
# ShuffleStats / registry-snapshot rendering
# ---------------------------------------------------------------------------


def _series_label(name: str, series: dict) -> str:
    labels = series.get("labels")
    if not labels:
        return name
    inner = ",".join(f"{k}={v}" for k, v in labels.items())
    return f"{name}{{{inner}}}"


def _counter_total(snapshot: dict, name: str) -> float:
    return sum(
        float(s.get("value", 0))
        for s in snapshot.get(name, {}).get("series", [])
    )


def _control_plane_line(
    snapshot: dict, reduce_tasks: Optional[int] = None
) -> Optional[str]:
    """One-line control-plane digest: tracker RPC round-trips issued (per
    reduce task when the enclosing ShuffleStats report says how many ran)
    and how reduce-side lookups were answered — epoch snapshot (zero
    round-trips) vs live RPC."""
    rpcs = _counter_total(snapshot, "meta_rpc_total")
    by_source = {
        s.get("labels", {}).get("source", "?"): float(s.get("value", 0))
        for s in snapshot.get("meta_lookup_source_total", {}).get("series", [])
    }
    lookups = sum(by_source.values())
    if rpcs <= 0 and lookups <= 0:
        return None
    line = f"Control plane: {rpcs:g} tracker RPCs"
    if reduce_tasks:
        line += f" ({rpcs / reduce_tasks:.2f} per reduce task)"
    if lookups > 0:
        hits = by_source.get("snapshot", 0.0)
        line += (
            f"; lookups {lookups:g} "
            f"({hits:g} snapshot / {by_source.get('rpc', 0.0):g} rpc, "
            f"{100.0 * hits / lookups:.2f}% snapshot hit ratio)"
        )
    return line


def _scan_planner_line(snapshot: dict) -> Optional[str]:
    """One-line scan-planner digest: GETs issued vs GETs saved by coalescing,
    and the over-read (waste) price paid for the merges."""
    segments = _counter_total(snapshot, "read_coalesced_segments_total")
    if segments <= 0:
        return None
    saved = _counter_total(snapshot, "read_gets_saved_total")
    waste = _counter_total(snapshot, "read_coalesce_waste_bytes_total")
    read_bytes = _counter_total(snapshot, "storage_read_bytes_total")
    line = (
        f"Scan planner: {segments:g} coalesced segments, {saved:g} GETs saved "
        f"({segments + saved:g} → {segments:g}), over-read {_fmt_bytes(waste)}"
    )
    if read_bytes > 0:
        line += f" ({100.0 * waste / read_bytes:.2f}% of bytes read)"
    return line


def _record_plane_line(snapshot: dict) -> Optional[str]:
    """One-line record-plane digest: rows moved through the columnar plane
    per side, frames by wire format, the vectorized partition pass's
    throughput, and how many rows fell back to per-record scalar routes."""
    by_plane: Dict[str, float] = {}
    for s in snapshot.get("record_rows_total", {}).get("series", []):
        p = s.get("labels", {}).get("plane", "?")
        by_plane[p] = by_plane.get(p, 0.0) + float(s.get("value", 0))
    rows_w = by_plane.get("write", 0.0)
    rows_r = by_plane.get("read", 0.0)
    frames = _counter_total(snapshot, "record_frames_total")
    fallback = _counter_total(snapshot, "record_fallback_rows_total")
    if rows_w <= 0 and rows_r <= 0 and frames <= 0 and fallback <= 0:
        return None
    line = f"Record plane: {rows_w:g} rows written / {rows_r:g} read"
    if frames > 0:
        column = sum(
            float(s.get("value", 0))
            for s in snapshot.get("record_frames_total", {}).get("series", [])
            if s.get("labels", {}).get("format") == "column"
        )
        line += f", {frames:g} frames ({100.0 * column / frames:.2f}% column)"
    part = snapshot.get("record_partition_seconds", {}).get("series", [])
    part_s = sum(float(s.get("sum", 0.0)) for s in part)
    if part_s > 0 and rows_w > 0:
        line += f"; partition {rows_w / part_s / 1e6:.1f}M rows/s"
    if fallback > 0:
        total = rows_w + rows_r + fallback
        line += (
            f"; {fallback:g} fallback rows "
            f"({100.0 * (total - fallback) / total:.2f}% vectorized)"
        )
    return line


def _write_plane_line(snapshot: dict) -> Optional[str]:
    """One-line write-plane digest: PUTs the composite commit plane issued
    vs what the one-object-per-map layout would have issued, the group
    fill ratio (maps per composite group), and compactor activity."""
    groups = _counter_total(snapshot, "write_composite_groups_total")
    compacted = _counter_total(snapshot, "write_compacted_objects_total")
    if groups <= 0 and compacted <= 0:
        return None
    parts = []
    if groups > 0:
        members = _counter_total(snapshot, "write_composite_members_total")
        saved = _counter_total(snapshot, "write_puts_saved_total")
        issued = 2 * groups  # data + fat index per sealed group
        parts.append(
            f"{groups:g} composite groups, {members:g} map outputs "
            f"({members / groups:.2f} maps/group fill), "
            f"{saved:g} PUTs saved ({issued + saved:g} → {issued:g})"
        )
    if compacted > 0:
        parts.append(f"compactor rewrote {compacted:g} singleton outputs")
    return "Write plane: " + "; ".join(parts)


def _op_total(snapshot: dict, name: str, op: str) -> float:
    return sum(
        float(s.get("value", 0))
        for s in snapshot.get(name, {}).get("series", [])
        if s.get("labels", {}).get("op") == op
    )


def _launch_queue_clause(snapshot: dict, op: str) -> str:
    """Device launches of one codec ``op`` and how many launches were queued
    ahead of each on its device when it was dispatched; "" when ``op``
    launched nothing."""
    launches = _op_total(snapshot, "codec_launches_total", op)
    if launches <= 0:
        return ""
    ahead = _op_total(snapshot, "codec_launches_ahead_total", op)
    return f"; {launches:g} device launches, {ahead / launches:.2f} queued ahead of each"


def _codec_line(snapshot: dict) -> Optional[str]:
    """One-line codec digest: batch encode throughput (raw MB/s through the
    compress+frame calls), host assembly throughput, fused-CRC coverage
    (frames whose stored-byte CRC rode the encode launch vs all frames
    emitted), and live in-flight window occupancy."""
    enc_bytes = _counter_total(snapshot, "codec_encode_bytes_total")
    series = snapshot.get("codec_encode_batch_seconds", {}).get("series", [])
    enc_seconds = sum(float(s.get("sum", 0.0)) for s in series)
    batches = sum(int(s.get("count", 0)) for s in series)
    if enc_bytes <= 0 or batches <= 0:
        return None
    line = f"Codec: encode {enc_bytes / 1e6 / max(enc_seconds, 1e-9):.1f} MB/s"
    line += f" over {batches} batches ({_fmt_bytes(enc_bytes)})"
    asm = snapshot.get("codec_assembly_seconds", {}).get("series", [])
    asm_seconds = sum(float(s.get("sum", 0.0)) for s in asm)
    if asm_seconds > 0:
        line += f", assembly {enc_bytes / 1e6 / asm_seconds:.1f} MB/s"
    frames = _counter_total(snapshot, "codec_frames_total")
    fused = _counter_total(snapshot, "codec_fused_crc_total")
    if frames > 0:
        line += (
            f"; fused CRC {fused:g}/{frames:g} frames "
            f"({100.0 * fused / frames:.2f}%)"
        )
    inflight = sum(
        float(s.get("value", 0))
        for s in snapshot.get("codec_encode_inflight", {}).get("series", [])
    )
    if inflight > 0:
        line += f"; {inflight:g} encode batches in flight"
    return line + _launch_queue_clause(snapshot, "encode")


def _codec_read_line(snapshot: dict) -> Optional[str]:
    """One-line READ-side codec digest: batch decode throughput (decoded
    MB/s through the batch decompress calls), fused-validation coverage
    (frames whose stored-byte CRC certificate rode the decode launch — each
    one a skipped host hashing pass), live in-flight decode window
    occupancy, the decode launch queue, and the pointer-jump rounds each
    decode launch ran."""
    dec_bytes = _counter_total(snapshot, "codec_decode_bytes_total")
    series = snapshot.get("codec_decode_batch_seconds", {}).get("series", [])
    dec_seconds = sum(float(s.get("sum", 0.0)) for s in series)
    batches = sum(int(s.get("count", 0)) for s in series)
    if dec_bytes <= 0 or batches <= 0:
        return None
    line = f"Codec read: decode {dec_bytes / 1e6 / max(dec_seconds, 1e-9):.1f} MB/s"
    line += f" over {batches} batches ({_fmt_bytes(dec_bytes)})"
    fused = _counter_total(snapshot, "codec_fused_crc_validated_total")
    if fused > 0:
        line += f"; fused-validated {fused:g} frames"
    inflight = sum(
        float(s.get("value", 0))
        for s in snapshot.get("codec_decode_inflight", {}).get("series", [])
    )
    if inflight > 0:
        line += f"; {inflight:g} decode batches in flight"
    line += _launch_queue_clause(snapshot, "decode")
    launches = _op_total(snapshot, "codec_launches_total", "decode")
    rounds = _counter_total(snapshot, "codec_decode_jump_rounds_total")
    if launches > 0 and rounds > 0:
        line += f"; {rounds / launches:.2f} pointer-jump rounds per launch"
    return line


def _coding_plane_line(snapshot: dict) -> Optional[str]:
    """One-line coding-plane digest: parity redundancy bought (bytes +
    encode wall), and what it paid for — speculative reads raced and byte
    ranges actually served by reconstruction, split by trigger reason."""
    parity_bytes = _counter_total(snapshot, "shuffle_parity_bytes_written_total")
    spec = _counter_total(snapshot, "shuffle_parity_speculative_reads_total")
    recon = _counter_total(snapshot, "shuffle_parity_reconstructions_total")
    if parity_bytes <= 0 and spec <= 0 and recon <= 0:
        return None
    parts = []
    if parity_bytes > 0:
        enc = snapshot.get("shuffle_parity_encode_seconds", {}).get("series", [])
        enc_s = sum(float(s.get("sum", 0.0)) for s in enc)
        piece = f"{_fmt_bytes(parity_bytes)} parity written"
        if enc_s > 0:
            piece += f" (encode {_fmt_seconds(enc_s)})"
        parts.append(piece)
    if spec > 0:
        parts.append(f"{spec:g} speculative reads")
    if recon > 0:
        by_reason = {
            s.get("labels", {}).get("reason", "?"): float(s.get("value", 0))
            for s in snapshot.get("shuffle_parity_reconstructions_total", {}).get(
                "series", []
            )
        }
        piece = f"{recon:g} reconstructions"
        if by_reason:
            piece += (
                " ("
                + ", ".join(f"{v:g} {r}" for r, v in sorted(by_reason.items()))
                + ")"
            )
        parts.append(piece)
    return "Coding plane: " + "; ".join(parts)


def _skew_line(snapshot: dict) -> Optional[str]:
    """One-line skew-plane digest: what each mitigation prong did — rows
    pre-reduced away by map-side combine sidecars, partitions whose split
    fan-out was recorded at commit, and reads diverted to parity-equivalent
    sources because the primary object was hot."""
    combined = _counter_total(snapshot, "shuffle_map_combine_rows_total")
    splits = _counter_total(snapshot, "shuffle_partition_splits_total")
    fanout = _counter_total(snapshot, "shuffle_hot_fanout_reads_total")
    if combined <= 0 and splits <= 0 and fanout <= 0:
        return None
    parts = []
    if combined > 0:
        parts.append(f"{combined:g} rows pre-reduced map-side")
    if splits > 0:
        parts.append(f"{splits:g} hot partitions split for read fan-out")
    if fanout > 0:
        parts.append(f"{fanout:g} hot-fanout reads served from parity")
    return "Skew: " + "; ".join(parts)


def _fleet_line(snapshot: dict) -> Optional[str]:
    """One-line elastic-fleet digest: membership churn (joins / drains /
    leaves / expiries), task requeues by trigger, graceful-drain wall, and
    how lost committed outputs were recovered (recompute vs reconstruct)."""
    events = _counter_total(snapshot, "worker_membership_events_total")
    requeues = _counter_total(snapshot, "task_requeues_total")
    decisions = _counter_total(snapshot, "recovery_decisions_total")
    drains = snapshot.get("worker_drain_seconds", {}).get("series", [])
    drain_count = sum(int(s.get("count", 0)) for s in drains)
    if events <= 0 and requeues <= 0 and decisions <= 0 and drain_count <= 0:
        return None

    def by_label(name: str, key: str) -> str:
        rows: Dict[str, float] = {}
        for s in snapshot.get(name, {}).get("series", []):
            label = s.get("labels", {}).get(key, "?")
            rows[label] = rows.get(label, 0.0) + float(s.get("value", 0))
        return ", ".join(f"{v:g} {k}" for k, v in sorted(rows.items()))

    parts = []
    if events > 0:
        parts.append(f"{events:g} membership events ({by_label('worker_membership_events_total', 'event')})")
    if requeues > 0:
        parts.append(f"{requeues:g} task requeues ({by_label('task_requeues_total', 'reason')})")
    if drain_count > 0:
        drain_s = sum(float(s.get("sum", 0.0)) for s in drains)
        parts.append(f"{drain_count} graceful drains ({_fmt_seconds(drain_s)} total)")
    if decisions > 0:
        parts.append(f"{decisions:g} recovery decisions ({by_label('recovery_decisions_total', 'choice')})")
    return "Fleet: " + "; ".join(parts)


def _concurrency_line(snapshot: dict) -> Optional[str]:
    """One-line concurrency-verification digest: happens-before access
    checks the race witness performed (and access pairs it reported —
    a nonzero report count is a FINDING, not noise) plus deterministic
    schedules the explorer drove through the cooperative scheduler."""
    checks = _counter_total(snapshot, "race_witness_checks_total")
    reports = _counter_total(snapshot, "race_witness_reports_total")
    explored = _counter_total(snapshot, "sched_schedules_explored_total")
    if checks <= 0 and reports <= 0 and explored <= 0:
        return None
    parts = []
    if checks > 0 or reports > 0:
        parts.append(f"{checks:g} HB checks, {reports:g} racy pair(s) flagged")
    if explored > 0:
        parts.append(f"{explored:g} schedules explored")
    return "Concurrency: " + "; ".join(parts)


def _mesh_plane_line(snapshot: dict) -> Optional[str]:
    """One-line multi-chip-plane digest: device batches the mesh dispatcher
    placed (and over how many devices), rows routed to their owner devices
    over ICI, full-window backpressure waits the dispatch window paid, and
    launches still in flight."""
    dispatched = _counter_total(snapshot, "mesh_batches_dispatched_total")
    routed = _counter_total(snapshot, "mesh_route_rows_total")
    if dispatched <= 0 and routed <= 0:
        return None
    parts = []
    if dispatched > 0:
        devices = {
            s.get("labels", {}).get("device", "?")
            for s in snapshot.get("mesh_batches_dispatched_total", {}).get(
                "series", []
            )
            if float(s.get("value", 0)) > 0
        }
        parts.append(
            f"{dispatched:g} batches dispatched over {len(devices)} device(s)"
        )
    if routed > 0:
        parts.append(f"{routed:g} rows routed over ICI")
    waits = snapshot.get("mesh_dispatch_wait_seconds", {}).get("series", [])
    wait_count = sum(int(s.get("count", 0)) for s in waits)
    if wait_count > 0:
        wait_s = sum(float(s.get("sum", 0.0)) for s in waits)
        parts.append(f"{wait_count} window waits ({_fmt_seconds(wait_s)} total)")
    inflight = sum(
        float(s.get("value", 0))
        for s in snapshot.get("mesh_device_outstanding", {}).get("series", [])
    )
    if inflight > 0:
        parts.append(f"{inflight:g} launches in flight")
    return "Mesh plane: " + "; ".join(parts)


def _tuning_line(snapshot: dict) -> Optional[str]:
    """One-line autotuner digest: controller decisions by outcome, the live
    rung of every tuned knob, and the closed loop's own overhead."""
    decisions = _counter_total(snapshot, "tune_decisions_total")
    if decisions <= 0:
        return None
    by_dir: Dict[str, float] = {}
    for s in snapshot.get("tune_decisions_total", {}).get("series", []):
        d = s.get("labels", {}).get("direction", "?")
        by_dir[d] = by_dir.get(d, 0.0) + float(s.get("value", 0))
    line = f"Tuning: {decisions:g} controller decisions"
    if by_dir:
        line += (
            " ("
            + ", ".join(f"{v:g} {d}" for d, v in sorted(by_dir.items()))
            + ")"
        )
    knobs = {
        s.get("labels", {}).get("knob", "?"): float(s.get("value", 0))
        for s in snapshot.get("tune_knob_value", {}).get("series", [])
    }
    if knobs:
        line += "; knobs " + ", ".join(
            f"{k}={v:g}" for k, v in sorted(knobs.items())
        )
    ctrl = snapshot.get("tune_controller_seconds", {}).get("series", [])
    secs = sum(float(s.get("sum", 0.0)) for s in ctrl)
    if secs > 0:
        line += f"; controller overhead {_fmt_seconds(secs)}"
    return line


def render_metrics_snapshot(
    snapshot: dict, top: int = 10, reduce_tasks: Optional[int] = None
) -> str:
    hist_rows: List[Tuple[float, Sequence[str]]] = []
    counter_rows: List[Sequence[str]] = []
    gauge_rows: List[Sequence[str]] = []
    for name, metric in sorted(snapshot.items()):
        kind = metric.get("kind")
        for series in metric.get("series", []):
            label = _series_label(name, series)
            if kind == "histogram":
                count = series.get("count", 0)
                if not count:
                    continue
                qs = [
                    histogram_quantile(series["le"], series["buckets"], q)
                    for q in QUANTILES
                ]
                total = float(series.get("sum", 0.0))
                is_seconds = name.endswith("_seconds")
                fmt = _fmt_seconds if is_seconds else (lambda v: f"{v:g}")
                hist_rows.append(
                    (
                        total if is_seconds else 0.0,
                        (
                            label,
                            count,
                            fmt(total),
                            fmt(qs[0]),
                            fmt(qs[1]),
                            fmt(qs[2]),
                        ),
                    )
                )
            elif kind == "counter":
                value = series.get("value", 0)
                pretty = (
                    _fmt_bytes(value) if "bytes" in name else f"{value:g}"
                )
                counter_rows.append((label, pretty))
            else:
                gauge_rows.append((label, f"{series.get('value', 0):g}"))
    out: List[str] = []
    if hist_rows:
        hist_rows.sort(key=lambda r: -r[0])
        out.append("Latency / size distributions (histograms, by total time):")
        out.append(
            _table(
                ("histogram", "count", "sum", "p50", "p95", "p99"),
                [r for _total, r in hist_rows],
            )
        )
    if counter_rows:
        out.append("")
        out.append("Counters:")
        out.append(_table(("counter", "value"), counter_rows))
    for line in (
        _record_plane_line(snapshot),
        _scan_planner_line(snapshot),
        _write_plane_line(snapshot),
        _coding_plane_line(snapshot),
        _skew_line(snapshot),
        _codec_line(snapshot),
        _codec_read_line(snapshot),
        _mesh_plane_line(snapshot),
        _tuning_line(snapshot),
        _fleet_line(snapshot),
        _concurrency_line(snapshot),
        _control_plane_line(snapshot, reduce_tasks=reduce_tasks),
    ):
        if line:
            out.append("")
            out.append(line)
    if gauge_rows:
        out.append("")
        out.append("Gauges:")
        out.append(_table(("gauge", "value"), gauge_rows))
    if not out:
        out.append("Empty metrics snapshot.")
    return "\n".join(out)


def _fmt_dollars(d: float) -> str:
    return f"${d:.4f}" if d >= 0.01 else f"${d:.6f}"


def render_fleet(doc: dict, top: int = 10) -> str:
    """Render a fleet-telemetry dump (``DistributedDriver.dump_fleet``):
    per-worker snapshot ages, the fleet-wide hot-object GET-concurrency
    peaks, the rate-card cost digest ($/shuffle), and the merged registry
    view over every worker plus the driver."""
    out: List[str] = []
    workers = doc.get("fleet_workers", {})
    out.append(f"Fleet: {len(workers)} worker(s)")
    if workers:
        rows = []
        for wid, info in sorted(workers.items()):
            peaks = info.get("peaks") or {}
            hottest = max(peaks.values()) if peaks else 0
            rows.append(
                (
                    wid,
                    f"{float(info.get('age_seconds', 0.0)):.1f}s",
                    len(peaks),
                    f"{hottest:g}",
                )
            )
        out.append(
            _table(("worker", "snapshot age", "objects tracked", "peak GETs"), rows)
        )
    peaks = doc.get("object_gets_peaks") or {}
    if peaks:
        hot = sorted(peaks.items(), key=lambda kv: -kv[1])[:top]
        out.append("")
        out.append("Hot objects (fleet-wide GET-concurrency peaks):")
        out.append(
            _table(
                ("object", "peak concurrent GETs"),
                [(name.rsplit("/", 1)[-1], f"{v:g}") for name, v in hot],
            )
        )
    cost = doc.get("cost") or {}
    if cost:
        ops = cost.get("ops", {})
        dollars = cost.get("dollars", {})
        rows = [
            (cls, f"{ops.get(cls, 0):g}", _fmt_dollars(float(dollars.get(cls, 0.0))))
            for cls in sorted(set(ops) | set(dollars))
            if ops.get(cls) or dollars.get(cls)
        ]
        out.append("")
        out.append("Cost (storage rate card):")
        if rows:
            out.append(_table(("op class", "ops", "dollars"), rows))
        shuffles = cost.get("shuffles", 1)
        out.append(
            f"  total {_fmt_dollars(float(cost.get('dollars_total', 0.0)))} over "
            f"{shuffles:g} shuffle(s) = "
            f"{_fmt_dollars(float(cost.get('dollars_per_shuffle', 0.0)))}/shuffle"
        )
    metrics = doc.get("metrics") or {}
    if metrics:
        out.append("")
        out.append("Merged fleet metrics (all workers + driver):")
        out.append(render_metrics_snapshot(metrics, top=top))
    return "\n".join(out)


def render_shuffle_stats(report: dict, top: int = 10) -> str:
    out = [f"ShuffleStats: shuffle {report.get('shuffle_id', '?')}"]
    rows = []
    bw, br = report.get("bytes_written", 0), report.get("bytes_read", 0)
    ws = report.get("write_seconds", 0.0)
    ps = report.get("read_prefetch_seconds", 0.0)
    rows.append(
        (
            "map",
            report.get("map_tasks", 0),
            _fmt_bytes(bw),
            report.get("records_written", 0),
            _fmt_seconds(ws),
            _fmt_bytes(bw / ws) + "/s" if ws else "-",
        )
    )
    rows.append(
        (
            "reduce",
            report.get("reduce_tasks", 0),
            _fmt_bytes(br),
            report.get("records_read", 0),
            _fmt_seconds(ps),
            _fmt_bytes(br / ps) + "/s" if ps else "-",
        )
    )
    out.append(
        _table(("plane", "tasks", "bytes", "records", "seconds", "throughput"), rows)
    )
    extras = []
    if report.get("spills"):
        extras.append(f"spills={report['spills']}")
    if report.get("read_wait_seconds"):
        extras.append(
            f"reduce consumer wait={_fmt_seconds(report['read_wait_seconds'])}"
        )
    if report.get("max_prefetch_threads"):
        extras.append(f"max prefetch threads={report['max_prefetch_threads']}")
    if extras:
        out.append("  " + ", ".join(extras))
    metrics = report.get("metrics") or {}
    if metrics:
        out.append("")
        out.append(
            render_metrics_snapshot(
                metrics, top=top, reduce_tasks=report.get("reduce_tasks") or None
            )
        )
    return "\n".join(out)


def render(doc: dict, top: int = 10) -> str:
    """Dispatch on document shape: Chrome trace, fleet-telemetry dump,
    ShuffleStats dump, a single report, or a bare registry snapshot (the
    BENCH ``metrics`` field). The fleet check precedes the generic
    ``metrics`` check — a dump_fleet doc carries both keys."""
    if "traceEvents" in doc:
        return render_trace(doc, top=top)
    if "fleet_workers" in doc:
        return render_fleet(doc, top=top)
    if "shuffles" in doc:
        return "\n\n".join(
            render_shuffle_stats(r, top=top) for r in doc["shuffles"]
        ) or "No shuffle reports in file."
    if "shuffle_id" in doc:
        return render_shuffle_stats(doc, top=top)
    if "metrics" in doc and isinstance(doc["metrics"], dict):
        return render_metrics_snapshot(doc["metrics"], top=top)
    # bare registry snapshot: {name: {kind, series}}
    if all(isinstance(v, dict) and "series" in v for v in doc.values()) and doc:
        return render_metrics_snapshot(doc, top=top)
    raise ValueError(
        "unrecognized document: expected a Chrome trace (traceEvents), a "
        "ShuffleStats report/dump, or a metrics registry snapshot"
    )


# ---------------------------------------------------------------------------
# Selftest (wired into the tier-1 run: python -m tools.trace_report --selftest)
# ---------------------------------------------------------------------------


def _synthetic_snapshot() -> dict:
    """A registry snapshot covering EVERY metric the package can emit,
    derived from the single source of truth
    (:mod:`s3shuffle_tpu.metrics.names`) — a metric registered anywhere in
    the data plane is automatically part of this selftest's rendering
    coverage, with no hand-maintained list to forget to extend."""
    try:
        from s3shuffle_tpu.metrics.names import KNOWN_METRICS
    except ModuleNotFoundError:
        # direct-script invocation (python tools/trace_report.py): sys.path[0]
        # is tools/, so bootstrap the repo root like `-m` would
        import os
        import sys

        sys.path.insert(
            0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        from s3shuffle_tpu.metrics.names import KNOWN_METRICS

    # synthetic histogram: 90 obs in (0.008, 0.016], 10 in (0.128, 0.256]
    bounds = [0.001 * 2**i for i in range(10)]
    buckets = [0] * 11
    buckets[4] = 90
    buckets[8] = 10
    _SAMPLE_LABELS = {"scheme": "file", "op": "read", "direction": "up",
                      "codec": "native", "method": "register_map_outputs",
                      "shard": "0", "source": "snapshot", "reason": "orphan",
                      "knob": "fetch_parallelism", "event": "join",
                      "choice": "reconstruct", "size_class": "le1m",
                      "format": "column", "plane": "write", "site": "write",
                      "worker": "w0", "op_class": "get", "device": "cpu:0"}
    _ALT_LABELS = {"scheme": "s3", "op": "open", "direction": "down",
                   "codec": "zlib", "method": "get_map_sizes_by_ranges",
                   "shard": "1", "source": "rpc", "reason": "generation",
                   "knob": "upload_queue_bytes", "event": "expire",
                   "choice": "recompute", "size_class": "gt64m",
                   "format": "legacy", "plane": "read", "site": "read",
                   "worker": "w1", "op_class": "put", "device": "cpu:1"}
    snapshot: Dict[str, dict] = {}
    for name, (kind, labelnames) in sorted(KNOWN_METRICS.items()):
        series_list = []
        # labeled metrics get TWO series so multi-row/label-grouping
        # rendering stays covered (each label combination is its own row)
        label_sets = [_SAMPLE_LABELS, _ALT_LABELS] if labelnames else [None]
        for values in label_sets:
            series: dict = {}
            if values is not None:
                series["labels"] = {ln: values.get(ln, "x") for ln in labelnames}
            if kind == "histogram":
                series.update(
                    {"le": bounds, "buckets": list(buckets),
                     "sum": 90 * 0.012 + 10 * 0.2, "count": 100}
                )
            else:
                series["value"] = (1 << 20) if "bytes" in name else 7
            series_list.append(series)
        metric = {"kind": kind, "series": series_list}
        if labelnames:
            metric["labelnames"] = list(labelnames)
        snapshot[name] = metric
    return snapshot


def _selftest() -> int:
    trace_doc = {
        "traceEvents": [
            {"name": "read.prefetch", "ph": "X", "ts": i * 1000.0, "dur": 1000.0 + i}
            for i in range(100)
        ]
        + [{"name": "write.commit", "ph": "X", "ts": 0.0, "dur": 250000.0}],
        "otherData": {"counters": {"io.bytes_read": 64 * 1024 * 1024}},
    }
    text = render_trace(trace_doc)
    for needle in ("write.commit", "read.prefetch", "p50", "p95", "p99", "MiB"):
        assert needle in text, f"trace render missing {needle!r}:\n{text}"

    bounds = [0.001 * 2**i for i in range(10)]
    buckets = [0] * 11
    buckets[4] = 90
    buckets[8] = 10
    metrics = _synthetic_snapshot()
    # a decode launch series, so the read line's per-launch clauses render
    metrics["codec_launches_total"]["series"].append(
        {"labels": {"op": "decode"}, "value": 2}
    )
    report = {
        "shuffle_id": 7,
        "map_tasks": 4,
        "reduce_tasks": 4,
        "bytes_written": 1 << 20,
        "bytes_read": 1 << 20,
        "records_written": 1000,
        "records_read": 1000,
        "write_seconds": 0.5,
        "read_prefetch_seconds": 0.25,
        "read_wait_seconds": 0.05,
        "spills": 2,
        "max_prefetch_threads": 3,
        "metrics": metrics,
    }
    text = render_shuffle_stats(report)
    # every declared metric name must render — names.py IS the coverage list
    for needle in ("shuffle 7", "p95", "throughput", *metrics):
        assert needle in text, f"stats render missing {needle!r}:\n{text}"
    # multi-series rendering: BOTH label rows of a labeled metric appear
    for needle in ("op=read", "op=open"):
        assert needle in text, f"multi-series row missing {needle!r}:\n{text}"
    # the record-plane digest renders from the synthetic record_* series
    # (rows 7 write / 7 read; frames 7 column + 7 legacy → 50% column;
    # fallback 7+7=14 → vectorized share (7+7)/(7+7+14) = 50%)
    for needle in (
        "Record plane: 7 rows written / 7 read",
        "14 frames (50.00% column)",
        "14 fallback rows (50.00% vectorized)",
    ):
        assert needle in text, f"record-plane line missing {needle!r}:\n{text}"
    # the concurrency-verification digest renders from the synthetic
    # witness/explorer counters (7 checks / 7 reports / 7 schedules)
    for needle in (
        "Concurrency: 7 HB checks, 7 racy pair(s) flagged",
        "7 schedules explored",
    ):
        assert needle in text, f"concurrency line missing {needle!r}:\n{text}"
    # the scan-planner digest renders from the synthetic planner counters
    # (7 segments + 7 saved GETs, 1 MiB waste over 2 MiB read = 50%)
    for needle in ("Scan planner:", "7 GETs saved", "(14 → 7)", "50.00% of bytes read"):
        assert needle in text, f"planner line missing {needle!r}:\n{text}"
    # the write-plane digest renders from the synthetic composite/compactor
    # counters (7 groups × 7 members → 1 map/group; 7 PUTs saved on 14)
    for needle in (
        "Write plane: 7 composite groups",
        "(1.00 maps/group fill)",
        "7 PUTs saved (21 → 14)",
        "compactor rewrote 7 singleton outputs",
    ):
        assert needle in text, f"write-plane line missing {needle!r}:\n{text}"
    # compactor-only runs (no composite groups) get a well-formed line too
    solo = _write_plane_line(
        {"write_compacted_objects_total": {"kind": "counter", "series": [{"value": 7}]}}
    )
    assert solo == "Write plane: compactor rewrote 7 singleton outputs", solo
    # the coding-plane digest renders from the synthetic parity series
    # (1 MiB parity bytes; 7 speculative reads; the labeled reconstruction
    # counter contributes its two 7-value series = 14)
    for needle in (
        "Coding plane: 1.00 MiB parity written",
        "7 speculative reads",
        "14 reconstructions",
    ):
        assert needle in text, f"coding line missing {needle!r}:\n{text}"
    # the skew digest renders from the synthetic skew counters (three
    # unlabeled 7-value counters — one clause per mitigation prong)
    for needle in (
        "Skew: 7 rows pre-reduced map-side",
        "7 hot partitions split for read fan-out",
        "7 hot-fanout reads served from parity",
    ):
        assert needle in text, f"skew line missing {needle!r}:\n{text}"
    # the codec digest renders from the synthetic codec-plane series
    # (1 MiB over a 3.08s histogram; 7 fused of 7 frames; gauge 7 in flight)
    for needle in (
        "Codec: encode 0.3 MB/s over 100 batches",
        "fused CRC 7/7 frames (100.00%)",
        "7 encode batches in flight",
    ):
        assert needle in text, f"codec line missing {needle!r}:\n{text}"
    # the READ-side codec digest renders from the synthetic decode series
    # (1 MiB decoded over a 3.08s histogram; 7 fused-validated frames;
    # gauge 7 decode batches in flight; 7 jump rounds over 2 decode launches)
    for needle in (
        "Codec read: decode 0.3 MB/s over 100 batches",
        "fused-validated 7 frames",
        "7 decode batches in flight",
        "2 device launches",
        "3.50 pointer-jump rounds per launch",
    ):
        assert needle in text, f"codec read line missing {needle!r}:\n{text}"
    # the mesh-plane digest renders from the synthetic mesh_* series (two
    # 7-value dispatched series over devices cpu:0/cpu:1 → 14 over 2; 7 rows
    # routed; the wait histogram contributes 100 waits over a 3.08s sum; two
    # 7-value outstanding gauges → 14 in flight)
    for needle in (
        "Mesh plane: 14 batches dispatched over 2 device(s)",
        "7 rows routed over ICI",
        "100 window waits (3.08s total)",
        "14 launches in flight",
    ):
        assert needle in text, f"mesh-plane line missing {needle!r}:\n{text}"
    # the tuning digest renders from the synthetic tune_* series (two
    # decision series of 7 → 14 decisions split 7 up / 7 down; two knob
    # gauges at 7; the controller-seconds histogram sums to 3.08s)
    for needle in (
        "Tuning: 14 controller decisions",
        "7 down, 7 up",
        "fetch_parallelism=7",
        "upload_queue_bytes=7",
        "controller overhead 3.08s",
    ):
        assert needle in text, f"tuning line missing {needle!r}:\n{text}"
    # the fleet digest renders from the synthetic membership/requeue/
    # recovery series (two 7-value series per labeled counter → 14;
    # the drain histogram contributes 100 drains over a 3.08s sum)
    for needle in (
        "Fleet: 14 membership events (7 expire, 7 join)",
        "14 task requeues (7 generation, 7 orphan)",
        "100 graceful drains (3.08s total)",
        "14 recovery decisions (7 recompute, 7 reconstruct)",
    ):
        assert needle in text, f"fleet line missing {needle!r}:\n{text}"
    # the control-plane digest: two meta_rpc_total series of 7 → 14 RPCs over
    # 4 reduce tasks; lookup sources 7 snapshot + 7 rpc → 50% hit ratio
    for needle in (
        "Control plane: 14 tracker RPCs",
        "(3.50 per reduce task)",
        "7 snapshot / 7 rpc",
        "50.00% snapshot hit ratio",
    ):
        assert needle in text, f"control-plane line missing {needle!r}:\n{text}"
    # fleet-telemetry dump rendering: worker table, hot-object peaks, the
    # rate-card cost digest ($/shuffle), and the merged registry view —
    # dispatched through render() by the 'fleet_workers' discriminator
    fleet_doc = {
        "fleet_workers": {
            "w0": {"age_seconds": 1.25, "wall_time": 0.0,
                   "peaks": {"app/shuffle_0/part_3.data": 9}},
            "w1": {"age_seconds": 0.5, "wall_time": 0.0, "peaks": {}},
        },
        "object_gets_peaks": {"app/shuffle_0/part_3.data": 9},
        "metrics": metrics,
        "cost": {
            "rate_card": {"get": 4e-7, "put": 5e-6},
            "ops": {"get": 1000.0, "put": 100.0},
            "read_bytes": 1 << 20, "written_bytes": 1 << 20,
            "dollars": {"get": 4e-4, "put": 5e-4},
            "dollars_total": 9e-4, "shuffles": 2, "dollars_per_shuffle": 4.5e-4,
        },
    }
    text = render(fleet_doc)
    for needle in (
        "Fleet: 2 worker(s)",
        "part_3.data",
        "Cost (storage rate card):",
        "$0.000900 over 2 shuffle(s) = $0.000450/shuffle",
        "Merged fleet metrics",
    ):
        assert needle in text, f"fleet render missing {needle!r}:\n{text}"
    # worker/op_class-labeled metric families render with both label rows
    for needle in ("worker=w0", "worker=w1", "op_class=get", "op_class=put"):
        assert needle in text, f"fleet label row missing {needle!r}:\n{text}"

    p50 = histogram_quantile(bounds, buckets, 0.5)
    assert 0.008 <= p50 <= 0.016, p50
    p99 = histogram_quantile(bounds, buckets, 0.99)
    assert 0.128 <= p99 <= 0.256, p99
    assert histogram_quantile(bounds, [0] * 11, 0.5) == 0.0
    # overflow-bin quantile answers the last finite bound
    over = [0] * 11
    over[10] = 5
    assert histogram_quantile(bounds, over, 0.5) == bounds[-1]
    print("trace_report selftest OK")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    ap.add_argument("path", nargs="?", help="trace JSON or ShuffleStats report")
    ap.add_argument("--top", type=int, default=10, help="rows in the span table")
    ap.add_argument("--fleet", action="store_true",
                    help="render a fleet-telemetry dump "
                         "(DistributedDriver.dump_fleet output) with the "
                         "$/shuffle cost digest")
    ap.add_argument("--selftest", action="store_true",
                    help="render synthetic inputs and verify the output")
    args = ap.parse_args(argv)
    if args.selftest:
        return _selftest()
    if not args.path:
        ap.error("need a trace/report path (or --selftest)")
    with open(args.path) as f:
        doc = json.load(f)
    if args.fleet and "fleet_workers" not in doc:
        ap.error(
            "--fleet needs a dump_fleet document (no 'fleet_workers' key "
            "in the file)"
        )
    print(render(doc, top=args.top))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
