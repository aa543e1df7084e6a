"""Measured-rate gate: pick device codec paths only when the chip has PROVEN
faster than the competing host implementation.

The 2026-08-04 chip probe inverted the device-codec story: on-chip TLZ encode
ran at 3.6 MB/s against 435 MB/s for the host C encoder, device CRC32C at
40.5 MB/s, and the fused decode collapsed 1004 MB/s to 51 MB/s. Until this
module existed every device path armed on *availability* ("a chip is
attached"), which silently turned the codec plane into the shuffle
bottleneck. Now availability only says a path CAN run; this table says
whether it SHOULD:

- rates come from the same per-metric ``bench_tpu_last_good.json`` cache the
  chip probe maintains (``bench.py device_kernel_rates`` merges fresh
  measurements per metric, so one failing kernel never erases a good
  baseline);
- **no probe data means host** — the honest default. A path is selected only
  when its cached measured rate beats the competing host rate;
- ``S3SHUFFLE_CODEC_RATE_GATE`` force-overrides either side:
  ``device`` / ``host`` pin every decision, ``off`` restores the legacy
  arm-on-availability behavior, ``auto``/unset consults the table;
- every decision increments ``codec_path_selected_total{path,reason}`` so an
  operator can see from metrics alone why a shuffle is (not) on the chip.

Host reference rates default to conservative figures measured on the bench
rig (``DEFAULT_HOST_RATES``); a cache file may override them with measured
``host_*`` fields when the probe records them.

Callers: ``codec/tpu.py`` (encode/decode/fused routing), ``coding/gf.py``
(parity encode). Test injection: :func:`set_rates_for_testing`.
"""

from __future__ import annotations

import json
import logging
import os
import threading
from typing import Dict, Optional, Tuple

from s3shuffle_tpu.metrics import registry as _metrics

logger = logging.getLogger("s3shuffle_tpu.ops.rates")

_C_SELECTED = _metrics.REGISTRY.counter(
    "codec_path_selected_total",
    "Codec/parity path-selection decisions by outcome: path is the "
    "side chosen (device/host, or fused/streaming for the decode-validation "
    "route), reason says why (measured-device, measured-host, no-data, "
    "forced, env-device, env-host, gate-off)",
    labelnames=("path", "reason"),
)

_C_DEVICE_BATCHES = _metrics.REGISTRY.counter(
    "codec_device_batches_total",
    "Device codec/parity batches by op (encode, decode, parity) and outcome "
    "(ok, or failed: the batch was redone on the host)",
    labelnames=("op", "outcome"),
)
_C_HOST_PINS = _metrics.REGISTRY.counter(
    "codec_host_pins_total",
    "Device-failure pins of a codec or of the parity encoder to the host "
    "path, by op (encode, decode, parity)",
    labelnames=("op",),
)


def count_device_batch(op: str, ok: bool) -> None:
    if _metrics.enabled():
        _C_DEVICE_BATCHES.labels(op=op, outcome="ok" if ok else "failed").inc()


def count_host_pin(op: str) -> None:
    if _metrics.enabled():
        _C_HOST_PINS.labels(op=op).inc()


_C_LAUNCHES = _metrics.REGISTRY.counter(
    "codec_launches_total",
    "Device codec kernel launches by op (encode, decode); a batch whose "
    "frames all go to the host decoder launches nothing",
    labelnames=("op",),
)
_C_LAUNCH_ROWS = _metrics.REGISTRY.counter(
    "codec_launch_rows_total",
    "Rows of device codec launches by op; row=real carries a block for the "
    "device, row=pad is bucket padding or a decode row left to the host",
    labelnames=("op", "row"),
)
_C_LAUNCHES_AHEAD = _metrics.REGISTRY.counter(
    "codec_launches_ahead_total",
    "Sum over launches of the launches already dispatched and not yet "
    "drained on the same device when each was dispatched, by op",
    labelnames=("op",),
)
_C_JUMP_ROUNDS = _metrics.REGISTRY.counter(
    "codec_decode_jump_rounds_total",
    "Pointer-jump rounds run by device decode launches: each launch runs "
    "as many as its deepest source chain needs, at most log2(block)",
)

#: launches dispatched and not yet drained, per device, process-wide
_queue_lock = threading.Lock()
_queued: Dict[object, int] = {}


def launch_dispatched(op: str, device, real: int, pad: int) -> None:
    """One codec launch was dispatched to ``device`` (None: the default
    device) with ``real`` + ``pad`` rows; pair with :func:`launch_drained`."""
    with _queue_lock:
        ahead = _queued.get(device, 0)
        _queued[device] = ahead + 1
    if _metrics.enabled():
        _C_LAUNCHES.labels(op=op).inc()
        _C_LAUNCH_ROWS.labels(op=op, row="real").inc(real)
        _C_LAUNCH_ROWS.labels(op=op, row="pad").inc(pad)
        _C_LAUNCHES_AHEAD.labels(op=op).inc(ahead)


def launch_drained(device) -> None:
    """A launch's outputs reached the host (or it was abandoned)."""
    with _queue_lock:
        _queued[device] -= 1


def decode_jump_rounds(rounds: int) -> None:
    """A drained decode launch ran ``rounds`` pointer-jump rounds."""
    if _metrics.enabled():
        _C_JUMP_ROUNDS.inc(rounds)


#: cache filename shared with bench.py (kept in sync by convention; bench
#: cannot be imported from package code — it pulls the whole harness in)
_CACHE_BASENAME = "bench_tpu_last_good.json"
_CACHE_ENV = "S3SHUFFLE_BENCH_TPU_CACHE"
_GATE_ENV = "S3SHUFFLE_CODEC_RATE_GATE"

#: competing host rates (MB/s) when the cache carries no measured host_*
#: field. Conservative figures from the bench rig so the device has to beat
#: a REAL host, not a strawman: the C TLZ encoder sustains ~435 MB/s and the
#: C decoder ~600 MB/s at SF1 block sizes, native crc32c >1.5 GB/s, and the
#: numpy GF(2^8) table encode ~800 MB/s on one core.
DEFAULT_HOST_RATES: Dict[str, float] = {
    "host_tlz_encode_mb_s": 435.0,
    "host_tlz_decode_mb_s": 600.0,
    "host_crc32c_mb_s": 1500.0,
    "host_gf_encode_mb_s": 800.0,
}

#: op -> (device metric, competing host metric)
OP_METRICS: Dict[str, Tuple[str, str]] = {
    "encode": ("tpu_tlz_encode_mb_s", "host_tlz_encode_mb_s"),
    "decode": ("tpu_tlz_decode_mb_s", "host_tlz_decode_mb_s"),
    "gf_encode": ("tpu_gf_encode_mb_s", "host_gf_encode_mb_s"),
}

_lock = threading.Lock()
_cached: Optional[Dict[str, float]] = None
_cached_key: Optional[Tuple[str, float, int]] = None  # (path, mtime, size)
_injected: Optional[Dict[str, float]] = None


def cache_path() -> str:
    """Path of the probe's rate cache: ``S3SHUFFLE_BENCH_TPU_CACHE`` when
    set, else ``bench_tpu_last_good.json`` next to the repo's ``bench.py``
    (two levels above this package)."""
    env = os.environ.get(_CACHE_ENV)
    if env:
        return env
    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg_dir), _CACHE_BASENAME)


def set_rates_for_testing(table: Optional[Dict[str, float]]) -> None:
    """Inject a rate table (None restores file-backed lookup). Tests use
    this to prove all three dispatch regimes without touching disk."""
    global _injected, _cached, _cached_key
    with _lock:
        _injected = None if table is None else dict(table)
        _cached = None
        _cached_key = None


def invalidate() -> None:
    """Drop the in-process snapshot so the next lookup re-reads the cache
    file (the probe just rewrote it, or a test swapped the path env)."""
    set_rates_for_testing(None)


def snapshot() -> Dict[str, float]:
    """Numeric fields of the rate cache (injected table, else the JSON file;
    missing/corrupt file = empty). Cached per (path, mtime, size)."""
    global _cached, _cached_key
    with _lock:
        if _injected is not None:
            return dict(_injected)
        path = cache_path()
        try:
            st = os.stat(path)
            key = (path, st.st_mtime, st.st_size)
        except OSError:
            _cached, _cached_key = {}, None
            return {}
        if _cached is not None and _cached_key == key:
            return dict(_cached)
    # the file read happens OUTSIDE the lock: the cache is tiny but lives
    # on disk, and every codec decision funnels through here — a slow read
    # must not convoy concurrent selections (racing readers both parse the
    # same file; last publication wins, harmlessly)
    try:
        with open(path, "r", encoding="utf-8") as fh:
            raw = json.load(fh)
        table = {
            k: float(v)
            for k, v in raw.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)
        }
    except (OSError, ValueError) as exc:
        logger.warning("unreadable rate cache %s: %s — device paths "
                       "stay host-gated", path, exc)
        table = {}
    with _lock:
        if _injected is not None:  # a test swapped tables mid-read
            return dict(_injected)
        _cached, _cached_key = table, key
    return dict(table)


def rate(metric: str) -> Optional[float]:
    """Measured rate for one metric, or None when the cache has no (finite,
    positive) figure for it."""
    val = snapshot().get(metric)
    if val is None or not val > 0:
        return None
    return float(val)


def host_rate(metric: str) -> float:
    """Competing host rate: measured ``host_*`` cache field when present,
    else the conservative :data:`DEFAULT_HOST_RATES` figure."""
    measured = rate(metric)
    if measured is not None:
        return measured
    return DEFAULT_HOST_RATES.get(metric, float("inf"))


def gate_mode() -> str:
    """``auto`` (measured table decides), ``device``/``host`` (env-forced),
    or ``off`` (legacy arm-on-availability)."""
    raw = os.environ.get(_GATE_ENV, "").strip().lower()
    if raw in ("device", "tpu", "1"):
        return "device"
    if raw in ("host", "cpu", "0"):
        return "host"
    if raw == "off":
        return "off"
    return "auto"


def record_selection(path: str, reason: str) -> None:
    if _metrics.enabled():
        _C_SELECTED.labels(path=path, reason=reason).inc()


def decide(op: str, *, forced: bool = False) -> Tuple[bool, str]:
    """(use_device, reason) for one op — no metric emission (see
    :func:`select`). ``forced`` marks an explicit codec-level device force
    (``use_device=True`` / ``S3SHUFFLE_TPU_CODEC_DEVICE=1``): the operator
    bypassed measurement, so the gate steps aside."""
    mode = gate_mode()
    if mode == "device":
        return True, "env-device"
    if mode == "host":
        return False, "env-host"
    if mode == "off":
        return True, "gate-off"
    if forced:
        return True, "forced"
    device_metric, host_metric = OP_METRICS[op]
    dev = rate(device_metric)
    if dev is None:
        return False, "no-data"
    if dev > host_rate(host_metric):
        return True, "measured-device"
    return False, "measured-host"


def select(op: str, *, forced: bool = False) -> bool:
    """:func:`decide` + one ``codec_path_selected_total`` increment."""
    use, reason = decide(op, forced=forced)
    record_selection("device" if use else "host", reason)
    return use


def fused_decode_decision(*, forced: bool = False) -> Tuple[bool, str]:
    """Should decode fuse its CRC pass into the device launch, or keep
    streaming (unfused decode + host CRC)? Fused wins only when its measured
    rate beats the EFFECTIVE rate of the two-stage alternative — the
    harmonic combination of unfused device decode and the host CRC pass
    (today: fused 51 MB/s vs 1/(1/1004 + 1/1500) ≈ 601 MB/s, a 20x
    regression the old availability gate shipped). No data = streaming.
    An explicitly device-forced codec keeps the legacy fused arming — the
    operator bypassed measurement for the whole device plane."""
    mode = gate_mode()
    if mode == "device" or mode == "off":
        return True, "env-device" if mode == "device" else "gate-off"
    if mode == "host":
        return False, "env-host"
    if forced:
        return True, "forced"
    fused = rate("tpu_tlz_decode_fused_mb_s")
    unfused = rate("tpu_tlz_decode_mb_s")
    if fused is None or unfused is None:
        return False, "no-data"
    crc = host_rate("host_crc32c_mb_s")
    streaming_effective = 1.0 / (1.0 / unfused + 1.0 / crc)
    if fused > streaming_effective:
        return True, "measured-device"
    return False, "measured-host"


def select_fused_decode(*, forced: bool = False) -> bool:
    use, reason = fused_decode_decision(forced=forced)
    record_selection("fused" if use else "streaming", reason)
    return use


def timed_first_call(kernel: str, fn):
    """Wrap a jitted kernel so its FIRST invocation (trace + lower + compile
    + run) is timed by the ``codec.compile`` span, which observes
    ``codec_kernel_compile_seconds{kernel}``. Warm calls go straight
    through. One wrapper per compiled shape — callers build these inside
    their per-shape lru caches."""
    state = {"cold": True}
    state_lock = threading.Lock()

    def wrapped(*args, **kwargs):
        with state_lock:
            cold = state["cold"]
            state["cold"] = False
        if not cold:
            return fn(*args, **kwargs)
        from s3shuffle_tpu.utils import trace

        with trace.span("codec.compile", kernel=kernel):
            return fn(*args, **kwargs)

    return wrapped
