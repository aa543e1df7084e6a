"""Chip-aware codec dispatcher: spread fixed-shape device batches across
every local chip instead of pinning them to device 0.

Until this module existed the batch executors (``ops/tlz.py``
``encode_batch_device`` / ``decode_batch_device``, ``coding/gf.py``
``encode_groups``) placed every launch with a bare ``jax.device_put`` — the
default device — so "bytes/sec/chip" was a single-device number no matter
how many chips the host had. The dispatcher is the placement layer under
those executors:

- **least-outstanding-work placement**: :meth:`DeviceDispatcher.acquire`
  picks the device with the fewest launches in flight (ties go to the
  lowest index, so a single-stream caller still walks devices
  round-robin);
- **per-device accounting**: ``mesh_batches_dispatched_total{device}``,
  the ``mesh_device_outstanding{device}`` gauge, and
  ``mesh_dispatch_wait_seconds`` (time a full in-flight window spent
  draining its oldest launch) tell an operator from metrics alone how work
  spread across the chips.

Arming follows the ``coalesce_gap_bytes=0`` contract: ``mesh_devices`` 0 or
1 (the default) means :func:`get_dispatcher` returns None and every caller
keeps today's single-device op pattern byte-for-byte. The knob arrives via
``ShuffleConfig.mesh_devices`` (plumbed through :func:`configure` by the
codec construction) or the ``S3SHUFFLE_MESH_DEVICES`` env override (the
bench/probe path). The dispatcher never *initiates* accelerator runtime
init: when jax has not been imported by the process yet, no device batch
can be in flight either, so :func:`get_dispatcher` answers None without
importing anything.
"""

from __future__ import annotations

import logging
import os
import sys
import threading
from typing import List, Optional

from s3shuffle_tpu.metrics import registry as _metrics

logger = logging.getLogger("s3shuffle_tpu.parallel")

_C_DISPATCHED = _metrics.REGISTRY.counter(
    "mesh_batches_dispatched_total",
    "Device batches placed by the mesh dispatcher, by target device "
    "(encode/decode/GF-parity launches riding the multi-chip plane)",
    labelnames=("device",),
)
_H_WAIT = _metrics.REGISTRY.histogram(
    "mesh_dispatch_wait_seconds",
    "Seconds a full dispatch window spent draining its oldest in-flight "
    "launch before the next batch could be placed",
)
_G_OUTSTANDING = _metrics.REGISTRY.gauge(
    "mesh_device_outstanding",
    "Launches currently in flight per device under the mesh dispatcher",
    labelnames=("device",),
)

#: operator/bench override for the configured width (takes precedence over
#: :func:`configure` so a probe subprocess can arm the plane without config
#: plumbing); unset/empty defers to the configured value.
_MESH_ENV = "S3SHUFFLE_MESH_DEVICES"

_lock = threading.Lock()
_configured = 0
_dispatcher: Optional["DeviceDispatcher"] = None
_built_for: Optional[int] = None


class DeviceDispatcher:
    """Least-outstanding-work placement over a fixed device tuple.

    Thread-safe: the per-device outstanding counters are only touched
    under ``_lock`` (the race-witness dispatcher units watch them).
    """

    def __init__(self, devices):
        if not devices:
            raise ValueError("dispatcher needs at least one device")
        self.devices = tuple(devices)
        self._labels = tuple(
            f"{getattr(d, 'platform', 'dev')}:{getattr(d, 'id', i)}"
            for i, d in enumerate(self.devices)
        )
        self._lock = threading.Lock()
        self._outstanding: List[int] = [0] * len(self.devices)

    # ------------------------------------------------------------------
    @property
    def n_devices(self) -> int:
        return len(self.devices)

    def device(self, idx: int):
        return self.devices[idx]

    def label(self, idx: int) -> str:
        return self._labels[idx]

    def max_inflight(self) -> int:
        """Launches a caller should keep in flight before draining — one
        per device keeps every chip busy without unbounded staging memory."""
        return len(self.devices)

    # ------------------------------------------------------------------
    def acquire(self) -> int:
        """Pick the device with the fewest launches in flight and claim one
        slot on it. Returns the device index (pair every acquire with a
        :meth:`release`)."""
        with self._lock:
            idx = min(range(len(self.devices)),
                      key=lambda i: (self._outstanding[i], i))
            self._outstanding[idx] += 1
            now = self._outstanding[idx]
        if _metrics.enabled():
            _C_DISPATCHED.labels(device=self._labels[idx]).inc()
            _G_OUTSTANDING.labels(device=self._labels[idx]).set(now)
        return idx

    def release(self, idx: int) -> None:
        with self._lock:
            self._outstanding[idx] -= 1
            now = self._outstanding[idx]
        if _metrics.enabled():
            _G_OUTSTANDING.labels(device=self._labels[idx]).set(now)

    def observe_wait(self, seconds: float) -> None:
        """Record one full-window drain wait (the dispatcher's only source
        of backpressure latency)."""
        if _metrics.enabled():
            _H_WAIT.observe(seconds)

    def outstanding_snapshot(self) -> List[int]:
        with self._lock:
            return list(self._outstanding)


# ---------------------------------------------------------------------------
# Module-level arming (config plumbing + env override)
# ---------------------------------------------------------------------------


def configure(mesh_devices: int) -> None:
    """Record the configured plane width (``ShuffleConfig.mesh_devices``).
    0/1 disarms: :func:`get_dispatcher` answers None and every executor
    keeps the single-device path op-for-op."""
    global _configured, _dispatcher, _built_for
    with _lock:
        width = max(0, int(mesh_devices))
        if width != _configured:
            _configured = width
            _dispatcher, _built_for = None, None


def requested_devices() -> int:
    """Effective requested width: the env override when set, else the
    configured value."""
    raw = os.environ.get(_MESH_ENV, "").strip()
    if raw:
        try:
            return max(0, int(raw))
        except ValueError:
            logger.warning("ignoring malformed %s=%r", _MESH_ENV, raw)
    return _configured


def reset_for_testing() -> None:
    """Drop the armed width and any built dispatcher."""
    global _configured, _dispatcher, _built_for
    with _lock:
        _configured = 0
        _dispatcher, _built_for = None, None


def get_dispatcher() -> Optional[DeviceDispatcher]:
    """The armed dispatcher, or None when the plane is off.

    None when the effective width is <= 1 (the op-for-op contract), when
    jax was never imported by this process (no device batch can exist), or
    when the host exposes fewer than two local devices. A failing device
    query raises."""
    n = requested_devices()
    if n <= 1:
        return None
    global _dispatcher, _built_for
    with _lock:
        if _dispatcher is not None and _built_for == n:
            return _dispatcher
    if "jax" not in sys.modules:
        return None
    import jax

    devices = list(jax.local_devices())
    if len(devices) < 2:
        return None
    built = DeviceDispatcher(devices[:n] if n < len(devices) else devices)
    with _lock:
        if _dispatcher is None or _built_for != n:
            _dispatcher, _built_for = built, n
        return _dispatcher
