"""Bounded-memory adaptive prefetcher — the read-side concurrency engine.

Parity: ``S3BufferedPrefetchIterator`` (S3BufferedPrefetchIterator.scala:16-213)
and ``S3BufferedInputStreamAdaptor`` (S3BufferedInputStreamAdaptor.scala:7-59):

- background threads pull (block, stream) pairs and *prefill* each stream's
  buffer — the actual store GET happens on the prefetch thread (adaptor
  :13-21), never on the consumer;
- memory budget: per-stream buffer = ``min(max_buffer_size, stream.max_bytes)``
  and the sum of in-flight buffers ≤ ``max_buffer_size``; producers wait when
  over budget (:122-135), consumers notify on stream close (:96-100);
- completed streams go on a LIFO stack (:30, 146, 209 — LIFO keeps the freshest
  buffer hot);
- **ThreadPredictor** (:32-69): a hill-climbing controller over thread count
  1..max_threads driven by *consumer wait latency* (not throughput — that
  choice is what keeps it stable on both NFS and S3, SURVEY.md §7.3): wait
  latencies go into a 20-sample ring; each full ring records the total for the
  current thread count and moves toward the neighboring count with the lower
  recorded total, exploring unmeasured neighbors first;
- thread management: new threads spawn when the target grows (:78-94); threads
  with id ≥ target retire themselves (:112-115);
- on exhaustion, per-task stats are logged: bytes, wait/prefetch ms, achieved
  MiB/s, avg block size, thread count (:155-186).
"""

from __future__ import annotations

import io
import logging
import threading
import time
from typing import Iterator, List, Optional, Tuple

from s3shuffle_tpu.metrics import registry as _metrics
from s3shuffle_tpu.read.block_stream import BlockStream
from s3shuffle_tpu.tuning.controller import Controller
from s3shuffle_tpu.utils import racewitness, trace
from s3shuffle_tpu.utils.io import read_up_to as _read_up_to

logger = logging.getLogger("s3shuffle_tpu.read")

RING_SIZE = 20

_H_WAIT = _metrics.REGISTRY.histogram(
    "read_prefetch_wait_seconds",
    "Consumer wait for the next prefetched block (the ThreadPredictor's "
    "control signal)",
)
_H_FILL = _metrics.REGISTRY.histogram(
    "read_prefetch_fill_seconds",
    "Background prefill latency per block (the actual store GET)",
)
_H_FILL_CLASS = _metrics.REGISTRY.histogram(
    "read_prefetch_fill_class_seconds",
    "Background prefill latency per block, bucketed by requested size "
    "class — the size-aware speculation threshold's evidence (a healthy "
    "64 MiB coalesced segment must be judged against 64 MiB peers, not a "
    "quantile dominated by 100 KiB fills)",
    labelnames=("size_class",),
)
_H_FILL_PER_MIB = _metrics.REGISTRY.histogram(
    "read_prefetch_fill_per_mib_seconds",
    "Background prefill latency per requested MiB (floored at 1 MiB so "
    "fixed round-trip cost never divides into noise), per size class — "
    "the seconds-per-byte speculation threshold's evidence: within a "
    "class, a 2 MiB and a 7 MiB fill normalize to the same scale, so a "
    "healthy fill at the class's large end stops reading as a straggler",
    labelnames=("size_class",),
)

#: size-class edges for ``read_prefetch_fill_class_seconds`` — coarse on
#: purpose: enough resolution to separate "small block" from "large
#: coalesced segment" regimes without fragmenting the sample counts the
#: quantiles need (MIN_FILL_SAMPLES per class before a threshold arms)
_SIZE_CLASS_EDGES = ((1 << 20, "le1m"), (8 << 20, "le8m"), (64 << 20, "le64m"))


def fill_size_class(nbytes: int) -> str:
    """The size-class label for one prefill's requested byte budget."""
    for edge, label in _SIZE_CLASS_EDGES:
        if nbytes <= edge:
            return label
    return "gt64m"


def fill_norm_mib(nbytes: int) -> float:
    """The per-MiB normalization divisor for one prefill: its size in MiB,
    floored at 1.0 — below a MiB fixed round-trip latency dominates and
    per-byte normalization would only amplify noise, so sub-MiB fills keep
    absolute-seconds semantics (observed value == fill seconds)."""
    return max(float(max(nbytes, 1)) / (1 << 20), 1.0)
_G_THREADS = _metrics.REGISTRY.gauge(
    "read_prefetch_threads", "Live ThreadPredictor thread-count decision"
)
_C_THREAD_MOVES = _metrics.REGISTRY.counter(
    "read_prefetch_thread_moves_total",
    "ThreadPredictor decisions that changed the thread count",
    labelnames=("direction",),
)


class ThreadPredictor(Controller):
    """Latency-driven hill climb over the prefetch thread count — a thin
    binding of the shared tuning Controller core (tuning/controller.py). The
    decisions are bit-for-bit the historical predictor's (hysteresis and
    cooldown off, the same 20-sample ring, ties resolving to fewer threads,
    the LOSING direction's stale total popped on every move so a drifting
    backend is re-probed — all pinned by the drift re-probe test)."""

    def __init__(self, max_threads: int, initial: int = 1):
        max_threads = max(1, max_threads)
        # knob stays UNSET: the predictor has its own dedicated instruments
        # (read_prefetch_threads / read_prefetch_thread_moves_total) and is
        # always on — emitting tune_* here would light the trace_report
        # "Tuning" digest on runs where the opt-in autotuner never ran
        super().__init__(
            ladder=range(1, max_threads + 1),
            initial=min(max(1, initial), max_threads),
            ring_size=RING_SIZE,
        )
        self.max_threads = max_threads


class PrefetchedBlockStream(io.RawIOBase):
    """A block stream whose first ``len(buffer)`` bytes were prefetched on a
    background thread; the remainder (blocks larger than the per-stream buffer)
    streams through synchronously. ``close`` is idempotent — a double close
    logs a warning (adaptor :49-58) — and releases budget via ``on_close``."""

    def __init__(self, block, stream: BlockStream, buffer: bytes, on_close):
        self.block = block
        self._stream = stream
        self._buffer = buffer
        self._pos = 0
        self._on_close = on_close
        self._closed_once = False
        self.buffer_size = len(buffer)

    def readable(self) -> bool:
        return True

    def buffer_view(self) -> memoryview:
        """Zero-copy view of the prefilled buffer — the coalesced scan
        planner slices member blocks out of a fetched segment through this
        (the view stays valid after :meth:`close` drops the buffer ref)."""
        return memoryview(self._buffer)

    def read(self, size: int = -1) -> bytes:
        if size is None or size < 0:
            return self.readall()
        if self._pos < len(self._buffer):
            end = min(self._pos + size, len(self._buffer))
            out = self._buffer[self._pos : end]
            self._pos = end
            return out
        return self._stream.read(size)

    def readall(self) -> bytes:
        chunks = []
        while True:
            chunk = self.read(1 << 20)
            if not chunk:
                return b"".join(chunks)
            chunks.append(chunk)

    def close(self) -> None:
        if self._closed_once:
            if not self.closed:
                logger.warning("Double close of prefetched stream for %s", self.block)
            return
        self._closed_once = True
        self._stream.close()
        self._buffer = b""
        if self._on_close is not None:
            self._on_close(self.buffer_size)
        super().close()


class BufferedPrefetchIterator:
    def __init__(
        self,
        source: Iterator[Tuple[object, BlockStream]],
        max_buffer_size: int,
        max_threads: int = 10,
        fetcher=None,
        speculation=None,
        initial_threads: int = 1,
    ):
        self._source = source
        self._max_buffer_size = max(1, max_buffer_size)
        # Optional ChunkedRangeFetcher: prefills larger than its chunk size
        # split into concurrent ranged sub-reads (byte-identical contract —
        # see read/chunked_fetch.py). None = plain serial prefill.
        self._fetcher = fetcher
        # Optional SpeculativeFetcher (coding/degraded.py): eligible
        # prefills race the store GET against a parity reconstruction once
        # they outlive the fill histogram's configured quantile — the
        # straggler half of the coded shuffle plane. None/ineligible =
        # exactly the plain path.
        self._speculation = speculation
        # ``initial_threads`` seeds the predictor's starting rung (still
        # clamped to max_threads; the hill climb tunes freely from there).
        # The default 1 is the reference's cold start; the skew plane's
        # split fan-out passes the ready-part count — a scan KNOWN to hold
        # K independent hot-partition sub-ranges must not serialize them
        # behind the predictor's 20-sample ramp, or the recorded split
        # would buy nothing on short scans.
        self._predictor = ThreadPredictor(max_threads, initial=initial_threads)
        self._lock = threading.Condition()
        # Separate lock for pulling source items: next(source) can do store
        # I/O (index GETs in BlockIterator) and must not serialize completions
        # or block the consumer on the main condition lock.
        self._source_lock = threading.Lock()
        self._completed: List[PrefetchedBlockStream] = []  # LIFO stack
        self._buffers_in_flight = 0
        self._active_fetches = 0
        self._source_exhausted = False
        self._error: Optional[BaseException] = None
        self._desired_threads = self._predictor.current
        self._thread_seq = 0
        self._threads: List[threading.Thread] = []
        # stats (printStatistics parity, :155-186)
        self._stat_bytes = 0
        self._stat_blocks = 0
        self._stat_prefetch_ns = 0
        self._stat_wait_ns = 0
        self._max_observed_threads = 1
        self._stats_printed = False
        # Backstop-wakeup visibility: the condition waits below carry
        # timeouts purely as missed-notify insurance — a timeout firing with
        # the wait condition still unmet means a notify was LOST, which this
        # rate-limited warning (at most one per interval per iterator) makes
        # visible in soak runs instead of silently adding latency.
        self._backstop_warn_interval_s = 30.0
        self._last_backstop_warn = -float("inf")
        # Race witness (no-op unless S3SHUFFLE_RACE_WITNESS=1): the budget
        # counters and the completion stack are the prefetcher's shared
        # state — every access must be ordered by self._lock (the PR-15
        # double-reserve lived exactly here). Watch BEFORE the fill threads
        # spawn so their accesses are ordered after construction.
        racewitness.watch_shared(
            self, ("_buffers_in_flight", "_active_fetches", "_completed")
        )
        #: the reduce task's trace context, adopted by every fill thread
        self._trace_ctx = trace.current_context()
        self._configure_threads()

    def _warn_backstop(self, which: str, detail: str) -> None:
        """Caller holds ``self._lock`` and observed a TIMED-OUT wait whose
        condition is still unmet (a backstop wakeup, not a notify)."""
        now = time.monotonic()
        if now - self._last_backstop_warn < self._backstop_warn_interval_s:
            return
        self._last_backstop_warn = now
        logger.warning(
            "prefetch %s wait woke on its backstop timeout, not a notify "
            "(possible missed-notify bug): %s; buffers_in_flight=%d/%d "
            "active_fetches=%d completed=%d threads=%d source_exhausted=%s",
            which, detail, self._buffers_in_flight, self._max_buffer_size,
            self._active_fetches, len(self._completed), len(self._threads),
            self._source_exhausted,
        )

    # ------------------------------------------------------------------
    # Producer side
    # ------------------------------------------------------------------
    def _configure_threads(self) -> None:
        with self._lock:
            while len(self._threads) < self._desired_threads:
                tid = self._thread_seq
                self._thread_seq += 1
                t = threading.Thread(
                    target=self._prefetch_loop, args=(tid,), daemon=True, name=f"prefetch-{tid}"
                )
                self._threads.append(t)
                self._max_observed_threads = max(self._max_observed_threads, len(self._threads))
                t.start()
            # Threads with id ≥ desired retire themselves in _prefetch_loop.

    def _prefetch_loop(self, thread_id: int) -> None:
        with trace.context(self._trace_ctx):
            self._fill_blocks()

    def _fill_blocks(self) -> None:
        me = threading.current_thread()
        while True:
            with self._lock:
                # Retire by *position*, not id (ids grow monotonically, so an
                # id comparison would instantly kill every respawned thread
                # after a scale-down): the newest len-desired threads retire
                # (S3BufferedPrefetchIterator.scala:112-115).
                try:
                    position = self._threads.index(me)
                except ValueError:
                    position = 0
                if position >= self._desired_threads:
                    self._threads.remove(me)
                    self._lock.notify_all()
                    return
                if self._source_exhausted or self._error is not None:
                    self._threads.remove(me)
                    self._lock.notify_all()
                    return
            # Pull the next item outside the main lock — may perform index
            # GETs inside the source generator.
            with self._source_lock:
                if self._source_exhausted:
                    continue
                try:
                    item = next(self._source)
                except StopIteration:
                    with self._lock:
                        self._source_exhausted = True
                        self._threads.remove(me)
                        self._lock.notify_all()
                    return
                except BaseException as e:  # surface to consumer
                    with self._lock:
                        self._error = e
                        self._source_exhausted = True
                        self._threads.remove(me)
                        self._lock.notify_all()
                    return
            block, stream = item
            bsize = min(self._max_buffer_size, max(1, stream.max_bytes))
            # Skew plane (read/scan_plan.SplitGroup): a split block's parts
            # share ONE budget claim — the first part to get here reserves
            # the whole block's bytes, siblings piggyback, and the last
            # member close releases. Funding the block atomically keeps the
            # consumer-side reassembly deadlock-free (a held part can never
            # be waiting on budget a sibling's consumer holds).
            group = getattr(stream, "budget_group", None)
            with self._lock:
                self._active_fetches += 1
                if group is not None:
                    need = min(self._max_buffer_size, group.total)
                    # siblings may race here: everyone waits until the group
                    # is funded (by WHOEVER claims first) or budget fits —
                    # the claim below is re-checked under this same lock, so
                    # exactly one part ever adds the reservation
                    self._await_budget_locked(
                        need, satisfied=lambda: group.reserved
                    )
                    if not group.reserved:
                        group.reserved = True
                        group.reserved_bytes = need
                        self._buffers_in_flight += need
                        # wake sibling parts parked on the same group wait
                        self._lock.notify_all()
                else:
                    # Budget wait (:122-135): sum of in-flight buffers ≤ budget.
                    self._await_budget_locked(bsize)
                    self._buffers_in_flight += bsize
            try:
                from s3shuffle_tpu.skew import tracked_get

                t0 = time.perf_counter_ns()
                with trace.span("read.prefetch", block=block.name, budget=bsize):
                    # ← the actual store GET (chunk-parallel for big prefills
                    # when a fetcher is attached; serial otherwise), wrapped
                    # in the per-object in-flight tracker so the hot-fanout
                    # gate sees live GET concurrency per data object
                    obj = getattr(
                        getattr(stream, "data_block", None), "name", None
                    )
                    if self._fetcher is not None:
                        primary = lambda s=stream, n=bsize, o=obj: tracked_get(  # noqa: E731
                            o, lambda: self._fetcher.prefill(s, n)
                        )
                    else:
                        primary = lambda s=stream, n=bsize, o=obj: tracked_get(  # noqa: E731
                            o, lambda: _read_up_to(s, n)
                        )
                    speculation_won = False
                    primary_exec_s = None
                    if (
                        self._speculation is not None
                        and self._speculation.eligible(stream, bsize)
                    ):
                        buffer, speculation_won, primary_exec_s = (
                            self._speculation.prefill(stream, bsize, primary)
                        )
                    else:
                        buffer = primary()
                dt = time.perf_counter_ns() - t0
                # the fill histogram drives the speculation threshold: a
                # speculation-won fill (duration = threshold +
                # reconstruction) is excluded, and a raced primary-won fill
                # observes the GET's own execution time (pool queue wait
                # excluded) — either would ratchet the quantile upward
                # during sustained straggler episodes
                if _metrics.enabled() and not speculation_won:
                    fill_s = (
                        primary_exec_s if primary_exec_s is not None else dt / 1e9
                    )
                    _H_FILL.observe(fill_s)
                    # same sample, size-classed: the speculation threshold
                    # reads the class matching its prefill's budget
                    cls = fill_size_class(bsize)
                    _H_FILL_CLASS.labels(size_class=cls).observe(fill_s)
                    # and per-MiB-normalized — the seconds-per-byte form the
                    # threshold actually consumes (coding/degraded.py)
                    _H_FILL_PER_MIB.labels(size_class=cls).observe(
                        fill_s / fill_norm_mib(bsize)
                    )
                on_close = (
                    self._release_group_budget(group)
                    if group is not None
                    else self._release_budget(len(buffer), bsize)
                )
                prefetched = PrefetchedBlockStream(block, stream, buffer, on_close)
                with self._lock:
                    self._stat_prefetch_ns += dt
                    self._stat_bytes += len(buffer)
                    self._stat_blocks += 1
                    self._completed.append(prefetched)  # LIFO push
                    self._active_fetches -= 1
                    self._lock.notify_all()
            except BaseException as e:
                with self._lock:
                    self._error = e
                    self._active_fetches -= 1
                    self._lock.notify_all()
                return

    def _await_budget_locked(self, need: int, satisfied=None) -> None:
        """Caller holds ``self._lock``: block until ``need`` budget bytes
        fit, an error is set, or ``satisfied()`` turns true (a sibling
        split part claimed the shared group reservation — the caller then
        piggybacks instead of reserving again). Every transition that can
        unblock this wait notifies (budget release on stream close, group
        claim, error) — the timeout is only a missed-notify backstop, not
        a polling interval."""

        def blocked() -> bool:
            return (
                (satisfied is None or not satisfied())
                and self._buffers_in_flight + need > self._max_buffer_size
                and self._error is None
            )

        while blocked():
            notified = self._lock.wait(timeout=5.0)
            if not notified and blocked():
                self._warn_backstop(
                    "budget", f"producer needs {need} budget bytes"
                )

    def _release_budget(self, actual: int, reserved: int):
        def on_close(_buffer_size: int) -> None:
            with self._lock:
                self._buffers_in_flight -= reserved
                self._lock.notify_all()

        return on_close

    def _release_group_budget(self, group):
        """Split-group budget release: the group's single whole-block
        reservation drops when the LAST member part closes."""

        def on_close(_buffer_size: int) -> None:
            with self._lock:
                group.closed += 1
                if group.closed >= group.count:
                    self._buffers_in_flight -= group.reserved_bytes
                    self._lock.notify_all()

        return on_close

    # ------------------------------------------------------------------
    # Shared-budget surface for the decode pipeline: in-flight DECODED bytes
    # (CodecInputStream's async batch window) count against the SAME
    # max_buffer_size_task budget as prefilled buffers, so N concurrent
    # reduce tasks never exceed their provisioned memory. Reservation is
    # NON-BLOCKING by design — the decode window shrinks instead of waiting,
    # because the consumer doing the reserving is the same thread whose
    # stream closes release prefill budget (a blocking wait could deadlock).
    # ------------------------------------------------------------------
    @property
    def budget(self) -> "BufferedPrefetchIterator":
        return self

    def try_reserve(self, nbytes: int) -> bool:
        """Reserve ``nbytes`` of the task budget if available RIGHT NOW."""
        with self._lock:
            if self._buffers_in_flight + nbytes > self._max_buffer_size:
                return False
            self._buffers_in_flight += nbytes
            return True

    def release_reserved(self, nbytes: int) -> None:
        with self._lock:
            self._buffers_in_flight -= nbytes
            self._lock.notify_all()

    # ------------------------------------------------------------------
    # Consumer side
    # ------------------------------------------------------------------
    def __iter__(self) -> "BufferedPrefetchIterator":
        return self

    def __next__(self) -> PrefetchedBlockStream:
        # read_prefetch_wait_seconds comes from _next_completed's own clock,
        # which the thread predictor needs with metrics off; this span only
        # names the wait for the profiler and counts its self time
        with trace.span("read.prefetch_wait"):
            return self._next_completed()

    def _next_completed(self) -> PrefetchedBlockStream:
        t0 = time.perf_counter_ns()
        with self._lock:
            while not self._completed:
                if self._error is not None:
                    raise self._error
                if self._source_exhausted and self._active_fetches == 0 and not self._threads_alive():
                    self._print_statistics()
                    raise StopIteration
                # Completion pushes, errors, exhaustion, and thread retirement
                # all notify — the timeout is only a backstop against a missed
                # wakeup, not a polling interval (no latency is added: a push
                # wakes this wait immediately).
                notified = self._lock.wait(timeout=2.0)
                if (
                    not notified
                    and not self._completed
                    and self._error is None
                    # mirror the loop-exit condition exactly: a lost
                    # thread-retirement notify must warn too
                    and not (
                        self._source_exhausted
                        and self._active_fetches == 0
                        and not self._threads_alive()
                    )
                ):
                    self._warn_backstop("consumer", "no completed block arrived")
            item = self._completed.pop()  # LIFO pop (:146, 209)
            wait_ns = time.perf_counter_ns() - t0
            self._stat_wait_ns += wait_ns
            previous = self._desired_threads
            self._desired_threads = self._predictor.add_measurement_and_predict(wait_ns)
        if _metrics.enabled():
            _H_WAIT.observe(wait_ns / 1e9)
            _G_THREADS.set(self._desired_threads)
            if self._desired_threads != previous:
                _C_THREAD_MOVES.labels(
                    direction="up" if self._desired_threads > previous else "down"
                ).inc()
        self._configure_threads()
        return item

    def _threads_alive(self) -> bool:
        self._threads = [t for t in self._threads if t.is_alive()]
        return bool(self._threads)

    def _print_statistics(self) -> None:
        if self._stats_printed or self._stat_blocks == 0:
            self._stats_printed = True
            return
        self._stats_printed = True
        total_ns = max(1, self._stat_prefetch_ns)
        mib = self._stat_bytes / (1024 * 1024)
        logger.info(
            "Statistics: %d bytes read in %d blocks (avg %.0f B), waiting %.1f ms, "
            "prefetching %.1f ms (%.1f MiB/s, %.0f%% waiting), threads=%d",
            self._stat_bytes,
            self._stat_blocks,
            self._stat_bytes / self._stat_blocks,
            self._stat_wait_ns / 1e6,
            self._stat_prefetch_ns / 1e6,
            mib / (total_ns / 1e9),
            100.0 * self._stat_wait_ns / max(1, self._stat_wait_ns + self._stat_prefetch_ns),
            self._max_observed_threads,
        )

    @property
    def stats(self) -> dict:
        return {
            "bytes": self._stat_bytes,
            "blocks": self._stat_blocks,
            "wait_ns": self._stat_wait_ns,
            "prefetch_ns": self._stat_prefetch_ns,
            "threads": self._max_observed_threads,
        }


