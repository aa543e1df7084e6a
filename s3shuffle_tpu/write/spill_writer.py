"""Map-task shuffle writer: partition records, spill when over budget, commit.

Parity: the role of Spark's three map-side writers (SortShuffleWriter /
UnsafeShuffleWriter / BypassMergeSortShuffleWriter) feeding the reference's
``S3ShuffleMapOutputWriter`` (SURVEY.md §3.2), collapsed into one strategy
that keeps their shared contract:

- records are routed to per-partition serializer→codec pipelines (map-side
  combine applied first when the dependency asks for it);
- memory is bounded: when buffered bytes exceed the budget, every partition's
  pipeline is flushed at a frame boundary and appended to a local spill file
  (the codec framing is concatenatable, so spill segments concatenate into a
  valid partition stream — the same relocatable-serializer property Spark's
  UnsafeShuffleWriter exploits);
- on ``stop(success=True)``, partitions are streamed in monotone order into
  the single data object via :class:`MapOutputWriter` and the commit registers
  a MapStatus addressed to the object store (S3ShuffleWriter.scala:10-18).
"""

from __future__ import annotations

import io
import logging
import os
import tempfile
import time
from typing import Any, Callable, Iterable, List, Optional, Tuple

import numpy as np

from s3shuffle_tpu.codec.framing import FrameCodec
from s3shuffle_tpu.metrics import registry as _metrics
from s3shuffle_tpu.utils import trace
from s3shuffle_tpu.write.map_output_writer import MapOutputCommitMessage, MapOutputWriter

logger = logging.getLogger("s3shuffle_tpu.write")

_H_SPILL = _metrics.REGISTRY.histogram(
    "write_spill_seconds", "Per-spill flush latency (all partitions)"
)
_C_SPILL_BYTES = _metrics.REGISTRY.counter(
    "write_spill_bytes_total", "Bytes moved to local spill files"
)


class _PartitionPipeline:
    """serializer → (codec) → in-memory sink for one reduce partition.

    ``fused_checksum`` (optional FusedChecksumAccumulator) rides the codec
    stream: it receives per-frame CRCs fused into the device encode launch
    (host byte-hashes for frames the device didn't produce), so at
    :meth:`finish` its value equals a byte-serial checksum of every stored
    byte this pipeline ever emitted — spilled segments included — and the
    commit path can skip re-hashing the partition on the host."""

    def __init__(self, serializer, codec: Optional[FrameCodec],
                 fused_checksum=None):
        self.sink = io.BytesIO()
        self.fused_checksum = fused_checksum if codec is not None else None
        if codec is not None:
            from s3shuffle_tpu.codec.framing import CodecOutputStream

            self.codec_stream: Optional[CodecOutputStream] = CodecOutputStream(
                codec, self.sink, close_sink=False, checksum=self.fused_checksum
            )
            target = self.codec_stream
        else:
            self.codec_stream = None
            target = self.sink
        self.record_writer = serializer.new_write_stream(target)
        self.spill_segments: List[Tuple[int, int]] = []  # (offset, length) in spill file

    def buffered_bytes(self) -> int:
        # Count bytes queued inside the codec stream too (batch codecs hold
        # full raw blocks until a batch flush) — the spill budget must see
        # them or a wide shuffle with the TPU codec blows past the budget.
        pending = self.codec_stream.pending_bytes if self.codec_stream is not None else 0
        return self.sink.tell() + pending

    def flush_to_frame_boundary(self) -> bytes:
        self.record_writer.flush()
        if self.codec_stream is not None:
            self.codec_stream.flush_block()
        data = self.sink.getvalue()
        self.sink.seek(0)
        self.sink.truncate(0)
        return data

    def spill_into(self, f) -> int:
        """Flush to a frame boundary and append the buffered bytes to ``f``
        WITHOUT materializing them (getbuffer, not getvalue — the spill path
        moves every over-budget byte, and the getvalue copy was a full pass;
        r5 profile). Returns the byte count written."""
        self.record_writer.flush()
        if self.codec_stream is not None:
            self.codec_stream.flush_block()
        view = self.sink.getbuffer()
        n = len(view)
        if n:
            f.write(view)
        view.release()  # BytesIO refuses truncate while a buffer is exported
        self.sink.seek(0)
        self.sink.truncate(0)
        return n

    def finish(self) -> Optional[int]:
        """Close the serializer + codec pipeline (final frames emitted into
        the local sink). Returns this partition's checksum value stitched
        from the fused per-frame CRCs, or None when the commit path must
        stream-hash the stored bytes itself."""
        self.record_writer.close()
        if self.codec_stream is not None:
            self.codec_stream.close()
        return (
            self.fused_checksum.value
            if self.fused_checksum is not None
            else None
        )

    def drain_into(self, writer) -> None:
        """Stream the sink's remaining bytes into ``writer`` WITHOUT
        materializing them (same zero-materialization contract as
        :meth:`spill_into`). Call :meth:`finish` first."""
        view = self.sink.getbuffer()
        if len(view):
            writer.write(view)
        view.release()

    def finalize_into(self, writer) -> None:
        """Close the pipeline and stream its remaining bytes into ``writer``
        (:meth:`finish` + :meth:`drain_into`)."""
        self.finish()
        self.drain_into(writer)

    def finalize(self) -> bytes:
        self.finish()
        return self.sink.getvalue()


class MapWriterBase:
    """Shared writer state + the stop()/commit/abort/cleanup protocol —
    subclasses implement the buffering strategy (`write`, `_commit`).
    Extracted so the buffer-per-partition and serialized-sort strategies
    cannot drift on the commit protocol (they once duplicated it)."""

    def __init__(
        self,
        handle,
        map_id: int,
        output_writer: MapOutputWriter,
        codec: Optional[FrameCodec],
        on_commit: Callable[..., None],  # (sid, map_id, lengths, map_index, message)
        spill_memory_budget: Optional[int] = None,
        map_index: Optional[int] = None,
    ):
        self.handle = handle
        self.dep = handle.dependency
        self.map_id = map_id
        self.map_index = map_id if map_index is None else map_index
        self.output_writer = output_writer
        self.codec = codec
        self.on_commit = on_commit
        cfg = output_writer.dispatcher.config
        # The record-plane write seam: a columnar serializer left unpinned
        # resolves its frame wire (column vs legacy) from cfg.columnar HERE —
        # the read side auto-detects, so only writers consult config.
        self.serializer = self.dep.serializer.resolve_for_write(cfg)
        self.spill_memory_budget = spill_memory_budget or cfg.max_buffer_size_task
        self._spill_file: Optional[str] = None
        self._spill_fd = None
        self._records_written = 0
        self._stopped = False
        self.spill_count = 0
        self._task_ctx = None  # write()'s trace context, adopted by stop()

    def write(self, records) -> None:
        """One map task's records (Spark's ``write``), under ``task.map``."""
        with trace.span("task.map"):
            self._task_ctx = trace.current_context()
            self._write(records)

    def _write(self, records) -> None:
        raise NotImplementedError

    def _commit(self) -> MapOutputCommitMessage:
        raise NotImplementedError

    def _on_abort(self) -> None:
        """Strategy-specific state release on unsuccessful stop."""

    # ------------------------------------------------------------------
    def stop(self, success: bool) -> Optional[MapOutputCommitMessage]:
        """Commit (or abort) the map output: the second half of the
        ``task.map`` span that :meth:`write` opened."""
        if self._stopped:
            return None
        self._stopped = True
        with trace.context(self._task_ctx), trace.span("task.map"):
            return self._stop(success)

    def _stop(self, success: bool) -> Optional[MapOutputCommitMessage]:
        if not success:
            self._on_abort()
            self.output_writer.abort()
            self._cleanup_spill()
            return None
        try:
            with trace.span(
                "write.commit", map_id=self.map_id, records=self._records_written
            ) as commit:
                message = self._commit()
            if _metrics.enabled():
                from s3shuffle_tpu.metrics.stats import COLLECTOR

                # map-commit ShuffleStats entry (reduce side reports at drain)
                COLLECTOR.record_map(
                    shuffle_id=self.handle.shuffle_id,
                    map_id=self.map_id,
                    bytes=int(np.sum(message.partition_lengths)),
                    records=self._records_written,
                    seconds=commit.seconds,
                    spills=self.spill_count,
                )
            return message
        except BaseException as e:
            self.output_writer.abort(e if isinstance(e, Exception) else None)
            raise
        finally:
            self._cleanup_spill()

    def _register_commit(self) -> MapOutputCommitMessage:
        """Shared commit tail: seal the data object (or hand the payload to
        the composite aggregator), write the sidecars, notify ``on_commit``
        with the full commit message — composite commits carry their
        ``(group, base_offset)`` coordinates and visibility defers to the
        group seal (the registrar decides what that means per mode)."""
        message = self.output_writer.commit_all_partitions()
        self.on_commit(
            self.handle.shuffle_id, self.map_id, message.partition_lengths,
            self.map_index, message,
        )
        return message

    def _fused_checksum_factory(self):
        """Per-partition FusedChecksumAccumulator factory, or None. Active
        when the codec can hand back CRCs fused into its encode launch AND
        the configured partition checksum is CRC32C (what the device
        computes): the sidecar value is then stitched from per-frame device
        CRCs instead of re-hashing every stored byte on the host — sidecar
        bytes stay byte-identical (regression-tested)."""
        cfg = self.output_writer.dispatcher.config
        if (
            not cfg.checksum_enabled
            or cfg.checksum_algorithm != "CRC32C"
            or not getattr(self.codec, "supports_fused_checksum", False)
        ):
            return None
        from s3shuffle_tpu.codec.tpu import FusedChecksumAccumulator
        from s3shuffle_tpu.ops.checksum import POLY_CRC32C

        return lambda: FusedChecksumAccumulator(POLY_CRC32C)

    def _record_spill(self, start_ns: int, nbytes: int) -> None:
        """Metrics hook shared by both buffering strategies' spill paths."""
        if _metrics.enabled():
            _H_SPILL.observe((time.perf_counter_ns() - start_ns) / 1e9)
            _C_SPILL_BYTES.inc(nbytes)

    def _chunk_rows(self) -> int:
        """Rows per columnar chunk on the write path (``columnar_batch_rows``
        — partition/route/frame granularity), consulted through the write
        tuner when autotune is on; the static config value otherwise.
        ``columnar=0`` pins the pre-format-5 chunking unconditionally — the
        knob must not be able to move legacy frame boundaries, or the
        byte-identity contract would only hold at the default value."""
        from s3shuffle_tpu.batch import DEFAULT_CHUNK_RECORDS

        cfg = self.output_writer.dispatcher.config
        if not cfg.columnar:
            return DEFAULT_CHUNK_RECORDS
        static = cfg.columnar_batch_rows
        tuner = getattr(self.output_writer.dispatcher, "commit_tuner", None)
        if tuner is None:
            return static
        return tuner.columnar_batch_rows(static)

    def _cleanup_spill(self) -> None:
        if self._spill_fd is not None:
            self._spill_fd.close()
            self._spill_fd = None
        if self._spill_file is not None:
            try:
                os.remove(self._spill_file)
            except OSError:
                pass
            self._spill_file = None

    def _copy_spill_range(self, writer, lo: int, hi: int) -> None:
        """Stream spill-file bytes [lo, hi) into a partition writer."""
        assert self._spill_fd is not None
        self._spill_fd.seek(lo)
        remaining = hi - lo
        while remaining > 0:
            chunk = self._spill_fd.read(min(remaining, 1 << 20))
            if not chunk:
                raise IOError("Truncated spill file")
            writer.write(chunk)
            remaining -= len(chunk)


class ShuffleMapWriter(MapWriterBase):
    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        fused = self._fused_checksum_factory()
        self._pipelines = [
            _PartitionPipeline(
                self.serializer, self.codec,
                fused() if fused is not None else None,
            )
            for _ in range(self.dep.num_partitions)
        ]
        self._combine_reducer = None  # columnar map-side combine state
        self._since_budget_check = 0
        # Skew plane, combine-sidecar prong: for aggregating deps whose
        # combine runs REDUCE-side (map_side_combine off), partitions whose
        # routed bytes cross combine_threshold_bytes get their chunks
        # pre-reduced map-side (colagg reduce_chunk) so hot partitions ship
        # partial aggregates. Static 0 = prong off, never overruled.
        dep = self.dep
        cfg = self.output_writer.dispatcher.config
        self._combine_gate = (
            not dep.map_side_combine
            and dep.aggregator is not None
            and getattr(dep.aggregator, "supports_columnar", False)
            and self.serializer.supports_batches
            and getattr(cfg, "combine_threshold_bytes", 0) > 0
        )
        self._sidecar_reducer = None
        self._sidecar_routed = None  # per-partition routed-bytes tally
        self._sidecar_threshold = 0

    # ------------------------------------------------------------------
    def _write(self, records: Iterable[Tuple[Any, Any]]) -> None:
        from s3shuffle_tpu.batch import RecordBatch

        dep = self.dep
        if self.serializer.supports_batches:
            if not dep.map_side_combine:
                self._write_batched(records)
                return
            if dep.aggregator is not None and dep.aggregator.supports_columnar:
                # Vectorized map-side combine: the whole map task's input —
                # across every write() call (production workers write one
                # batch per call) — flows through one bounded-memory
                # ColumnarReducer (sorted unique-key partials, spills at
                # budget); partition routing happens at commit when the
                # reducer drains.
                from s3shuffle_tpu.batch import iter_record_batches

                if self._combine_reducer is None:
                    self._combine_reducer = dep.aggregator.new_reducer(
                        spill_bytes=self.output_writer.dispatcher.config.aggregator_spill_bytes
                    )
                # _records_written counts at the commit drain (post-combine
                # rows, matching the per-record combine route's semantics)
                rows = self._chunk_rows()
                for chunk in iter_record_batches(records, chunk_records=rows):
                    self._combine_reducer.add(chunk)
                return
        if isinstance(records, RecordBatch):
            # Per-record routes (combine, or a non-batch serializer) consume
            # (k, v) tuples — expand columnar input at the boundary.
            records = records.iter_records()
        if dep.map_side_combine:
            assert dep.aggregator is not None
            records = dep.aggregator.combine_values_by_key(
                records,
                spill_bytes=self.output_writer.dispatcher.config.aggregator_spill_bytes,
            )
        import itertools

        from s3shuffle_tpu.utils import gc_paused

        partitioner = dep.partitioner
        pipelines = self._pipelines
        check_every = 4096
        # Running total across write() calls — incremental callers writing
        # small batches must still hit the budget check.
        n = self._records_written
        it = iter(records)
        while True:
            # Pull each chunk with the collector LIVE: `records` may run
            # arbitrary user compute (combine functions, lazy sources), and a
            # process-wide gc pause across it would let reference cycles pile
            # up for the whole task (ADVICE r3). The pause below covers only
            # writer-internal routing + serialization.
            chunk = list(itertools.islice(it, check_every))
            if not chunk:
                break
            with gc_paused:
                for k, v in chunk:
                    pipelines[partitioner(k)].record_writer.write(k, v)
            n += len(chunk)
            if _metrics.enabled():
                from s3shuffle_tpu.serializer import count_fallback_rows

                count_fallback_rows("write", len(chunk))
            # amortize the O(num_partitions) budget scan across write()
            # calls: incremental callers writing tiny batches must not pay
            # a full-pipeline scan per call
            self._since_budget_check += len(chunk)
            if self._since_budget_check >= check_every:
                self._since_budget_check = 0
                if self._buffered_total() > self.spill_memory_budget:
                    self._spill()
        self._records_written = n

    def _write_batched(self, records: Iterable[Tuple[Any, Any]]) -> None:
        """Vectorized route: chunk records into columnar RecordBatches,
        vectorized partition assignment + stable grouping, one columnar frame
        per (chunk × partition) through each pipeline."""
        from s3shuffle_tpu.batch import iter_record_batches

        self._write_batches(
            iter_record_batches(records, chunk_records=self._chunk_rows())
        )

    def _write_batches(self, batches) -> None:
        from s3shuffle_tpu.batch import split_by_partition
        from s3shuffle_tpu.serializer import count_plane_rows

        dep = self.dep
        for batch in batches:
            if batch.n == 0:
                continue
            with trace.span("record.partition"):
                pids = dep.partitioner.partition_batch(batch)
                grouped, bounds = split_by_partition(batch, pids, dep.num_partitions)
            count_plane_rows("write", batch.n)
            with trace.span("record.serialize"):
                for pid in range(dep.num_partitions):
                    lo, hi = int(bounds[pid]), int(bounds[pid + 1])
                    if hi > lo:
                        sl = grouped.slice_rows(lo, hi)
                        if self._combine_gate:
                            sl = self._maybe_combine_chunk(pid, sl)
                        self._pipelines[pid].record_writer.write_batch(sl)
            self._records_written += batch.n
            if self._buffered_total() > self.spill_memory_budget:
                self._spill()

    def _maybe_combine_chunk(self, pid: int, sl):
        """Combine-sidecar decision for one (chunk × partition) slice: once
        the partition's routed bytes cross the threshold, its chunks are
        pre-reduced (argsort + reduceat, chunk-local — streaming, bounded
        by the chunk itself) and the smaller form ships. A chunk the
        reduction does not shrink (mostly-unique keys — the widening of a
        narrow schema can even grow it) ships raw, so the sidecar can only
        ever REMOVE wire bytes. Shipping any partial flags the map output
        (note_combined → the index sidecar's FLAG_COMBINED) so readers
        merge through the aggregator."""
        if self._sidecar_routed is None:
            cfg = self.output_writer.dispatcher.config
            threshold = cfg.combine_threshold_bytes
            tuner = getattr(self.output_writer.dispatcher, "commit_tuner", None)
            if tuner is not None:
                threshold = tuner.combine_threshold_bytes(threshold)
            self._sidecar_threshold = int(threshold)
            self._sidecar_routed = np.zeros(self.dep.num_partitions, dtype=np.int64)
            self._sidecar_reducer = self.dep.aggregator.new_reducer(
                spill_bytes=cfg.aggregator_spill_bytes
            )
        routed = int(self._sidecar_routed[pid])
        self._sidecar_routed[pid] += sl.nbytes
        if routed < self._sidecar_threshold:
            return sl
        try:
            reduced = self._sidecar_reducer.reduce_chunk(sl)
        except ValueError as e:
            # a value shape the columnar plane cannot combine (outside the
            # declared schema): ship raw and stop trying for this task
            logger.debug(
                "map-side combine sidecar disabled for map %d: %s",
                self.map_id, e,
            )
            self._combine_gate = False
            return sl
        if reduced.n < sl.n and reduced.nbytes < sl.nbytes:
            if _metrics.enabled():
                from s3shuffle_tpu.skew import C_MAP_COMBINE_ROWS

                C_MAP_COMBINE_ROWS.inc(sl.n - reduced.n)
            self.output_writer.note_combined()
            return reduced
        return sl

    def _buffered_total(self) -> int:
        return sum(p.buffered_bytes() for p in self._pipelines)

    def _spill(self) -> None:
        t0 = time.perf_counter_ns()
        if self._spill_fd is None:
            fd, self._spill_file = tempfile.mkstemp(prefix="s3shuffle-map-spill-")
            self._spill_fd = os.fdopen(fd, "wb+")
        f = self._spill_fd
        spilled = 0
        for pipeline in self._pipelines:
            offset = f.tell()
            n = pipeline.spill_into(f)
            if n:
                pipeline.spill_segments.append((offset, n))
                spilled += n
        self._record_spill(t0, spilled)
        self.spill_count += 1
        logger.info(
            "Map %d spilled to %s (spill #%d)", self.map_id, self._spill_file, self.spill_count
        )

    # ------------------------------------------------------------------
    def _on_abort(self) -> None:
        if self._combine_reducer is not None:
            self._combine_reducer.cleanup()
            self._combine_reducer = None

    def _commit(self) -> MapOutputCommitMessage:
        if self._combine_reducer is not None:
            # drain the map-side combine: reduced partials route to partition
            # pipelines now, so every partition's stream is complete below
            self._write_batches(self._combine_reducer.results())
            self._combine_reducer = None
        for pid, pipeline in enumerate(self._pipelines):
            # finish() BEFORE the writer exists: the codec stream's final
            # frames land in the local sink and complete the fused checksum,
            # which then replaces the writer's byte-serial hashing outright
            with trace.span("record.serialize"):
                fused_value = pipeline.finish()
            writer = self.output_writer.get_partition_writer(
                pid, precomputed_checksum=fused_value
            )
            for offset, length in pipeline.spill_segments:
                self._copy_spill_range(writer, offset, offset + length)
            pipeline.drain_into(writer)
            writer.close()
        return self._register_commit()
