"""Serialized-handle map-side fast path — the UnsafeShuffleWriter analog.

Parity: Spark's SortShuffleManager picks a *serialized* write strategy when
the serializer is relocatable and there is no aggregator
(sort/S3ShuffleManager.scala:114-146 routes such handles to
UnsafeShuffleWriter, which buffers serialized records with their partition
ids and sorts ONE buffer by partition id at spill time). The buffer-per-
partition strategy (:class:`~s3shuffle_tpu.write.spill_writer.ShuffleMapWriter`)
keeps ``num_partitions`` live serializer→codec pipelines; for wide shuffles
(thousands of reduce partitions) that is thousands of stream states and
per-partition flush overhead per spill.

This writer is the columnar equivalent: accumulate RecordBatches plus their
partition-id arrays untouched; at spill/commit, ONE stable radix argsort by
partition id groups the whole buffer (``split_by_partition``), and each
present partition's rows stream through a short-lived serializer→codec
pipeline into the spill file (recording per-partition byte ranges) or the
output object. Codec framing and columnar frames are concatenatable, so
spill segments + the final in-memory segment concatenate into valid
partition streams — the same relocatable-serializer property Spark's
UnsafeShuffleWriter exploits.
"""

from __future__ import annotations

import logging
import os
import tempfile
import time
from typing import Iterable, List, Tuple

import numpy as np

from s3shuffle_tpu.utils import trace
from s3shuffle_tpu.write.map_output_writer import MapOutputCommitMessage
from s3shuffle_tpu.write.spill_writer import MapWriterBase

logger = logging.getLogger("s3shuffle_tpu.write")


class SerializedSortMapWriter(MapWriterBase):
    """Drop-in alternative to ShuffleMapWriter for SerializedShuffleHandle
    dependencies whose serializer supports columnar batches."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._batches: List = []
        self._pids: List[np.ndarray] = []
        self._buffered = 0
        #: per spill: int64 array of num_partitions+1 absolute file offsets
        self._spill_offsets: List[np.ndarray] = []

    # ------------------------------------------------------------------
    def _write(self, records: Iterable[Tuple]) -> None:
        from s3shuffle_tpu.batch import iter_record_batches
        from s3shuffle_tpu.serializer import count_plane_rows

        partitioner = self.dep.partitioner
        for batch in iter_record_batches(
            records, chunk_records=self._chunk_rows()
        ):
            if batch.n == 0:
                continue
            with trace.span("record.partition"):
                pids = partitioner.partition_batch(batch)
            count_plane_rows("write", batch.n)
            self._batches.append(batch)
            self._pids.append(np.asarray(pids))
            self._buffered += batch.nbytes + pids.nbytes
            self._records_written += batch.n
            if self._buffered > self.spill_memory_budget:
                self._spill()

    # ------------------------------------------------------------------
    def _sorted_pending(self):
        """One argsort over everything buffered → (grouped batch, partition
        bounds). Clears the buffer."""
        from s3shuffle_tpu.batch import RecordBatch, split_by_partition

        big = RecordBatch.concat(self._batches)
        pids = (
            np.concatenate(self._pids) if self._pids else np.empty(0, dtype=np.int64)
        )
        self._batches = []
        self._pids = []
        self._buffered = 0
        # no rows counted: they were counted at their write() pass — this is
        # the spill/commit-time re-grouping of the same buffer
        with trace.span("record.partition"):
            return split_by_partition(big, pids, self.dep.num_partitions)

    def _emit_partition(self, sink, rows) -> None:
        """Serialize one partition's rows through serializer→codec into
        ``sink`` (anything with .write). The pipeline is short-lived: frames
        are self-delimiting, so consecutive emissions concatenate."""
        from s3shuffle_tpu.codec.framing import CodecOutputStream

        with trace.span("record.serialize"):
            if self.codec is not None:
                codec_stream = CodecOutputStream(self.codec, sink, close_sink=False)
                target = codec_stream
            else:
                codec_stream = None
                target = sink
            w = self.serializer.new_write_stream(target)
            w.write_batch(rows)
            w.close()
            if codec_stream is not None:
                codec_stream.close()

    def _spill(self) -> None:
        if not self._batches:
            return
        t0 = time.perf_counter_ns()
        grouped, bounds = self._sorted_pending()
        if self._spill_fd is None:
            fd, self._spill_file = tempfile.mkstemp(prefix="s3shuffle-sersort-")
            self._spill_fd = os.fdopen(fd, "wb+")
        f = self._spill_fd
        f.seek(0, os.SEEK_END)
        n_parts = self.dep.num_partitions
        offsets = np.empty(n_parts + 1, dtype=np.int64)
        offsets[0] = f.tell()
        for pid in range(n_parts):
            lo, hi = int(bounds[pid]), int(bounds[pid + 1])
            if hi > lo:
                self._emit_partition(f, grouped.slice_rows(lo, hi))
            offsets[pid + 1] = f.tell()
        self._spill_offsets.append(offsets)
        self._record_spill(t0, int(offsets[-1] - offsets[0]))
        self.spill_count += 1
        logger.info(
            "Map %d (serialized path) spilled to %s (spill #%d)",
            self.map_id, self._spill_file, self.spill_count,
        )

    # ------------------------------------------------------------------
    def _commit(self) -> MapOutputCommitMessage:
        grouped, bounds = self._sorted_pending()
        for pid in range(self.dep.num_partitions):
            writer = self.output_writer.get_partition_writer(pid)
            for offsets in self._spill_offsets:
                lo, hi = int(offsets[pid]), int(offsets[pid + 1])
                if hi > lo:
                    self._copy_spill_range(writer, lo, hi)
            lo, hi = int(bounds[pid]), int(bounds[pid + 1])
            if hi > lo:
                self._emit_partition(writer, grouped.slice_rows(lo, hi))
            writer.close()
        return self._register_commit()
