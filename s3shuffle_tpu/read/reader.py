"""Reduce-side pipeline assembler.

Parity: ``S3ShuffleReader`` (storage/S3ShuffleReader.scala:37-198), adapted
from Spark's BlockStoreShuffleReader. ``read()`` assembles:

1. block enumeration — driver-metadata mode via the MapOutputTracker
   (:169-180, with contiguous-range batch merging) or store-listing mode
   (:181-196) when ``use_block_manager`` is off;
2. block-range resolution → drop empty blocks + remote-bytes/blocks metrics
   (:91-97);
3. the prefetching scan iterator — the coalescing planner's segment pipeline
   by default, or the per-block ``BufferedPrefetchIterator`` path at
   ``coalesce_gap_bytes=0`` (read/scan_plan.py; :98);
4. per block: optional :class:`ChecksumValidationStream` over the stored bytes,
   then codec decompression (the analog of ``serializerManager.wrapStream``),
   then the serializer's record iterator (:99-110);
5. per-record metrics + completion accounting (:113-122);
6. optional aggregation (:124-138) and key-ordering external sort (:141-149).

Batch-fetch eligibility matches the reference (:55-75): relocatable serializer
∧ concatenatable codec framing (always true here) — merged ranges become
``ShuffleBlockBatchId`` per map task.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Iterator, List, Optional, Tuple

from s3shuffle_tpu.block_ids import ShuffleBlockBatchId, ShuffleBlockId
from s3shuffle_tpu.codec import CodecInputStream
from s3shuffle_tpu.codec.framing import FrameCodec
from s3shuffle_tpu.dependency import ShuffleDependency
from s3shuffle_tpu.metadata.helper import ScanIndexMemo, ShuffleHelper
from s3shuffle_tpu.metadata.map_output import MapOutputTrackerLike
from s3shuffle_tpu.read.block_iterator import ReadableBlockId
from s3shuffle_tpu.read.checksum_stream import ChecksumValidationStream
from s3shuffle_tpu.sorter import ExternalSorter
from s3shuffle_tpu.storage.dispatcher import Dispatcher
from s3shuffle_tpu.utils import trace

logger = logging.getLogger("s3shuffle_tpu.read")


@dataclasses.dataclass
class ShuffleReadMetrics:
    """Parity: the Spark metric names fed at S3ShuffleReader.scala:91-118."""

    remote_blocks_fetched: int = 0
    remote_bytes_read: int = 0
    records_read: int = 0
    wait_ns: int = 0
    prefetch_ns: int = 0


class ShuffleReader:
    def __init__(
        self,
        dispatcher: Dispatcher,
        helper: ShuffleHelper,
        tracker: Optional[MapOutputTrackerLike],
        dependency: ShuffleDependency,
        start_partition: int,
        end_partition: int,
        start_map_index: int = 0,
        end_map_index: Optional[int] = None,
        codec: Optional[FrameCodec] = None,
    ):
        self.dispatcher = dispatcher
        self.helper = helper
        self.tracker = tracker
        self.dep = dependency
        self.start_partition = start_partition
        self.end_partition = end_partition
        self.start_map_index = start_map_index
        self.end_map_index = end_map_index
        self.codec = codec
        self.metrics = ShuffleReadMetrics()
        # replaced with a fresh memo per scan in _make_prefetcher; this one
        # only backs a _wrapped_stream call that skipped the pipeline
        self._scan_memo = ScanIndexMemo(helper)
        cfg = dispatcher.config
        # Batch-fetch eligibility (S3ShuffleReader.scala:55-75): relocatable
        # serializer + concatenatable codec framing (ours always is).
        self.do_batch_fetch = (
            self.dep.serializer.relocatable
            and (end_partition - start_partition > 1)
        ) or cfg.force_batch_fetch

    # ------------------------------------------------------------------
    def _seed_composite_hints(self, sid: int) -> None:
        """Feed the tracker's composite coordinates into the helper so
        composite members resolve (object + base offset) without any
        per-map index fetch. Served locally by a snapshot-backed tracker
        (zero round-trips) or the in-process tracker; one extra RPC per
        scan on a bare remote tracker. Best effort: a failure only means
        resolution falls back to store-side discovery."""
        cfg = self.dispatcher.config
        if cfg.composite_commit_maps <= 1 and cfg.compact_below_bytes <= 0:
            # composite plane off in this deployment: skip the lookup so the
            # composite-off control-plane traffic stays exactly as before
            return
        locs = getattr(self.tracker, "composite_locations", None)
        if locs is None:
            return
        try:
            for map_id, group_id, base in locs(sid):
                self.helper.note_composite_location(sid, map_id, group_id, base)
        except Exception as e:
            logger.warning(
                "composite-location seed for shuffle %d failed: %s", sid, e
            )

    def compute_shuffle_blocks(self) -> List[ReadableBlockId]:
        """Parity: computeShuffleBlocks (S3ShuffleReader.scala:160-197)."""
        cfg = self.dispatcher.config
        sid = self.dep.shuffle_id
        if cfg.use_block_manager:
            if self.tracker is None:
                raise RuntimeError("use_block_manager=True requires a MapOutputTracker")
            self._seed_composite_hints(sid)
            # batch enumeration form: ONE control-plane round-trip for the
            # whole scan (and with a snapshot-backed tracker, zero) — never
            # one per partition
            entries = self.tracker.get_map_sizes_by_ranges(
                sid,
                self.start_map_index,
                self.end_map_index,
                [(self.start_partition, self.end_partition)],
            )[0]
            blocks: List[ReadableBlockId] = []
            for map_id, sizes in entries:
                if self.do_batch_fetch:
                    if any(n > 0 for _r, n in sizes):
                        blocks.append(
                            ShuffleBlockBatchId(sid, map_id, self.start_partition, self.end_partition)
                        )
                else:
                    blocks.extend(
                        ShuffleBlockId(sid, map_id, rid) for rid, n in sizes if n > 0
                    )
            return blocks
        # Listing mode: enumerate committed indices from the store
        # (S3ShuffleReader.scala:181-196), filtered by map range. One
        # listing pass yields both the per-map ``*.index`` sidecars and the
        # sealed composite groups; each group's fat index (ONE GET, cached)
        # enumerates its members and seeds the helper's composite hints so
        # range resolution never looks for per-map indexes that don't
        # exist. A map present in both layouts (post-hoc compaction before
        # the old objects' TTL expired) is deduped — composite hints win at
        # resolution either way.
        from s3shuffle_tpu.block_ids import ShuffleIndexBlockId

        singles, groups = self.dispatcher.list_committed_outputs(sid)
        by_map = {idx.map_id: idx for idx in singles}
        for group_id in groups:
            try:
                fat = self.helper.read_fat_index(sid, group_id)
            except (OSError, ValueError) as e:
                logger.warning(
                    "Skipping composite group %d of shuffle %d: unreadable "
                    "fat index (%s)", group_id, sid, e,
                )
                continue
            for m in fat.members.values():
                self.helper.note_composite_location(
                    sid, m.map_id, group_id, m.base_offset
                )
                by_map.setdefault(m.map_id, ShuffleIndexBlockId(sid, m.map_id))
        indices = [by_map[mid] for mid in sorted(by_map)]
        stride = cfg.map_id_attempt_stride
        if stride:
            # attempt-strided ids (distributed workers): the logical map
            # index is map_id // stride. Dedupe duplicate committed attempts
            # (attempt 1 committed but its lease was reaped → attempt 2 also
            # committed) keeping the latest attempt, and range-filter on the
            # LOGICAL index — the listing-mode counterpart of the tracker's
            # map_index filtering (same shared helper, so the two paths
            # cannot diverge on which attempt they serve).
            from s3shuffle_tpu.metadata.map_output import dedupe_latest_attempt

            deduped = dedupe_latest_attempt(
                indices,
                logical_of=lambda idx: idx.map_id // stride,
                map_id_of=lambda idx: idx.map_id,
            )
            indices = [idx for _lg, idx in deduped]
            logical = lambda idx: idx.map_id // stride  # noqa: E731
        else:
            logical = lambda idx: idx.map_id  # noqa: E731
        blocks = []
        for idx in indices:
            if logical(idx) < self.start_map_index:
                continue
            if self.end_map_index is not None and logical(idx) >= self.end_map_index:
                continue
            if self.do_batch_fetch:
                blocks.append(
                    ShuffleBlockBatchId(sid, idx.map_id, self.start_partition, self.end_partition)
                )
            else:
                blocks.extend(
                    ShuffleBlockId(sid, idx.map_id, rid)
                    for rid in range(self.start_partition, self.end_partition)
                )
        return blocks

    # ------------------------------------------------------------------
    def _count_block(self, _block, nbytes: int) -> None:
        """Remote-bytes/blocks metrics (:91-97), fed per non-empty block by
        whichever scan path runs."""
        self.metrics.remote_blocks_fetched += 1
        self.metrics.remote_bytes_read += nbytes

    def _make_prefetcher(self):
        """Build the scan's prefetching stream iterator.

        With ``coalesce_gap_bytes > 0`` the scan planner merges nearby block
        ranges into fewer, bigger GETs and bulk-prefetches the map indices
        (read/scan_plan.py — a deliberate divergence from the reference's
        one-GET-per-block reduce path); at 0 this is the reference-parity
        per-block pipeline. Either way a fresh per-scan index memo backs
        range resolution AND checksum-offset lookups, so no index object is
        fetched twice within one scan regardless of the cache knobs."""
        blocks = self.compute_shuffle_blocks()
        self._scan_memo = ScanIndexMemo(self.helper)

        from s3shuffle_tpu.read.chunked_fetch import ChunkedRangeFetcher
        from s3shuffle_tpu.read.scan_plan import (
            build_scan_iterator,
            tuned_scan_config,
        )

        # Autotuner consult BEFORE the fetcher is built, so the chunk size /
        # parallelism the fetcher carries match what the planner plans with
        # (tuner_consulted=True: build_scan_iterator does not consult again
        # — one consult per scan).
        cfg = tuned_scan_config(self.dispatcher, self.dispatcher.config)

        return build_scan_iterator(
            self.dispatcher,
            self._scan_memo,
            blocks,
            cfg,
            fetcher=ChunkedRangeFetcher.from_config(cfg),
            on_block=self._count_block,
            tuner_consulted=True,
        )

    def read(self) -> Iterator[Tuple[Any, Any]]:
        trace.count("read.tasks")
        if self.dep.serializer.supports_batches:
            if self.dep.aggregator is None:
                return self._read_batched()
            if self.dep.aggregator.supports_columnar:
                return self._read_columnar_agg()

        import itertools

        prefetcher = self._make_prefetcher()
        # chunk-level iteration + C-level flattening: 3 fewer Python frames
        # per record than per-record generators, with counting per chunk
        records = itertools.chain.from_iterable(self._chunk_iterator(prefetcher))

        if self.dep.aggregator is not None:
            if self.dep.map_side_combine:
                records = self.dep.aggregator.combine_combiners_by_key(
                    records, spill_bytes=self.dispatcher.config.aggregator_spill_bytes
                )
            else:
                records = self.dep.aggregator.combine_values_by_key(
                    records, spill_bytes=self.dispatcher.config.aggregator_spill_bytes
                )
        if self.dep.key_ordering is not None:
            sorter = ExternalSorter(
                key_func=self.dep.key_ordering,
                spill_bytes=self.dispatcher.config.sorter_spill_bytes,
            )
            sorter.insert_all(records)
            records = sorter.sorted_iterator()
        return records

    def _finish_read(self, prefetcher) -> None:
        """Drain hook: fold prefetcher stats into the task metrics and record
        the reduce-completion ShuffleStats entry (pushed through the tracker
        when it aggregates stats — the metadata-service analog of the
        reference's per-task printStatistics log)."""
        stats = prefetcher.stats
        self.metrics.wait_ns += stats["wait_ns"]
        self.metrics.prefetch_ns += stats["prefetch_ns"]
        from s3shuffle_tpu.metrics import registry as _metrics_registry

        if not _metrics_registry.enabled():
            return
        from s3shuffle_tpu.metrics.stats import COLLECTOR

        COLLECTOR.record_reduce(
            shuffle_id=self.dep.shuffle_id,
            partition=self.start_partition,
            bytes=self.metrics.remote_bytes_read,
            records=self.metrics.records_read,
            prefetch_seconds=stats["prefetch_ns"] / 1e9,
            wait_seconds=stats["wait_ns"] / 1e9,
            threads=stats["threads"],
        )

    def _wrapped_stream(self, prefetched, budget=None):
        """checksum validation + codec decompression over one block stream —
        the analog of ``serializerManager.wrapStream`` (:98-110). ``budget``
        (the scan's prefetcher) lets the codec stream's async decode window
        count its in-flight decoded bytes against ``max_buffer_size_task``."""
        cfg = self.dispatcher.config
        block = prefetched.block
        stream = prefetched
        # Skew-plane safety rail: a map output flagged as carrying map-side
        # combined PARTIAL rows changes the record multiset — it is only
        # meaningful through the aggregator that merges partials. Refuse a
        # raw read loudly instead of silently serving partial aggregates.
        # (Resolution is memoized per scan — the planner already did it.)
        location = self._scan_memo.resolve_map_location(
            block.shuffle_id, block.map_id
        )
        if location.combined and self.dep.aggregator is None:
            raise ValueError(
                f"map output {block.shuffle_id}/{block.map_id} carries "
                "map-side-combined partial rows (skew plane combine "
                "sidecar) but this read has no aggregator to merge them; "
                "read with the aggregating dependency that wrote the data"
            )
        if cfg.checksum_enabled:
            # per-scan memo: one index/checksum GET per map per scan even
            # with the process-wide caches off
            offsets = location.offsets
            checksums = self._scan_memo.get_checksums(block.shuffle_id, block.map_id)
            if isinstance(block, ShuffleBlockBatchId):
                start, end = block.start_reduce_id, block.end_reduce_id
            else:
                start, end = block.reduce_id, block.reduce_id + 1
            stream = ChecksumValidationStream(
                block, stream, offsets, checksums, start, end, cfg.checksum_algorithm
            )
        if self.codec is not None:
            stream = CodecInputStream(self.codec, stream, budget=budget)
        return stream

    def _chunk_iterator(self, prefetcher):
        """Record chunks (lists) from every prefetched block.

        ``records_read`` is counted at chunk granularity, and a chunk is
        charged only once fully consumed (the flattening consumer asks for
        chunk N+1 after draining chunk N) — an early-stopping caller never
        over-counts; at most the final, partially-consumed chunk goes
        uncounted."""
        from s3shuffle_tpu.serializer import count_fallback_rows

        pending = 0
        budget = getattr(prefetcher, "budget", None)
        for prefetched in prefetcher:
            stream = self._wrapped_stream(prefetched, budget=budget)
            try:
                for chunk in _deserialized(
                    self.dep.serializer.new_chunk_read_stream(stream)  # type: ignore[arg-type]
                ):
                    self.metrics.records_read += pending
                    count_fallback_rows("read", pending)
                    pending = len(chunk)
                    yield chunk
            finally:
                stream.close()
                prefetched.close()
        self.metrics.records_read += pending
        count_fallback_rows("read", pending)
        self._finish_read(prefetcher)

    # ------------------------------------------------------------------
    # Vectorized plane: columnar serializers stream RecordBatches; ordering
    # runs as np.lexsort over fixed-width key views (s3shuffle_tpu.batch)
    # instead of a per-record Python sort.
    # ------------------------------------------------------------------
    def read_batches(self):
        """Yield RecordBatches (no aggregation/ordering applied)."""
        from s3shuffle_tpu.serializer import count_plane_rows

        prefetcher = self._make_prefetcher()
        budget = getattr(prefetcher, "budget", None)
        for prefetched in prefetcher:
            stream = self._wrapped_stream(prefetched, budget=budget)
            try:
                for batch in _deserialized(self.dep.serializer.new_batch_read_stream(stream)):
                    self.metrics.records_read += batch.n
                    count_plane_rows("read", batch.n)
                    yield batch
            finally:
                stream.close()
                prefetched.close()
        self._finish_read(prefetcher)

    def _read_batched(self) -> Iterator[Tuple[Any, Any]]:
        from s3shuffle_tpu.batch import BatchSorter
        from s3shuffle_tpu.dependency import natural_key

        key_ordering = self.dep.key_ordering
        if key_ordering is None:
            for batch in self.read_batches():
                yield from batch.iter_records()
            return
        if key_ordering is natural_key:
            yield from self._fed_batch_sorter().sorted_records()
            return
        # custom key function: per-record external sort over batch records
        # (batch-wise insertion: byte accounting comes from the batch's own
        # nbytes instead of a per-record getsizeof walk)
        sorter = ExternalSorter(
            key_func=key_ordering,
            spill_bytes=self.dispatcher.config.sorter_spill_bytes,
        )
        for batch in self.read_batches():
            sorter.insert_batch(batch)
        yield from sorter.sorted_iterator()

    def _reduced_batches(self):
        """Columnar combine: stream read batches through the aggregator's
        ColumnarReducer (sort + reduceat group-by, bounded memory — see
        s3shuffle_tpu.colagg). Replaces the per-record dict combine the
        reference delegates to ExternalAppendOnlyMap
        (S3ShuffleReader.scala:124-138). Output batches arrive key-sorted."""
        reducer = self.dep.aggregator.new_reducer(
            spill_bytes=self.dispatcher.config.aggregator_spill_bytes
        )
        for batch in self.read_batches():
            reducer.add(batch)
        return reducer.results()

    def _read_columnar_agg(self) -> Iterator[Tuple[Any, Any]]:
        from s3shuffle_tpu.dependency import natural_key

        key_ordering = self.dep.key_ordering
        if key_ordering is None or key_ordering is natural_key:
            # reducer output is already in key-byte order — natural ordering
            # is free
            for batch in self._reduced_batches():
                yield from batch.iter_records()
            return
        sorter = ExternalSorter(
            key_func=key_ordering,
            spill_bytes=self.dispatcher.config.sorter_spill_bytes,
        )
        for batch in self._reduced_batches():
            sorter.insert_batch(batch)
        yield from sorter.sorted_iterator()

    def _fed_batch_sorter(self):
        """Build the natural-byte-order BatchSorter and feed it every read
        batch — shared by the records and batches terminal paths."""
        from s3shuffle_tpu.batch import BatchSorter

        sorter = BatchSorter(spill_bytes=self.dispatcher.config.sorter_spill_bytes)
        for batch in self.read_batches():
            with trace.span("agg.sort"):
                sorter.add(batch)
        return sorter

    def read_result_batches(self):
        """Fully-columnar terminal read: the reduce output as a list of
        RecordBatches (ordering applied when the dependency asks for natural
        byte ordering). The columnar sibling of :meth:`read` for callers that
        stay in batch land (bench, device repartition). The whole call is
        the ``task.reduce`` span."""
        with trace.span("task.reduce"):
            return self._read_result_batches()

    def _read_result_batches(self):
        from s3shuffle_tpu.batch import RecordBatch
        from s3shuffle_tpu.dependency import natural_key

        def fallback():
            records = list(self.read())
            for k, v in records[:1]:
                if not isinstance(k, (bytes, bytearray, memoryview)) or not isinstance(
                    v, (bytes, bytearray, memoryview)
                ):
                    raise ValueError(
                        "materialize='batches' requires byte keys/values "
                        f"(got {type(k).__name__}/{type(v).__name__}); use a "
                        "bytes serializer or materialize='records'"
                    )
            return [RecordBatch.from_records(records)]

        if not self.dep.serializer.supports_batches:
            return fallback()
        if self.dep.aggregator is not None:
            if self.dep.aggregator.supports_columnar and (
                self.dep.key_ordering is None or self.dep.key_ordering is natural_key
            ):
                return list(self._reduced_batches())
            return fallback()
        if self.dep.key_ordering is None:
            return list(self.read_batches())
        if self.dep.key_ordering is natural_key:
            sorter = self._fed_batch_sorter()
            with trace.span("agg.sort"):
                return list(sorter.sorted_batches())
        return fallback()


def _deserialized(stream):
    """The serializer's batches (or record chunks) of one block stream, each
    pull under ``record.deserialize``: the decode window's harvest, inside
    it, is its own span."""
    it = iter(stream)
    while True:
        with trace.span("record.deserialize"):
            item = next(it, None)
        if item is None:
            return
        yield item

