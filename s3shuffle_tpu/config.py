"""Configuration for the shuffle framework.

Parity: the reference centralizes every ``spark.shuffle.s3.*`` flag in the
dispatcher constructor (helper/S3ShuffleDispatcher.scala:36-70), logs every
value at startup (:81-102), and documents them in README.md:31-85. Defaults
here match the reference's defaults exactly (SURVEY.md §5.6 flag table).

TPU-first additions: ``codec`` / ``codec_block_size`` / ``codec_batch_blocks``
/ ``encode_inflight_batches`` select and tune the block codec (none / zlib /
zstd / native C++ / TPU), which replaces the JVM codec streams
(``spark.io.compression.*``) the reference delegates to Spark.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Any, Mapping

logger = logging.getLogger("s3shuffle_tpu.config")

MiB = 1024 * 1024

#: Self-describing benchmark codec labels → (ShuffleConfig.codec,
#: tpu_host_fallback). Shared by the terasort and SQL harnesses so their
#: artifacts label identical modes identically: "tpu-hostpath" pins the
#: no-chip host TLZ encode path (fallback disabled — the documented ~5x
#: encode penalty, not a bug); "tpu" is the deployment default (loud-warning
#: SLZ fallback without a chip, device path with one).
CODEC_LABEL_MODES = {
    "tpu-hostpath": ("tpu", False),
    "tpu": ("tpu", True),
}

# Mapping from reference flag names (README.md:31-85) to our field names, kept
# so configs written for the reference translate one-for-one.
_REFERENCE_KEYS = {
    "spark.shuffle.s3.rootDir": "root_dir",
    "spark.shuffle.s3.bufferSize": "buffer_size",
    "spark.shuffle.s3.maxBufferSizeTask": "max_buffer_size_task",
    "spark.shuffle.s3.maxConcurrencyTask": "max_concurrency_task",
    "spark.shuffle.s3.cachePartitionLengths": "cache_partition_lengths",
    "spark.shuffle.s3.cacheChecksums": "cache_checksums",
    "spark.shuffle.s3.cleanup": "cleanup",
    "spark.shuffle.s3.folderPrefixes": "folder_prefixes",
    "spark.shuffle.s3.alwaysCreateIndex": "always_create_index",
    "spark.shuffle.s3.useBlockManager": "use_block_manager",
    "spark.shuffle.s3.forceBatchFetch": "force_batch_fetch",
    "spark.shuffle.s3.useSparkShuffleFetch": "use_fallback_fetch",
    "spark.shuffle.checksum.enabled": "checksum_enabled",
    "spark.shuffle.checksum.algorithm": "checksum_algorithm",
    # legacy name from before the device-codec-pipeline rework (PR 8): the
    # knob is codec-scoped, not TPU-scoped — configs written against the old
    # name keep working through from_dict
    "tpu_batch_blocks": "codec_batch_blocks",
}


@dataclasses.dataclass
class ShuffleConfig:
    """All knobs, parsed once, every value logged (see :meth:`log_values`)."""

    # --- storage layout (S3ShuffleDispatcher.scala:39-70) ---
    root_dir: str = "file:///tmp/s3shuffle_tpu"
    folder_prefixes: int = 10
    # --- write plane ---
    buffer_size: int = 8 * MiB
    always_create_index: bool = False
    # --- read plane ---
    max_buffer_size_task: int = 128 * MiB
    max_concurrency_task: int = 10
    # --- transfer plane (TPU-first addition; the reference delegates ranged-
    # GET readahead and multipart upload tuning to Hadoop S3A config,
    # reference README.md:146-178) ---
    # prefills larger than this split into concurrent positioned sub-reads on
    # the shared fetch executor (the S3A readahead / multipart-download analog)
    fetch_chunk_size: int = 8 * MiB
    # process-wide ranged-GET executor width; <= 1 disables chunked fetch
    fetch_parallelism: int = 4
    # bytes allowed in flight between commit serialization and the background
    # uploader thread (the S3A fast-upload buffer analog); 0 disables the
    # pipelined upload path (serial drain -> PUT)
    upload_queue_bytes: int = 32 * MiB
    # --- reduce-side scan planner (TPU-first addition; the reference issues
    # one ranged GET per sub-block, S3ShuffleBlockStream) ---
    # merge reduce-side block ranges on the same data object when the byte gap
    # between them is <= this; the gap bytes are fetched and discarded
    # (metered as read_coalesce_waste_bytes_total). 0 disables the planner
    # entirely and preserves the per-block request pattern exactly.
    coalesce_gap_bytes: int = 1 * MiB
    # ceiling on one merged segment; also clamped to max_buffer_size_task so
    # a merged segment always fits the prefetch budget in one prefill
    coalesce_max_bytes: int = 64 * MiB
    # --- composite commit plane (TPU-first addition; the reference always
    # writes one data + one index (+ checksum) object PER MAP TASK, so PUT
    # count scales with maps — BlobShuffle's request-count argument applied
    # to the write side) ---
    # map outputs composed into ONE composite data object + ONE fat index
    # before the group seals; 0 or 1 disables the plane entirely and
    # reproduces the one-object-per-map layout op-for-op
    composite_commit_maps: int = 0
    # seal the open composite group when its data bytes reach this
    composite_flush_bytes: int = 64 * MiB
    # seal groups older than this on the next aggregator touch (commit /
    # barrier / worker idle poll); 0 disables age-based sealing
    composite_flush_ms: float = 250.0
    # a composite-mode map commit spools its payload in memory up to this
    # many bytes, then overflows to a local temp file
    composite_spool_bytes: int = 8 * MiB
    # background compactor: committed singleton data objects smaller than
    # this are rewritten into composites post-hoc (old objects generation-
    # stamped, tracker re-pointed); 0 disables compaction
    compact_below_bytes: int = 0
    # generation sweep: tombstoned (superseded) objects are deleted once
    # their generation stamp is older than this many seconds
    tombstone_ttl_s: float = 300.0
    # --- coded shuffle plane (TPU-first addition; the reference tolerates
    # only transient storage faults — a lost or slow object stalls the scan.
    # Coded TeraSort / Coded MapReduce, PAPERS.md) ---
    # parity sidecar objects (m) emitted per data object; 0 disables the
    # plane entirely and reproduces the uncoded store request pattern
    # op-for-op (the coalesce_gap_bytes=0 contract). Full-object loss is
    # recoverable when parity_segments >= parity_stripe_k; smaller m still
    # covers partial-range loss/corruption and straggler speculation.
    parity_segments: int = 0
    # data chunks (k) per stripe group: parity overhead is m/k of the
    # payload; k=1 degenerates to mirrored replicas (cheapest full-loss
    # recovery), larger k trades recovery envelope for overhead
    parity_stripe_k: int = 1
    # stripe chunk granularity — also the unit of degraded-read GETs
    parity_chunk_bytes: int = 1 * MiB
    # straggler speculation: when a segment GET outlives this quantile of
    # the live read_prefetch_fill_seconds histogram, race it against a
    # parity reconstruction and take whichever finishes first. 0 disables
    # speculation (loss reconstruction stays active regardless).
    speculative_read_quantile: float = 0.99
    # --- skew mitigation plane (TPU-first addition; the reference has no
    # hot-key story — a fat partition serializes on one ranged GET and hot
    # aggregations ship every raw row. Coded TeraSort/MapReduce, PAPERS.md) ---
    # map-side combine sidecar: partitions whose routed bytes cross this
    # threshold get their chunks pre-reduced with the columnar combine
    # INSIDE the map task (aggregating deps with a columnar aggregator and
    # reduce-side combine only), so hot partitions ship partial aggregates;
    # the output is flagged in the index sidecar. 0 disables the prong
    # entirely and keeps the shipped rows byte-identical to the pre-skew
    # wire (the coalesce_gap_bytes=0 contract).
    combine_threshold_bytes: int = 0
    # hot-partition splitting: a partition whose committed size crosses this
    # threshold has a stripe granularity (= the threshold) recorded in its
    # index sidecar / fat-index v3 header; the scan planner then fans the
    # partition out as independent sub-range GETs across the prefetch pool.
    # 0 disables the prong (no trailer, unsplit reads, op-for-op).
    split_threshold_bytes: int = 0
    # coded read fan-out: when a data object's LIVE in-process GET
    # concurrency reaches this count, further eligible reads of it
    # reconstruct from parity-equivalent sources (different objects) instead
    # of queueing on the hot one — the degraded-read plane as load
    # balancing. Needs parity coverage (parity_segments >= stripe real-chunk
    # count) to ever engage. 0 disables the prong.
    hot_read_fanout: int = 0
    # --- columnar record plane (TPU-first addition; the reference moves
    # records through per-record JVM serializer streams — SURVEY.md §3.2) ---
    # 1 = columnar serializers emit the self-describing COLUMN-FRAME wire
    # (colframe.py: per-column dtype/width table, fixed-width columns ship no
    # per-row lengths, one-pass zero-copy reduce-side deserialize). 0 = emit
    # the legacy frame wire, op-for-op byte-identical to the pre-format-5
    # data objects (the coalesce_gap_bytes=0 contract). Readers auto-detect
    # per frame, so this only steers the write side.
    columnar: int = 1
    # rows per columnar chunk on the map write path (partition/route/frame
    # granularity); joins CommitTuner's ladder when autotune is on. Inert at
    # columnar=0: the legacy plane keeps its fixed pre-format-5 chunking so
    # the byte-identity contract holds at ANY knob value.
    columnar_batch_rows: int = 65536
    # in-memory budget for key-ordered reduce output before the batch sorter
    # spills sorted columnar runs (analog of Spark's ExternalSorter memory)
    sorter_spill_bytes: int = 256 * MiB
    # in-memory budget for reduce-side combine before the aggregator spills
    # hash-sorted runs (analog of Spark's ExternalAppendOnlyMap memory)
    aggregator_spill_bytes: int = 256 * MiB
    use_block_manager: bool = True
    force_batch_fetch: bool = False
    # attempt-unique map-id convention (0 = map_ids ARE logical indices, the
    # local-mode default). Distributed workers set this to their
    # ATTEMPT_STRIDE so LISTING-mode enumeration can recover the logical map
    # index (map_id // stride) for range filtering and dedupe committed
    # duplicate attempts — the tracker path carries map_index explicitly.
    map_id_attempt_stride: int = 0
    # --- resilient storage plane (the S3A ``fs.s3a.retry.*`` analog; the
    # reference delegates transient-failure handling to the Hadoop client) ---
    # re-drives per store op after the first attempt; 0 disables the retry
    # layer entirely (fail-fast, today's behavior)
    storage_retries: int = 3
    # exponential-backoff base; actual sleep is full-jitter
    # uniform(0, min(cap, base * 2**attempt))
    storage_retry_base_ms: float = 50.0
    # wall-clock budget per op including backoff sleeps; 0 = unbounded
    storage_op_deadline_s: float = 30.0
    # --- control plane (TPU-first addition; the reference delegates to the
    # Spark driver's MapOutputTracker RPC + broadcast) ---
    # tracker shard count on the coordinator: the shuffle/map keyspace is
    # hashed across this many independent lock domains, so concurrent
    # registrations/lookups stop serializing on one lock. 1 = flat tracker.
    metadata_shards: int = 4
    # EXTRA coordinator listener sockets (each its own accept loop) that
    # batched clients spread connections across; 0 = primary socket only
    metadata_shard_endpoints: int = 0
    # registrations buffered client-side before an automatic batch flush
    # (flushes also happen at every commit barrier and before any read)
    metadata_batch_max: int = 64
    # publish an epoch-stamped map-output snapshot through the storage plane
    # when a map stage completes; workers pull it once and serve reduce-scan
    # lookups locally (zero tracker round-trips). false = every lookup is a
    # live RPC (the pre-snapshot behavior).
    metadata_snapshots: bool = True
    # --- elastic fleet (TPU-first addition; the reference's decommission /
    # fallback-storage mode covers planned executor removal only — this is
    # the membership/lease layer that also survives UNPLANNED preemption) ---
    # worker-silence lease: a worker that sent no heartbeat/poll for this
    # long is declared expired — its membership drops, its in-flight tasks
    # requeue across EVERY live stage, and its uncommitted attempts are
    # invalidated (the lease-holder commit fence refuses them). The
    # WorkerAgent heartbeats every ~5 s, so keep this comfortably larger
    # than the heartbeat interval.
    worker_lease_s: float = 30.0
    # SIGTERM triggers a graceful drain (stop taking tasks, seal open
    # composite groups, flush parity + deferred reports, push stats,
    # deregister) instead of the default die-mid-task behavior — the
    # spot/preemption notice path. false = legacy SIGTERM (process death,
    # lease reaping recovers).
    drain_on_sigterm: bool = True
    # --- online autotuner (TPU-first addition; the reference's only adaptive
    # element is the prefetch thread-count hill climb) ---
    # master switch for the closed-loop knob controllers (tuning/): a
    # read-side ScanTuner (fetch_chunk_size / fetch_parallelism /
    # coalesce_gap_bytes / max_buffer_size_task) and a write-side CommitTuner
    # (upload_queue_bytes / composite seal thresholds /
    # encode_inflight_batches) read the live metrics registry and retune the
    # knobs online within per-knob clamps. Off (the default) reproduces the
    # static configuration's store request pattern op-for-op, the same
    # contract as coalesce_gap_bytes=0 for the scan planner. Knobs whose
    # static value disables a plane stay disabled either way.
    autotune: bool = False
    # controller cooldown: each knob moves at most once per this interval
    # (cost samples keep accumulating between moves)
    autotune_interval_s: float = 0.25
    # persisted warm-start profile: when set (and autotune is on), tuner rung
    # tables load from this JSON sidecar at dispatcher construction and are
    # dumped back at manager stop, so a process restart resumes from the
    # learned landscape instead of re-paying the exploration burn-in. ""
    # (the default) disables persistence entirely.
    autotune_profile_path: str = ""
    # --- caches ---
    cache_partition_lengths: bool = True
    cache_checksums: bool = True
    # --- lifecycle ---
    cleanup: bool = True
    # --- fallback-fetch mode (S3ShuffleDispatcher.scala:39-47, §3.4) ---
    use_fallback_fetch: bool = False
    # --- checksums (Spark-native flags consumed at :69-70) ---
    checksum_enabled: bool = True
    checksum_algorithm: str = "ADLER32"  # ADLER32 | CRC32 | CRC32C
    # --- codec (TPU-first addition; reference delegates to Spark codec streams) ---
    codec: str = "auto"  # none | zlib | zstd | native | lz4 | tpu | auto
    # None → each codec's own default (64 KiB for the CPU codecs' cache-sized
    # blocks; 256 KiB for the TPU codec, whose ratio improves with block
    # length while its match window stays a separate 64 KiB distance cap)
    codec_block_size: int | None = None
    codec_level: int = 1
    # blocks staged per device round-trip: 64 x the 256 KiB default block
    # keeps one staging batch at 16 MiB. (Formerly ``tpu_batch_blocks``;
    # the old key is still accepted by from_dict.)
    codec_batch_blocks: int = 64
    # encode batches allowed in flight between the serializer and the store
    # sink (CodecOutputStream async batch mode): the serializer fills batch
    # N+1 and the pipelined-upload sink PUTs batch N-1 while the chip
    # encodes batch N. <= 1 keeps every batch synchronous on the producer
    # thread (today's behavior); the window only engages when the codec
    # itself runs the TLZ encoder (device or host C), never on the SLZ
    # host-fallback delegate.
    encode_inflight_batches: int = 2
    # read-side mirror of codec_batch_blocks: frames the codec input stream
    # reads ahead and decodes per batch (one native/device call instead of
    # one per frame). <= 1 reproduces the per-frame decode path op-for-op;
    # joins ScanTuner's ladder when autotune is on (live instance attribute,
    # so retunes apply mid-stream).
    decode_batch_frames: int = 32
    # decode batches allowed in flight between the source and the consumer
    # (CodecInputStream async batch mode): the consumer deserializes chunk N
    # and pulls the next coalesced-segment GET's bytes while the shared
    # decode thread works on chunk N+1. In-flight decoded bytes reserve
    # against max_buffer_size_task (non-blocking: a full budget shrinks the
    # window). <= 1 keeps every decode synchronous on the consumer thread
    # (the pre-pipeline behavior).
    decode_inflight_batches: int = 2
    # codec=tpu with no accelerator attached: reroute shuffle-write encode to
    # SLZ frames (loud warning) instead of the ~5x-slower host C TLZ encoder;
    # TLZ decode stays active for existing data. false = always encode TLZ.
    tpu_host_fallback: bool = True
    # seconds a device-failure host pin lasts before the codec re-probes the
    # device with ONE trial batch (a transient device fault usually clears;
    # the old permanent pin parked long-running workers on the host
    # forever). 0 = the legacy permanent pin.
    codec_repin_probe_s: float = 300.0
    # --- mesh plane (TPU-first addition; the reference's only data plane is
    # the object store) --- local devices the multi-chip execution plane may
    # schedule across: the codec batch executors and the GF parity kernel
    # spread fixed-shape launches over this many chips
    # (parallel/dispatch.py, least-outstanding-work placement), and
    # mesh-routed shuffles build their ICI mesh this wide. 0 or 1 keeps
    # today's single-device behavior op-for-op (the coalesce_gap_bytes=0
    # contract); widths beyond the attached device count clamp.
    mesh_devices: int = 0
    # --- observability / trace plane (TPU-first addition; the reference's
    # quantitative story is the external jvm-profiler → InfluxDB → Grafana
    # stack, examples/README.md:54-101) ---
    # flight recorder: records retained in the always-on bounded in-memory
    # ring (task milestones always; completed spans too when tracing is on).
    # 0 disables recording entirely (the overhead-probe baseline).
    flight_ring_events: int = 512
    # directory for postmortem flight-recorder dumps (written atomically on
    # graceful drain, task failure, protocol-witness violation, SIGTERM, and
    # atexit-after-error). "" keeps the ring recording but never writes a
    # dump — clean runs leave zero residual files either way.
    flight_dir: str = ""
    # storage rate card feeding trace_report's $/shuffle cost digest:
    # "class=rate,..." in dollars per op (get / put / list / delete) and per
    # GiB moved (gb_read / gb_written); "" uses the built-in
    # S3-standard-like card (s3shuffle_tpu/costs.py).
    cost_rate_card: str = ""
    # --- misc ---
    app_id: str = "app"
    supports_rename: bool | None = None  # None → probe backend
    # Driver options passed to the object-store client (fsspec storage
    # options: credentials, endpoint_url, multipart sizing ...). The analog
    # of the reference delegating S3A tuning to Hadoop FS config
    # (README.md:146-178). NEVER logged or repr'd (may hold secrets).
    storage_options: dict = dataclasses.field(default_factory=dict, repr=False)

    def __post_init__(self) -> None:
        if self.folder_prefixes < 1:
            raise ValueError("folder_prefixes must be >= 1")
        if self.fetch_chunk_size < 1:
            raise ValueError("fetch_chunk_size must be >= 1")
        if self.fetch_parallelism < 0 or self.upload_queue_bytes < 0:
            raise ValueError("fetch_parallelism / upload_queue_bytes must be >= 0")
        if self.coalesce_gap_bytes < 0:
            raise ValueError("coalesce_gap_bytes must be >= 0")
        if self.coalesce_max_bytes < 1:
            raise ValueError("coalesce_max_bytes must be >= 1")
        if self.composite_commit_maps < 0 or self.compact_below_bytes < 0:
            raise ValueError(
                "composite_commit_maps / compact_below_bytes must be >= 0"
            )
        if self.composite_flush_bytes < 1 or self.composite_spool_bytes < 1:
            raise ValueError(
                "composite_flush_bytes / composite_spool_bytes must be >= 1"
            )
        if self.composite_flush_ms < 0 or self.tombstone_ttl_s < 0:
            raise ValueError("composite_flush_ms / tombstone_ttl_s must be >= 0")
        if (
            self.storage_retries < 0
            or self.storage_retry_base_ms < 0
            or self.storage_op_deadline_s < 0
        ):
            raise ValueError("storage retry knobs must be >= 0")
        if self.parity_segments < 0 or self.parity_stripe_k < 1:
            raise ValueError("parity_segments must be >= 0, parity_stripe_k >= 1")
        if self.parity_segments + self.parity_stripe_k > 255:
            # GF(256) erasure coding addresses at most 255 segments total
            raise ValueError("parity_segments + parity_stripe_k must be <= 255")
        if self.parity_chunk_bytes < 1:
            raise ValueError("parity_chunk_bytes must be >= 1")
        if not (0.0 <= self.speculative_read_quantile < 1.0):
            raise ValueError("speculative_read_quantile must be in [0, 1)")
        if (
            self.combine_threshold_bytes < 0
            or self.split_threshold_bytes < 0
            or self.hot_read_fanout < 0
        ):
            raise ValueError(
                "combine_threshold_bytes / split_threshold_bytes / "
                "hot_read_fanout must be >= 0"
            )
        if self.codec_batch_blocks < 1:
            raise ValueError("codec_batch_blocks must be >= 1")
        if self.encode_inflight_batches < 0:
            raise ValueError("encode_inflight_batches must be >= 0")
        if self.decode_batch_frames < 1:
            raise ValueError("decode_batch_frames must be >= 1")
        if self.decode_inflight_batches < 0:
            raise ValueError("decode_inflight_batches must be >= 0")
        if self.codec_repin_probe_s < 0:
            raise ValueError("codec_repin_probe_s must be >= 0")
        if self.mesh_devices < 0:
            raise ValueError("mesh_devices must be >= 0")
        if self.autotune_interval_s < 0:
            raise ValueError("autotune_interval_s must be >= 0")
        if self.columnar not in (0, 1):
            raise ValueError("columnar must be 0 or 1")
        if self.columnar_batch_rows < 1:
            raise ValueError("columnar_batch_rows must be >= 1")
        if self.metadata_shards < 1 or self.metadata_batch_max < 1:
            raise ValueError("metadata_shards / metadata_batch_max must be >= 1")
        if self.worker_lease_s <= 0:
            raise ValueError("worker_lease_s must be > 0")
        if self.metadata_shard_endpoints < 0:
            raise ValueError("metadata_shard_endpoints must be >= 0")
        if self.flight_ring_events < 0:
            raise ValueError("flight_ring_events must be >= 0")
        # parse-validate the rate card now — a typo'd card must fail at
        # config construction, not at the first cost digest after the run
        from s3shuffle_tpu.costs import parse_rate_card

        parse_rate_card(self.cost_rate_card)
        algo = self.checksum_algorithm.upper()
        if algo not in ("ADLER32", "CRC32", "CRC32C"):
            # Parity: reference supports ADLER32 & CRC32 only and raises
            # otherwise (S3ShuffleHelper.scala:94-103); CRC32C is our extension.
            raise ValueError(f"Unsupported checksum algorithm: {self.checksum_algorithm}")
        self.checksum_algorithm = algo
        if not self.root_dir.endswith("/"):
            self.root_dir += "/"

    # ------------------------------------------------------------------
    @classmethod
    def from_dict(cls, d: Mapping[str, Any], **overrides: Any) -> "ShuffleConfig":
        """Build from a dict accepting both our field names and the reference's
        ``spark.shuffle.s3.*`` key names."""
        kwargs: dict[str, Any] = {}
        fields = {f.name: f for f in dataclasses.fields(cls)}
        for key, value in d.items():
            name = _REFERENCE_KEYS.get(key, key)
            if name not in fields:
                raise KeyError(f"Unknown shuffle config key: {key}")
            kwargs[name] = _coerce(value, fields[name].type)
        kwargs.update(overrides)
        return cls(**kwargs)

    @classmethod
    def from_env(cls, env: Mapping[str, str] | None = None, **overrides: Any) -> "ShuffleConfig":
        """Build from ``S3SHUFFLE_<FIELD>`` environment variables. Renamed
        knobs keep their old env spelling working (``S3SHUFFLE_<OLDNAME>``,
        from the non-reference aliases in ``_REFERENCE_KEYS``); the new name
        wins when both are set."""
        env = os.environ if env is None else env
        fields = {f.name: f for f in dataclasses.fields(cls)}
        kwargs: dict[str, Any] = {}
        for old, new in _REFERENCE_KEYS.items():
            if "." in old:  # spark.* reference keys aren't env-shaped
                continue
            key = "S3SHUFFLE_" + old.upper()
            if key in env:
                kwargs[new] = _coerce(env[key], fields[new].type)
        for f in fields.values():
            key = "S3SHUFFLE_" + f.name.upper()
            if key in env:
                kwargs[f.name] = _coerce(env[key], f.type)
        kwargs.update(overrides)
        return cls(**kwargs)

    # ------------------------------------------------------------------
    def log_values(self) -> None:
        """Log every config value, like the reference dispatcher does at init
        (helper/S3ShuffleDispatcher.scala:81-102) — the only way to know what a
        run actually did."""
        for f in dataclasses.fields(self):
            if f.name == "storage_options":
                # keys only — values may hold credentials
                logger.info(
                    "config: storage_options keys=%r", sorted(self.storage_options)
                )
                continue
            logger.info("config: %s=%r", f.name, getattr(self, f.name))

    @property
    def scheme(self) -> str:
        return self.root_dir.split("://", 1)[0] if "://" in self.root_dir else "file"


def _coerce(value: Any, typ: Any) -> Any:
    if not isinstance(value, str):
        return value
    typ = str(typ)
    if "None" in typ and value.strip().lower() in ("", "none", "null"):
        # optional fields (codec_block_size: int|None, supports_rename:
        # bool|None="probe backend") accept their None default from strings
        return None
    if "bool" in typ:
        return value.strip().lower() in ("1", "true", "yes", "on")
    if "float" in typ:
        return float(value)
    if "int" in typ:
        from s3shuffle_tpu.utils import parse_size

        return parse_size(value)
    if "dict" in typ:
        import json as _json

        parsed = _json.loads(value)
        if not isinstance(parsed, dict):
            raise ValueError(f"expected a JSON object, got {type(parsed).__name__}")
        return parsed
    return value
