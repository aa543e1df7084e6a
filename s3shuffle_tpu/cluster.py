"""Multi-process shuffle execution — the scale-out slice.

Parity: the reference runs inside Spark executors — separate JVMs that share
nothing but the object store and the driver's RPC endpoint (SURVEY.md §3.2,
§3.3). :class:`LocalCluster` reproduces that topology on one host: a
coordinator process hosts the :class:`~s3shuffle_tpu.metadata.service.
MetadataServer`; map and reduce tasks run in **worker processes** (fresh
interpreters) that reach the coordinator over TCP and the data through the
store. Because a stage's worker pool is torn down before the next stage runs,
every run proves the executor-independence property the reference gets from
its FALLBACK_BLOCK_MANAGER_ID rebranding (S3ShuffleWriter.scala:7-21): map
workers are *dead* by the time reducers read — the shuffle survives because
data lives in the store and metadata on the coordinator.

On a multi-host TPU pod the same wiring applies: one MetadataServer on the
coordinator host (DCN-reachable), one worker process per host/chip, store =
GCS/S3. The task functions here are module-level so they pickle under the
``spawn`` start method.
"""

from __future__ import annotations

import dataclasses
import logging
import multiprocessing as mp
import pickle
from typing import Any, Iterable, List, Optional, Sequence, Tuple

from s3shuffle_tpu.config import ShuffleConfig
from s3shuffle_tpu.dependency import ShuffleDependency
from s3shuffle_tpu.metadata.service import MetadataServer, stage_id_for
from s3shuffle_tpu.utils import trace

logger = logging.getLogger("s3shuffle_tpu.cluster")


# Built once per worker process by the Pool initializer (one manager, one
# coordinator connection per worker — not per task).
_WORKER_MANAGER = None
# Lazily-built snapshot facade over the worker manager's tracker: reduce
# tasks that advertise a sealed shuffle's snapshot epoch serve their scan
# lookups locally (zero tracker round-trips); one instance per process so
# the snapshot is pulled once, not once per task.
_WORKER_META = None


def _init_worker(cfg_dict: dict, tracker_addr: Tuple[str, int]) -> None:
    global _WORKER_MANAGER, _WORKER_META
    from s3shuffle_tpu.manager import ShuffleManager
    from s3shuffle_tpu.metadata.async_client import AsyncTrackerClient
    from s3shuffle_tpu.storage.dispatcher import Dispatcher

    Dispatcher.reset()  # fresh process; never inherit another config
    cfg = ShuffleConfig(**cfg_dict)
    # batched/pipelined control-plane client: registrations buffer and ride
    # ONE RPC per commit; lookups fan over the coordinator's shard endpoints
    tracker = AsyncTrackerClient(tracker_addr, batch_max=cfg.metadata_batch_max)
    _WORKER_MANAGER = ShuffleManager(config=cfg, tracker=tracker)
    _WORKER_META = None


def _worker_meta():
    """The per-process snapshot-backed tracker facade (built on first use)."""
    global _WORKER_META
    if _WORKER_META is None:
        from s3shuffle_tpu.metadata.snapshot import SnapshotBackedTracker

        manager = _WORKER_MANAGER

        def load(shuffle_id: int, epoch: int):
            from s3shuffle_tpu.block_ids import ShuffleSnapshotBlockId

            path = manager.dispatcher.get_path(
                ShuffleSnapshotBlockId(shuffle_id, epoch)
            )
            try:
                return manager.dispatcher.backend.read_all(path)
            except (OSError, ValueError) as e:
                logger.warning("snapshot object %s unreadable: %s", path, e)
                return None

        _WORKER_META = SnapshotBackedTracker(manager.tracker, loader=load)
    return _WORKER_META


def _run_map_task(args: Tuple[int, bytes, int, bytes]) -> int:
    shuffle_id, dep_bytes, map_id, records_bytes = args
    manager = _WORKER_MANAGER
    assert manager is not None, "worker pool missing _init_worker initializer"
    dep: ShuffleDependency = pickle.loads(dep_bytes)
    handle = manager.register_shuffle(shuffle_id, dep)  # idempotent on tracker
    records = pickle.loads(records_bytes)
    writer = manager.get_writer(handle, map_id)
    try:
        writer.write(records)
        writer.stop(success=True)
    except BaseException:
        writer.stop(success=False)
        raise
    # commit barrier: pool workers are torn down right after the stage, so
    # any open composite group must seal BEFORE this task reports done
    # (registration is group-granular; a pool worker holding an unsealed
    # group across its own exit would lose the members silently) — then the
    # buffered MapStatus registrations must be durable on the coordinator
    # (one RPC for the whole commit — a flush failure fails the task,
    # which then retries)
    if manager.composite is not None:
        manager.composite.flush_shuffle(shuffle_id)
    manager.tracker.flush()
    return map_id


def _run_reduce_task(args: Tuple[int, bytes, int, object]) -> bytes:
    shuffle_id, dep_bytes, reduce_id, snap_epoch = args
    manager = _WORKER_MANAGER
    assert manager is not None, "worker pool missing _init_worker initializer"
    dep: ShuffleDependency = pickle.loads(dep_bytes)
    tracker = None
    if snap_epoch is not None:
        meta = _worker_meta()
        if meta.ensure(shuffle_id, int(snap_epoch)):
            tracker = meta
    handle = manager.register_shuffle(shuffle_id, dep)
    reader = manager.get_reader(handle, reduce_id, reduce_id + 1, tracker=tracker)
    with trace.span("task.reduce"):
        records = list(reader.read())
    return pickle.dumps(records, protocol=pickle.HIGHEST_PROTOCOL)


def publish_snapshot(tracker, config: ShuffleConfig, shuffle_id: int):
    """Freeze the (coordinator-side) tracker's map-output table for one
    SEALED shuffle and publish it as a store object — the epoch-stamped
    snapshot workers pull once instead of asking the tracker per scan.
    Returns the stamped epoch (the value to advertise in reduce task
    descriptors), or None when snapshots are disabled or publication failed
    (workers then stay on the live-RPC path — strictly the old behavior)."""
    if not config.metadata_snapshots:
        return None
    from s3shuffle_tpu.block_ids import ShuffleSnapshotBlockId
    from s3shuffle_tpu.metadata.snapshot import build_snapshot
    from s3shuffle_tpu.storage.dispatcher import Dispatcher

    try:
        snap = build_snapshot(tracker, shuffle_id)
        dispatcher = Dispatcher.get(config)
        path = dispatcher.get_path(ShuffleSnapshotBlockId(shuffle_id, snap.epoch))
        with dispatcher.backend.create(path) as sink:
            sink.write(snap.to_bytes())
        logger.info(
            "published map-output snapshot for shuffle %d at epoch %d "
            "(%d entries)",
            shuffle_id, snap.epoch, len(snap.entries),
        )
        return snap.epoch
    except Exception:
        logger.warning(
            "snapshot publication for shuffle %d failed; reduce scans fall "
            "back to live tracker RPCs", shuffle_id, exc_info=True,
        )
        return None


class LocalCluster:
    """Coordinator + per-stage worker process pools.

    The coordinator owns the metadata service and the store lifecycle
    (cleanup); workers are stage-scoped and disposable — the decommission
    story is structural, not a recovery protocol (SURVEY.md §5.3).
    """

    def __init__(self, config: ShuffleConfig, num_workers: int = 2):
        self.config = config
        self.num_workers = max(1, num_workers)
        self.server = MetadataServer(
            shards=config.metadata_shards,
            shard_endpoints=config.metadata_shard_endpoints,
        ).start()
        self._cfg_dict = dataclasses.asdict(config)
        self._ctx = mp.get_context("spawn")
        self._next_shuffle_id = 0

    # ------------------------------------------------------------------
    def run_shuffle(
        self,
        input_partitions: Sequence[Iterable[Tuple[Any, Any]]],
        dependency_factory,
    ) -> List[List[Tuple[Any, Any]]]:
        """Run one full shuffle with stage-scoped worker pools.

        ``dependency_factory(shuffle_id)`` must return a picklable
        ShuffleDependency (module-level key functions, no lambdas).
        """
        shuffle_id = self._next_shuffle_id
        self._next_shuffle_id += 1
        dep = dependency_factory(shuffle_id)
        dep_bytes = pickle.dumps(dep, protocol=pickle.HIGHEST_PROTOCOL)
        addr = self.server.address
        # coordinator registers first so reducers never race an empty tracker
        self.server.tracker.register_shuffle(shuffle_id, dep.num_partitions)

        map_args = [
            (shuffle_id, dep_bytes, map_id,
             pickle.dumps(list(records), protocol=pickle.HIGHEST_PROTOCOL))
            for map_id, records in enumerate(input_partitions)
        ]
        init = (_init_worker, (self._cfg_dict, addr))
        with self._ctx.Pool(self.num_workers, *init) as pool:
            done = pool.map(_run_map_task, map_args)
        logger.info("map stage done: %d tasks (workers now dead)", len(done))

        # the map stage is the epoch barrier: publish the sealed shuffle's
        # map-output snapshot through the storage plane so reduce workers
        # serve their scan lookups locally (zero tracker round-trips)
        snap_epoch = publish_snapshot(self.server.tracker, self.config, shuffle_id)

        # map-stage workers are gone; a fresh pool serves the reduce stage —
        # the read path may only depend on the store + metadata service.
        reduce_args = [
            (shuffle_id, dep_bytes, rid, snap_epoch)
            for rid in range(dep.num_partitions)
        ]
        with self._ctx.Pool(self.num_workers, *init) as pool:
            blobs = pool.map(_run_reduce_task, reduce_args)
        return [pickle.loads(b) for b in blobs]

    # ------------------------------------------------------------------
    def cleanup_shuffle(self, shuffle_id: int) -> None:
        from s3shuffle_tpu.storage.dispatcher import Dispatcher

        self.server.tracker.unregister_shuffle(shuffle_id)
        Dispatcher.get(self.config).remove_shuffle(shuffle_id)

    def shutdown(self, remove_root: bool = True) -> None:
        from s3shuffle_tpu.storage.dispatcher import Dispatcher

        self.server.stop()
        if remove_root and self.config.cleanup:
            Dispatcher.get(self.config).remove_root()


class DistributedDriver:
    """Driver for :class:`~s3shuffle_tpu.worker.WorkerAgent` fleets.

    The multi-host topology: this driver hosts the metadata service + task
    queue; worker agents — on this host or any other host that can reach the
    coordinator address and the store — pull tasks and execute. Record data
    moves exclusively through the store (driver stages input objects; the
    reduce stage writes output objects); the control plane carries only JSON
    descriptors.
    """

    def __init__(self, config: ShuffleConfig, host: str = "127.0.0.1", port: int = 0):
        from s3shuffle_tpu.storage.dispatcher import Dispatcher

        self.config = config
        self.server = MetadataServer(
            host=host, port=port,
            shards=config.metadata_shards,
            shard_endpoints=config.metadata_shard_endpoints,
        ).start()
        self.dispatcher = Dispatcher.get(config)
        from s3shuffle_tpu.metadata.helper import ShuffleHelper

        self.helper = ShuffleHelper(self.dispatcher)
        # the driver's flight ring records job phases too; worker_id tags
        # its postmortem dumps apart from the agents'
        trace.configure_flight(
            dir=config.flight_dir,
            ring=config.flight_ring_events,
            worker_id="driver",
        )
        self._next_shuffle_id = 0
        # the worker-silence lease is an operator knob now (worker_lease_s);
        # the attribute stays assignable for tests/tools that tighten it
        self.task_lease_s = float(config.worker_lease_s)
        # per-shuffle recovery state: staged inputs + dependency descriptor
        # (to recompute a lost map), recovery round counter (attempt-unique
        # recompute ids), and a per-map attempt budget (loss loops bound)
        self._job_state: dict = {}
        self._recovering = False

    @property
    def coordinator_address(self) -> Tuple[str, int]:
        return self.server.address

    # ------------------------------------------------------------------
    def _scratch(self, shuffle_id: int, name: str) -> str:
        return f"{self.config.root_dir}_stage/{self.config.app_id}/{shuffle_id}/{name}"

    #: worker-silence lease: the fleet reap re-queues tasks whose worker
    #: sent no heartbeat for this long (crash/kill detection — WorkerAgent
    #: beats every ~5s, so a LONG task on a healthy worker is never reaped).
    #: Re-execution is idempotent (task outputs are store objects keyed by
    #: task identity, index-is-commit), and stale zombie reports are refused
    #: by the lease-holder check in the task queue. The instance value comes
    #: from ``ShuffleConfig.worker_lease_s``; this class default keeps older
    #: callers working.
    task_lease_s = 30.0

    def _reap_fleet(self) -> None:
        """One fleet-reap beat: expire silent task leases across EVERY live
        stage (not just the one being waited on — the old per-stage reap
        missed a worker dying while holding another stage's task), then
        expire silent fleet MEMBERSHIPS and run the per-death handling
        (cross-stage requeue + lost-output recovery) for each newly dead
        worker. Runs during stage waits AND between stages."""
        self.server.task_queue.reap_expired_all(self.task_lease_s)
        for worker_id in self.server.membership.expire_silent(self.task_lease_s):
            self._on_worker_lost(worker_id)

    def _wait_stage(self, stage_id: str, poll: float = 0.02, on_failed=None) -> dict:
        import time

        last_reap = time.monotonic()
        while True:
            status = self.server.task_queue.stage_status(stage_id)
            if status["failed"]:
                # ``on_failed`` (the recovery hook) may consume failures by
                # re-queueing the tasks; anything it cannot handle is fatal
                if on_failed is None or not on_failed(dict(status["failed"])):
                    raise RuntimeError(
                        f"stage {stage_id} failed: {status['failed']}"
                    )
                continue
            if not status["pending"] and not status["running"]:
                return status["done"]
            now = time.monotonic()
            if now - last_reap > min(5.0, self.task_lease_s / 4):
                last_reap = now
                self._reap_fleet()
            time.sleep(poll)

    # -- elastic fleet -------------------------------------------------
    def drain_workers(self, worker_ids=None) -> List[str]:
        """Request a graceful drain of ``worker_ids`` (default: every live
        worker): each stops taking tasks at its next poll, seals open
        composite groups, flushes deferred reports and stats, and
        deregisters. Returns the ids actually flagged."""
        membership = self.server.membership
        targets = (
            list(worker_ids) if worker_ids is not None
            else membership.live_workers()
        )
        return [w for w in targets if membership.request_drain(w)]

    def _on_worker_lost(self, worker_id: str) -> None:
        """Per-death handling, run exactly once per membership expiry:
        requeue the dead worker's in-flight tasks across every stage (its
        uncommitted attempts are invalidated by the lease-holder commit
        fence), then probe its COMMITTED map outputs for objects that died
        with it and plan recompute-vs-reconstruct recovery."""
        requeued = self.server.task_queue.requeue_lost_all(worker_id)
        if requeued:
            logger.warning(
                "worker %s expired; requeued %d in-flight task(s)",
                worker_id, requeued,
            )
        by_shuffle: dict = {}
        for stage_id, task_id in self.server.task_queue.tasks_done_by(worker_id):
            if not stage_id.startswith("shuffle") or "-map" not in stage_id:
                continue
            try:
                sid = int(stage_id[len("shuffle"):].split("-", 1)[0])
            except ValueError:
                continue
            by_shuffle.setdefault(sid, set()).add(int(task_id))
        for sid, map_indices in by_shuffle.items():
            self._recover_shuffle_losses(sid, map_indices=map_indices)

    def _recover_shuffle_losses(self, shuffle_id: int, map_indices=None) -> bool:
        """Probe for lost committed map outputs and recover them. Maps the
        planner routes to "reconstruct" need no driver action (reduce
        scans heal through the coded plane's degraded reads); "recompute"
        maps re-run from their staged inputs in a recovery stage, with
        attempt-unique ids ABOVE every prior attempt so the tracker's
        latest-attempt dedupe picks the fresh output. Returns True iff
        any loss was found (and recovery was planned)."""
        state = self._job_state.get(shuffle_id)
        if state is None or self._recovering:
            return False
        from s3shuffle_tpu.metadata.service import TaskQueue
        from s3shuffle_tpu.recovery import RecoveryPlanner, probe_lost_maps

        try:
            losses = probe_lost_maps(
                self.dispatcher, self.server.tracker, shuffle_id,
                map_indices=map_indices,
            )
        except KeyError:
            return False  # shuffle already unregistered
        if not losses:
            return False
        planner = RecoveryPlanner(stripe_k=self.config.parity_stripe_k)
        try:
            stats = self.server.tracker.get_shuffle_stats(shuffle_id)
        except Exception as e:
            # evidence is optional — the planner has a structural default
            logger.debug("no shuffle stats for recovery costing: %s", e)
            stats = None
        budget = state["recovery_attempts"]
        recompute = []
        for lost in losses:
            if budget.get(lost.map_index, 0) >= TaskQueue.MAX_ATTEMPTS:
                continue  # out of budget: the reduce failure will surface it
            if planner.decide(lost, stats) == "recompute":
                budget[lost.map_index] = budget.get(lost.map_index, 0) + 1
                recompute.append(lost)
        if not recompute:
            return True
        state["recovery_round"] += 1
        rec_round = state["recovery_round"]
        rec_stage = stage_id_for(shuffle_id, f"maprec{rec_round}")
        logger.warning(
            "recomputing %d lost map output(s) of shuffle %d (round %d): %s",
            len(recompute), shuffle_id, rec_round,
            [lost.map_index for lost in recompute],
        )
        self.server.task_queue.submit_stage(
            rec_stage,
            [
                {
                    "task_id": lost.map_index, "kind": "map",
                    "shuffle_id": shuffle_id, "map_id": lost.map_index,
                    "dep": state["desc"],
                    "input_path": state["input_paths"][lost.map_index],
                    # recompute attempts must outrank every original attempt
                    # AND every prior recompute of THIS map (latest-attempt
                    # dedupe keys on map_id). The base scales with the
                    # per-map recovery count — bounded by MAX_ATTEMPTS^2 —
                    # never the shared round counter, whose growth on large
                    # jobs could push map_id past ATTEMPT_STRIDE into the
                    # next logical map's id space.
                    "_attempt_base": (
                        TaskQueue.MAX_ATTEMPTS * budget[lost.map_index]
                    ),
                }
                for lost in recompute
            ],
        )
        self._recovering = True
        try:
            self._wait_stage(rec_stage)
        finally:
            self._recovering = False
            self.server.task_queue.drop_stage(rec_stage)
        # re-seal the shuffle at the new epoch so fresh scans see the
        # recomputed attempts without a tracker round-trip; already-running
        # reduce attempts fall back to the live tracker on their retry
        publish_snapshot(self.server.tracker, self.config, shuffle_id)
        return True

    def _handle_reduce_failures(
        self, shuffle_id: int, reduce_stage: str, failed: dict
    ) -> bool:
        """Recovery hook for the reduce wait: failures carrying the
        MapOutputLost marker re-probe the shuffle, plan recovery, and
        re-queue the reduce task (bounded by the shared attempt budget).
        Any other failure, an exhausted budget, or a probe that finds NO
        loss stays fatal — retrying a task that just proved its inputs
        unreadable, without anything having been recovered, would burn the
        whole attempt budget on identical failures."""
        from s3shuffle_tpu.recovery import MAP_OUTPUT_LOST_MARKER

        if not all(MAP_OUTPUT_LOST_MARKER in str(e) for e in failed.values()):
            return False
        recovered = self._recover_shuffle_losses(shuffle_id)
        state = self._job_state.get(shuffle_id)
        if not recovered and not (state and state["recovery_round"] > 0):
            # nothing is lost and nothing was ever recovered: the retry
            # would re-fail identically — stay fatal. (A clean probe AFTER
            # a recovery round is the benign race — the task failed while
            # the recompute was landing — and retries.)
            return False
        return all(
            self.server.task_queue.retry_failed(
                reduce_stage, task_id, reason="map_output_lost"
            )
            for task_id in failed
        )

    def run_sort_shuffle(self, input_batches, num_partitions: int, serializer=None):
        """Distributed range-partitioned sort (the terasort shape): stages
        input to the store, runs map+reduce stages on whatever workers are
        connected, returns the sorted output RecordBatches. ``serializer``
        overrides the wire serializer (default: the columnar plane) — it
        must have a registry name (serializer.get_serializer) so workers can
        reconstruct it from the JSON task descriptor; the record-plane bench
        uses this to drive the scalar path through identical machinery."""
        from s3shuffle_tpu.batch import RecordBatch
        from s3shuffle_tpu.dependency import RangePartitioner, natural_key, range_bounds
        from s3shuffle_tpu.serializer import ColumnarKVSerializer
        from s3shuffle_tpu.worker import dep_to_descriptor, read_input_batches, write_input_object

        shuffle_id = self._next_shuffle_id
        self._next_shuffle_id += 1

        # the job root span: every driver phase below is its DIRECT child,
        # so the critical-path analyzer's coverage check (phase durations
        # vs job wall) holds by construction
        with trace.span(
            "driver.job", shuffle_id=shuffle_id, partitions=num_partitions
        ):
            with trace.span("driver.stage_inputs", shuffle_id=shuffle_id):
                # range bounds from a columnar sample
                sample: List[bytes] = []
                for b in input_batches:
                    ko = b.koffsets
                    step = max(1, b.n // 64)
                    sample.extend(
                        b.keys[ko[i] : ko[i + 1]].tobytes()
                        for i in range(0, b.n, step)
                    )
                dep = ShuffleDependency(
                    shuffle_id=shuffle_id,
                    partitioner=RangePartitioner(range_bounds(sample, num_partitions)),
                    serializer=serializer if serializer is not None else ColumnarKVSerializer(),
                    key_ordering=natural_key,
                )
                desc = dep_to_descriptor(dep)
                self.server.tracker.register_shuffle(shuffle_id, dep.num_partitions)

                # stage inputs to the store
                input_paths = []
                for map_id, batch in enumerate(input_batches):
                    path = self._scratch(shuffle_id, f"input_{map_id}")
                    write_input_object(self.dispatcher.backend, path, batch)
                    input_paths.append(path)

            # recovery state: everything a recompute of any one map needs,
            # kept for the job's lifetime (inputs stay staged in the store)
            self._job_state[shuffle_id] = {
                "desc": desc, "input_paths": list(input_paths),
                "recovery_round": 0, "recovery_attempts": {},
            }
            map_stage = stage_id_for(shuffle_id, "map")
            reduce_stage = stage_id_for(shuffle_id, "reduce")
            try:
                return self._run_sort_stages(
                    shuffle_id, dep, desc, input_paths, map_stage, reduce_stage
                )
            finally:
                # teardown on EVERY exit: a failed job's stages must not stay
                # in the queue — the fleet-level reap iterates ALL stages, so a
                # leaked stage's tasks would be requeued and re-executed during
                # later jobs, and its _job_state could spawn recovery stages
                # for a shuffle nobody is waiting on
                self.server.task_queue.drop_stage(map_stage)
                self.server.task_queue.drop_stage(reduce_stage)
                self._job_state.pop(shuffle_id, None)

    def _run_sort_stages(
        self, shuffle_id, dep, desc, input_paths, map_stage, reduce_stage
    ):
        from s3shuffle_tpu.batch import RecordBatch
        from s3shuffle_tpu.worker import read_input_batches

        with trace.span("driver.map_stage", shuffle_id=shuffle_id):
            # the map tasks' causal parent is THIS stage span: workers adopt
            # the descriptor's context, so their spans land in the driver's
            # tree across the process boundary
            ctx = trace.current_context()
            self.server.task_queue.submit_stage(
                map_stage,
                [
                    {"task_id": m, "kind": "map", "shuffle_id": shuffle_id,
                     "map_id": m, "dep": desc, "input_path": p,
                     **({"trace": ctx} if ctx else {})}
                    for m, p in enumerate(input_paths)
                ],
            )
            self._wait_stage(map_stage)
        # between-stage fleet beat: a worker dying right after its last map
        # poll is detected HERE (membership expiry + cross-stage requeue +
        # lost-output recovery), not first deep into the reduce wait
        self._reap_fleet()
        # Orphan sweep (VERDICT r4 ask #7): a map worker that died mid-write
        # never registered, so its attempt-unique objects are invisible to
        # the tracker but still occupy the store; reclaim them as soon as
        # the winner set is final instead of waiting for unregister_shuffle.
        try:
            self.dispatcher.sweep_orphan_attempts(
                shuffle_id, self.server.tracker.registered_map_ids(shuffle_id)
            )
        except Exception:
            logger.warning("orphan sweep failed for shuffle %d", shuffle_id,
                           exc_info=True)

        # small-map compaction (write/compactor.py): rewrite tiny singleton
        # outputs into composites between the barriers, BEFORE the snapshot
        # publishes, so reduce scans resolve the compacted layout and the
        # superseded objects ride their generation tombstones to the TTL
        # sweep. Best-effort: the old layout stays fully live on failure.
        if self.config.compact_below_bytes > 0:
            from s3shuffle_tpu.write.compactor import compact_shuffle

            with trace.span("driver.compact", shuffle_id=shuffle_id):
                try:
                    compact_shuffle(
                        self.dispatcher, self.helper, shuffle_id,
                        tracker=self.server.tracker,
                    )
                except Exception:
                    logger.warning("compaction failed for shuffle %d", shuffle_id,
                                   exc_info=True)

        # the map stage is this shuffle's epoch barrier: seal it with a
        # store-published snapshot and advertise (epoch) to reduce tasks so
        # their scans run with zero tracker round-trips
        with trace.span("driver.publish_snapshot", shuffle_id=shuffle_id):
            snap_epoch = publish_snapshot(
                self.server.tracker, self.config, shuffle_id
            )

        out_paths = [self._scratch(shuffle_id, f"output_{r}") for r in range(dep.num_partitions)]
        with trace.span("driver.reduce_stage", shuffle_id=shuffle_id):
            ctx = trace.current_context()
            self.server.task_queue.submit_stage(
                reduce_stage,
                [
                    {"task_id": r, "kind": "reduce", "shuffle_id": shuffle_id,
                     "reduce_id": r, "dep": desc, "output_path": p,
                     **({"snapshot": {"epoch": snap_epoch}} if snap_epoch is not None else {}),
                     **({"trace": ctx} if ctx else {})}
                    for r, p in enumerate(out_paths)
                ],
            )
            done = self._wait_stage(
                reduce_stage,
                on_failed=lambda failed: self._handle_reduce_failures(
                    shuffle_id, reduce_stage, failed
                ),
            )

        with trace.span("driver.collect", shuffle_id=shuffle_id):
            out = []
            for r, base in enumerate(out_paths):
                # the COMMITTED attempt's result names the actual (attempt-
                # suffixed) object — a zombie attempt's object is never read
                result = done.get(r) or done.get(str(r)) or {}
                path = result.get("path", base)
                batches = read_input_batches(self.dispatcher.backend, path)
                out.append(batches[0] if batches else RecordBatch.empty())
        return out

    # -- distributed trace & fleet telemetry ---------------------------
    def dump_trace(self, path: Optional[str] = None) -> Optional[str]:
        """Assemble ONE merged Chrome-trace file: the driver's own spans
        plus every span shard the workers shipped to the coordinator's
        trace store, with cross-process flow events on the causal edges.
        ``path`` defaults to the path ``trace.enable`` was given. Returns
        the path written, or None when tracing is off or there is nowhere
        to write."""
        if not trace.enabled():
            return None
        target = path or trace.trace_path()
        if target is None:
            return None
        try:
            worker_spans = self.server.trace_store.drain()
        except Exception:
            logger.warning("worker trace-shard drain failed", exc_info=True)
            worker_spans = []
        doc = trace.assemble(
            [trace.drain_spans(), worker_spans], counters=trace.counters()
        )
        return trace.write_trace_doc(target, doc)

    def fleet_view(self) -> dict:
        """Coordinator-merged fleet telemetry: per-worker snapshot ages and
        hot-object GET peaks, the merged metrics registry view (this
        process's own snapshot folded in, so driver-side staging I/O is
        priced too), and the ``$/shuffle`` cost digest from the configured
        rate card."""
        from s3shuffle_tpu.costs import cost_digest, parse_rate_card
        from s3shuffle_tpu.metadata.service import merge_registry_snapshots
        from s3shuffle_tpu.metrics import registry as metrics_registry

        view = self.server.fleet.view()
        if metrics_registry.enabled():
            view["metrics"] = merge_registry_snapshots(
                [view["metrics"], metrics_registry.REGISTRY.snapshot(compact=True)]
            )
        view["cost"] = cost_digest(
            view["metrics"],
            parse_rate_card(self.config.cost_rate_card),
            shuffles=max(1, self._next_shuffle_id),
        )
        return view

    def dump_fleet(self, path: str) -> str:
        """Write the fleet view as the JSON doc ``trace_report --fleet``
        renders (atomic write), mirroring the cost digest into
        ``cost_dollars_total`` on the way out."""
        from s3shuffle_tpu.costs import record_cost_metrics

        view = self.fleet_view()
        record_cost_metrics(view["cost"])
        doc = {
            "fleet_workers": view["workers"],
            "object_gets_peaks": view["object_gets_peaks"],
            "metrics": view["metrics"],
            "cost": view["cost"],
        }
        return trace.write_trace_doc(path, doc)

    # ------------------------------------------------------------------
    def shutdown(self, remove_root: bool = True) -> None:
        self.server.task_queue.stop_workers()
        self.server.stop()
        if remove_root and self.config.cleanup:
            self.dispatcher.remove_root()
            self.dispatcher.backend.delete_prefix(f"{self.config.root_dir}_stage")
