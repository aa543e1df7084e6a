#!/usr/bin/env python
"""Distributed terasort across worker agents — the multi-host demo.

One process drives (metadata service + task queue + input staging); workers
pull tasks from anywhere that reaches the coordinator address and the store:

    # coordinator (this script)
    python examples/multihost_terasort.py --serve 0.0.0.0:7777 --size 100m

    # on each worker host
    S3SHUFFLE_ROOT_DIR=gs://bucket/shuffle/ \
        python -m s3shuffle_tpu.worker --coordinator COORD_HOST:7777

``--local-workers N`` spawns N agent processes locally instead (the one-host
demo; same code path as real multi-host). Prints one JSON line with wall
times and validation results, like examples/terasort.py.
"""

import argparse
import dataclasses
import json
import multiprocessing as mp
import os
import random
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

KEY_BYTES, VALUE_BYTES = 10, 90


def _agent_main(coordinator, cfg_dict, worker_id):
    from s3shuffle_tpu.config import ShuffleConfig
    from s3shuffle_tpu.storage.dispatcher import Dispatcher
    from s3shuffle_tpu.worker import WorkerAgent

    Dispatcher.reset()
    WorkerAgent(
        tuple(coordinator), config=ShuffleConfig(**cfg_dict), worker_id=worker_id
    ).run_forever(poll_interval=0.02)


def local_workers_conflict(codec: str, local_workers: int) -> str | None:
    """Why ``--local-workers`` cannot run this config, or None. A TPU chip
    belongs to one process: with ``codec=tpu`` on a TPU host every local
    agent would try to open the same chip (and this process, by asking,
    already holds it). Run one agent per chip host instead."""
    if codec != "tpu" or local_workers <= 0:
        return None
    import jax

    if jax.default_backend() != "tpu":
        return None
    return (
        f"--local-workers {local_workers} with codec=tpu on a TPU host: the "
        "chip belongs to one process, so local agents cannot share it. Start "
        "one `python -m s3shuffle_tpu.worker` per chip host and pass "
        "--local-workers 0, or pick a host codec."
    )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--serve", default="127.0.0.1:0", help="coordinator bind HOST:PORT")
    ap.add_argument("--size", default="20m", help="total dataset size (e.g. 100m, 1g)")
    ap.add_argument("--maps", type=int, default=8)
    ap.add_argument("--partitions", type=int, default=8)
    ap.add_argument("--root", default=None, help="store root (default: temp dir)")
    ap.add_argument("--codec", default=None,
                    help="codec override (default: S3SHUFFLE_CODEC env, else "
                         "'auto' = native if built, zlib otherwise)")
    ap.add_argument("--local-workers", type=int, default=2,
                    help="spawn N local worker agents (one-host demo); pass 0 "
                         "to wait for external workers (multi-host mode)")
    args = ap.parse_args()

    from s3shuffle_tpu.batch import RecordBatch
    from s3shuffle_tpu.cluster import DistributedDriver
    from s3shuffle_tpu.config import ShuffleConfig
    from s3shuffle_tpu.storage.dispatcher import Dispatcher

    import tempfile

    # Config from S3SHUFFLE_* env first (how the k8s pods configure root and
    # codec — deploy/coordinator.yml), CLI flags override, temp dir as the
    # local-demo fallback. The coordinator and external workers MUST agree on
    # root_dir: all data moves through the store.
    overrides = {"app_id": "multihost-terasort"}
    if args.root:
        overrides["root_dir"] = args.root
    elif not os.environ.get("S3SHUFFLE_ROOT_DIR"):
        overrides["root_dir"] = f"file://{tempfile.mkdtemp(prefix='s3shuffle-multihost-')}"
    if args.codec:
        overrides["codec"] = args.codec
    host, port = args.serve.rsplit(":", 1)
    Dispatcher.reset()
    cfg = ShuffleConfig.from_env(**overrides)
    conflict = local_workers_conflict(cfg.codec, args.local_workers)
    if conflict:
        print(conflict, file=sys.stderr)
        return 2

    from s3shuffle_tpu.utils import parse_size

    n_records = max(args.maps, parse_size(args.size) // (KEY_BYTES + VALUE_BYTES))
    per_map = n_records // args.maps
    rng = random.Random(42)
    fillers = [rng.randbytes(VALUE_BYTES) for _ in range(64)]
    t0 = time.perf_counter()
    batches = [
        RecordBatch.from_records(
            [(rng.randbytes(KEY_BYTES), fillers[rng.randrange(64)]) for _ in range(per_map)]
        )
        for _ in range(args.maps)
    ]
    gen_s = time.perf_counter() - t0

    driver = DistributedDriver(cfg, host=host, port=int(port))
    print(f"coordinator at {driver.coordinator_address[0]}:{driver.coordinator_address[1]}",
          file=sys.stderr)

    workers = []
    if not args.local_workers:
        print("waiting for external workers (start them with: "
              f"python -m s3shuffle_tpu.worker --coordinator HOST:{driver.coordinator_address[1]})",
              file=sys.stderr)
    if args.local_workers:
        ctx = mp.get_context("spawn")
        workers = [
            ctx.Process(
                target=_agent_main,
                args=(list(driver.coordinator_address), dataclasses.asdict(cfg), f"local-{i}"),
                daemon=True,
            )
            for i in range(args.local_workers)
        ]
        for w in workers:
            w.start()

    try:
        t0 = time.perf_counter()
        out = driver.run_sort_shuffle(batches, num_partitions=args.partitions)
        shuffle_s = time.perf_counter() - t0

        total = sum(b.n for b in out)
        prev = None
        ordered = True
        for b in out:
            if b.n == 0:
                continue
            sk = b.key_strings(width=KEY_BYTES)
            ordered &= bool((sk[:-1] <= sk[1:]).all())
            if prev is not None:
                ordered &= bool(prev <= sk[0])
            prev = sk[-1]
        raw_bytes = total * (KEY_BYTES + VALUE_BYTES + 8)
        print(json.dumps({
            "workload": "multihost-terasort",
            "records": total,
            "valid": bool(total == args.maps * per_map and ordered),
            "maps": args.maps,
            "partitions": args.partitions,
            "workers": args.local_workers or "external",
            "gen_s": round(gen_s, 2),
            "shuffle_s": round(shuffle_s, 2),
            "mb_per_s": round(raw_bytes / shuffle_s / 1e6, 1),
        }))
        return 0
    finally:
        driver.shutdown()
        for w in workers:
            w.join(timeout=10)
            if w.is_alive():
                w.terminate()


if __name__ == "__main__":
    raise SystemExit(main())
