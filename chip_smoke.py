#!/usr/bin/env python
"""Chip smoke: the ``codec=tpu`` terasort shuffle, end to end, on one TPU.

Runs in ONE process (threads only): a chip belongs to one process, so no
phase may hand device work to a child. It never falls back to the CPU: with
no TPU attached it exits non-zero before printing any result.

- Build: ``make -B -C s3shuffle_tpu/native`` from the committed Makefile and
  source, then the codec registry must load the fresh library.
- Phase A (the main path): terasort records (10 B keys, 90 B values) from
  ``--seed`` through ``ShuffleContext.sort_by_key`` exactly as
  ``examples/terasort.py`` calls it, with ``codec=tpu``, no host fallback,
  CRC32C checksums and a ``file://`` root; teravalidate; then the same data
  through ``codec=native`` as the plain reference. Each output partition's
  multiset of records must match. ``S3SHUFFLE_CODEC_RATE_GATE=device`` keeps
  every codec decision on the chip, so a host selection, a device failure or
  a host pin fails the run.
- Phase B: each device kernel once at the codec's real shape (64 x 256 KiB),
  byte for byte against its host oracle (TLZ: the numpy encoder, which
  makes the device graph's match decisions; CRC: ``crc32c_py`` and native
  crc32c; GF parity: the numpy table encoder).
- ``--chips 4`` runs ONLY the mesh phase: ``ShuffleContext.mesh_shuffle``
  at ``S3SHUFFLE_MESH_DEVICES=4`` (rows routed between chips by
  ``all_to_all``) against the same call at 1, through ``codec=native``.

Lines before the last are observations of one run, not benchmark numbers.
The last line is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import hashlib
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
BLOCK = 256 * 1024  # TpuCodec default block_size
ROWS = 64  # TpuCodec default batch_blocks
KEY_BYTES, VALUE_BYTES = 10, 90
SIZE = 1 << 30  # terasort bytes: "1g", the reference's smallest published size
MAPS, REDUCERS, WORKERS = 8, 8, 4


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def observe(name: str, value) -> None:
    """One labelled observation line (not a metric: one run, no spread)."""
    print(f"observation {name}: {json.dumps(value, sort_keys=True)}", flush=True)


def _terasort_module():
    spec = importlib.util.spec_from_file_location(
        "terasort_example", os.path.join(REPO, "examples", "terasort.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build_native() -> None:
    native_dir = os.path.join(REPO, "s3shuffle_tpu", "native")
    subprocess.run(["make", "-B", "-C", native_dir], check=True,
                   stdout=subprocess.DEVNULL)
    from s3shuffle_tpu.codec.native import native_available

    check(native_available(), "codec.native cannot load the built library")


def _series(snap: dict, name: str) -> list:
    return snap.get(name, {}).get("series", [])


def _counter_total(snap: dict, name: str, **labels) -> float:
    return sum(
        s["value"] for s in _series(snap, name)
        if all(s.get("labels", {}).get(k) == v for k, v in labels.items())
    )


def partition_digests(out_batches) -> list:
    """Per output partition: (record count, sha256 of its records sorted as
    fixed-width rows) — equal digests mean equal multisets."""
    import numpy as np

    from s3shuffle_tpu.batch import RecordBatch

    digests = []
    for part in out_batches:
        b = RecordBatch.concat(part) if isinstance(part, list) else part
        if b.n == 0:
            digests.append((0, ""))
            continue
        check(bool((b.klens == KEY_BYTES).all() and (b.vlens == VALUE_BYTES).all()),
              "output records lost their terasort widths")
        rows = np.concatenate(
            [b.keys.reshape(b.n, KEY_BYTES), b.values.reshape(b.n, VALUE_BYTES)],
            axis=1,
        )
        rows = np.sort(np.ascontiguousarray(rows).view(f"V{KEY_BYTES + VALUE_BYTES}")
                       .reshape(-1))
        digests.append((int(b.n), hashlib.sha256(rows.tobytes()).hexdigest()))
    return digests


def run_sort(parts, codec: str, host_fallback: bool):
    from s3shuffle_tpu.config import ShuffleConfig
    from s3shuffle_tpu.serializer import ColumnarKVSerializer
    from s3shuffle_tpu.shuffle import ShuffleContext
    from s3shuffle_tpu.storage.dispatcher import Dispatcher

    tmp = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        Dispatcher.reset()
        cfg = ShuffleConfig(
            root_dir=f"file://{tmp}",
            app_id=f"chip-smoke-{codec}",
            codec=codec,
            tpu_host_fallback=host_fallback,
            checksum_enabled=True,
            checksum_algorithm="CRC32C",
        )
        ctx = ShuffleContext(config=cfg, num_workers=WORKERS)
        try:
            t0 = time.perf_counter()
            out = ctx.sort_by_key(
                parts,
                num_partitions=REDUCERS,
                serializer=ColumnarKVSerializer(),
                materialize="batches",
            )
            wall = time.perf_counter() - t0
        finally:
            ctx.stop()
        return out, wall
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def phase_a(parts) -> None:
    from s3shuffle_tpu.metrics import registry

    terasort = _terasort_module()
    n_records = sum(p.n for p in parts)
    mb = n_records * (KEY_BYTES + VALUE_BYTES) / 1e6

    registry.REGISTRY.reset_values()
    out, wall = run_sort(parts, "tpu", False)
    snap = registry.REGISTRY.snapshot()
    terasort.teravalidate(out, n_records)
    tpu_digests = partition_digests(out)
    del out
    observe("phase_a_tpu_wall_s", {"wall_s": wall, "mb": mb, "records": n_records})

    selected = {
        f'{s["labels"]["path"]}/{s["labels"]["reason"]}': s["value"]
        for s in _series(snap, "codec_path_selected_total")
    }
    batches = {
        f'{s["labels"]["op"]}/{s["labels"]["outcome"]}': s["value"]
        for s in _series(snap, "codec_device_batches_total")
    }
    pins = {s["labels"]["op"]: s["value"]
            for s in _series(snap, "codec_host_pins_total")}
    compile_s = {
        s["labels"]["kernel"]: {"count": s["count"], "sum_s": s["sum"]}
        for s in _series(snap, "codec_kernel_compile_seconds")
    }
    fused_write = _counter_total(snap, "codec_fused_crc_total")
    fused_read = _counter_total(snap, "codec_fused_crc_validated_total")
    observe("codec_path_selected_total", selected)
    observe("codec_device_batches_total", batches)
    observe("codec_host_pins_total", pins)
    observe("codec_fused_crc_frames", {"write": fused_write, "read": fused_read})
    observe("codec_kernel_compile_seconds", compile_s)
    # batch-call latencies (device launch + host work around it), summed
    # over the run's encode/decode calls: the split of Phase A's wall time
    for name in ("codec_encode_batch_seconds", "codec_assembly_seconds",
                 "codec_decode_batch_seconds"):
        observe(name, {"count": sum(s["count"] for s in _series(snap, name)),
                       "sum_s": sum(s["sum"] for s in _series(snap, name))})
    observe("codec_bytes_total", {
        "encode": _counter_total(snap, "codec_encode_bytes_total"),
        "decode": _counter_total(snap, "codec_decode_bytes_total"),
    })

    host_sel = {k: v for k, v in selected.items()
                if k.split("/")[0] in ("host", "streaming") and v}
    check(not host_sel, f"host selections under the forced gate: {host_sel}")
    failed = {k: v for k, v in batches.items() if k.endswith("/failed") and v}
    check(not failed, f"device batch failures: {failed}")
    check(not any(pins.values()), f"device-failure host pins: {pins}")
    check(batches.get("encode/ok", 0) > 0, "no device encode batch ran")
    check(batches.get("decode/ok", 0) > 0, "no device decode batch ran")
    check(fused_write > 0, "no frame took its CRC from the encode launch")
    check(fused_read > 0, "no frame took its CRC from the decode launch")

    ref, ref_wall = run_sort(parts, "native", True)
    terasort.teravalidate(ref, n_records)
    ref_digests = partition_digests(ref)
    del ref
    observe("phase_a_native_reference_wall_s", {"wall_s": ref_wall, "mb": mb})
    check(tpu_digests == ref_digests,
          "codec=tpu output partitions differ from the codec=native reference")
    observe("phase_a_partitions_match_reference", len(tpu_digests))


def _phase_b_blocks(parts):
    """64 full blocks of the serialized terasort payload, the last four
    replaced by random bytes so the raw-escape branch runs too."""
    import io

    import numpy as np

    from s3shuffle_tpu.batch import write_frame

    buf = io.BytesIO()
    for p in parts:
        write_frame(buf, p)
        if buf.tell() >= ROWS * BLOCK:
            break
    payload = buf.getvalue()
    check(len(payload) >= ROWS * BLOCK, "phase B needs 16 MiB of payload")
    blocks = np.frombuffer(payload[: ROWS * BLOCK], np.uint8).reshape(ROWS, BLOCK).copy()
    blocks[-4:] = np.random.default_rng(7).integers(0, 256, (4, BLOCK), dtype=np.uint8)
    return blocks


def phase_b(parts) -> None:
    import jax
    import numpy as np

    from s3shuffle_tpu.coding import gf
    from s3shuffle_tpu.ops import tlz
    from s3shuffle_tpu.ops.checksum import POLY_CRC32C, crc32_batch
    from s3shuffle_tpu.utils.checksums import _crc32c_fn, crc32c_py

    crc = _crc32c_fn()
    blocks = _phase_b_blocks(parts)
    raw = [blocks[i].tobytes() for i in range(ROWS)]
    # the numpy encoder makes the device graph's match decisions; the host C
    # encoder shares the wire format but not the decisions (ROADMAP D4)
    host = [tlz._assemble_payload_numpy(r) for r in raw]
    results = {}

    payloads, _ = tlz.encode_batch_device(blocks.tobytes(), ROWS, BLOCK,
                                          batch_blocks=ROWS)
    check([bytes(p) for p in payloads] == host,
          "tlz encode differs from the host encoder")
    results["tlz_encode"] = "identical"
    # decode only what the codec would: TLZ frames (raw escapes skip it)
    tlz_rows = [i for i, h in enumerate(host) if len(h) < BLOCK]
    check(len(tlz_rows) >= ROWS // 2, "too few compressible phase-B blocks")
    enc = [host[i] for i in tlz_rows]
    dec, _ = tlz.decode_batch_device(enc, [BLOCK] * len(enc), BLOCK,
                                     batch_rows=ROWS)
    check([bytes(d) for d in dec] == [raw[i] for i in tlz_rows],
          "device decode differs from the input")
    results["tlz_decode"] = "identical"

    payloads, (block_crcs, lit_crcs, lit_lens) = tlz.encode_batch_device(
        blocks.tobytes(), ROWS, BLOCK, batch_blocks=ROWS, poly=POLY_CRC32C
    )
    check([bytes(p) for p in payloads] == host,
          "fused tlz encode differs from the host encoder")
    check([int(c) for c in block_crcs] == [crc(r) for r in raw],
          "fused encode block CRCs wrong")
    check([int(c) for c in lit_crcs] == [
        crc(bytes(p)[len(p) - int(n):]) for p, n in zip(payloads, lit_lens)
    ], "fused encode literal CRCs wrong")
    results["tlz_encode_fused"] = "identical"

    dec, crcs = tlz.decode_batch_device(enc, [BLOCK] * len(enc), BLOCK,
                                        batch_rows=ROWS, poly=POLY_CRC32C)
    check([bytes(d) for d in dec] == [raw[i] for i in tlz_rows],
          "fused decode differs from the input")
    check(all(c is not None and int(c) == crc(e) for c, e in zip(crcs, enc)),
          "fused decode CRCs wrong")
    results["tlz_decode_fused"] = "identical"

    got = crc32_batch(blocks, [BLOCK] * ROWS, POLY_CRC32C)
    check([int(c) for c in got] == [crc32c_py(r) for r in raw],
          "crc32_batch differs from crc32c_py")
    results["crc32c"] = "identical"

    chunks = blocks.reshape(4, 4, ROWS * BLOCK // 16)  # G=4, k=4, 1 MiB lanes
    coefs = gf.parity_coefficients(2, 4)
    got = gf._device_kernel(2, 4)(jax.device_put(chunks), jax.device_put(coefs))
    check(np.array_equal(np.asarray(got), gf._encode_host(chunks, coefs)),
          "gf table-kernel parity differs from the numpy table encoder")
    results["gf_parity"] = "identical"
    observe("phase_b_kernels", results)


def phase_mesh(parts, n_chips: int) -> int:
    import jax

    from s3shuffle_tpu.config import ShuffleConfig
    from s3shuffle_tpu.shuffle import ShuffleContext
    from s3shuffle_tpu.storage.dispatcher import Dispatcher

    devices = jax.local_devices()[:n_chips]
    check(len({d.id for d in devices}) == n_chips,
          f"need {n_chips} distinct local devices, have {len(jax.local_devices())}")
    results = {}
    for width in (n_chips, 1):
        os.environ["S3SHUFFLE_MESH_DEVICES"] = str(width)
        tmp = tempfile.mkdtemp(prefix="chip-smoke-mesh-")
        try:
            Dispatcher.reset()
            # the mesh phase measures the ICI route, which exists only across
            # chips; the device codec is the one-chip phases' subject, and
            # its Phase A takes ~450 s on one chip (my chip run, PR 21): 4x
            # chips for minutes of single-chip work
            cfg = ShuffleConfig(
                root_dir=f"file://{tmp}", app_id=f"chip-smoke-mesh-{width}",
                codec="native", checksum_enabled=True,
                checksum_algorithm="CRC32C",
            )
            with ShuffleContext(config=cfg, num_workers=WORKERS) as ctx:
                t0 = time.perf_counter()
                out, used_mesh = ctx.mesh_shuffle(parts, REDUCERS)
                wall = time.perf_counter() - t0
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        results[width] = ([sorted(p) for p in out], used_mesh)
        del out
        observe(f"mesh_devices_{width}", {"wall_s": wall, "used_mesh": used_mesh})
    check(results[n_chips][1], f"mesh_devices={n_chips} did not use the mesh")
    check(not results[1][1], "mesh_devices=1 used the mesh")
    check(results[n_chips][0] == results[1][0],
          f"mesh_devices={n_chips} output differs from mesh_devices=1")
    observe("mesh_outputs_identical", {
        "partitions": len(results[1][0]),
        "records": sum(len(p) for p in results[1][0]),
        "devices": [str(d) for d in devices],
    })
    return n_chips


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh phase over four chips")
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args(argv)

    # before any package import: every codec decision goes to the chip
    os.environ["S3SHUFFLE_CODEC_RATE_GATE"] = "device"
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (platform {devices[0].platform!r})",
              file=sys.stderr)
        return 1
    sys.path.insert(0, REPO)
    from s3shuffle_tpu.metrics import registry
    from s3shuffle_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    registry.enable()
    try:
        build_native()
        observe("device_kind", devices[0].device_kind)
        observe("compile_cache_dir", cache_dir)
        t0 = time.perf_counter()
        parts = _terasort_module().generate(SIZE, MAPS, seed=args.seed)
        observe("generate_s", time.perf_counter() - t0)
        if args.chips == 4:
            count = phase_mesh(parts, 4)
        else:
            phase_a(parts)
            phase_b(parts)
            count = len(devices)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": count,
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
