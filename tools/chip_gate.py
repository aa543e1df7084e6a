#!/usr/bin/env python
"""Chip gate: judge the measured device-kernel rates against their floors.

The 2026-08-04 chip probe showed the device codec plane losing to the host
on every axis: TLZ encode 3.6 vs 435 MB/s for the host C encoder, CRC32C
40.5 vs ~1500 MB/s native, and the fused decode collapsing 1004 MB/s to
51 MB/s. This tool is the scoreboard for closing that gap. It reads the
per-metric probe cache (``bench_tpu_last_good.json``) and checks:

- **staged floor** — each device kernel must beat the HOST implementation
  it would replace (encode >= the host C encoder, CRC >= native crc32c,
  GF parity >= the numpy table encoder) before the measured-rate gate
  (ops/rates.py) will ever select it in production;
- **fusion sanity** — a fused launch must stay within 20% of its unfused
  formulation in either direction. Fusing a CRC pass into a decode adds a
  little work, so a fused kernel 20x slower than its parts (the old
  1004 -> 51 MB/s decode collapse) is a broken kernel, not a trade; 20%
  FASTER than the plain kernel is equally a measurement smell.

Exit 0 when every metric that has data passes; nonzero otherwise, with a
readable delta table either way. Metrics with no probe data are SKIPped
and do not fail the gate (``--strict`` makes them fail): on a rig with no
chip the gate can prove nothing, and the rate gate already treats no-data
as host.

:func:`merge_probe_metrics` is the shared per-metric cache merge
``bench.py device_kernel_rates`` applies when a fresh probe lands: fresh
good fields win, ``<metric>_error`` fields are dropped and never erase the
cached last-good number for that metric.

Usage:  python -m tools.chip_gate [--cache PATH] [--strict]
        python -m tools.chip_gate --selftest
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, Optional, Tuple

#: (device metric, host reference metric, human label for the floor)
FLOOR_CHECKS: Tuple[Tuple[str, str, str], ...] = (
    ("tpu_tlz_encode_mb_s", "host_tlz_encode_mb_s",
     "host C TLZ encoder"),
    ("tpu_crc32c_mb_s", "host_crc32c_mb_s", "native host crc32c"),
    ("tpu_gf_encode_mb_s", "host_gf_encode_mb_s", "numpy GF(2^8) encoder"),
)

#: (fused metric, unfused metric it must track, relative tolerance)
FUSION_CHECKS: Tuple[Tuple[str, str, float], ...] = (
    ("tpu_tlz_decode_fused_mb_s", "tpu_tlz_decode_mb_s", 0.20),
    ("tpu_tlz_encode_fused_mb_s", "tpu_tlz_encode_mb_s", 0.20),
)


def merge_probe_metrics(cached: Dict, fresh: Dict) -> Dict:
    """Per-metric merge of a fresh probe into the last-good cache.

    Fresh GOOD fields win; ``<metric>_error`` fields (timing jitter, a
    lowering this jaxlib lacks, a device lost mid-probe) are dropped
    from both sides and must NOT erase the cached last-good number for
    that metric; the ``measured_at_utc`` stamp is regenerated. This is the
    whole reason one failing kernel never blinds the measured-rate gate
    (ops/rates.py) on every OTHER kernel.
    """
    good = {k: v for k, v in fresh.items() if not k.endswith("_error")}
    base = {
        k: v for k, v in cached.items()
        if k != "measured_at_utc" and not k.endswith("_error")
    }
    return {
        "measured_at_utc": time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
        ),
        **base,
        **good,
    }


def _num(table: Dict, key: str) -> Optional[float]:
    val = table.get(key)
    if isinstance(val, (int, float)) and not isinstance(val, bool) and val > 0:
        return float(val)
    return None


def _default_host_rates() -> Dict[str, float]:
    from s3shuffle_tpu.ops import rates

    return dict(rates.DEFAULT_HOST_RATES)


def evaluate(table: Dict) -> Tuple[list, int, int]:
    """Gate one rate table. Returns (rows, n_failures, n_nodata) with each
    row ``(metric, measured, target, verdict)`` already formatted."""
    defaults = _default_host_rates()
    rows = []
    failures = 0
    nodata = 0
    for metric, host_metric, desc in FLOOR_CHECKS:
        floor = _num(table, host_metric) or defaults.get(
            host_metric, float("inf")
        )
        target = f">= {floor:.1f} ({desc})"
        dev = _num(table, metric)
        if dev is None:
            rows.append((metric, "no data", target, "SKIP"))
            nodata += 1
            continue
        delta = (dev - floor) / floor * 100.0
        ok = dev >= floor
        rows.append((
            metric, f"{dev:.1f}", target,
            f"{'PASS' if ok else 'MISS'} ({delta:+.1f}%)",
        ))
        failures += 0 if ok else 1
    for fused_m, unfused_m, tol in FUSION_CHECKS:
        fused = _num(table, fused_m)
        unfused = _num(table, unfused_m)
        if fused is None or unfused is None:
            rows.append((
                fused_m,
                "no data" if fused is None else f"{fused:.1f}",
                f"within {tol:.0%} of {unfused_m}",
                "SKIP",
            ))
            nodata += 1
            continue
        drift = fused / unfused - 1.0
        ok = abs(drift) <= tol
        rows.append((
            fused_m, f"{fused:.1f}",
            f"within {tol:.0%} of {unfused_m} ({unfused:.1f})",
            f"{'PASS' if ok else 'MISS'} ({drift * 100.0:+.1f}%)",
        ))
        failures += 0 if ok else 1
    return rows, failures, nodata


def render(rows: list) -> str:
    headers = ("metric", "measured MB/s", "floor / target", "verdict")
    cols = [
        max(len(headers[i]), *(len(r[i]) for r in rows)) if rows
        else len(headers[i])
        for i in range(4)
    ]
    lines = [
        "  ".join(h.ljust(cols[i]) for i, h in enumerate(headers)),
        "  ".join("-" * c for c in cols),
    ]
    for r in rows:
        lines.append("  ".join(r[i].ljust(cols[i]) for i in range(4)))
    return "\n".join(lines)


def _selftest() -> int:
    # 1) merge semantics: an _error field must not erase the cached
    #    last-good value, and brand-new probe fields must survive
    cached = {
        "measured_at_utc": "2026-08-04T00:00:00Z",
        "tpu_crc32c_mb_s": 2000.0,
        "tpu_tlz_decode_mb_s": 1004.2,
        "stale_error": "gone",
    }
    fresh = {
        "tpu_crc32c_mb_s_error": "timing jitter",
        "tpu_gf_encode_mb_s": 950.0,
        "tpu_gf_encode_cold_s": 1.2,
    }
    merged = merge_probe_metrics(cached, fresh)
    assert merged["tpu_crc32c_mb_s"] == 2000.0, merged
    assert merged["tpu_gf_encode_mb_s"] == 950.0, merged
    assert merged["tpu_gf_encode_cold_s"] == 1.2, merged
    assert merged["tpu_tlz_decode_mb_s"] == 1004.2, merged
    assert not any(k.endswith("_error") for k in merged), merged
    assert merged["measured_at_utc"] != "2026-08-04T00:00:00Z", merged

    # 2) a winning table passes every check
    winning = {
        "tpu_crc32c_mb_s": 2000.0,
        "tpu_gf_encode_mb_s": 950.0,
        "tpu_tlz_decode_mb_s": 1004.2,
        "tpu_tlz_decode_fused_mb_s": 950.0,
        "tpu_tlz_encode_mb_s": 590.0,
        "tpu_tlz_encode_fused_mb_s": 560.0,
    }
    rows, failures, nodata = evaluate(winning)
    assert failures == 0 and nodata == 0, (failures, nodata, rows)

    # 3) the 2026-08-04 reality fails loudly: encode below the host C
    #    floor, fused decode 20x under its unfused formulation
    losing = {
        "tpu_tlz_encode_mb_s": 3.6,
        "tpu_crc32c_mb_s": 40.5,
        "tpu_tlz_decode_mb_s": 1004.2,
        "tpu_tlz_decode_fused_mb_s": 51.2,
    }
    rows, failures, nodata = evaluate(losing)
    assert failures == 3, (failures, rows)
    table = render(rows)
    assert "tpu_tlz_encode_mb_s" in table and "MISS" in table, table

    # 4) an empty cache skips everything instead of failing
    rows, failures, nodata = evaluate({})
    assert failures == 0 and nodata == len(rows) > 0, (failures, rows)

    # 5) measured host_* fields override the conservative defaults
    slow_host = dict(losing, host_tlz_encode_mb_s=3.0)
    _rows, failures, _n = evaluate(slow_host)
    assert failures == 2, failures  # encode floor now met

    print("chip_gate selftest: OK")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="gate the probe's device-kernel rates against their "
                    "host floors and fusion-sanity targets"
    )
    ap.add_argument("--cache", default=None,
                    help="rate cache path (default: the probe cache next "
                         "to bench.py, honoring S3SHUFFLE_BENCH_TPU_CACHE)")
    ap.add_argument("--strict", action="store_true",
                    help="metrics with no probe data fail instead of SKIP")
    ap.add_argument("--selftest", action="store_true",
                    help="run the built-in self-checks and exit")
    args = ap.parse_args(argv)
    if args.selftest:
        return _selftest()

    if args.cache:
        path = args.cache
    else:
        from s3shuffle_tpu.ops import rates

        path = rates.cache_path()
    try:
        with open(path, "r", encoding="utf-8") as fh:
            table = json.load(fh)
    except (OSError, ValueError) as exc:
        print(f"chip_gate: unreadable rate cache {path}: {exc}")
        return 2

    rows, failures, nodata = evaluate(table)
    print(f"chip gate over {path}")
    stamp = table.get("measured_at_utc")
    if stamp:
        print(f"  (last probe: {stamp})")
    print(render(rows))
    if failures:
        print(f"chip_gate: {failures} metric(s) below floor/target")
        return 1
    if nodata and args.strict:
        print(f"chip_gate: {nodata} metric(s) have no probe data (--strict)")
        return 1
    print("chip_gate: all measured metrics at or above their floors"
          + (f" ({nodata} with no data skipped)" if nodata else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
