#!/usr/bin/env python3
"""Readings that set the limits of ``correct``, on the chip, in one process.

    python3 perfbench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 7,8,9 --seconds 1 [--trace-excerpt <path>]

For each seed of ``--seeds`` one short window of the cell as committed; for
each of ``--control-seeds`` one window of the control: the program with its
stored checksum switched from CRC32C to ADLER32 (``checksum_algorithm``, a
path of the program's own), which breaks a guarantee the configuration
states. The store check reads ADLER32 too, but holds a run to the
algorithm the configuration states, so the control's store, which has no
``checksum.CRC32C`` object, counts every partition as a mismatch. Both
print every number compared, one JSON line per run. The kernels
compile once: only the first run of each kind has a warm-up job. The
benchmark's own runs never run this. ``--trace-excerpt`` also traces the
first seed's window and writes a slice of the trace, the fixture of
``tests/test_trace_reduce.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CONTROL = {"checksum_algorithm": "ADLER32"}


def excerpt(trace: dict, span_ns: float = 20e6, per_line: int = 300) -> dict:
    """Events of every line that overlap a stretch of the busiest device's
    timeline, the first ``per_line`` of each line."""
    dev = [p for p in trace["planes"] if p["name"].startswith("/device:")]
    starts = sorted(s for p in dev for ln in p["lines"] for _n, s, _d in ln["events"])
    mid = starts[len(starts) // 2] if starts else 0.0
    lo, hi = mid - span_ns / 2, mid + span_ns / 2
    out = {"planes": []}
    for p in trace["planes"]:
        lines = []
        for ln in p["lines"]:
            evs = [e for e in ln["events"] if e[1] < hi and e[1] + e[2] > lo][:per_line]
            if evs:
                lines.append({"name": ln["name"], "events": evs})
        if lines:
            out["planes"].append({"name": p["name"], "lines": lines})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--trace-excerpt", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(1, os.path.dirname(HERE))
    import harness

    bench, cell, config, traffic = harness.load_cell(args.workload)
    os.environ.update(config.get("env", {}))
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: no TPU", file=sys.stderr)
        return 2
    runs = [(int(s), None) for s in args.seeds.split(",") if s]
    runs += [(int(s), CONTROL) for s in args.control_seeds.split(",") if s]
    warm: set = set()
    for i, (seed, over) in enumerate(runs):
        kind = "control" if over else "program"
        trace = bool(args.trace_excerpt) and i == 0

        def keep(t):
            with open(args.trace_excerpt, "w") as f:
                json.dump(excerpt(t), f)

        res = harness.run_cell(bench, cell, config, traffic, seed, args.seconds, trace,
                               shuffle_overrides=over, warmup=kind not in warm,
                               keep_trace=keep if trace else None)
        warm.add(kind)
        print(json.dumps({"workload": cell["name"], "kind": kind, "seed": seed,
                          "correct": res["correct"], "attempted": res["attempted"],
                          "checks": {k: v["value"] for k, v in res["checks"].items()},
                          "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                          "device": res["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
