"""decode_jump_rounds_per_launch: the ratio on a synthetic registry snapshot,
silence where no decode launched or the program counts no rounds, and a
reading inside the round cap from a CPU job with the device path forced."""

import pytest

import harness

METRIC = "decode_jump_rounds_per_launch"


def _snapshot(decode_launches=16, rounds=160):
    def series(name, rows):
        return {name: {"series": [{"labels": labels, "value": float(v)} for labels, v in rows]}}

    snap = {}
    snap.update(series("codec_launches_total",
                       [({"op": "encode"}, 8), ({"op": "decode"}, decode_launches)]))
    if rounds is not None:
        snap.update(series("codec_decode_jump_rounds_total", [({}, rounds)]))
    return snap


def _read(snap):
    w = harness._window_record(snap, tasks=[])
    return harness.load_module(f"metrics/{METRIC}.py").read(w)


@pytest.mark.parametrize("snap, want", [
    (_snapshot(), 10.0),
    (_snapshot(decode_launches=3, rounds=3 * 18), 18.0),
    (_snapshot(decode_launches=0), None),
    (_snapshot(rounds=None), None),
], ids=["ratio", "cap", "no_decode_launch", "no_counter"])
def test_jump_rounds_per_decode_launch(snap, want):
    """Every launch runs at least one round, so a decode launch with no
    rounds counted means a program without the counter: no value."""
    got = _read(snap)
    assert got == (None if want is None else pytest.approx(want))


def test_jump_rounds_are_read_from_a_cpu_job(monkeypatch):
    bench, cell, config, traffic = harness.load_cell("terasort.r8")
    per_segment = config["records_per_job"] // 200
    traffic["reducers"] = 2
    config["records_per_job"] = 2 * per_segment
    for k, v in {**config.get("env", {}), "S3SHUFFLE_TPU_CODEC_DEVICE": "1"}.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(harness, "cell_metrics", lambda b, c, t: b["per_layer"])
    res = harness.run_cell(bench, cell, config, traffic, 2**31 + 77, 0.3, False)
    assert res["correct"], res["checks"]
    assert 1 <= res["metrics"][METRIC]["value"] <= 18
