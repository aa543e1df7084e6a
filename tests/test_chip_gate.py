"""tools/chip_gate.py: floors/targets gate + the shared per-metric merge.

The gate is the scoreboard for the chip-kernel rescue: each device kernel
must beat the host implementation it replaces, and fused launches must stay
within 20% of their unfused formulations. ``merge_probe_metrics`` is the
per-metric cache merge bench.py applies when a probe lands — a fresh
``<metric>_error`` must never erase the cached last-good number.
"""

import json

import pytest

from tools import chip_gate


def test_selftest_passes():
    assert chip_gate.main(["--selftest"]) == 0


def test_gate_fails_nonzero_on_regression(tmp_path, capsys):
    cache = tmp_path / "rates.json"
    cache.write_text(json.dumps({
        "measured_at_utc": "2026-08-04T01:44:37Z",
        "tpu_tlz_encode_mb_s": 3.6,
        "tpu_tlz_decode_mb_s": 1004.2,
        "tpu_tlz_decode_fused_mb_s": 51.2,
    }))
    rc = chip_gate.main(["--cache", str(cache)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "MISS" in out and "tpu_tlz_encode_mb_s" in out
    assert "below floor/target" in out


def test_gate_passes_when_kernels_beat_floors(tmp_path, capsys):
    cache = tmp_path / "rates.json"
    cache.write_text(json.dumps({
        "tpu_crc32c_mb_s": 2000.0,
        "tpu_gf_encode_mb_s": 950.0,
        "tpu_tlz_decode_mb_s": 1004.2,
        "tpu_tlz_decode_fused_mb_s": 950.0,
        "tpu_tlz_encode_mb_s": 590.0,
        "tpu_tlz_encode_fused_mb_s": 560.0,
    }))
    assert chip_gate.main(["--cache", str(cache)]) == 0
    assert "PASS" in capsys.readouterr().out


def test_no_data_skips_unless_strict(tmp_path):
    cache = tmp_path / "rates.json"
    cache.write_text("{}")
    assert chip_gate.main(["--cache", str(cache)]) == 0
    assert chip_gate.main(["--cache", str(cache), "--strict"]) == 1


def test_unreadable_cache_exits_2(tmp_path):
    assert chip_gate.main(["--cache", str(tmp_path / "missing.json")]) == 2


# ---------------------------------------------------------------------------
# merge_probe_metrics: the per-metric merge bench.py applies
# ---------------------------------------------------------------------------


def test_new_probe_fields_survive_merge():
    cached = {
        "measured_at_utc": "2026-08-04T01:44:37Z",
        "tpu_tlz_encode_mb_s": 3.6,
        "tpu_tlz_decode_mb_s": 1004.2,
    }
    fresh = {
        "tpu_tlz_encode_fused_mb_s": 620.0,
        "tpu_tlz_encode_fused_cold_s": 4.1,
        "tpu_crc32c_mb_s": 1900.0,
        "tpu_gf_encode_mb_s": 910.0,
    }
    merged = chip_gate.merge_probe_metrics(cached, fresh)
    # every new field landed, cold-compile fields included
    for k, v in fresh.items():
        assert merged[k] == v
    # prior metrics the fresh probe did not re-measure are kept
    assert merged["tpu_tlz_encode_mb_s"] == 3.6
    assert merged["tpu_tlz_decode_mb_s"] == 1004.2
    assert merged["measured_at_utc"] != "2026-08-04T01:44:37Z"


def test_error_fields_never_erase_last_good():
    cached = {"tpu_crc32c_mb_s": 1900.0, "old_error": "stale"}
    fresh = {
        "tpu_crc32c_mb_s_error": "timing jitter",
        "tpu_gf_encode_mb_s": 910.0,
    }
    merged = chip_gate.merge_probe_metrics(cached, fresh)
    assert merged["tpu_crc32c_mb_s"] == 1900.0
    assert merged["tpu_gf_encode_mb_s"] == 910.0
    assert not any(k.endswith("_error") for k in merged)


def test_fresh_good_value_wins_over_cached():
    merged = chip_gate.merge_probe_metrics(
        {"tpu_gf_encode_mb_s": 100.0}, {"tpu_gf_encode_mb_s": 910.0}
    )
    assert merged["tpu_gf_encode_mb_s"] == 910.0
