"""Measured-rate gate (ops/rates.py) + TpuCodec dispatch + host-pin re-probe.

The rate gate exists because the 2026-08-04 probe showed every device codec
path losing to the host (encode 3.6 vs 435 MB/s, fused decode 51 vs ~600
effective); availability-only arming shipped those regressions silently.
These tests inject rate tables (:func:`rates.set_rates_for_testing`) to
prove all three dispatch regimes — measured-device, measured-host, no-data
— plus forced/env overrides, the fused-decode harmonic rule, the
``codec_path_selected_total`` accounting, and the ``codec_repin_probe_s``
host-pin expiry state machine.
"""

import numpy as np
import pytest

import s3shuffle_tpu.codec.tpu as tpu_mod
from s3shuffle_tpu.codec.tpu import TpuCodec
from s3shuffle_tpu.metrics import registry as mreg
from s3shuffle_tpu.ops import rates

#: a table where every device kernel beats its host floor
WINNING = {
    "tpu_tlz_encode_mb_s": 900.0,
    "tpu_tlz_decode_mb_s": 1004.2,
    "tpu_tlz_decode_fused_mb_s": 900.0,
    "tpu_gf_encode_mb_s": 1000.0,
}

#: the real 2026-08-04 numbers: chip loses everywhere
LOSING = {
    "tpu_tlz_encode_mb_s": 3.6,
    "tpu_tlz_decode_mb_s": 1004.2,
    "tpu_tlz_decode_fused_mb_s": 51.2,
}


@pytest.fixture(autouse=True)
def _clean_gate(monkeypatch):
    monkeypatch.delenv("S3SHUFFLE_CODEC_RATE_GATE", raising=False)
    monkeypatch.delenv("S3SHUFFLE_TPU_CODEC_DEVICE", raising=False)
    yield
    rates.set_rates_for_testing(None)


@pytest.fixture
def chip_attached(monkeypatch):
    """Pretend an accelerator answered the backend probe."""
    monkeypatch.setattr(tpu_mod, "_device_backend", lambda: True)


# ---------------------------------------------------------------------------
# rates.decide — the three regimes and the overrides
# ---------------------------------------------------------------------------


def test_no_probe_data_means_host():
    rates.set_rates_for_testing({})
    for op in ("encode", "decode", "gf_encode"):
        assert rates.decide(op) == (False, "no-data")


def test_measured_device_wins_over_default_host_rate():
    rates.set_rates_for_testing(WINNING)
    assert rates.decide("encode") == (True, "measured-device")
    assert rates.decide("decode") == (True, "measured-device")
    assert rates.decide("gf_encode") == (True, "measured-device")


def test_measured_host_when_chip_loses():
    rates.set_rates_for_testing(LOSING)
    assert rates.decide("encode") == (False, "measured-host")
    # decode measured 1004 > 600 host default: the one path the chip won
    assert rates.decide("decode") == (True, "measured-device")


def test_measured_host_field_overrides_default_floor():
    rates.set_rates_for_testing(
        {"tpu_tlz_encode_mb_s": 100.0, "host_tlz_encode_mb_s": 50.0}
    )
    assert rates.decide("encode") == (True, "measured-device")


def test_forced_bypasses_measurement():
    rates.set_rates_for_testing(LOSING)
    assert rates.decide("encode", forced=True) == (True, "forced")
    rates.set_rates_for_testing({})
    assert rates.decide("encode", forced=True) == (True, "forced")


def test_env_gate_overrides_everything(monkeypatch):
    rates.set_rates_for_testing(LOSING)
    monkeypatch.setenv("S3SHUFFLE_CODEC_RATE_GATE", "device")
    assert rates.decide("encode") == (True, "env-device")
    monkeypatch.setenv("S3SHUFFLE_CODEC_RATE_GATE", "host")
    # env-host outranks even an explicit codec force
    assert rates.decide("encode", forced=True) == (False, "env-host")
    monkeypatch.setenv("S3SHUFFLE_CODEC_RATE_GATE", "off")
    assert rates.decide("encode") == (True, "gate-off")


# ---------------------------------------------------------------------------
# fused decode: harmonic rule (fused vs unfused-device + host CRC)
# ---------------------------------------------------------------------------


def test_fused_decode_wins_when_beating_effective_streaming():
    # streaming effective = 1/(1/1004.2 + 1/1500) ~= 601 MB/s
    rates.set_rates_for_testing(
        {"tpu_tlz_decode_mb_s": 1004.2,
         "tpu_tlz_decode_fused_mb_s": 900.0}
    )
    assert rates.fused_decode_decision() == (True, "measured-device")


def test_fused_decode_loses_on_the_measured_collapse():
    rates.set_rates_for_testing(LOSING)  # fused 51.2 vs ~601 effective
    assert rates.fused_decode_decision() == (False, "measured-host")


def test_fused_decode_no_data_means_streaming():
    rates.set_rates_for_testing({})
    assert rates.fused_decode_decision() == (False, "no-data")
    # an explicitly-forced codec keeps the legacy fused arming
    assert rates.fused_decode_decision(forced=True) == (True, "forced")


# ---------------------------------------------------------------------------
# codec_path_selected_total accounts for every selection
# ---------------------------------------------------------------------------


def test_every_selection_is_counted():
    mreg.REGISTRY.reset_values()
    mreg.enable()
    try:
        rates.set_rates_for_testing(WINNING)
        assert rates.select("encode") is True
        rates.set_rates_for_testing(LOSING)
        assert rates.select("encode") is False
        rates.set_rates_for_testing({})
        assert rates.select("encode") is False
        assert rates.select_fused_decode() is False
        series = {
            (s["labels"]["path"], s["labels"]["reason"]): s["value"]
            for s in mreg.REGISTRY.snapshot()[
                "codec_path_selected_total"
            ]["series"]
        }
        assert series[("device", "measured-device")] == 1.0
        assert series[("host", "measured-host")] == 1.0
        assert series[("host", "no-data")] == 1.0
        assert series[("streaming", "no-data")] == 1.0
    finally:
        mreg.disable()
        mreg.REGISTRY.reset_values()


# ---------------------------------------------------------------------------
# TpuCodec dispatch through the gate (chip attached in all three regimes)
# ---------------------------------------------------------------------------


def test_codec_routes_device_only_when_measured_faster(chip_attached):
    codec = TpuCodec(block_size=1024, batch_blocks=4)
    rates.set_rates_for_testing(WINNING)
    assert codec._select_device("encode") is True
    assert codec.supports_fused_checksum is True
    rates.set_rates_for_testing(LOSING)
    assert codec._select_device("encode") is False
    assert codec.supports_fused_checksum is False
    rates.set_rates_for_testing({})
    assert codec._select_device("encode") is False
    assert codec.supports_fused_checksum is False


def test_forced_codec_bypasses_gate(chip_attached):
    rates.set_rates_for_testing(LOSING)
    codec = TpuCodec(block_size=1024, batch_blocks=4, use_device=True)
    assert codec._select_device("encode") is True
    assert codec.supports_fused_checksum is True


def test_wants_fused_decode_validation_three_regimes(chip_attached):
    from s3shuffle_tpu.ops.checksum import POLY_CRC32C

    codec = TpuCodec(block_size=1024, batch_blocks=4)
    # fused wins: decode on device AND fused beats effective streaming
    rates.set_rates_for_testing(
        {"tpu_tlz_decode_mb_s": 1004.2,
         "tpu_tlz_decode_fused_mb_s": 900.0}
    )
    assert codec.wants_fused_decode_validation(POLY_CRC32C) is True
    # fused loses: decode stays device, validation stays streaming
    rates.set_rates_for_testing(LOSING)
    assert codec.wants_fused_decode_validation(POLY_CRC32C) is False
    # no data: everything host
    rates.set_rates_for_testing({})
    assert codec.wants_fused_decode_validation(POLY_CRC32C) is False


def test_no_probe_data_keeps_todays_host_behavior(chip_attached):
    """With an attached chip but an empty rate table the codec must behave
    exactly like the host path: same payload bytes, no device routing."""
    rates.set_rates_for_testing({})
    codec = TpuCodec(block_size=1024, batch_blocks=4)
    rng = np.random.default_rng(7)
    block = (b"terasort row " * 100)[:1024]
    blocks = [block, bytes(rng.integers(0, 256, 1024, dtype=np.uint8))]
    out = codec.compress_blocks(blocks)
    assert out == [codec._compress_block_local(b) for b in blocks]
    for raw, payload in zip(blocks, out):
        assert codec.decompress_block(payload, len(raw)) == raw


# ---------------------------------------------------------------------------
# codec_repin_probe_s: pin -> re-probe -> clear / re-pin
# ---------------------------------------------------------------------------


def _pinned_codec(monkeypatch, repin_probe_s):
    """A device-forced codec whose device encode always fails; returns the
    codec (pinned after 3 batches) and the controllable clock cell."""
    codec = TpuCodec(
        block_size=1024, batch_blocks=4, use_device=True,
        repin_probe_s=repin_probe_s,
    )
    now = [100.0]
    codec._clock = lambda: now[0]

    def boom(*a, **k):
        raise RuntimeError("injected device failure")

    monkeypatch.setattr(tpu_mod.tlz, "encode_batch_device", boom)
    mv = memoryview(b"\x00" * 2048)
    for _ in range(3):
        payloads, crcs = codec._encode_full_blocks(mv, 2, 1024, None)
        assert len(payloads) == 2 and crcs is None  # host fallback, no loss
    assert codec._use_device is False
    return codec, now, mv


def test_pin_after_three_failures_then_reprobe_success(monkeypatch):
    codec, now, mv = _pinned_codec(monkeypatch, repin_probe_s=300.0)
    assert codec._host_pinned_at == 100.0
    # still pinned inside the window
    now[0] = 399.0
    assert codec._device_path() is False
    # window elapsed: ONE trial batch goes back to the device
    now[0] = 401.0
    assert codec._device_path() is True
    assert codec._reprobing is True
    monkeypatch.setattr(
        tpu_mod.tlz, "encode_batch_device",
        lambda mv, n, bs, **k: ([b"payload"] * n, None),
    )
    payloads, _ = codec._encode_full_blocks(mv, 2, 1024, None)
    assert payloads == [b"payload", b"payload"]
    assert codec._reprobing is False and codec._host_pinned_at is None
    assert codec._device_path() is True  # back on the device for good


def test_reprobe_failure_repins_immediately(monkeypatch):
    codec, now, mv = _pinned_codec(monkeypatch, repin_probe_s=300.0)
    now[0] = 500.0
    assert codec._device_path() is True  # trial armed
    # the trial itself fails: ONE failure re-pins (not three)
    payloads, _ = codec._encode_full_blocks(mv, 2, 1024, None)
    assert len(payloads) == 2  # batch still host-encoded, no loss
    assert codec._use_device is False
    assert codec._host_pinned_at == 500.0  # fresh window from the re-pin
    now[0] = 799.0
    assert codec._device_path() is False
    now[0] = 801.0
    assert codec._device_path() is True  # next trial arms on schedule


def test_repin_zero_keeps_legacy_permanent_pin(monkeypatch):
    codec, now, mv = _pinned_codec(monkeypatch, repin_probe_s=0.0)
    assert codec._host_pinned_at is None  # no expiry bookkeeping
    now[0] = 1e9
    assert codec._device_path() is False  # pinned forever


def test_decode_pin_mirrors_encode(monkeypatch):
    rates.set_rates_for_testing(WINNING)
    codec = TpuCodec(
        block_size=1024, batch_blocks=4, use_device=True, repin_probe_s=60.0
    )
    now = [0.0]
    codec._clock = lambda: now[0]

    def boom(*a, **k):
        raise RuntimeError("injected device decode failure")

    monkeypatch.setattr(tpu_mod.tlz, "decode_batch_device", boom)
    monkeypatch.setattr(
        TpuCodec, "decompress_block", lambda self, b, n: b"\x00" * n
    )
    blocks = [(b"p1", 4), (b"p2", 4)]
    for _ in range(3):
        out, crcs = codec._decode_full_blocks(blocks, None)
        assert out == [b"\x00" * 4] * 2 and crcs is None  # no frame lost
    assert codec._use_device is False and codec._host_pinned_at == 0.0
    now[0] = 61.0
    assert codec._device_path() is True and codec._reprobing is True


# ---------------------------------------------------------------------------
# GF parity encode rides the same gate
# ---------------------------------------------------------------------------


def test_gf_encode_groups_consults_gate(monkeypatch):
    from s3shuffle_tpu.coding import gf

    chunks = np.arange(4 * 4 * 65536, dtype=np.uint8).reshape(4, 4, 65536)
    assert chunks.nbytes >= gf._DEVICE_MIN_BYTES
    coefs = gf.parity_coefficients(2, 4)
    host = gf._encode_host(chunks, coefs)
    calls = []

    def spy(c, co):
        calls.append(c.shape)
        return host

    monkeypatch.setattr(gf, "_encode_device", spy)
    rates.set_rates_for_testing({})  # no data -> host, device never touched
    assert np.array_equal(gf.encode_groups(chunks, coefs), host)
    assert calls == []
    rates.set_rates_for_testing(WINNING)
    assert np.array_equal(gf.encode_groups(chunks, coefs), host)
    assert calls == [chunks.shape]
