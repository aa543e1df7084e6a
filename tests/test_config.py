import os

import pytest

from s3shuffle_tpu.config import MiB, ShuffleConfig


@pytest.mark.skipif(
    os.environ.get("S3SHUFFLE_TEST_MODE", "default") != "default",
    reason="conftest mode matrix overrides config defaults",
)
def test_defaults_match_reference():
    # SURVEY.md §5.6 flag table defaults
    c = ShuffleConfig()
    assert c.buffer_size == 8 * MiB
    assert c.max_buffer_size_task == 128 * MiB
    assert c.max_concurrency_task == 10
    assert c.cache_partition_lengths and c.cache_checksums and c.cleanup
    assert c.folder_prefixes == 10
    assert not c.always_create_index
    assert c.use_block_manager
    assert not c.force_batch_fetch
    assert not c.use_fallback_fetch
    assert c.checksum_enabled and c.checksum_algorithm == "ADLER32"


def test_from_dict_reference_keys():
    c = ShuffleConfig.from_dict(
        {
            "spark.shuffle.s3.rootDir": "memory://bucket/root",
            "spark.shuffle.s3.bufferSize": "1m",
            "spark.shuffle.s3.folderPrefixes": "3",
            "spark.shuffle.s3.cleanup": "false",
            "spark.shuffle.checksum.algorithm": "CRC32",
        }
    )
    assert c.root_dir == "memory://bucket/root/"
    assert c.buffer_size == MiB
    assert c.folder_prefixes == 3
    assert not c.cleanup
    assert c.checksum_algorithm == "CRC32"


def test_from_env(monkeypatch):
    monkeypatch.setenv("S3SHUFFLE_MAX_CONCURRENCY_TASK", "4")
    monkeypatch.setenv("S3SHUFFLE_CHECKSUM_ENABLED", "false")
    c = ShuffleConfig.from_env()
    assert c.max_concurrency_task == 4
    assert not c.checksum_enabled


def test_from_env_optional_int_accepts_none(monkeypatch):
    """A string "none"/"null"/"" must express the None default of optional
    int fields like codec_block_size (ADVICE r2) instead of raising from
    parse_size."""
    for s in ("none", "NULL", ""):
        monkeypatch.setenv("S3SHUFFLE_CODEC_BLOCK_SIZE", s)
        assert ShuffleConfig.from_env().codec_block_size is None
    monkeypatch.setenv("S3SHUFFLE_CODEC_BLOCK_SIZE", "64k")
    assert ShuffleConfig.from_env().codec_block_size == 64 * 1024
    # optional BOOLS too: "none" must mean probe-the-backend, not False
    monkeypatch.setenv("S3SHUFFLE_SUPPORTS_RENAME", "none")
    assert ShuffleConfig.from_env().supports_rename is None


def test_bad_algorithm_raises():
    # Parity: unsupported algorithms raise (S3ShuffleHelper.scala:94-103)
    with pytest.raises(ValueError):
        ShuffleConfig(checksum_algorithm="MD5")


def test_unknown_key_raises():
    with pytest.raises(KeyError):
        ShuffleConfig.from_dict({"spark.shuffle.s3.nope": "1"})


def test_trace_records_spans_and_counters(tmp_path):
    # The tracing subsystem: spans + counters recorded end to end through a
    # real shuffle and exported as Chrome trace-event JSON.
    import json

    from s3shuffle_tpu.utils import trace

    trace.reset()
    trace.enable(str(tmp_path / "trace.json"))
    try:
        from s3shuffle_tpu.config import ShuffleConfig
        from s3shuffle_tpu.shuffle import ShuffleContext
        from s3shuffle_tpu.storage.dispatcher import Dispatcher

        Dispatcher.reset()
        cfg = ShuffleConfig(root_dir=f"file://{tmp_path}/shuffle", app_id="trace-app")
        with ShuffleContext(config=cfg, num_workers=2) as ctx:
            out = ctx.fold_by_key(
                [[(k % 5, 1) for k in range(200)]], 0, lambda a, b: a + b, num_partitions=2
            )
        assert dict(out) == {k: 40 for k in range(5)}
        names = {e["name"] for e in trace.events_snapshot()}
        assert "write.commit" in names
        assert "read.prefetch" in names
        assert trace.counters().get("read.tasks", 0) >= 2
        path = trace.flush()
        with open(path) as f:
            doc = json.load(f)
        assert doc["traceEvents"] and "counters" in doc["otherData"]
    finally:
        trace.disable()
        trace.reset()


def test_trace_disabled_is_noop():
    from s3shuffle_tpu.utils import trace

    trace.reset()
    assert not trace.enabled()
    with trace.span("x", a=1):
        pass
    trace.count("y")
    assert trace.events_snapshot() == []
    assert trace.counters() == {}


def test_codec_batch_blocks_flag_reaches_codec():
    # the flag must actually size the device round-trip batch (was parsed
    # but unplumbed), and the async window knob must reach the codec too
    jax = pytest.importorskip("jax")  # noqa: F841
    from s3shuffle_tpu.config import ShuffleConfig
    from s3shuffle_tpu.manager import ShuffleManager

    m = ShuffleManager(
        ShuffleConfig(
            root_dir="memory://tpu-flag", codec="tpu", codec_batch_blocks=16,
            encode_inflight_batches=3,
        )
    )
    assert m._codec.batch_blocks == 16
    assert m._codec.encode_inflight_batches == 3


def test_legacy_tpu_batch_blocks_key_still_accepted():
    # configs written against the pre-rework knob name translate via
    # from_dict, like the reference's spark.shuffle.s3.* keys do
    from s3shuffle_tpu.config import ShuffleConfig

    cfg = ShuffleConfig.from_dict({"tpu_batch_blocks": 32})
    assert cfg.codec_batch_blocks == 32
    # ... and via the env path, where the NEW spelling wins when both exist
    cfg = ShuffleConfig.from_env({"S3SHUFFLE_TPU_BATCH_BLOCKS": "32"})
    assert cfg.codec_batch_blocks == 32
    cfg = ShuffleConfig.from_env({
        "S3SHUFFLE_TPU_BATCH_BLOCKS": "32",
        "S3SHUFFLE_CODEC_BATCH_BLOCKS": "16",
    })
    assert cfg.codec_batch_blocks == 16
