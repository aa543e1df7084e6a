"""Build info, logged at manager startup.

Parity: the reference generates ``SparkS3ShuffleBuild`` via sbt-buildinfo
(build.sbt:18-27) and logs name/version/spark-version/build-time at manager
startup (sort/S3ShuffleManager.scala:39-41).
"""

__version__ = "0.1.0"

#: Shuffle wire-contract version: partition functions (_stable_key_hash,
#: BytesHashPartitioner), codec framing, index/checksum sidecar layout, and
#: serializer frames. Bumped on ANY change that would make a different
#: framework version route or parse shuffle data differently (e.g. r3's
#: _stable_key_hash fast-path rewrite → 2; r7's composite commit layout —
#: fat indexes, snapshot wire v2, registration composite coordinates → 3;
#: r10's coded shuffle plane — parity sidecars, index geometry trailer,
#: fat-index v2 header, snapshot wire v3, registration parity field → 4;
#: r13's columnar record plane — the column-frame data wire is the default
#: framing of columnar serializers (columnar=0 restores the format-4
#: frames byte-identically) → 5; r15's skew mitigation plane — the skew
#: index trailer and fat-index v3 (combined-partials flags + hot-partition
#: split stripes; combine/split=0 restores the format-5 blobs
#: byte-identically) → 6).
#: Driver and all workers of one job must run the same value; re-reading
#: kept shuffle data (cleanup=False) across versions is unsupported.
SHUFFLE_FORMAT_VERSION = 6

BUILD_INFO = {
    "name": "s3shuffle_tpu",
    "version": __version__,
    "shuffle_format": SHUFFLE_FORMAT_VERSION,
    "target": "tpu (jax/xla) + cpu fallback",
}
