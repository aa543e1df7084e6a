"""s3shuffle_tpu — a TPU-native shuffle framework with the capability surface of
IBM/spark-s3-shuffle (reference: /root/reference, a Spark shuffle plugin that stores
shuffle data on S3-compatible object storage).

Capability parity map (reference file → this package):

- ``S3ShuffleManager``        → :mod:`s3shuffle_tpu.manager`
- ``S3ShuffleDataIO``         → :mod:`s3shuffle_tpu.dataio`
- ``S3ShuffleMapOutputWriter``→ :mod:`s3shuffle_tpu.write.map_output_writer`
- ``S3ShuffleReader``         → :mod:`s3shuffle_tpu.read.reader`
- ``S3ShuffleDispatcher``     → :mod:`s3shuffle_tpu.storage.dispatcher`
- ``S3ShuffleHelper``         → :mod:`s3shuffle_tpu.metadata.helper`
- ``S3BufferedPrefetchIterator`` → :mod:`s3shuffle_tpu.read.prefetch`
- ``S3ChecksumValidationStream`` → :mod:`s3shuffle_tpu.read.checksum_stream`

TPU-first additions the reference lacks: batched XLA codec kernels
(:mod:`s3shuffle_tpu.ops`), a C++ native CPU codec (:mod:`s3shuffle_tpu.codec`),
an ICI all-to-all repartition fast path (:mod:`s3shuffle_tpu.parallel`), and a
typed metrics subsystem with per-shuffle stats reports
(:mod:`s3shuffle_tpu.metrics` — replaces the reference's external
jvm-profiler → InfluxDB → Grafana stack).
"""

from s3shuffle_tpu.version import BUILD_INFO, __version__
from s3shuffle_tpu.config import ShuffleConfig
from s3shuffle_tpu.block_ids import (
    BlockId,
    ShuffleBlockId,
    ShuffleBlockBatchId,
    ShuffleDataBlockId,
    ShuffleIndexBlockId,
    ShuffleChecksumBlockId,
    NOOP_REDUCE_ID,
)

_LAZY = {"ShuffleManager": "s3shuffle_tpu.manager", "ShuffleContext": "s3shuffle_tpu.shuffle"}


def __getattr__(name):  # lazy: avoid importing jax at package-import time
    if name in _LAZY:
        import importlib

        return getattr(importlib.import_module(_LAZY[name]), name)
    raise AttributeError(name)

__all__ = [
    "BUILD_INFO",
    "__version__",
    "ShuffleConfig",
    "BlockId",
    "ShuffleBlockId",
    "ShuffleBlockBatchId",
    "ShuffleDataBlockId",
    "ShuffleIndexBlockId",
    "ShuffleChecksumBlockId",
    "NOOP_REDUCE_ID",
    "ShuffleManager",
    "ShuffleContext",
]
