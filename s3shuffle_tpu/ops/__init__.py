"""TPU ops: batched checksum and compression kernels (XLA graphs).

These replace the JVM codec/checksum byte loops of the reference
(java.util.zip via S3ShuffleHelper.createChecksumAlgorithm,
S3ShuffleHelper.scala:94-103, and Spark codec streams) with batched
device kernels — the north-star differentiator (BASELINE.json).
"""

from s3shuffle_tpu.ops.checksum import (
    POLY_CRC32,
    POLY_CRC32C,
    adler32_batch,
    crc32_batch,
    crc_combine,
)

__all__ = [
    "POLY_CRC32",
    "POLY_CRC32C",
    "crc32_batch",
    "adler32_batch",
    "crc_combine",
]
