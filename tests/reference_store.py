"""A plain reference of the small-object path, for tests to compare with.

Independent of the code under test: it imports nothing from
``parallel/batching.py`` or ``object/erasure.py``. Parity comes from the
table-lookup oracle ``ops/rs_ref.py`` and digests from the numpy
HighwayHash-256 of ``ops/highwayhash.py`` -- neither the device kernels nor
the native host kernels.

(i)  ``inline_shards(body, k, m)``: what each shard row of an inline object
     has to hold inside ``xl.meta``: per block of at most 1 MiB the reference's
     Split (ceil(n/k) bytes a shard, the tail zero-padded), the parity rows,
     and per row the frame digest || chunk, blocks in order.
(ii) ``ModelStore``: a dictionary model of an unversioned bucket's answers to
     PUT / overwrite / GET / HEAD / DELETE. Two answers are this program's own
     and are written down as such: a DELETE of a key that is not there is 404
     (S3 answers 204), and an object of 128 KiB or more, which is not inline,
     carries an opaque ETag derived from its bitrot digests where S3 gives the
     md5 -- the model holds GET and HEAD to the ETag the PUT answered.
"""

from __future__ import annotations

import hashlib
import zlib

import numpy as np

from minio_tpu.ops import highwayhash, rs_ref

BLOCK_BYTES = 1 << 20
INLINE_BELOW = 128 << 10  # smaller objects live inside xl.meta and keep a real md5 ETag


def split_block(block: bytes, k: int) -> np.ndarray:
    """[k, ceil(n/k)] data shards of one block, the tail zero-padded."""
    n = len(block)
    per = -(-n // k)
    flat = np.zeros(k * per, dtype=np.uint8)
    flat[:n] = np.frombuffer(block, dtype=np.uint8)
    return flat.reshape(k, per)


def encode_block(block: bytes, k: int, m: int) -> tuple[list[bytes], list[bytes]]:
    """(the k+m shard rows, their HighwayHash-256 digests) of one block."""
    rows = rs_ref.encode(split_block(block, k), m)
    digests = highwayhash.hash256_batch(rows)
    return [r.tobytes() for r in rows], [d.tobytes() for d in digests]


def inline_shards(body: bytes, k: int, m: int) -> list[bytes]:
    """Per shard row, the image an inline object's ``xl.meta`` holds."""
    images = [bytearray() for _ in range(k + m)]
    for off in range(0, len(body), BLOCK_BYTES):
        rows, digests = encode_block(body[off : off + BLOCK_BYTES], k, m)
        for image, digest, row in zip(images, digests, rows):
            image += digest + row
    return [bytes(image) for image in images]


def hash_order(key: str, cardinality: int) -> list[int]:
    """1-based shard row per drive (the reference's hashOrder,
    cmd/erasure-metadata-utils.go): drive i holds row hash_order(...)[i] - 1."""
    start = (zlib.crc32(key.encode()) & 0xFFFFFFFF) % cardinality
    return [1 + ((start + i) % cardinality) for i in range(1, cardinality + 1)]


class ModelStore:
    """What an unversioned bucket answers: (status, body or size or None, etag
    or None). Keys are independent, so callers that own disjoint keys may
    share one."""

    def __init__(self):
        self.objects: dict[str, tuple[bytes, str]] = {}

    def put(self, key: str, body: bytes, answered_etag: str):
        inline = len(body) < INLINE_BELOW
        etag = hashlib.md5(body).hexdigest() if inline else answered_etag
        self.objects[key] = (body, etag)
        return 200, None, etag

    def get(self, key: str):
        if key not in self.objects:
            return 404, None, None
        body, etag = self.objects[key]
        return 200, body, etag

    def head(self, key: str):
        if key not in self.objects:
            return 404, None, None
        body, etag = self.objects[key]
        return 200, len(body), etag

    def delete(self, key: str):
        return (204 if self.objects.pop(key, None) is not None else 404), None, None
