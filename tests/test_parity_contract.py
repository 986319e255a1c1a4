"""The full-block encode contract: the device program returns what the host
lacks -- the M parity rows and the digests of all K+M rows -- and the batcher
hands back each block's K data chunks from the split it uploaded.

Parametrised over the three served geometries (12+4, 4+4, 2+2). Everything is
held bit for bit against the host codec: what lands on the drives and the
digests beside it must not depend on which rows crossed back from the device.
"""

import numpy as np
import pytest

from minio_tpu.models.pipeline import ErasurePipeline, Geometry
from minio_tpu.object.codec import HostCodec
from minio_tpu.ops import rs_matrix
from minio_tpu.parallel.batching import BatchingDeviceCodec

GEOMETRIES = [(12, 4), (4, 4), (2, 2)]
BLOCK = 1 << 20
# A block length none of 12, 4 and 2 divides: the last data shard of every
# geometry ends in zero padding, which is hashed and stored as it is.
ODD_BLOCK = 3 * (1 << 16) + 5


def _blocks(seed: int, n: int, size: int) -> list[bytes]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size, dtype=np.uint8).tobytes() for _ in range(n)]


@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_program_returns_parity_and_all_digests(k, m):
    """[B, K, S] -> ([B, M, S], [B, K+M, 32]): parity and every row's digest
    equal the host codec's, at the production block's shard length."""
    host = HostCodec()
    pipe = ErasurePipeline(Geometry(k, m, BLOCK))
    blocks = _blocks(100 * k + m, 2, BLOCK)
    data = np.stack([rs_matrix.split(b, k) for b in blocks])  # [2, K, S]
    s = rs_matrix.shard_size(BLOCK, k)
    parity, digests = pipe.encode(data)
    assert parity.shape == (2, m, s) and parity.dtype == np.uint8
    assert digests.shape == (2, k + m, 32)
    for i, (rows, want_digests) in enumerate(host.encode(blocks, k, m)):
        got = np.asarray(parity[i])
        assert [got[j].tobytes() for j in range(m)] == rows[k:]
        assert [np.asarray(digests[i, j]).tobytes() for j in range(k + m)] == want_digests


@pytest.mark.parametrize("block_size", [BLOCK, ODD_BLOCK], ids=["1MiB", "odd"])
@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_batcher_returns_its_own_data_rows_and_device_parity(k, m, block_size):
    """Three blocks in one call ride one batch padded to four (b_real <
    b_pad). Data chunks are rs_matrix.split of the input, zero padding of the
    last shard included; parity and all digests are the host codec's; and
    what crossed back is b_pad x (M x S parity bytes + K+M digests)."""
    s = rs_matrix.shard_size(block_size, k)
    pad = k * s - block_size  # zero bytes closing the last data shard
    blocks = _blocks(7 * k + m + block_size % 97, 3, block_size)
    # A long collect window: all three blocks of the one call, one batch.
    codec = BatchingDeviceCodec(block_size=block_size, max_batch=8, batch_timeout_s=0.3)
    try:
        got = codec.encode(blocks, k, m)
        st = codec.stats()
    finally:
        codec.close()
    want = HostCodec().encode(blocks, k, m)
    for block, (rows, digests), (want_rows, want_digests) in zip(blocks, got, want):
        split = rs_matrix.split(block, k)
        assert rows[:k] == [split[j].tobytes() for j in range(k)]
        assert rows[k - 1].endswith(b"\0" * pad)
        assert rows[k:] == want_rows[k:]
        assert all(type(r) is bytes and len(r) == s for r in rows)
        assert digests == want_digests
    assert (st["batches_run"], st["blocks_encoded"], st["blocks_padded"]) == (1, 3, 4)
    assert st["host_fallback_blocks"] == 0
    # On conftest's virtual devices a geometry that tiles the codec mesh is
    # uploaded once per tp replica (one device, or no tiling: once).
    mesh = codec._pipelines[(k, m)].mesh
    assert st["h2d_bytes"] == 4 * k * s * (mesh.shape["tp"] if mesh is not None else 1)
    assert st["d2h_bytes"] == 4 * (m * s + 32 * (k + m))
    assert st["encoded_user_bytes"] == 3 * block_size
