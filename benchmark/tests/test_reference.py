"""The plain reference of a healed shard file (harness/reference.py) against
the program's own oracles: two independent writings of one definition agree.

The benchmark's runs never import the program's oracles; this test does, as a
witness, and needs no server and no jax.
"""

import numpy as np
import pytest

from benchmark.harness import reference


@pytest.mark.parametrize("k,m", [(12, 4), (4, 4), (2, 2), (13, 3)])
def test_encode_matrix_is_the_programs(k, m):
    from minio_tpu.ops import rs_matrix

    assert (reference.encode_matrix(k, m) == rs_matrix.encode_matrix(k, m)).all()


@pytest.mark.parametrize("length", [0, 1, 3, 4, 16, 17, 20, 31, 32, 33, 63, 100, 87382])
def test_highwayhash_is_the_programs(length):
    from minio_tpu.ops import highwayhash

    x = np.random.default_rng(length).integers(0, 256, (3, length), dtype=np.uint8)
    assert (reference.highwayhash256(x) == highwayhash.hash256_batch(x)).all()


def test_shard_files_are_the_programs_frames_of_its_rows():
    from minio_tpu.ops import highwayhash, rs_ref

    body = np.random.default_rng(5).bytes(3 << 20)
    rows = [0, 7, 12, 15]
    files = reference.shard_files(body, 12, 4, 1 << 20, rows)
    for row, got in zip(rows, files):
        due = b""
        for b in range(3):
            shard = rs_ref.encode_data(body[b << 20:(b + 1) << 20], 12, 4)[row].tobytes()
            due += highwayhash.hash256(shard) + shard
        assert got == due, row
    with pytest.raises(ValueError):
        reference.shard_files(body[:-1], 12, 4, 1 << 20, rows)
