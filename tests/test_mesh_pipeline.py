"""Mesh-sharded encode pipeline vs host oracle (VERDICT r3 #5).

Runs the full SPMD encode+hash step on the conftest's 8-device virtual CPU
platform: the erasure matmul sp-sharded, the encode->hash boundary as an
explicit lax.all_to_all, streams tp-sliced. Pins sharded outputs bit-exactly
against the host reference so a sharding regression cannot ship green.
"""

import numpy as np
import pytest

import jax

from minio_tpu.models.pipeline import ErasurePipeline, Geometry
from minio_tpu.ops import highwayhash as hh
from minio_tpu.ops import rs_ref
from minio_tpu.parallel import mesh as mesh_lib

K, M = 12, 4


def _host_oracle(data, m=M):
    """[B, K, S] -> (parity, digests of all K+M rows) via the numpy reference."""
    k = data.shape[1]
    shards = np.stack([rs_ref.encode(data[i], m) for i in range(data.shape[0])])
    digests = np.stack(
        [
            np.stack(
                [
                    np.frombuffer(hh.hash256(shards[i, j].tobytes()), dtype=np.uint8)
                    for j in range(k + m)
                ]
            )
            for i in range(data.shape[0])
        ]
    )
    return shards[:, k:], digests


# The shapes a four-chip host can take (parallel/mesh.py factor_mesh picks one
# of them, MTPU_MESH_SHAPE any), at the three served geometries and at one
# whose six streams do not divide a 2 x 2 stream grid.
FOUR_CHIP_SHAPES = [(4, 1, 1), (2, 2, 1), (2, 1, 2), (1, 2, 2)]
EIGHT = [(8, shape, K, M) for shape in [(2, 2, 2), (4, 2, 1), (8, 1, 1), (1, 2, 4)]]
FOUR = [(4, shape, k, m) for shape in FOUR_CHIP_SHAPES
        for k, m in [(12, 4), (4, 4), (2, 2), (4, 2)]]


@pytest.mark.parametrize(
    "n,shape,k,m", EIGHT + FOUR,
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_mesh_encode_matches_host(n, shape, k, m):
    if jax.device_count() < n:
        pytest.skip("needs the 8-device virtual platform from conftest")
    from minio_tpu.parallel.batching import BatchingDeviceCodec

    dp, tp, sp = shape
    geom = Geometry(k, m, block_size=k * 64 * max(sp, 1))
    # The batcher's rule decides whether this geometry runs on the mesh: where
    # its streams do not tile the tp x sp grid it gets the single-device
    # program, which must be as right.
    batcher = BatchingDeviceCodec(block_size=geom.block_size, mesh=mesh_lib.make_mesh(n, shape=shape))
    mesh = batcher._mesh_for(k, m)
    tiles = (k + m) % (tp * sp) == 0
    assert (mesh is not None) == tiles
    pipe = ErasurePipeline(geom, mesh=mesh)
    rng = np.random.default_rng(42)
    data = rng.integers(0, 256, (2 * dp, k, geom.shard_size), dtype=np.uint8)

    parity, digests = pipe.encode(pipe.place(data))
    if mesh is not None:
        assert parity.sharding.is_equivalent_to(
            jax.sharding.NamedSharding(mesh, mesh_lib.parity_spec()), parity.ndim)
        assert len(parity.sharding.device_set) == n
    want_parity, want_digests = _host_oracle(data, m)
    assert parity.shape == (2 * dp, m, geom.shard_size)
    assert np.array_equal(np.asarray(parity), want_parity)
    assert np.array_equal(np.asarray(digests), want_digests)


def test_mesh_factoring():
    assert mesh_lib.factor_mesh(1) == (1, 1, 1)
    for n in (2, 4, 8, 16, 64):
        dp, tp, sp = mesh_lib.factor_mesh(n)
        assert dp * tp * sp == n
        assert dp >= tp >= sp


class TestMeshShapeEnv:
    """MTPU_MESH_SHAPE parsing + the cached codec mesh BatchingDeviceCodec
    fans batches over."""

    def test_explicit_shape(self, monkeypatch):
        monkeypatch.setenv("MTPU_MESH_SHAPE", "4,2,1")
        assert mesh_lib.mesh_shape_from_env(8) == (4, 2, 1)

    def test_off_disables(self, monkeypatch):
        for raw in ("off", "0", "1"):
            monkeypatch.setenv("MTPU_MESH_SHAPE", raw)
            assert mesh_lib.mesh_shape_from_env(8) is None

    def test_auto_and_malformed_fall_back_to_factoring(self, monkeypatch):
        want = mesh_lib.factor_mesh(8)
        for raw in ("", "auto", "banana", "2,2", "3,3,3", "-1,4,2"):
            monkeypatch.setenv("MTPU_MESH_SHAPE", raw)
            assert mesh_lib.mesh_shape_from_env(8) == want

    def test_codec_mesh_cached(self, monkeypatch):
        if jax.device_count() < 8:
            pytest.skip("needs the 8-device virtual platform from conftest")
        monkeypatch.setattr(mesh_lib, "_codec_mesh_cache", [])
        monkeypatch.setenv("MTPU_MESH_SHAPE", "8,1,1")
        m1 = mesh_lib.codec_mesh()
        assert m1 is not None and m1.shape["dp"] == 8
        # Cached: a later env change does not rebuild (one mesh per process).
        monkeypatch.setenv("MTPU_MESH_SHAPE", "off")
        assert mesh_lib.codec_mesh() is m1

    def test_codec_mesh_off(self, monkeypatch):
        monkeypatch.setattr(mesh_lib, "_codec_mesh_cache", [])
        monkeypatch.setenv("MTPU_MESH_SHAPE", "off")
        assert mesh_lib.codec_mesh() is None


def test_rs_under_dp_mesh_matches_host():
    """The RS codec shard_mapped data-parallel over all 8 virtual devices
    stays bit-identical to the host oracle (the bench's
    multichip_encode_gibs program)."""
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device virtual platform from conftest")
    from jax.sharding import PartitionSpec as P

    from minio_tpu.ops.rs import RSCodec

    n = 8
    mesh = mesh_lib.make_mesh(n, (n, 1, 1))
    codec = RSCodec(K, M)
    enc = jax.jit(
        jax.shard_map(
            codec.encode, mesh=mesh,
            in_specs=P("dp", None, None), out_specs=P("dp", None, None),
            check_vma=False,
        )
    )
    rng = np.random.default_rng(13)
    data = rng.integers(0, 256, (n, K, 4096), dtype=np.uint8)
    got = np.asarray(enc(jax.device_put(data, mesh_lib.data_sharding(mesh))))
    for i in range(n):
        np.testing.assert_array_equal(got[i], rs_ref.encode(data[i], M)[K:])


def test_default_mesh_dryrun():
    """The exact program the driver's dryrun_multichip exercises."""
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device virtual platform from conftest")
    mesh = mesh_lib.make_mesh(8)
    geom = Geometry(K, M, block_size=K * 128 * mesh.shape["sp"])
    pipe = ErasurePipeline(geom, mesh=mesh)
    rng = np.random.default_rng(7)
    data = rng.integers(
        0, 256, (2 * mesh.shape["dp"], K, geom.shard_size), dtype=np.uint8
    )
    parity, digests = pipe.encode(jax.device_put(data, mesh_lib.data_sharding(mesh)))
    want_parity, want_digests = _host_oracle(data)
    assert np.array_equal(np.asarray(parity), want_parity)
    assert np.array_equal(np.asarray(digests), want_digests)
