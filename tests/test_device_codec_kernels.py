"""The device kernels that serve, against the numpy oracles.

One kernel per stage per platform: RS is the XLA bit-matmul (ops/rs) everywhere,
the hash is the Pallas chain on a TPU and the XLA scan on the CPU backend
(models/pipeline.hash_batch_fn). These cases carry the geometries, ragged
lengths, random coefficient matrices and loss patterns that used to pin a second
RS kernel, on the path a request takes: RSCodec.encode, rs.gf_matmul,
fused.make_step and ErasurePipeline, each compared with ops/rs_ref and the host
HighwayHash (ops/highwayhash), which the golden vectors pin in turn.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from minio_tpu import jaxenv
from minio_tpu.models import pipeline
from minio_tpu.models.pipeline import ErasurePipeline, Geometry
from minio_tpu.ops import fused, rs, rs_matrix, rs_ref
from minio_tpu.ops import highwayhash as hh_host
from minio_tpu.ops import highwayhash_jax as hhj
from minio_tpu.ops import highwayhash_pallas as hhp

GEOMETRIES = [(2, 1), (2, 2), (3, 2), (4, 2), (5, 3), (8, 4), (12, 4), (16, 4)]
RAGGED = (1, 100, 4096, 5000)
ENCODE_CASES = [(k, m, s) for k, m in GEOMETRIES for s in RAGGED] + [(12, 4, 64), (16, 4, 257)]


def _rng(*seed):
    return np.random.default_rng(list(seed))


# -- RS: the bit-matmul vs the table-lookup oracle ------------------------------


@pytest.mark.parametrize("k,m,s", ENCODE_CASES)
def test_encode_matches_oracle(k, m, s):
    data = _rng(k, m, s).integers(0, 256, (2, k, s), dtype=np.uint8)
    got = np.asarray(rs.RSCodec(k, m).encode(data))
    assert got.shape == (2, m, s)
    for b in range(2):
        np.testing.assert_array_equal(got[b], rs_ref.encode(data[b], m)[k:])


@pytest.mark.parametrize("seed", range(8))
def test_gf_matmul_random_coeffs_matches_apply_coeffs(seed):
    """Arbitrary [R, K] coefficient matrices, as reconstruct feeds them, not
    just Cauchy parity rows."""
    rng = _rng(seed)
    r, k, s = int(rng.integers(1, 6)), int(rng.integers(1, 9)), int(rng.integers(1, 600))
    coeffs = rng.integers(0, 256, (r, k), dtype=np.uint8)
    shards = rng.integers(0, 256, (1, k, s), dtype=np.uint8)
    w_bits = rs_matrix.bit_expand(coeffs).astype(np.int8)
    got = np.asarray(rs.gf_matmul(shards, w_bits))[0]
    np.testing.assert_array_equal(got, rs_ref.apply_coeffs(coeffs, shards[0]))


# -- the served programs ---------------------------------------------------------


@pytest.mark.parametrize("with_digests", [False, True])
@pytest.mark.parametrize("k,m,missing", [(4, 2, (0,)), (12, 4, (0, 5, 13, 14)), (8, 4, (1, 2))])
def test_pipeline_reconstruct_matches_oracle(k, m, missing, with_digests):
    s = 333
    data = _rng(k, m).integers(0, 256, (2, k, s), dtype=np.uint8)
    full = np.stack([rs_ref.encode(d, m) for d in data])
    present = tuple(i not in missing for i in range(k + m))
    survivors = full[:, [i for i in range(k + m) if present[i]][:k]]
    pipe = ErasurePipeline(Geometry(k, m, block_size=k * s))
    rebuilt, digests = pipe.reconstruct(survivors, present, missing, with_digests=with_digests)
    rebuilt = np.asarray(rebuilt)
    for b in range(2):
        lost = [None if i in missing else full[b, i] for i in range(k + m)]
        want = rs_ref.reconstruct(lost, k, m)
        for j, i in enumerate(missing):
            np.testing.assert_array_equal(rebuilt[b, j], want[i])
    if not with_digests:
        assert digests is None
        return
    want = hh_host.hash256_batch(full[:, list(missing)].reshape(-1, s))
    np.testing.assert_array_equal(np.asarray(digests).reshape(-1, 32), want)


@pytest.mark.parametrize("s", [1024, 333])  # 333: not a multiple of the 32 B hash packet
@pytest.mark.parametrize("k,m", [(12, 4), (4, 4), (2, 2)])  # 2+2: the K+M < 8 barrier arm
def test_fused_step_parity_and_digests(k, m, s):
    data = _rng(k, m, s).integers(0, 256, (3, k, s), dtype=np.uint8)
    step = jax.jit(fused.make_step(rs.RSCodec(k, m).encode, pipeline.hash_batch_fn()))
    parity, digests = (np.asarray(a) for a in step(data))
    assert parity.shape == (3, m, s) and digests.shape == (3, k + m, 32)
    for b in range(3):
        shards = rs_ref.encode(data[b], m)  # data rows first, then parity
        np.testing.assert_array_equal(parity[b], shards[k:])
        np.testing.assert_array_equal(digests[b], hh_host.hash256_batch(shards))


@pytest.mark.parametrize("k,m", [(12, 4), (4, 4), (2, 2)])
def test_parity_step_prefix_is_exact_under_byte_padding(k, m):
    """The small queue pads the shard-byte axis to a bucket; GF(2^8) math is
    per byte position, so the parity prefix at the true length is exact."""
    true_len, bucket = 683, 1024
    data = _rng(k, m).integers(0, 256, (2, k, true_len), dtype=np.uint8)
    padded = np.zeros((2, k, bucket), np.uint8)
    padded[:, :, :true_len] = data
    got = np.asarray(ErasurePipeline(Geometry(k, m)).encode_parity(padded))
    assert got.shape == (2, m, bucket) and not got[:, :, true_len:].any()
    for b in range(2):
        np.testing.assert_array_equal(got[b, :, :true_len], rs_ref.encode(data[b], m)[k:])


# -- which kernel serves: the platform decides -----------------------------------


def test_hash_is_the_xla_scan_off_the_chip():
    assert not jaxenv.on_tpu()
    assert pipeline.hash_batch_fn() is hhj.hash256_batch


def test_hash_is_the_pallas_chain_on_the_chip(monkeypatch):
    monkeypatch.setattr(jaxenv, "on_tpu", lambda: True)
    assert pipeline.hash_batch_fn() is hhp.hash256_batch


def test_kernel_status_names_each_stage_and_its_rule(monkeypatch):
    assert pipeline.kernel_status()["hash"]["serving"] == "xla"
    monkeypatch.setattr(jaxenv, "on_tpu", lambda: True)
    ks = pipeline.kernel_status()
    assert set(ks) == {"rs", "hash"}
    assert ks["rs"]["serving"] == "xla" and ks["hash"]["serving"] == "pallas"
    assert all(set(v) == {"serving", "detail"} and v["detail"] for v in ks.values())
