"""Property tests for the XOR-bitmatrix codec stack.

Three independent layers cross-checked against each other and the GF
oracle (ops/rs_ref, ops/gf):

  * the bitmatrix lift + XOR-schedule compiler (ops/bitmatrix) -- pure
    numpy, no JAX;
  * the Pallas kernel (ops/rs_pallas) -- interpret mode on CPU-only
    hosts, so these tests pin kernel *semantics* everywhere;
  * the fused encode+hash step (ops/fused) vs the standalone hash.

Randomized over geometry (k, m) and ragged shard lengths with fixed
seeds: the schedules are data-dependent (the generator matrix changes
with k, m), so sweeping geometry is what actually exercises the compiler.
"""

from __future__ import annotations

import numpy as np
import pytest

from minio_tpu.ops import bitmatrix, rs_matrix, rs_ref
from minio_tpu.ops.rs_pallas import RSPallasCodec, apply


GEOMETRIES = [(2, 1), (2, 2), (3, 2), (4, 2), (5, 3), (8, 4), (12, 4), (16, 4)]


# -- schedule compiler vs GF oracle -------------------------------------------


@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_encode_schedule_matches_gf_oracle(k, m):
    rng = np.random.default_rng(k * 100 + m)
    for s in (1, 7, 64, 257):
        shards = rng.integers(0, 256, (k, s), dtype=np.uint8)
        got = bitmatrix.eval_bytes(bitmatrix.encode_schedule(k, m), shards)
        want = rs_ref.encode(shards, m)[k:]
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("seed", range(8))
def test_random_coeff_schedule_matches_apply_coeffs(seed):
    """Arbitrary [R, K] coefficient matrices (the reconstruct path feeds
    these), not just Cauchy parity rows."""
    rng = np.random.default_rng(seed)
    r, k, s = int(rng.integers(1, 6)), int(rng.integers(1, 9)), int(rng.integers(1, 400))
    coeffs = rng.integers(0, 256, (r, k), dtype=np.uint8)
    shards = rng.integers(0, 256, (k, s), dtype=np.uint8)
    sched = bitmatrix.schedule_for_coeffs(coeffs)
    np.testing.assert_array_equal(
        bitmatrix.eval_bytes(sched, shards), rs_ref.apply_coeffs(coeffs, shards)
    )


@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_cse_invariants(k, m):
    sched = bitmatrix.encode_schedule(k, m)
    assert sched.scheduled_xors <= sched.naive_xors
    assert sched.cse_saved == sched.naive_xors - sched.scheduled_xors
    assert sched.n_inputs == k * 8 and sched.n_rows == m * 8
    # Every op references an already-defined node (straight-line program).
    for i, (a, b) in enumerate(sched.ops):
        assert 0 <= a < sched.n_inputs + i
        assert 0 <= b < sched.n_inputs + i
    for r in sched.roots:
        assert -1 <= r < sched.n_inputs + len(sched.ops)
    # Parity rows of a Cauchy matrix are never all-zero.
    assert all(r >= 0 for r in sched.roots)
    assert sched.depth >= 1
    stats = sched.stats()
    assert stats["scheduled_xors"] == len(sched.ops)


def test_production_geometry_cse_actually_saves():
    # 12+4 is the serving geometry; Paar sharing must beat naive by a
    # meaningful margin (measured 58% -- gate far below that).
    sched = bitmatrix.encode_schedule(12, 4)
    assert sched.cse_saved > sched.naive_xors * 0.3
    assert sched.depth <= 24  # log-ish depth from the balanced phase 2


def test_schedule_cache_returns_same_object():
    a = bitmatrix.encode_schedule(4, 2)
    b = bitmatrix.encode_schedule(4, 2)
    assert a is b  # lru_cache identity => free jit static-arg reuse


def test_zero_rows_allowed():
    sched = bitmatrix.schedule_for_coeffs(np.zeros((1, 2), dtype=np.uint8))
    shards = np.arange(16, dtype=np.uint8).reshape(2, 8)
    np.testing.assert_array_equal(
        bitmatrix.eval_bytes(sched, shards), np.zeros((1, 8), dtype=np.uint8)
    )


# -- Pallas kernel (interpret mode on CPU) vs both oracles ---------------------


@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_pallas_encode_matches_oracles(k, m):
    rng = np.random.default_rng(k * 7 + m)
    for s in (1, 100, 4096, 5000):  # ragged tails included
        shards = rng.integers(0, 256, (2, k, s), dtype=np.uint8)
        got = np.asarray(RSPallasCodec(k, m).encode(shards))
        for b in range(shards.shape[0]):
            want = rs_ref.encode(shards[b], m)[k:]
            np.testing.assert_array_equal(got[b], want)
            np.testing.assert_array_equal(
                got[b], bitmatrix.eval_bytes(bitmatrix.encode_schedule(k, m), shards[b])
            )


@pytest.mark.parametrize("seed", range(4))
def test_pallas_apply_random_bitmatrix(seed):
    rng = np.random.default_rng(100 + seed)
    r, k, s = int(rng.integers(1, 5)), int(rng.integers(1, 7)), int(rng.integers(1, 600))
    coeffs = rng.integers(0, 256, (r, k), dtype=np.uint8)
    shards = rng.integers(0, 256, (1, k, s), dtype=np.uint8)
    w_bits = rs_matrix.bit_expand(coeffs)
    got = np.asarray(apply(shards, w_bits))[0]
    np.testing.assert_array_equal(got, rs_ref.apply_coeffs(coeffs, shards[0]))


@pytest.mark.parametrize("k,m,missing", [(4, 2, (0,)), (12, 4, (0, 5, 13, 14)), (8, 4, (1, 2))])
def test_pallas_reconstruct_matches_oracle(k, m, missing):
    rng = np.random.default_rng(k + m)
    s = 333
    shards = rng.integers(0, 256, (k, s), dtype=np.uint8)
    full = rs_ref.encode(shards, m)
    present = tuple(i not in missing for i in range(k + m))
    survivors = np.stack([full[i] for i in range(k + m) if present[i]][:k])
    coeffs = rs_matrix.reconstruct_rows(k, m, present, tuple(missing))
    sched = bitmatrix.schedule_for_coeffs(coeffs)
    got = bitmatrix.eval_bytes(sched, survivors)
    for idx, w in enumerate(missing):
        np.testing.assert_array_equal(got[idx], full[w])


# -- fused encode+hash vs standalone hash --------------------------------------


def test_fused_digests_match_hash_batch():
    from minio_tpu.ops import fused as fused_ops
    from minio_tpu.ops import highwayhash_jax as hhj

    k, m, s = 4, 2, 2048
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (3, k, s), dtype=np.uint8)
    parity, digests = fused_ops.fused_encode_hash(data, k, m, "pallas", "xla")
    parity, digests = np.asarray(parity), np.asarray(digests)
    assert parity.shape == (3, m, s) and digests.shape == (3, k + m, 32)
    for b in range(3):
        shards = rs_ref.encode(data[b], m)  # data rows, then parity
        np.testing.assert_array_equal(parity[b], shards[k:])
        want = np.asarray(hhj.hash256_batch(shards))
        np.testing.assert_array_equal(digests[b], want)


def test_fused_xla_and_pallas_rs_agree():
    from minio_tpu.ops import fused as fused_ops

    k, m, s = 6, 3, 1024
    rng = np.random.default_rng(6)
    data = rng.integers(0, 256, (2, k, s), dtype=np.uint8)
    pp, dp = fused_ops.fused_encode_hash(data, k, m, "pallas", "xla")
    px, dx = fused_ops.fused_encode_hash(data, k, m, "xla", "xla")
    assert np.asarray(pp).shape == (2, m, s)
    np.testing.assert_array_equal(np.asarray(pp), np.asarray(px))
    np.testing.assert_array_equal(np.asarray(dp), np.asarray(dx))
