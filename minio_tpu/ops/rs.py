"""Device (JAX/XLA) Reed-Solomon codec as GF(2) bit-matmuls.

The TPU-first formulation: multiplication by a GF(2^8) constant is linear over
GF(2), so the whole parity computation
    parity[m] = XOR_k coeffs[m,k] * data[k]
lifts to a single {0,1} matrix product over bits:
    y_bits = (x_bits @ W) mod 2,   W = bit_expand(coeffs)  # [K*8, M*8]
with x_bits the LSB-first bits of the data bytes. A [B, K, S] u8 shard batch
becomes a [B*S, K*8] bit matrix; the matmul runs on the MXU (int8 x int8 ->
int32), and the mod-2 + bit-pack are cheap VPU ops that XLA fuses. Encode,
decode/reconstruct, and heal all reduce to this one kernel with different
coefficient matrices (reference equivalents: Encode/ReconstructData/Heal at
/root/reference/cmd/erasure-coding.go:77-119 and erasure-lowlevel-heal.go:31).

This is the one device RS kernel, on every platform (tests/test_rs.py pins it
against the host reference and the golden vectors).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import rs_matrix

_BITS = jnp.arange(8, dtype=jnp.uint8)


def _unpack_bits(x: jax.Array) -> jax.Array:
    """[..., K, S] u8 -> [..., S, K*8] int8 bits, LSB-first."""
    *lead, k, s = x.shape
    xt = jnp.swapaxes(x, -1, -2)  # [..., S, K]
    bits = (xt[..., None] >> _BITS) & jnp.uint8(1)  # [..., S, K, 8]
    return bits.reshape(*lead, s, k * 8).astype(jnp.int8)


def _pack_bits(bits: jax.Array, r: int) -> jax.Array:
    """[..., S, R*8] int bits -> [..., R, S] u8."""
    *lead, s, _ = bits.shape
    b = bits.reshape(*lead, s, r, 8).astype(jnp.uint8)
    packed = jnp.sum(b << _BITS, axis=-1, dtype=jnp.uint8)  # [..., S, R]
    return jnp.swapaxes(packed, -1, -2)


def gf_matmul(data: jax.Array, w_bits: jax.Array) -> jax.Array:
    """Apply a bit-expanded GF coefficient matrix to a shard batch.

    data: [..., K, S] u8; w_bits: [K*8, R*8] {0,1} int8 -> [..., R, S] u8.
    """
    r8 = w_bits.shape[1]
    bits = _unpack_bits(data)
    acc = jax.lax.dot_general(
        bits,
        w_bits,
        (((bits.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return _pack_bits(acc & 1, r8 // 8)


@functools.lru_cache(maxsize=64)
def parity_weights(k: int, m: int) -> np.ndarray:
    # numpy, not jnp: this cache is populated from inside jit traces, and a
    # jnp constant created there would be a leaked Tracer on the next trace.
    return rs_matrix.bit_expand(rs_matrix.parity_matrix(k, m)).astype(np.int8)


@functools.partial(jax.jit, static_argnums=(1,))
def _encode_jit(data: jax.Array, km: tuple[int, int]) -> jax.Array:
    return gf_matmul(data, jnp.asarray(parity_weights(*km)))


class RSCodec:
    """Batched Reed-Solomon codec for a fixed (K data, M parity) geometry."""

    def __init__(self, k: int, m: int):
        if k <= 0 or m <= 0:
            raise ValueError("data and parity counts must be positive")
        if k + m > rs_matrix.MAX_SHARDS:
            raise ValueError(f"at most {rs_matrix.MAX_SHARDS} shards")
        self.k = k
        self.m = m

    def encode(self, data_shards: jax.Array) -> jax.Array:
        """[..., K, S] u8 data shards -> [..., M, S] parity shards."""
        return _encode_jit(data_shards, (self.k, self.m))

    def encode_all(self, data_shards: jax.Array) -> jax.Array:
        """[..., K, S] -> [..., K+M, S] (data then parity), device-side concat."""
        parity = self.encode(data_shards)
        return jnp.concatenate([data_shards, parity], axis=-2)

    def reconstruct_weights(
        self, present: tuple[bool, ...], want: tuple[int, ...]
    ) -> jax.Array:
        """Bit weights rebuilding `want` rows from the first K surviving rows."""
        coeffs = rs_matrix.reconstruct_rows(self.k, self.m, present, want)
        return jnp.asarray(rs_matrix.bit_expand(coeffs).astype(np.int8))

    def apply(self, survivors: jax.Array, w_bits: jax.Array) -> jax.Array:
        """[..., K, S] survivors x precomputed weights -> [..., R, S]."""
        return _apply_jit(survivors, w_bits)


@jax.jit
def _apply_jit(survivors: jax.Array, w_bits: jax.Array) -> jax.Array:
    return gf_matmul(survivors, w_bits)
