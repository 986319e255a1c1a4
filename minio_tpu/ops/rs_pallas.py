"""XOR-bitmatrix Pallas TPU kernel for the Reed-Solomon codec.

The previous kernel here re-expressed RS as an MXU int8 bit-matmul; Mosaic
rejected it on hardware because the repack needed sub-32-bit iota and
unsigned reductions, so `pallas_encode_gibs` sat at 0.0 while the XLA path
carried all device traffic. This rewrite drops the matmul formulation
entirely and uses the op family Mosaic demonstrably supports on the VPU
(the HighwayHash kernel next door runs on it): 32-bit AND / logical shift /
XOR, nothing else.

Formulation (arXiv:2108.02692 XOR-scheduled bitmatrix coding over the
Cauchy/Vandermonde construction of arXiv:1611.09968):

  * Host side, shard bytes are bitcast to little-endian u32 lanes -- byte j
    of a shard lands in bits [8j, 8j+8) of word j//4 (the same packing the
    HighwayHash kernel relies on).
  * The [R, K] GF(2^8) coefficient matrix lifts to a binary bitmatrix
    (ops/bitmatrix), compiled once per geometry into an XOR schedule with
    cross-row CSE.
  * In-kernel, input bit-plane (k, b) is the lane-aligned mask
    `(x[k] >> b) & 0x01010101`: bit b of all four bytes in a word, moved to
    bit 0 of each byte. Logical (unsigned) shift never smears sign bits and
    the masked bits never cross byte lanes (b, b_out in 0..7 keeps every
    bit inside its source byte). The schedule XORs planes; output bit-row
    (r, b_out) shifts its root left by b_out and XOR-accumulates into the
    parity word.

Bit-exactness is pinned by tests against ops/rs_ref (and transitively the
reference's golden self-test vectors, /root/reference/cmd/erasure-coding.go:
158-216) plus the schedule-level numpy oracle in ops/bitmatrix. Encode and
reconstruct are the same kernel with different coefficient matrices.

Off-TPU the kernel runs in interpret mode (tests); on a real chip
`encode_all` / `apply` are drop-in peers of ops/rs.RSCodec and bench.py
measures both so the faster path can be picked per-platform.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .. import jaxenv
from . import bitmatrix, rs_matrix

# VPU-native tile: 8 sublanes x TILE_LANE u32 lanes per shard per grid step.
# Small shards take the 128-lane tile (4 KiB/shard/step -- bounds padding on
# the coalesced small-object path); big shards take 512 lanes to amortize
# grid overhead, same lane width the HighwayHash kernel runs.
_TILE_SUB = 8
_SMALL_LANES = 128
_BIG_LANES = 512
_BIG_CUTOFF = 1 << 15  # shard bytes at/above which the 512-lane tile wins

_PLANE_MASK = 0x01010101  # bit 0 of each byte in a u32 word


def _pick_lanes(s: int) -> int:
    return _BIG_LANES if s >= _BIG_CUTOFF else _SMALL_LANES


def _kernel(x_ref, o_ref, *, sched: bitmatrix.XorSchedule, r: int):
    # Pure u32 elementwise: AND + logical shifts + XOR. No iota, no
    # reductions, no sub-32-bit types past the host-side bitcast.
    x = x_ref[0]  # [K, 8, L] u32
    mask = jnp.uint32(_PLANE_MASK)
    vals: dict[int, jax.Array] = {}

    def node(i: int) -> jax.Array:
        v = vals.get(i)
        if v is None:  # an input plane, materialized lazily
            k, b = divmod(i, 8)
            xi = x[k]
            if b:
                xi = jax.lax.shift_right_logical(xi, jnp.uint32(b))
            v = jnp.bitwise_and(xi, mask)
            vals[i] = v
        return v

    for t, (a, b) in enumerate(sched.ops, start=sched.n_inputs):
        vals[t] = jnp.bitwise_xor(node(a), node(b))

    for rr in range(r):
        acc = None
        for bo in range(8):
            root = sched.roots[rr * 8 + bo]
            if root < 0:
                continue
            v = node(root)
            if bo:
                v = jax.lax.shift_left(v, jnp.uint32(bo))
            acc = v if acc is None else jnp.bitwise_xor(acc, v)
        if acc is None:
            acc = jnp.zeros_like(x[0])
        o_ref[0, rr] = acc


@functools.partial(jax.jit, static_argnums=(1,))
def _apply_sched(data: jax.Array, sched: bitmatrix.XorSchedule) -> jax.Array:
    """[B, K, S] u8 shards -> [B, R, S] u8 via the compiled XOR schedule."""
    b, k, s = data.shape
    if k * 8 != sched.n_inputs:
        raise ValueError(f"schedule wants {sched.n_inputs // 8} shards, got {k}")
    r = sched.n_rows // 8
    lanes = _pick_lanes(s)
    tile_bytes = _TILE_SUB * lanes * 4
    s_pad = -(-max(s, 1) // tile_bytes) * tile_bytes
    if s_pad != s:
        data = jnp.pad(data, [(0, 0), (0, 0), (0, s_pad - s)])
    # Little-endian u32 packing: byte j -> bits [8j, 8j+8) of word j//4.
    xu = jax.lax.bitcast_convert_type(
        data.reshape(b, k, s_pad // (_TILE_SUB * lanes * 4), _TILE_SUB, lanes, 4),
        jnp.uint32,
    )  # [B, K, nT, 8, L]
    nt = xu.shape[2]
    out = pl.pallas_call(
        functools.partial(_kernel, sched=sched, r=r),
        grid=(b, nt),
        in_specs=[
            pl.BlockSpec((1, k, 1, _TILE_SUB, lanes), lambda i, j: (i, 0, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, r, 1, _TILE_SUB, lanes), lambda i, j: (i, 0, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, r, nt, _TILE_SUB, lanes), jnp.uint32),
        interpret=not jaxenv.on_tpu(),  # Mosaic runs on the chip only
    )(xu)
    ob = jax.lax.bitcast_convert_type(out, jnp.uint8).reshape(b, r, s_pad)
    return ob[:, :, :s]


def apply(data: jax.Array, w_bits) -> jax.Array:
    """[B, K, S] u8 shards x bit-expanded [K*8, R*8] weights -> [B, R, S] u8.

    Weight orientation matches ops/rs.gf_matmul (rs_matrix.bit_expand
    output). The bitmatrix is compiled to a cached XOR schedule on first
    use; subsequent calls with the same weights hit the schedule cache and
    the jit cache.
    """
    sched = bitmatrix.schedule_for_bits(np.asarray(w_bits))
    return _apply_sched(jnp.asarray(data), sched)


class RSPallasCodec:
    """Drop-in peer of ops/rs.RSCodec backed by the XOR-bitmatrix kernel."""

    def __init__(self, k: int, m: int):
        if k <= 0 or m <= 0:
            raise ValueError("data and parity counts must be positive")
        if k + m > rs_matrix.MAX_SHARDS:
            raise ValueError(f"at most {rs_matrix.MAX_SHARDS} shards")
        self.k = k
        self.m = m
        self._sched = bitmatrix.encode_schedule(k, m)

    def encode(self, data_shards: jax.Array) -> jax.Array:
        """[B, K, S] u8 -> [B, M, S] parity."""
        return _apply_sched(jnp.asarray(data_shards), self._sched)

    def encode_all(self, data_shards: jax.Array) -> jax.Array:
        parity = self.encode(data_shards)
        return jnp.concatenate([data_shards, parity], axis=-2)

    def reconstruct_weights(self, present: tuple[bool, ...], want: tuple[int, ...]):
        coeffs = rs_matrix.reconstruct_rows(self.k, self.m, present, want)
        return rs_matrix.bit_expand(coeffs).astype(np.int8)  # same lift as rs.RSCodec

    def apply(self, survivors: jax.Array, w_bits) -> jax.Array:
        return apply(survivors, w_bits)

    def schedule_stats(self) -> dict:
        return self._sched.stats()
