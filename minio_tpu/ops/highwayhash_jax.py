"""Batched HighwayHash-256 on device (JAX), u64 emulated as (lo, hi) u32 pairs.

TPU has no native 64-bit integers, so every u64 state word is a pair of u32
arrays and the 32x32->64 multiply is built from 16-bit partial products. The
hash is sequential per stream (lax.scan over 32-byte packets) and batched over
B independent streams -- the bitrot layout hashes each shard-chunk
independently (cmd/bitrot-streaming.go:43-65), so B = shards x blocks supplies
the vector parallelism the VPU needs.

Bit-exactness vs the numpy oracle (ops/highwayhash.py, itself pinned by the
reference self-test golden, cmd/bitrot.go:214-245) is tested across lengths
covering the remainder path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import jaxenv
from .highwayhash import MAGIC_KEY, _INIT0, _INIT1

U32 = jnp.uint32
_M16 = np.uint32(0xFFFF)

# A u64 "pair" is a tuple (lo, hi) of equal-shape u32 arrays.


def _xor(a, b):
    return (a[0] ^ b[0], a[1] ^ b[1])


def _add(a, b):
    lo = a[0] + b[0]
    carry = (lo < a[0]).astype(U32)
    return lo, a[1] + b[1] + carry


def _mul32(a, b):
    """Full 64-bit product of two u32 arrays, via 16-bit partials."""
    a0 = a & _M16
    a1 = a >> 16
    b0 = b & _M16
    b1 = b >> 16
    t = a0 * b0
    w0 = t & _M16
    k = t >> 16
    t = a1 * b0 + k
    w1 = t & _M16
    w2 = t >> 16
    t = a0 * b1 + w1
    k2 = t >> 16
    hi = a1 * b1 + w2 + k2
    lo = (t << 16) | w0
    return lo, hi


def _shl(a, n: int):
    lo, hi = a
    if n == 0:
        return a
    if n < 32:
        return lo << n, (hi << n) | (lo >> (32 - n))
    return jnp.zeros_like(lo), lo << (n - 32)


def _shr(a, n: int):
    lo, hi = a
    if n == 0:
        return a
    if n < 32:
        return (lo >> n) | (hi << (32 - n)), hi >> n
    return hi >> (n - 32), jnp.zeros_like(hi)


def _byte(pair, i: int):
    """Extract byte i (0 = LSB) of a u64 pair as a u32 array."""
    lo, hi = pair
    if i < 4:
        return (lo >> (8 * i)) & 0xFF
    return (hi >> (8 * (i - 4))) & 0xFF


# Zipper-merge byte shuffles, derived from the reference mask expressions
# (see ops/highwayhash.py::_zipper_merge). Index 0-7 = even-lane bytes,
# 8-15 = odd-lane bytes; output LSB-first.
_ZIP_EVEN = (3, 12, 2, 5, 14, 1, 15, 0)
_ZIP_ODD = (11, 4, 10, 13, 9, 6, 8, 7)


def _zipper_pair(even, odd):
    """Zipper terms for one (even, odd) u64 lane pair."""
    src = [_byte(even, i) for i in range(8)] + [_byte(odd, i) for i in range(8)]

    def build(perm):
        lo = src[perm[0]]
        for j in range(1, 4):
            lo = lo | (src[perm[j]] << (8 * j))
        hi = src[perm[4]]
        for j in range(1, 4):
            hi = hi | (src[perm[4 + j]] << (8 * j))
        return lo, hi

    return build(_ZIP_EVEN), build(_ZIP_ODD)


class _VState:
    """State as 8 arrays of shape [..., 4] u32 (lane-major)."""

    __slots__ = ("v0", "v1", "mul0", "mul1")

    def __init__(self, v0, v1, mul0, mul1):
        self.v0, self.v1, self.mul0, self.mul1 = v0, v1, mul0, mul1

    def flat(self):
        return (*self.v0, *self.v1, *self.mul0, *self.mul1)

    @staticmethod
    def unflat(t):
        return _VState((t[0], t[1]), (t[2], t[3]), (t[4], t[5]), (t[6], t[7]))


def _zipper(v):
    """v: u64 pair with lane axis last (shape [..., 4]) -> zipper terms."""
    lo, hi = v
    even = (lo[..., 0::2], hi[..., 0::2])  # lanes 0, 2
    odd = (lo[..., 1::2], hi[..., 1::2])  # lanes 1, 3
    (e_lo, e_hi), (o_lo, o_hi) = _zipper_pair(even, odd)
    out_lo = jnp.stack([e_lo[..., 0], o_lo[..., 0], e_lo[..., 1], o_lo[..., 1]], axis=-1)
    out_hi = jnp.stack([e_hi[..., 0], o_hi[..., 0], e_hi[..., 1], o_hi[..., 1]], axis=-1)
    return out_lo, out_hi


def _update(st: _VState, lanes) -> _VState:
    v1 = _add(st.v1, _add(st.mul0, lanes))
    mul0 = _xor(st.mul0, _mul32(v1[0], st.v0[1]))
    v0 = _add(st.v0, st.mul1)
    mul1 = _xor(st.mul1, _mul32(v0[0], v1[1]))
    v0 = _add(v0, _zipper(v1))
    v1 = _add(v1, _zipper(v0))
    return _VState(v0, v1, mul0, mul1)


def _permute(v0):
    """Permute(v0): lanes [2,3,0,1] with 32-bit halves swapped."""
    lo, hi = v0
    perm = (2, 3, 0, 1)
    return hi[..., perm], lo[..., perm]


def _rotate_32_by(v, count: int):
    lo, hi = v
    if count == 0:
        return v
    rl = (lo << count) | (lo >> (32 - count))
    rh = (hi << count) | (hi >> (32 - count))
    return rl, rh


def _modular_reduction(a3, a2, a1, a0):
    a3 = (a3[0], a3[1] & np.uint32(0x3FFFFFFF))
    m1 = _xor(a1, _xor(_or64(_shl(a3, 1), _shr(a2, 63)), _or64(_shl(a3, 2), _shr(a2, 62))))
    m0 = _xor(a0, _xor(_shl(a2, 1), _shl(a2, 2)))
    return m0, m1


def _or64(a, b):
    return (a[0] | b[0], a[1] | b[1])


def _lane(pairs, i):
    lo, hi = pairs
    return lo[..., i], hi[..., i]


def _init_state(key: bytes, lead: tuple[int, ...]) -> _VState:
    key_lanes = np.frombuffer(key, dtype="<u8")
    rot = (key_lanes >> np.uint64(32)) | (key_lanes << np.uint64(32))
    v0_np = _INIT0 ^ key_lanes
    v1_np = _INIT1 ^ rot

    def pair(arr64):
        lo = (arr64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        hi = (arr64 >> np.uint64(32)).astype(np.uint32)
        return (
            jnp.broadcast_to(jnp.asarray(lo), (*lead, 4)),
            jnp.broadcast_to(jnp.asarray(hi), (*lead, 4)),
        )

    return _VState(pair(v0_np), pair(v1_np), pair(_INIT0.copy()), pair(_INIT1.copy()))


def _lanes_from_words(words):
    """[..., 8] u32 packet words -> u64 pair with lane axis last [..., 4]."""
    return words[..., 0::2], words[..., 1::2]


# Packets consumed per scan step. The hash is a sequential chain per stream,
# so throughput comes from (a) stream-batch width and (b) amortizing loop
# overhead: each scan step dynamic-slices one contiguous [..., CHUNK, 8]
# window out of HBM (no up-front transpose of the whole buffer, unlike a
# scan over a leading packet axis) and runs CHUNK statically-unrolled
# updates back to back. The deep unroll only pays on the TPU (loop overhead
# dominates there); on CPU it mostly bloats XLA compile time, so the test
# platform keeps the shallow one. Override by setting CHUNK to an int.
CHUNK: int | None = None


def _chunk() -> int:
    if CHUNK is not None:
        return CHUNK
    return 16 if jaxenv.on_tpu() else 4


@functools.partial(jax.jit, static_argnames=("length", "key"))
def _hh256_impl(data: jax.Array, length: int, key: bytes) -> jax.Array:
    lead = data.shape[:-1]
    st = _init_state(key, lead)
    n_full = length // 32
    r = length - n_full * 32

    if n_full:
        words = jax.lax.bitcast_convert_type(
            data[..., : n_full * 32].reshape(*lead, n_full, 8, 4), jnp.uint32
        )  # [..., n_full, 8]  (little-endian u32 words)
        ck = _chunk()
        n_chunks, rem = divmod(n_full, ck)

        if n_chunks:

            def step(carry, i):
                stc = _VState.unflat(carry)
                chunk = jax.lax.dynamic_slice_in_dim(
                    words, i * ck, ck, axis=words.ndim - 2
                )  # [..., ck, 8]
                for c in range(ck):
                    stc = _update(stc, _lanes_from_words(chunk[..., c, :]))
                return stc.flat(), None

            carry, _ = jax.lax.scan(step, st.flat(), jnp.arange(n_chunks, dtype=jnp.int32))
            st = _VState.unflat(carry)

        for c in range(rem):
            st = _update(st, _lanes_from_words(words[..., n_chunks * ck + c, :]))

    if r:
        inc = ((np.uint32(r)), (np.uint32(r)))  # (r<<32) + r as (lo, hi)
        st.v0 = _add(
            st.v0,
            (jnp.full((*lead, 4), inc[0], U32), jnp.full((*lead, 4), inc[1], U32)),
        )
        st.v1 = _rotate_32_by(st.v1, r)
        tail = data[..., n_full * 32 :]
        mod4 = r & 3
        packet = jnp.zeros((*lead, 32), dtype=jnp.uint8)
        packet = packet.at[..., : r & ~3].set(tail[..., : r & ~3])
        if r & 16:
            for i in range(4):
                packet = packet.at[..., 28 + i].set(tail[..., r + i - 4])
        elif mod4:
            rem = tail[..., r & ~3 :]
            packet = packet.at[..., 16].set(rem[..., 0])
            packet = packet.at[..., 17].set(rem[..., mod4 >> 1])
            packet = packet.at[..., 18].set(rem[..., mod4 - 1])
        words = jax.lax.bitcast_convert_type(packet.reshape(*lead, 8, 4), jnp.uint32)
        st = _update(st, _lanes_from_words(words))

    for _ in range(10):
        st = _update(st, _permute(st.v0))

    halves = []
    for base in (0, 2):
        a3 = _add(_lane(st.v1, base + 1), _lane(st.mul1, base + 1))
        a2 = _add(_lane(st.v1, base), _lane(st.mul1, base))
        a1 = _add(_lane(st.v0, base + 1), _lane(st.mul0, base + 1))
        a0 = _add(_lane(st.v0, base), _lane(st.mul0, base))
        m0, m1 = _modular_reduction(a3, a2, a1, a0)
        halves.extend([m0, m1])
    # halves = [h0, h1, h2, h3] as u64 pairs; serialize little-endian.
    words = jnp.stack(
        [w for h in halves for w in (h[0], h[1])], axis=-1
    )  # [..., 8] u32
    return jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(*lead, 32)


def hash256_batch(data: jax.Array, key: bytes = MAGIC_KEY) -> jax.Array:
    """HighwayHash-256 of a batch of equal-length streams on device.

    data: [..., L] u8 -> [..., 32] u8 digests (any leading batch shape).
    """
    return _hh256_impl(data, data.shape[-1], key)
