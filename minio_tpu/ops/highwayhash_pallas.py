"""Pallas TPU kernel for batched HighwayHash-256: the bitrot serving path.

The XLA scan version (ops/highwayhash_jax.py) pays a while-loop dispatch per
packet chunk -- thousands of tiny sequential steps per shard chunk. This
kernel runs the WHOLE packet chain of a stream tile in one Mosaic program:
hash state lives in a VMEM scratch that persists across the packet-chunk
grid axis, each grid step consumes CHUNK_P statically-unrolled 32-byte
packets for TILE_N independent streams, and only the final state leaves the
chip. Remainder packets (< CHUNK_P) and the tail/finalization (10 permute
rounds + modular reduction) run in plain XLA on the exported state -- they
are O(10) updates vs O(L/32) in the chain.

Layouts:
  * streams ride the LANE axis: every state word is a [4(hash lane), T] u32
    array, so per-update elementwise work is wide VPU ops;
  * hash lanes are stored in order (0, 2, 1, 3): the zipper's even/odd lane
    split then becomes contiguous sublane halves (no strided shuffles);
  * u64 state words are (lo, hi) u32 pairs -- same emulation as the XLA
    path; the elementwise helpers (_add/_mul32/_zipper_pair) are reused
    verbatim from ops/highwayhash_jax since they are axis-agnostic.

Bit-exactness is pinned against the numpy oracle (itself pinned by the
reference's golden vectors, /root/reference/cmd/bitrot.go:214-245) in
tests/test_highwayhash_pallas.py; interpret mode covers CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import jaxenv
from . import highwayhash_jax as hhj
from .highwayhash import MAGIC_KEY, _INIT0, _INIT1

TILE_N = 512  # streams per grid tile (lane axis; multiple of 128)
CHUNK_P = 8  # packets per grid step (statically unrolled updates)

# In-kernel hash-lane order: even lanes first so the zipper splits into
# contiguous sublane halves. Self-inverse permutation.
_LANE_ORDER = (0, 2, 1, 3)
# Word index per (half, kernel lane): lane i consumes words (2i, 2i+1).
_LO_WORDS = tuple(2 * lane for lane in _LANE_ORDER)
_HI_WORDS = tuple(2 * lane + 1 for lane in _LANE_ORDER)


def _zipper_k(v):
    """Zipper with lane axis FIRST in kernel order (even lanes rows 0:2)."""
    lo, hi = v
    even = (lo[0:2], hi[0:2])
    odd = (lo[2:4], hi[2:4])
    (e_lo, e_hi), (o_lo, o_hi) = hhj._zipper_pair(even, odd)
    return (
        jnp.concatenate([e_lo, o_lo], axis=0),
        jnp.concatenate([e_hi, o_hi], axis=0),
    )


def _update_k(st: hhj._VState, lanes) -> hhj._VState:
    """One packet update, lane-axis-first (mirror of hhj._update)."""
    v1 = hhj._add(st.v1, hhj._add(st.mul0, lanes))
    mul0 = hhj._xor(st.mul0, hhj._mul32(v1[0], st.v0[1]))
    v0 = hhj._add(st.v0, st.mul1)
    mul1 = hhj._xor(st.mul1, hhj._mul32(v0[0], v1[1]))
    v0 = hhj._add(v0, _zipper_k(v1))
    v1 = hhj._add(v1, _zipper_k(v0))
    return hhj._VState(v0, v1, mul0, mul1)


def _init_rows(key: bytes) -> np.ndarray:
    """[4 var, 2 half, 4 lane] u32 initial state in kernel lane order."""
    key_lanes = np.frombuffer(key, dtype="<u8")
    rot = (key_lanes >> np.uint64(32)) | (key_lanes << np.uint64(32))
    vals64 = [
        _INIT0 ^ key_lanes,  # v0
        _INIT1 ^ rot,  # v1
        _INIT0,  # mul0
        _INIT1,  # mul1
    ]
    out = np.zeros((4, 2, 4), dtype=np.uint32)
    for vi, v in enumerate(vals64):
        v = v[list(_LANE_ORDER)]
        out[vi, 0] = (v & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        out[vi, 1] = (v >> np.uint64(32)).astype(np.uint32)
    return out


def _kernel(init_ref, data_ref, out_ref, state_ref, *, n_chunks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        state_ref[...] = jnp.broadcast_to(
            init_ref[...][:, :, :, None], state_ref.shape
        )

    st = hhj._VState(
        (state_ref[0, 0], state_ref[0, 1]),
        (state_ref[1, 0], state_ref[1, 1]),
        (state_ref[2, 0], state_ref[2, 1]),
        (state_ref[3, 0], state_ref[3, 1]),
    )
    for c in range(CHUNK_P):
        lanes = (data_ref[c, 0], data_ref[c, 1])  # ([4, T], [4, T]) u32
        st = _update_k(st, lanes)
    for vi, pair in enumerate((st.v0, st.v1, st.mul0, st.mul1)):
        state_ref[vi, 0] = pair[0]
        state_ref[vi, 1] = pair[1]

    @pl.when(j == n_chunks - 1)
    def _():
        out_ref[...] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("n_chunks",))
def _run_chain(init: jax.Array, packets: jax.Array, n_chunks: int) -> jax.Array:
    """packets: [n_chunks*CHUNK_P, 2, 4, N] u32 -> final state [4,2,4,N]."""
    n = packets.shape[-1]
    grid = (n // TILE_N, n_chunks)
    return pl.pallas_call(
        functools.partial(_kernel, n_chunks=n_chunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((4, 2, 4), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((CHUNK_P, 2, 4, TILE_N), lambda i, j: (j, 0, 0, i)),
        ],
        out_specs=pl.BlockSpec((4, 2, 4, TILE_N), lambda i, j: (0, 0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((4, 2, 4, n), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((4, 2, 4, TILE_N), jnp.uint32)],
        interpret=not jaxenv.on_tpu(),
    )(init, packets)


@functools.partial(jax.jit, static_argnames=("length", "key"))
def _hh256_pallas(data: jax.Array, length: int, key: bytes) -> jax.Array:
    """[N, L] u8 -> [N, 32] digests; the packet chain runs in the kernel."""
    n = data.shape[0]
    n_full = length // 32
    chain_p = (n_full // CHUNK_P) * CHUNK_P
    n_pad = -(-n // TILE_N) * TILE_N

    if chain_p:
        words = jax.lax.bitcast_convert_type(
            data[:, : chain_p * 32].reshape(n, chain_p, 8, 4), jnp.uint32
        )  # [N, P, 8]
        lo = words[:, :, np.array(_LO_WORDS)]  # [N, P, 4]
        hi = words[:, :, np.array(_HI_WORDS)]
        packed = jnp.stack([lo, hi], axis=2)  # [N, P, 2, 4]
        arr = jnp.moveaxis(packed, 0, -1)  # [P, 2, 4, N]
        if n_pad != n:
            arr = jnp.pad(arr, ((0, 0), (0, 0), (0, 0), (0, n_pad - n)))
        final = _run_chain(
            jnp.asarray(_init_rows(key)), arr, chain_p // CHUNK_P
        )  # [4, 2, 4, n_pad], kernel lane order
        inv = np.array(_LANE_ORDER)  # self-inverse

        def pair(vi):
            lo_ = final[vi, 0][inv][:, :n]  # [4, N] true lane order
            hi_ = final[vi, 1][inv][:, :n]
            return jnp.moveaxis(lo_, 0, -1), jnp.moveaxis(hi_, 0, -1)  # [N, 4]

        st = hhj._VState(pair(0), pair(1), pair(2), pair(3))
    else:
        st = hhj._init_state(key, (n,))

    # Remainder full packets (< CHUNK_P) + tail + finalization in XLA.
    for p in range(chain_p, n_full):
        words = jax.lax.bitcast_convert_type(
            data[:, p * 32 : (p + 1) * 32].reshape(n, 8, 4), jnp.uint32
        )
        st = hhj._update(st, hhj._lanes_from_words(words))

    r = length - n_full * 32
    if r:
        inc = (np.uint32(r), np.uint32(r))
        st.v0 = hhj._add(
            st.v0, (jnp.full((n, 4), inc[0], jnp.uint32), jnp.full((n, 4), inc[1], jnp.uint32))
        )
        st.v1 = hhj._rotate_32_by(st.v1, r)
        tail = data[:, n_full * 32 :]
        mod4 = r & 3
        packet = jnp.zeros((n, 32), dtype=jnp.uint8)
        packet = packet.at[:, : r & ~3].set(tail[:, : r & ~3])
        if r & 16:
            for i in range(4):
                packet = packet.at[:, 28 + i].set(tail[:, r + i - 4])
        elif mod4:
            rem = tail[:, r & ~3 :]
            packet = packet.at[:, 16].set(rem[:, 0])
            packet = packet.at[:, 17].set(rem[:, mod4 >> 1])
            packet = packet.at[:, 18].set(rem[:, mod4 - 1])
        words = jax.lax.bitcast_convert_type(packet.reshape(n, 8, 4), jnp.uint32)
        st = hhj._update(st, hhj._lanes_from_words(words))

    for _ in range(10):
        st = hhj._update(st, hhj._permute(st.v0))

    halves = []
    for base in (0, 2):
        a3 = hhj._add(hhj._lane(st.v1, base + 1), hhj._lane(st.mul1, base + 1))
        a2 = hhj._add(hhj._lane(st.v1, base), hhj._lane(st.mul1, base))
        a1 = hhj._add(hhj._lane(st.v0, base + 1), hhj._lane(st.mul0, base + 1))
        a0 = hhj._add(hhj._lane(st.v0, base), hhj._lane(st.mul0, base))
        m0, m1 = hhj._modular_reduction(a3, a2, a1, a0)
        halves.extend([m0, m1])
    words = jnp.stack([w for h in halves for w in (h[0], h[1])], axis=-1)
    return jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(n, 32)


def hash256_batch(data: jax.Array, key: bytes = MAGIC_KEY) -> jax.Array:
    """Drop-in peer of highwayhash_jax.hash256_batch: [N, L] u8 -> [N, 32]."""
    if data.ndim != 2:
        lead = data.shape[:-1]
        flat = data.reshape(-1, data.shape[-1])
        return _hh256_pallas(flat, flat.shape[-1], key).reshape(*lead, 32)
    return _hh256_pallas(data, data.shape[-1], key)
