"""The flagship device program: fused erasure-encode + bitrot-hash pipeline.

One jitted step turns a batch of 1 MiB-block data shards into parity shards
plus per-shard HighwayHash-256 bitrot digests -- the device-side fusion of the
reference's per-request hot loop (cmd/erasure-encode.go:73-109 feeding
cmd/bitrot-streaming.go:43-65), batched across concurrent uploads so the
host<->device transfer and kernel launches amortize (the BASELINE.json north
star). The decode/heal steps reuse the same GF(2) matmul with reconstruction
weights (cmd/erasure-decode.go:206, erasure-lowlevel-heal.go:31 equivalents).

With a mesh, the steps are pjit-sharded: encode runs with bytes sp-sharded
(pointwise in the byte axis), then the encode->hash boundary reshards streams
across (tp, sp) -- an all-to-all over ICI, the storage analogue of sequence
parallelism. See parallel/mesh.py.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import jaxenv
from ..ops import fused as fused_ops
from ..ops import highwayhash_jax as hhj
from ..ops import rs, rs_matrix
from ..parallel import mesh as mesh_lib


def hash_batch_fn():
    """The device hash that serves on this platform: the Pallas VMEM chain on a
    TPU, the XLA scan on the CPU backend (where Pallas only interprets)."""
    if jaxenv.on_tpu():
        from ..ops import highwayhash_pallas as hhp

        return hhp.hash256_batch
    return hhj.hash256_batch


def kernel_status() -> dict:
    """Which kernel serves each stage and the rule that fixes it, for the
    install report. A fixed rule, not a measurement: a kernel that fails on
    its platform fails the oracle-compared warm-up and the host codec serves."""
    return {
        "rs": {
            "serving": "xla",
            "detail": "fixed: XLA bit-matmul; 38/38 v5e starts, PR 30",
        },
        "hash": {
            "serving": "xla" if hash_batch_fn() is hhj.hash256_batch else "pallas",
            "detail": "by platform: Pallas chain on a TPU, XLA scan on the CPU "
                      "backend; 38/38 v5e starts, PR 30",
        },
    }


@dataclass(frozen=True)
class Geometry:
    """Erasure geometry: K data + M parity shards over a block size."""

    data: int
    parity: int
    block_size: int = 1 << 20  # blockSizeV2, cmd/object-api-common.go:40

    @property
    def total(self) -> int:
        return self.data + self.parity

    @property
    def shard_size(self) -> int:
        return rs_matrix.shard_size(self.block_size, self.data)


class ErasurePipeline:
    """Batched encode/decode/heal steps for a fixed geometry.

    All steps take shard batches shaped [B, K(+M), S] u8 and are jitted once
    per (geometry, batch shape). `mesh` enables SPMD sharding over dp/tp/sp.
    """

    def __init__(self, geometry: Geometry, mesh=None):
        self.geom = geometry
        self.mesh = mesh
        self.codec = rs.RSCodec(geometry.data, geometry.parity)
        self._encode_fn = self._build_encode()

    # -- encode ------------------------------------------------------------

    def _build_encode(self):
        geom = self.geom
        mesh = self.mesh
        hash_fn = hash_batch_fn()
        # Parity-only step for the small-object coalescing path: those
        # batches are padded on the shard-byte axis, so their digests are
        # host-computed at true lengths and the device only owes parity.
        tag = f"k{geom.data}m{geom.parity}"  # the programs' names carry the geometry

        def parity_step(data_shards: jax.Array):
            with jax.named_scope("mtpu.rs_parity_small"):
                return self.codec.encode(data_shards)

        parity_step.__name__ = parity_step.__qualname__ = f"mtpu_parity_{tag}"
        self._parity_fn = jax.jit(parity_step)

        if mesh is None:
            return jax.jit(
                fused_ops.make_step(self.codec.encode, hash_fn, f"mtpu_encode_hash_{tag}")
            )

        # Mesh path: explicit SPMD. The erasure matmul is pointwise in the
        # byte axis so it runs sp-sharded with no communication; the
        # encode->hash boundary is a REAL ICI all-to-all (lax.all_to_all
        # moves the sp byte shards into the stream axis) plus a tp slice of
        # the streams. Round 3 expressed this reshard as a
        # with_sharding_constraint, which GSPMD lowered as an involuntary
        # full rematerialization (replicate + slice); shard_map pins the
        # collective instead.
        tp, sp = mesh.shape["tp"], mesh.shape["sp"]
        if geom.total % (tp * sp):
            raise ValueError(
                f"shard streams ({geom.total}) must divide evenly over the "
                f"tp x sp grid ({tp}x{sp}); uneven stream sharding would "
                "silently drop digests"
            )
        w_parity = rs.parity_weights(geom.data, geom.parity)

        def encode_local(data_local: jax.Array):
            # data_local: [B/dp, K, S/sp], replicated over tp.
            with jax.named_scope("mtpu.rs_encode"):
                parity = rs.gf_matmul(data_local, jnp.asarray(w_parity))
            all_local = jnp.concatenate([data_local, parity], axis=1)
            # Barrier: without it XLA keeps the parameter-aliasing data rows
            # and the freshly computed parity rows in different layouts, and
            # the tiled all-to-all verifier rejects the mixed-layout chunks.
            all_local = jax.lax.optimization_barrier(all_local)
            # [B/dp, T, S/sp] -> all-to-all -> [B/dp, T/sp, S]: byte shards
            # ride ICI into full per-stream rows (sp-major stream order).
            x = jax.lax.all_to_all(all_local, "sp", split_axis=1, concat_axis=2, tiled=True)
            t_loc = x.shape[1] // tp
            ti = jax.lax.axis_index("tp")
            x = jax.lax.dynamic_slice_in_dim(x, ti * t_loc, t_loc, axis=1)
            with jax.named_scope("mtpu.hh256"):
                digests = hash_fn(x.reshape(-1, x.shape[-1])).reshape(
                    x.shape[0], t_loc, 32
                )
            return parity, digests

        # check_vma off: the encode->hash all-to-all mixes parameter-aliasing
        # and computed rows, which the replication checker rejects.
        mapped = jax.shard_map(
            encode_local,
            mesh=mesh,
            in_specs=mesh_lib.data_spec(),
            out_specs=(mesh_lib.parity_spec(), mesh_lib.digest_spec()),
            check_vma=False,
        )

        def mesh_step(data_shards: jax.Array):
            return mapped(data_shards)

        mesh_step.__name__ = mesh_step.__qualname__ = f"mtpu_encode_hash_{tag}"
        return jax.jit(mesh_step)

    def place(self, data_shards: np.ndarray):
        """The host batch where encode() takes it from: on a mesh one sharded
        upload (each chip its [B/dp, K, S/sp] slice from the host, every tp
        replica a copy of its own), else the array as it is."""
        if self.mesh is None:
            return data_shards
        return jax.device_put(data_shards, mesh_lib.data_sharding(self.mesh))

    def encode(self, data_shards) -> tuple[jax.Array, jax.Array]:
        """[B, K, S] -> ([B, M, S] parity, [B, K+M, 32] digests).

        One program encodes and hashes all K+M rows (data rows first, then
        parity) and returns what the caller lacks: the parity rows and every
        row's digest. The data rows are the caller's own input, bit for bit,
        and do not come back.
        """
        return self._encode_fn(data_shards)

    def encode_parity(self, data_shards) -> jax.Array:
        """[B, K, S] -> [B, M, S] parity only, no digests.

        The small-object coalescing path pads the shard-BYTE axis to a
        bucketed length; GF(2^8) math is per byte position, so the parity
        prefix at the true length is bit-exact, but digests of padded rows
        would be wrong -- the caller hashes host-side at true lengths.
        """
        return self._parity_fn(data_shards)

    # -- decode / heal -----------------------------------------------------

    @functools.lru_cache(maxsize=256)
    def _recon_weights(self, present: tuple[bool, ...], want: tuple[int, ...]) -> jax.Array:
        """The bit-expanded reconstruct weights of one loss pattern, resident
        on the device: uploaded on first use, not once a batch."""
        return jnp.asarray(
            rs_matrix.bit_expand(
                rs_matrix.reconstruct_rows(self.geom.data, self.geom.parity, present, want)
            ).astype(np.int8)
        )

    def reconstruct(
        self,
        survivors,
        present: tuple[bool, ...],
        want: tuple[int, ...],
        with_digests: bool = True,
    ):
        """[B, K, S] survivor shards (first K present rows, index order) ->
        [B, len(want), S] rebuilt shards + their digests (or None).

        Degraded GETs don't need digests of the rebuilt rows -- skipping the
        hash halves the device work on that path; heal keeps it fused.
        """
        # hash_fn is a static arg: a stable module-level function, so the jit
        # cache keys cleanly on it.
        hash_fn = hash_batch_fn() if with_digests else None
        return _reconstruct_step(survivors, self._recon_weights(present, want), hash_fn)

    def verify_digests(self, shards) -> jax.Array:
        """[B, T, S] shards -> [B, T, 32] digests (for bitrot deep-scan)."""
        b, t, s = shards.shape
        return hash_batch_fn()(shards.reshape(b * t, s)).reshape(b, t, 32)


def _rebuilt_digests(rebuilt: jax.Array, hash_fn):
    if hash_fn is None:
        return rebuilt, None
    b, r, s = rebuilt.shape
    with jax.named_scope("mtpu.hh256"):
        digests = hash_fn(rebuilt.reshape(b * r, s)).reshape(b, r, 32)
    return rebuilt, digests


def mtpu_reconstruct(survivors: jax.Array, w_bits: jax.Array, hash_fn):
    with jax.named_scope("mtpu.rs_reconstruct"):
        rebuilt = rs.gf_matmul(survivors, w_bits)
    return _rebuilt_digests(rebuilt, hash_fn)


# The function's own name is what a trace's module line reads.
_reconstruct_step = jax.jit(mtpu_reconstruct, static_argnums=(2,))
