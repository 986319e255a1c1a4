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
import threading
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

import os

from ..ops import bitmatrix
from ..ops import fused as fused_ops
from ..ops import highwayhash_jax as hhj
from ..ops import rs, rs_matrix, rs_pallas
from ..parallel import mesh as mesh_lib
from ..control.sanitizer import san_lock, san_rlock


# Per-backend hash-kernel selection, cached after one probe+timing pass:
# {"choice": "pallas"|"xla", "pallas_ok": bool, "pallas_gibs": float,
#  "xla_gibs": float, "detail": str}
_HASH_SELECT: dict[str, dict] = {}
# Guards the check-then-probe in hash_selection(): two threads racing the
# first call would otherwise both run the (expensive, jit-compiling) probe
# and clobber each other's verdict.
_HASH_SELECT_LOCK = san_lock("pipeline._HASH_SELECT_LOCK")

# Same shape, for the RS encode kernel (XOR-bitmatrix Pallas vs XLA bit-
# matmul). Separate lock: a hash probe and an rs probe may run concurrently.
_RS_SELECT: dict[str, dict] = {}
_RS_SELECT_LOCK = san_lock("pipeline._RS_SELECT_LOCK")

# Production chunk length: the per-shard slice a 1 MiB block / 12 data
# shards produces (cmd/erasure-utils.go shard math) — the length every
# serving PutObject actually hashes. Probing at toy sizes let a kernel
# that lowers at 8 packets but breaks at the real multi-step grid pass.
_PROBE_CHUNK = rs_matrix.shard_size(1 << 20, 12)


def _probe_and_time_hash(backend: str) -> dict:
    """Correctness-probe the Pallas hash at PRODUCTION chunk size, then time
    it against the XLA scan and select by measurement.

    The Pallas kernel must (a) lower on this backend (Mosaic op support
    varies by release) and (b) match the host oracle bit-for-bit at the
    real ~87 KiB serving chunk length — a multi-step grid, not the 8-packet
    toy shape round 3 probed — before it may serve. A kernel that fails
    either degrades to the XLA scan rather than crashing every PutObject.
    """
    sel = {"choice": "xla", "pallas_ok": False, "pallas_gibs": 0.0,
           "xla_gibs": 0.0, "detail": ""}
    if backend != "tpu":
        # On CPU the Pallas kernel only runs in interpret mode — a pure-
        # Python emulation orders of magnitude slower than compiled XLA,
        # not a serving-grade candidate; timing it at 87 KiB would stall
        # server boot for minutes to confirm a foregone conclusion.
        sel["detail"] = f"backend={backend}: pallas=interpret-only, xla serves"
        return sel
    import time as _time

    from ..ops import highwayhash as hh_host
    from ..ops import highwayhash_pallas as hhp

    rng = np.random.default_rng(7)
    probe = rng.integers(0, 256, (2, _PROBE_CHUNK), dtype=np.uint8)
    try:
        got = np.asarray(hhp.hash256_batch(probe))
        want = hh_host.hash256_batch(probe)
        sel["pallas_ok"] = np.array_equal(got, want)
        if not sel["pallas_ok"]:
            sel["detail"] = f"pallas mismatch at L={_PROBE_CHUNK}"
            return sel
    except Exception as e:  # noqa: BLE001 - any lowering/runtime failure
        sel["detail"] = f"pallas probe failed: {type(e).__name__}: {e}"[:300]
        return sel

    # Both correct — pick by measured throughput at the serving shape.
    timing = rng.integers(0, 256, (16, _PROBE_CHUNK), dtype=np.uint8)
    dev = jax.device_put(jnp.asarray(timing))
    nbytes = timing.size

    def _gibs(fn):
        jax.block_until_ready(fn(dev))  # compile
        t0 = _time.perf_counter()
        iters = 4
        for _ in range(iters):
            out = fn(dev)
        jax.block_until_ready(out)
        return nbytes * iters / (_time.perf_counter() - t0) / (1 << 30)

    try:
        sel["pallas_gibs"] = _gibs(jax.jit(hhp.hash256_batch))
        sel["xla_gibs"] = _gibs(jax.jit(hhj.hash256_batch))
    except Exception as e:  # noqa: BLE001
        sel["detail"] = f"timing failed: {type(e).__name__}: {e}"[:300]
        return sel
    sel["choice"] = "pallas" if sel["pallas_gibs"] >= sel["xla_gibs"] else "xla"
    sel["detail"] = (
        f"measured @L={_PROBE_CHUNK}: pallas={sel['pallas_gibs']:.2f} "
        f"xla={sel['xla_gibs']:.2f} GiB/s -> {sel['choice']}"
    )
    return sel


def hash_selection() -> dict:
    """The cached per-backend probe+timing verdict (for diagnostics/bench)."""
    backend = jax.default_backend()
    with _HASH_SELECT_LOCK:
        if backend not in _HASH_SELECT:
            _HASH_SELECT[backend] = _probe_and_time_hash(backend)
        return _HASH_SELECT[backend]


def _probe_and_time_rs(backend: str) -> dict:
    """Correctness-probe the XOR-bitmatrix Pallas encode at production shape,
    then time it against the XLA GF(2) bit-matmul and select by measurement.

    Mirrors _probe_and_time_hash: the kernel must lower on this backend AND
    match the XLA path bit-for-bit (which is itself pinned to the golden
    vectors) at the real (12, 4) x ~87 KiB serving shape before it may
    serve. Any failure degrades to the XLA matmul with the cause recorded --
    never a silent 0.0.
    """
    sel = {"choice": "xla", "pallas_ok": False, "pallas_gibs": 0.0,
           "xla_gibs": 0.0, "detail": ""}
    if backend != "tpu":
        sel["detail"] = f"backend={backend}: pallas=interpret-only, xla serves"
        return sel
    import time as _time

    rng = np.random.default_rng(11)
    pc = rs_pallas.RSPallasCodec(12, 4)
    xc = rs.RSCodec(12, 4)
    probe = rng.integers(0, 256, (2, 12, _PROBE_CHUNK), dtype=np.uint8)
    try:
        got = np.asarray(pc.encode(probe))
        want = np.asarray(xc.encode(probe))
        sel["pallas_ok"] = np.array_equal(got, want)
        if not sel["pallas_ok"]:
            sel["detail"] = f"pallas encode mismatch at S={_PROBE_CHUNK}"
            return sel
    except Exception as e:  # noqa: BLE001 - any lowering/runtime failure
        sel["detail"] = f"pallas probe failed: {type(e).__name__}: {e}"[:300]
        return sel

    timing = rng.integers(0, 256, (16, 12, _PROBE_CHUNK), dtype=np.uint8)
    dev = jax.device_put(jnp.asarray(timing))
    nbytes = timing.size

    def _gibs(fn):
        jax.block_until_ready(fn(dev))  # compile
        t0 = _time.perf_counter()
        iters = 4
        for _ in range(iters):
            out = fn(dev)
        jax.block_until_ready(out)
        return nbytes * iters / (_time.perf_counter() - t0) / (1 << 30)

    try:
        sel["pallas_gibs"] = _gibs(jax.jit(pc.encode))
        sel["xla_gibs"] = _gibs(jax.jit(xc.encode))
    except Exception as e:  # noqa: BLE001
        sel["detail"] = f"timing failed: {type(e).__name__}: {e}"[:300]
        return sel
    sel["choice"] = "pallas" if sel["pallas_gibs"] >= sel["xla_gibs"] else "xla"
    sel["detail"] = (
        f"measured @S={_PROBE_CHUNK}: pallas={sel['pallas_gibs']:.2f} "
        f"xla={sel['xla_gibs']:.2f} GiB/s -> {sel['choice']}"
    )
    return sel


def codec_selection() -> dict:
    """The cached per-backend RS-kernel probe+timing verdict."""
    backend = jax.default_backend()
    with _RS_SELECT_LOCK:
        if backend not in _RS_SELECT:
            _RS_SELECT[backend] = _probe_and_time_rs(backend)
        return _RS_SELECT[backend]


def rs_encode_mode() -> str:
    """Which RS encode kernel serves: "pallas" or "xla".

    MINIO_TPU_RS = xla | pallas | auto (default). Auto probes the
    XOR-bitmatrix kernel at production shape and serves with whichever
    measured faster -- cached per backend. XLA serves on CPU and whenever
    the probe or timing fails.
    """
    mode = os.environ.get("MINIO_TPU_RS", "auto").lower()
    if mode in ("xla", "pallas"):
        return mode
    return codec_selection()["choice"]


def kernel_status(k: int = 12, m: int = 4) -> dict:
    """Honest per-kernel status for the install report and bench: which
    kernel serves each stage, why, and what the XOR schedule costs. Never a
    silent 0.0 -- a kernel that can't serve carries its cause in `detail`."""
    return {
        "backend": jax.default_backend(),
        "hash": dict(hash_selection()),
        "rs": dict(codec_selection()),
        "hash_mode": hash_mode(),
        "rs_mode": rs_encode_mode(),
        "xor_schedule": bitmatrix.schedule_stats(k, m),
    }


def hash_mode() -> str:
    """Which device hash serves: "pallas" or "xla".

    MINIO_TPU_HASH = xla | pallas | auto (default). Auto probes the Pallas
    VMEM-chain kernel at the production chunk size against the host oracle,
    times it against the XLA scan, and serves with whichever measured
    faster -- cached per backend. The XLA scan serves on CPU (Pallas
    interpret mode is not a compiled candidate) and whenever the probe or
    timing fails.
    """
    mode = os.environ.get("MINIO_TPU_HASH", "auto").lower()
    if mode in ("xla", "pallas"):
        return mode
    return hash_selection()["choice"]


def hash_batch_fn():
    """The device hash implementation the pipeline serves with (hash_mode)."""
    if hash_mode() == "pallas":
        from ..ops import highwayhash_pallas as hhp

        return hhp.hash256_batch
    return hhj.hash256_batch


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
        self.rs_impl = "xla"  # resolved for real in _build_encode
        self._encode_fn = self._build_encode()

    # -- encode ------------------------------------------------------------

    def _build_encode(self):
        geom = self.geom
        mesh = self.mesh
        # Resolved at build time so the probe+timing selection passes run
        # here, as plain device work — never inside a jit trace.
        hash_fn = hash_batch_fn()
        self.rs_impl = rs_encode_mode()
        dev_codec = (
            rs_pallas.RSPallasCodec(geom.data, geom.parity)
            if self.rs_impl == "pallas"
            else self.codec
        )
        # Parity-only step for the small-object coalescing path: those
        # batches are padded on the shard-byte axis, so their digests are
        # host-computed at true lengths and the device only owes parity.
        tag = f"k{geom.data}m{geom.parity}"  # the programs' names carry the geometry

        def parity_step(data_shards: jax.Array):
            with jax.named_scope("mtpu.rs_parity_small"):
                return dev_codec.encode(data_shards)

        parity_step.__name__ = parity_step.__qualname__ = f"mtpu_parity_{tag}"
        self._parity_fn = jax.jit(parity_step)

        if mesh is None:
            return jax.jit(
                fused_ops.make_step(dev_codec.encode, hash_fn, f"mtpu_encode_hash_{tag}")
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
        # hash_fn (resolved above, outside the shard_map trace) gives
        # multi-chip serving the same measured-fastest kernel as
        # single-device — round 4 hardcoded the XLA scan here, silently
        # dropping the Pallas kernel on the scaling path.

        def encode_local(data_local: jax.Array):
            # data_local: [B/dp, K, S/sp], replicated over tp. The RS kernel
            # choice rides into the shard_map body: the XOR-bitmatrix Pallas
            # kernel is pointwise in the byte axis exactly like the matmul,
            # so it runs sp-sharded with no extra communication.
            with jax.named_scope("mtpu.rs_encode"):
                if self.rs_impl == "pallas":
                    parity = dev_codec.encode(data_local)
                else:
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
    def _recon_weights(self, present: tuple[bool, ...], want: tuple[int, ...]):
        return np.asarray(
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
        # hash_fn resolved here (probe runs outside the trace) and passed as
        # a static arg: both candidates are stable module-level functions, so
        # the jit cache keys cleanly on the selection.
        hash_fn = hash_batch_fn() if with_digests else None
        if self.rs_impl == "pallas":
            # Reconstruct variant of the XOR-bitmatrix kernel: same kernel,
            # reconstruction coefficients compiled to their own cached
            # schedule (a static jit arg, like the hash selection).
            sched = bitmatrix.schedule_for_coeffs(
                rs_matrix.reconstruct_rows(self.geom.data, self.geom.parity, present, want)
            )
            return _reconstruct_sched_step(survivors, sched, hash_fn)
        w = jnp.asarray(self._recon_weights(present, want))
        return _reconstruct_step(survivors, w, hash_fn)

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


def mtpu_reconstruct_sched(survivors: jax.Array, sched, hash_fn):
    with jax.named_scope("mtpu.rs_reconstruct"):
        rebuilt = rs_pallas._apply_sched(jnp.asarray(survivors), sched)
    return _rebuilt_digests(rebuilt, hash_fn)


# The functions' own names are what a trace's module line reads.
_reconstruct_step = jax.jit(mtpu_reconstruct, static_argnums=(2,))
_reconstruct_sched_step = jax.jit(mtpu_reconstruct_sched, static_argnums=(1, 2))
