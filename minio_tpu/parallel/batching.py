"""Cross-request device batching: the encode service.

The BASELINE.json north star: 1 MiB blocks from *concurrent* uploads and heal
scans are fanned into fixed-shape device batches, amortizing host<->device
transfer and program launch across requests (the reference instead runs
per-request SIMD calls on the CPU, cmd/erasure-coding.go:63; its analogous
fan-in point is erasure-sets.go routing concurrent uploads).

Design:
  * Full 1 MiB blocks take the batched device path -- uniform [B, K, S]
    shapes, one fused encode+hash program (models/pipeline.py). With more
    than one local chip the pipeline is shard_map'd over the codec mesh
    (parallel/mesh.py codec_mesh, MTPU_MESH_SHAPE): batches pad to a
    multiple of dp, their real blocks are dealt round-robin to the dp
    groups (_deal), and the array crosses to the chips as one sharded
    device_put.
  * Sub-window blocks >= 4 KiB coalesce on a second queue behind a bounded
    latency budget (MTPU_BATCH_WAIT_US): concurrent small-object PUTs share
    one parity-only device batch, padded on the shard-BYTE axis (GF math is
    per byte position, so the true-length parity prefix is bit-exact);
    digests are host-computed at true lengths. Tiny blocks and low-QPS
    traffic still fall back to the host C++ codec (object/codec.py
    HostCodec) -- a device round-trip isn't worth it for a cold single
    block (the latency-SLO-vs-occupancy tradeoff from SURVEY.md section 7
    step 2).
  * The batcher thread collects requests until `max_batch` or
    `batch_timeout_s` after the first arrival, pads the batch to a bucketed
    size (1/2/4/8/16/32...) to bound XLA compilations, runs the program, and
    resolves futures. Each batch's copy home starts when it is launched.
    Under sustained load the worker double-buffers: batch i+1 is
    dispatched (JAX async) before batch i is resolved, so batch i's
    transfer runs beside the device's compute of i+1 and the worker's own
    host work on the batches around it.
"""

from __future__ import annotations

import os
import queue
import threading
import time as _time
from concurrent.futures import Future
from dataclasses import dataclass, field

import jax
import numpy as np

from ..control import tracing
from ..control.perf import GLOBAL_PERF
from ..control.profiler import COPIED, GLOBAL_PROFILER
from ..models.pipeline import ErasurePipeline, Geometry
from ..object.codec import (
    BlockCodec,
    HostCodec,
    ReconStaging,
    run_device_reconstruct,
    strided_runs,
    uniform_recon_plan,
)
from ..ops import rs_matrix
from ..parallel import mesh as mesh_lib
from ..control.sanitizer import san_lock, san_rlock

_BUCKETS = (1, 2, 4, 8, 16, 32, 64)

# Small-object coalescing floor: below this a device trip can't win even
# fully batched, and the host codec's latency is already microseconds.
_SMALL_MIN = 4 << 10
# Shard-byte-axis padding buckets start here (powers of two above) so the
# small path compiles O(log(block_size)) programs per (k, m), not one per
# object size.
_SMALL_LEN_MIN = 1 << 10


def _bucket(n: int) -> int:
    for b in _BUCKETS:
        if n <= b:
            return b
    return _BUCKETS[-1]


def _len_bucket(s: int) -> int:
    b = _SMALL_LEN_MIN
    while b < s:
        b <<= 1
    return b


def _deal(b_real: int, b_pad: int, dp: int) -> list[int]:
    """The slot of each real block of a batch of b_pad slots (dp divides it).
    Block i goes to dp group i mod dp, so the groups' real blocks differ by
    at most one and the padding is shared; with dp 1 block i is in slot i."""
    per = b_pad // dp
    return [(i % dp) * per + i // dp for i in range(b_real)]


def _small_wait_s() -> float | None:
    """MTPU_BATCH_WAIT_US: microseconds to hold a small-object batch open
    after the first arrival. Negative or "off" disables the small device
    path entirely (everything sub-window falls back to the host codec);
    0 batches only what is already queued."""
    raw = os.environ.get("MTPU_BATCH_WAIT_US", "").strip().lower()
    if raw in ("off", "disable", "disabled"):
        return None
    try:
        wait = float(raw) if raw else 500.0
    except ValueError:
        wait = 500.0
    if wait < 0:
        return None
    return wait / 1e6


@dataclass
class _Request:
    # The caller's full block (a view into its window, or bytes), not a
    # copy: the worker packs it into the batch's staging array, the only
    # copy, and the result's K data chunks come from there (the device
    # returns parity and digests only). The caller waits on the future, so
    # its window outlives the batch; no view of it may outlive the pack.
    block: bytes | memoryview
    future: Future
    # When the request was queued (perf_counter): codec/queue-wait is the
    # dispatch start less this, for the oldest request of a batch.
    enqueued: float = field(default_factory=_time.perf_counter)


@dataclass
class _SmallRequest:
    block: bytes  # raw sub-window block (bytes or memoryview)
    future: Future
    # As _Request.enqueued: codec/small-queue-wait is the pack's start less
    # this, for the oldest request of a batch (the hold included).
    enqueued: float = field(default_factory=_time.perf_counter)


class BatchingDeviceCodec(BlockCodec):
    """BlockCodec running full blocks through a batched device pipeline."""

    def __init__(
        self,
        block_size: int = 1 << 20,
        max_batch: int = 64,
        batch_timeout_s: float = 0.0005,
        mesh="auto",
    ):
        self.block_size = block_size
        self.max_batch = max_batch
        self.batch_timeout_s = batch_timeout_s
        # "auto" resolves to parallel/mesh.codec_mesh() at first worker
        # creation (device enumeration stays off the constructor); None on
        # single-device hosts keeps the plain per-device pipeline.
        self.mesh = mesh
        self.small_wait_s = _small_wait_s()
        self._host = HostCodec()
        self._queues: dict[tuple, queue.Queue] = {}
        self._pipelines: dict[tuple[int, int], ErasurePipeline] = {}
        self._threads: dict[tuple, threading.Thread] = {}
        self._lock = san_lock("BatchingDeviceCodec._lock")
        # Counters are bumped by batch workers AND request threads; += is
        # load/add/store, so a dedicated leaf lock (LOCK_ORDER: taken inside
        # _lock, never the reverse) guards every read-modify-write.
        self._stats_lock = san_lock("BatchingDeviceCodec._stats_lock")
        self._stop = threading.Event()
        # Served-traffic counters (admin/metrics + tests assert the device
        # pipeline actually carries production blocks).
        self.blocks_encoded = 0
        self.batches_run = 0
        self.blocks_reconstructed = 0
        self.recon_batches_run = 0
        # Of the K surviving shards of every reconstruct batch, how many
        # crossed into the staging array as one strided copy (the rest row
        # by row: rows in buffers of their own).
        self.recon_shards_packed = 0
        self.recon_shards_strided = 0
        self._recon_staging = ReconStaging()
        # Full-block batches stage in arrays of their own free list: two a
        # shape, the batch being packed and the one in the pending slot.
        # pack_copies counts the numpy copies that moved block bytes into
        # them: one a run of a caller's window (and dp group), one a block
        # that joins no run.
        self._encode_staging = ReconStaging(per_shape=2)
        self.pack_copies = 0
        self.digests_verified = 0
        self.verify_batches_run = 0
        # Padded-slot total: blocks_encoded / blocks_padded = batch occupancy
        # (how much of each fixed-shape device program carries real data).
        self.blocks_padded = 0
        # Device-vs-CPU routing: work the batcher DECLINED to put on the
        # device (tails, irregular patterns, over-budget chunk lengths).
        self.host_fallback_blocks = 0
        self.host_fallback_recon_blocks = 0
        self.host_fallback_digest_chunks = 0
        # Small-object coalescing path (sub-window blocks, parity on device,
        # digests host-side at true lengths).
        self.small_blocks_encoded = 0
        self.small_batches_run = 0
        self.small_blocks_padded = 0
        # The life of a small batch, from the measurements that feed the
        # codec/small-* ledger rows: the round trip of the parity program
        # (launch to parity bytes on the host), how long sub-blocks sat
        # queued (summed over blocks, the hold included), the small workers'
        # idle time out of their whole loop time, and the user bytes.
        self.small_encode_seconds = 0.0
        self.small_queue_wait_block_seconds = 0.0
        self.small_worker_idle_seconds = 0.0
        self.small_worker_wall_seconds = 0.0
        self.small_user_bytes = 0
        # Batches whose device->host transfer overlapped the next batch's
        # compute (the worker's one-deep pending slot engaged).
        self.double_buffered_batches = 0
        # Of those, the batches whose copy home had been started at their
        # launch, so that the transfer also ran beside the worker's host
        # work before it came back for them. Every launch starts its copy.
        self.d2h_early_batches = 0
        # Multi-chip fan-out accounting: chip_blocks[g] counts real (non-pad)
        # blocks the dp-group g carried; with no mesh both stay trivial.
        self.mesh_devices = 1
        self.chip_blocks: list[int] = []
        # Over the full-block batches of a mesh: b_real / dp (what every dp
        # group would carry if blocks could be cut), the real blocks of the
        # fullest group (even / fullest = the balance of the dealing), and
        # the chips that were given at least one real block.
        self.mesh_blocks_even = 0.0
        self.mesh_blocks_fullest = 0
        self.mesh_chip_batches = 0
        # Round-trip seconds per kernel class by the HOST clock: launch to
        # the bytes' arrival on the host, queueing behind the batch before
        # included. Not device time (control/devtrace.py reads that from a
        # profiler trace). device_encode_seconds holds full-block batches
        # only (/ batches_run = a full batch's round trip); small batches
        # have small_encode_seconds.
        self.device_encode_seconds = 0.0
        self.device_recon_seconds = 0.0
        self.device_verify_seconds = 0.0
        # The life of a full-block batch, from the measurements that feed
        # the codec/* ledger rows: how long blocks sat queued (summed over
        # blocks), how long the workers sat on an empty queue out of their
        # whole loop time (idle + the stages ~= wall), and the bytes that
        # crossed to the device and back for the user bytes encoded.
        self.queue_wait_block_seconds = 0.0
        self.worker_idle_seconds = 0.0
        self.worker_wall_seconds = 0.0
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.encoded_user_bytes = 0
        # Chunk lengths the device verify path has compiled for. Tail chunks
        # are effectively unique per object size; without a cap every
        # distinct length would pay a fresh XLA compile.
        self._verify_lens: set[int] = set()

    # -- worker management ---------------------------------------------------

    def _mesh_for(self, k: int, m: int):
        """The codec mesh, or None when the geometry doesn't tile it.

        Caller holds self._lock ("auto" resolution mutates self.mesh). The
        pipeline's shard_map path needs (k+m) streams to divide the tp x sp
        grid and the shard byte axis to divide sp; geometries that don't fit
        run the plain single-device pipeline rather than refusing to serve.
        """
        if self.mesh == "auto":
            self.mesh = mesh_lib.codec_mesh()
        mesh = self.mesh
        if mesh is None:
            return None
        tp, sp = mesh.shape["tp"], mesh.shape["sp"]
        geom = Geometry(k, m, self.block_size)
        if geom.total % (tp * sp) or geom.shard_size % sp:
            return None
        return mesh

    def _pipeline_locked(self, k: int, m: int) -> ErasurePipeline:
        key = (k, m)
        pipe = self._pipelines.get(key)
        if pipe is None:
            mesh = self._mesh_for(k, m)
            pipe = self._pipelines[key] = ErasurePipeline(
                Geometry(k, m, self.block_size), mesh=mesh
            )
            if mesh is not None:
                with self._stats_lock:
                    self.mesh_devices = max(self.mesh_devices, mesh.size)
                    dp = mesh.shape["dp"]
                    if len(self.chip_blocks) < dp:
                        self.chip_blocks.extend([0] * (dp - len(self.chip_blocks)))
        return pipe

    def _ensure_worker(self, k: int, m: int) -> queue.Queue:
        key = (k, m)
        with self._lock:
            if key not in self._queues:
                q: queue.Queue[_Request] = queue.Queue()
                self._queues[key] = q
                self._pipeline_locked(k, m)
                t = threading.Thread(
                    target=self._worker, args=(key,), daemon=True, name=f"encode-batch-{k}-{m}"
                )
                t.start()  # start before registering: close() joins registrants
                self._threads[key] = t
        return self._queues[key]

    def _ensure_small_worker(self, k: int, m: int) -> queue.Queue:
        key = (k, m, "small")
        with self._lock:
            if key not in self._queues:
                q: queue.Queue[_SmallRequest] = queue.Queue()
                self._queues[key] = q
                self._pipeline_locked(k, m)
                t = threading.Thread(
                    target=self._small_worker,
                    args=(key,),
                    daemon=True,
                    name=f"encode-batch-small-{k}-{m}",
                )
                t.start()
                self._threads[key] = t
        return self._queues[key]

    # -- warm-up ---------------------------------------------------------------

    def warm(self, k: int, m: int) -> dict:
        """Compile, run and oracle-check the programs a (k, m) deployment's
        PUT path reaches, before this codec serves.

        A request waits at most 60 s for its batch (_encode), and on the chip
        each program costs seconds to tens of seconds to compile, so nothing
        the encode path can reach may compile under a request: one fused
        encode+hash program per padded batch size, and one parity program per
        (batch bucket x shard-length bucket) of the small-object queue. On
        the CPU backend (tests, sandbox dry runs) programs compile in about a
        second and are not worth a minute of boot, so only the smallest of
        each kind runs there -- same code, shorter list. Two reconstruct
        programs (degraded GET and heal of the first m data rows) prove the
        decode side; other loss patterns compile on first use, off any
        timeout.

        Every output is compared with the host codec: a kernel that runs but
        disagrees raises here, not in a user's object. Returns
        {"programs", "seconds"}."""
        from concurrent.futures import ThreadPoolExecutor

        from .. import jaxenv

        t0 = _time.perf_counter()
        self._ensure_worker(k, m)  # builds the pipeline
        pipe = self._pipelines[(k, m)]
        full = jaxenv.on_tpu()
        s = rs_matrix.shard_size(self.block_size, k)
        dp = pipe.mesh.shape["dp"] if pipe.mesh is not None else 1
        batches = _BUCKETS if full else _BUCKETS[:1]
        plan: list[tuple] = [
            ("encode", b, s) for b in sorted({-(-b // dp) * dp for b in batches})
        ]
        if self.small_wait_s is not None:
            lens = []  # every shard-length bucket a [4 KiB, block) tail maps to
            n = _len_bucket(rs_matrix.shard_size(_SMALL_MIN, k))
            while n <= _len_bucket(rs_matrix.shard_size(self.block_size - 1, k)):
                lens.append(n)
                n <<= 1
            plan += [("parity", b, n) for b in batches for n in (lens if full else lens[:1])]
        recon_b = 16 if full else 2  # one codec group, as GET and heal send it
        plan += [("reconstruct", recon_b, s), ("reconstruct+digests", recon_b, s)]

        def host_digests(rows: np.ndarray) -> np.ndarray:  # [B, T, S] -> [B, T, 32]
            flat = np.ascontiguousarray(rows).reshape(-1, rows.shape[-1])
            return self._host._digests(flat).reshape(*rows.shape[:2], 32)

        def run(step: tuple) -> None:
            kind, b, n = step
            rng = np.random.default_rng(b * 1_000_003 + n)
            data = rng.integers(0, 256, (b, k, n), dtype=np.uint8)
            want = np.stack([self._host._encode_one(data[i], m) for i in range(b)])
            if kind == "encode":  # parity rows back, all k+m rows hashed
                got, digests = pipe.encode(pipe.place(data))  # as a batch is launched
                want_rows, hashed = want[:, k:], want
            elif kind == "parity":
                got, digests = pipe.encode_parity(data), None
                want_rows = hashed = want[:, k:]
            else:  # the first m data rows lost, rebuilt from the next k rows
                present = (False,) * m + (True,) * k
                got, digests = pipe.reconstruct(
                    want[:, m : m + k], present, tuple(range(m)),
                    with_digests=kind.endswith("digests"),
                )
                want_rows = hashed = want[:, :m]
            ok = np.array_equal(np.asarray(got), want_rows) and (
                digests is None
                or np.array_equal(np.asarray(digests), host_digests(hashed))
            )
            if not ok:
                raise RuntimeError(
                    f"device {kind} program (batch {b}, shard {n} B, {k}+{m}) "
                    "disagrees with the host codec"
                )

        # XLA compiles outside the GIL: a few threads cut a cold boot's
        # compile wall without changing what is compiled.
        with ThreadPoolExecutor(
            max_workers=min(8, os.cpu_count() or 1), thread_name_prefix="codec-warm"
        ) as pool:
            for f in [pool.submit(run, step) for step in plan]:
                f.result()
        return {"programs": len(plan), "seconds": round(_time.perf_counter() - t0, 3)}

    def _collect(self, q: queue.Queue, first, window_s: float) -> list:
        batch = [first]
        start = _time.monotonic()
        while len(batch) < self.max_batch:
            remaining = window_s - (_time.monotonic() - start)
            if remaining <= 0:
                break
            try:
                batch.append(q.get(timeout=remaining))
            except queue.Empty:
                break
        return batch

    def _worker(self, key: tuple[int, int]) -> None:
        k, m = key
        q = self._queues[key]
        pipe = self._pipelines[key]
        # One-deep pending slot: under sustained load batch i+1 is
        # dispatched (JAX queues transfer+compute asynchronously) before
        # batch i is resolved. Batch i's copy home started at its launch,
        # so its D2H overlaps the device's compute of i+1 and the worker's
        # scatter of i-1 and collect, pack and h2d of i+1; d2h is what is
        # left of it when the worker comes back.
        pending = None
        mark = _time.perf_counter()
        while not self._stop.is_set():
            with tracing.stage("worker-idle", "codec") as idle:
                try:
                    first = q.get(timeout=0.1)
                except queue.Empty:
                    first = None
            if first is None:
                if pending is not None:
                    self._resolve_batch(pending)
                    pending = None
            else:
                with tracing.stage("collect", "codec"):
                    batch = self._collect(q, first, self.batch_timeout_s)
                dispatched = self._dispatch_batch(pipe, k, m, batch)
                if pending is not None:
                    self._resolve_batch(pending)
                    if dispatched is not None:
                        with self._stats_lock:
                            self.double_buffered_batches += 1
                            self.d2h_early_batches += 1
                pending = dispatched
                if pending is not None and q.empty():
                    # No follow-on work queued: resolve now, don't buy overlap
                    # with latency the SLO pays for.
                    self._resolve_batch(pending)
                    pending = None
            now = _time.perf_counter()
            with self._stats_lock:
                self.worker_idle_seconds += idle.wall
                self.worker_wall_seconds += now - mark
            mark = now
        if pending is not None:
            self._resolve_batch(pending)

    def _pack(self, flat: np.ndarray, batch: list[_Request], slots: list[int], dp: int) -> int:
        """Copy the batch's blocks into their slots of `flat` ([b_pad, K*S],
        every byte of it written): one strided copy per run of a caller's
        window (strided_runs) and dp group, one copy per block that joins no
        run; then the real slots' K*S - n tail and the pad slots zeroed.
        Returns the copies that moved block bytes. No view of a caller's
        buffer outlives this call (_Window.release() invalidates them)."""
        n = self.block_size
        per = flat.shape[0] // dp
        copies = 0
        runs = strided_runs([req.block for req in batch], n)
        try:
            for start, stop, run in runs:
                if run is None:
                    flat[slots[start], :n] = np.frombuffer(batch[start].block, np.uint8)
                    copies += 1
                    continue
                for g in range(dp):  # block i sits in slot (i mod dp)*per + i div dp
                    first = start + (g - start) % dp
                    if first < stop:
                        src = run[first - start :: dp]
                        flat[slots[first] : slots[first] + len(src), :n] = src
                        copies += 1
        finally:
            runs = run = src = None  # a traceback must not pin the caller's buffer
        if flat.shape[1] > n:
            flat[:, n:] = 0
        for g in range(dp):
            real = len(range(g, len(batch), dp))
            if real < per:
                flat[g * per + real : (g + 1) * per] = 0
        return copies

    def _dispatch_batch(self, pipe: ErasurePipeline, k: int, m: int, batch: list[_Request]):
        """Marshal + launch one encode batch; returns the pending record to
        resolve later, or None if dispatch itself failed."""
        staging = None
        try:
            # Each stage takes its own bookkeeping inside, so that the stages
            # leave nothing of the worker's time between them.
            with tracing.stage("pack", "codec"):
                t_dispatch = _time.perf_counter()
                waits = [t_dispatch - req.enqueued for req in batch]
                GLOBAL_PERF.ledger.record("codec", "queue-wait", max(waits))
                with self._stats_lock:
                    self.queue_wait_block_seconds += sum(waits)
                s = rs_matrix.shard_size(self.block_size, k)
                b_real = len(batch)
                b_pad = _bucket(b_real)
                dp = pipe.mesh.shape["dp"] if pipe.mesh is not None else 1
                b_pad = -(-b_pad // dp) * dp  # dp must divide the batch axis
                slots = _deal(b_real, b_pad, dp)
                # Reused across batches of this shape: the program has read
                # it by the time _resolve_batch gives it back.
                staging = self._encode_staging.acquire((b_pad, k, s))
                copies = self._pack(staging.reshape(b_pad, k * s), batch, slots, dp)
            # encode-batch runs from the launch to the bytes' arrival on the
            # host (_resolve_batch closes it): the round trip, by the host's
            # clock. Under double-buffering the next batch's dispatch falls
            # inside it.
            enc = tracing.stage("encode-batch", "codec")
            enc.__enter__()
            with tracing.stage("h2d", "codec"):
                arr = staging
                h2d = arr.nbytes
                if pipe.mesh is not None:
                    # The sharded upload alone, apart from the launch: every
                    # tp replica takes its own copy of its slice from the host.
                    with tracing.stage("mesh-put", "codec"):
                        arr = pipe.place(arr)
                    h2d *= pipe.mesh.shape["tp"]
                parity, digests = pipe.encode(arr)
                # Start the way home now, every shard of it: the bytes come
                # back while the worker does other host work, and
                # _resolve_batch's d2h waits only for what is left.
                parity.copy_to_host_async()
                digests.copy_to_host_async()
                GLOBAL_PROFILER.copy.record("device-h2d", COPIED, h2d)
                with self._stats_lock:
                    self.h2d_bytes += h2d
            return (batch, parity, digests, k, m, slots, b_pad, enc, pipe, staging, copies)
        except Exception as e:  # noqa: BLE001
            if staging is not None:
                self._encode_staging.discard(staging)  # the runtime may still read it
            for req in batch:
                if not req.future.done():
                    req.future.set_exception(e)
            return None

    def _resolve_batch(self, rec) -> None:
        batch, parity, digests, k, m, slots, b_pad, enc, pipe, staging, copies = rec
        b_real = len(batch)
        try:
            # What the worker pays waiting for the device (not device time:
            # under double-buffering the next batch is already in flight),
            # then what is left of the way back to the host, started at
            # launch, of what the host lacks: the parity rows and the
            # digests. The data rows are in the staging array.
            with tracing.stage("device-wait", "codec"):
                jax.block_until_ready((parity, digests))
            with tracing.stage("d2h", "codec"):
                parity_np = np.asarray(parity)  # [b_pad, M, S]
                digests_np = np.asarray(digests)  # [b_pad, K+M, 32]
            with tracing.stage("scatter", "codec"):
                enc.__exit__(None, None, None)
                d2h = parity_np.nbytes + digests_np.nbytes
                GLOBAL_PROFILER.copy.record("device-d2h", COPIED, d2h)
                if pipe.mesh is not None:
                    dp = pipe.mesh.shape["dp"]
                    groups = [0] * dp  # real blocks of each dp group
                    for slot in slots:
                        groups[slot // (b_pad // dp)] += 1
                with self._stats_lock:
                    self.device_encode_seconds += enc.wall
                    self.batches_run += 1
                    self.blocks_encoded += b_real
                    self.blocks_padded += b_pad
                    self.pack_copies += copies
                    self.d2h_bytes += d2h
                    self.encoded_user_bytes += b_real * self.block_size
                    if pipe.mesh is not None:
                        for g in range(min(dp, len(self.chip_blocks))):
                            self.chip_blocks[g] += groups[g]
                        self.mesh_blocks_even += b_real / dp
                        self.mesh_blocks_fullest += max(groups)
                        self.mesh_chip_batches += (
                            sum(1 for n in groups if n) * (pipe.mesh.size // dp)
                        )
                for slot, req in zip(slots, batch):
                    req.future.set_result(
                        (
                            [staging[slot, j].tobytes() for j in range(k)]
                            + [parity_np[slot, j].tobytes() for j in range(m)],
                            [digests_np[slot, j].tobytes() for j in range(k + m)],
                        )
                    )
        except Exception as e:  # noqa: BLE001
            self._encode_staging.discard(staging)  # the runtime may still read it
            for req in batch:
                if not req.future.done():
                    req.future.set_exception(e)
            return
        # Parity and digests are home, so the program has consumed its input
        # and every result holds its own bytes: the array may stage again.
        self._encode_staging.release(staging)

    def _small_worker(self, key: tuple) -> None:
        k, m = key[0], key[1]
        q = self._queues[key]
        pipe = self._pipelines[(k, m)]
        window = self.small_wait_s or 0.0
        mark = _time.perf_counter()
        while not self._stop.is_set():
            with tracing.stage("small-worker-idle", "codec") as idle:
                try:
                    first = q.get(timeout=0.1)
                except queue.Empty:
                    first = None
            if first is not None:
                with tracing.stage("small-collect", "codec"):
                    batch = self._collect(q, first, window)
                self._run_small_batch(pipe, k, m, batch)
            now = _time.perf_counter()
            with self._stats_lock:
                self.small_worker_idle_seconds += idle.wall
                self.small_worker_wall_seconds += now - mark
            mark = now

    def _run_small_batch(self, pipe: ErasurePipeline, k: int, m: int, batch: list[_SmallRequest]) -> None:
        """One small batch on its worker thread, stage by stage: small-pack,
        encode-batch-small (h2d, the parity program, d2h), small-digest,
        small-scatter. With small-worker-idle and small-collect they are the
        worker's wall time."""
        try:
            with tracing.stage("small-pack", "codec"):
                t_pack = _time.perf_counter()
                waits = [t_pack - req.enqueued for req in batch]
                GLOBAL_PERF.ledger.record("codec", "small-queue-wait", max(waits))
                datas = [np.frombuffer(req.block, dtype=np.uint8) for req in batch]
                shard_lens = [rs_matrix.shard_size(d.size, k) for d in datas]
                # Pad the shard BYTE axis, not the block: GF(2^8) is per byte
                # position, so parity[:, :true_len] of the padded batch is
                # bit-identical to encoding at true length. (Padding the block
                # itself would change ceil(len/k) and thus the parity bytes.)
                s_pad = _len_bucket(max(shard_lens))
                b_real = len(batch)
                b_pad = _bucket(b_real)
                arr = np.zeros((b_pad, k, s_pad), dtype=np.uint8)
                for i, d in enumerate(datas):
                    arr[i, :, : shard_lens[i]] = rs_matrix.split(d, k)
            with tracing.stage("encode-batch-small", "codec") as st:
                parity = np.asarray(pipe.encode_parity(arr))  # [b_pad, M, s_pad]
            with tracing.stage("small-digest", "codec"):
                # Digests at TRUE length, same host hash HostCodec uses --
                # padded-row digests would be wrong, and this keeps the
                # result bit-identical to the host fallback.
                rows = [
                    np.ascontiguousarray(
                        np.concatenate([arr[i, :, :s_i], parity[i, :, :s_i]], axis=0)
                    )  # [K+M, s_i]
                    for i, s_i in enumerate(shard_lens)
                ]
                digests = [self._host._digests(r) for r in rows]
            with tracing.stage("small-scatter", "codec"):
                with self._stats_lock:
                    self.small_encode_seconds += st.wall
                    self.small_queue_wait_block_seconds += sum(waits)
                    self.small_batches_run += 1
                    self.small_blocks_encoded += b_real
                    self.small_blocks_padded += b_pad
                    self.small_user_bytes += sum(d.size for d in datas)
                for req, r, digs in zip(batch, rows, digests):
                    req.future.set_result(
                        (
                            [r[j].tobytes() for j in range(k + m)],
                            [digs[j].tobytes() for j in range(k + m)],
                        )
                    )
        except Exception as e:  # noqa: BLE001
            for req in batch:
                if not req.future.done():
                    req.future.set_exception(e)

    # -- BlockCodec interface -------------------------------------------------

    def encode(self, blocks, k, m):
        with tracing.span(
            "erasure.encode", "erasure", blocks=len(blocks), k=k, m=m
        ):
            return self._encode(blocks, k, m)

    def _encode(self, blocks, k, m):
        futures: list[Future | None] = [None] * len(blocks)
        host_idx: list[int] = []
        q = None
        sq = None
        for i, block in enumerate(blocks):
            n = len(block)
            if n == self.block_size:
                if q is None:
                    q = self._ensure_worker(k, m)
                f: Future = Future()
                q.put(_Request(block, f))
                futures[i] = f
            elif self.small_wait_s is not None and _SMALL_MIN <= n < self.block_size:
                # Sub-window block: coalesce with concurrent small PUTs into
                # one parity-only device batch (MTPU_BATCH_WAIT_US window).
                if sq is None:
                    sq = self._ensure_small_worker(k, m)
                f = Future()
                sq.put(_SmallRequest(block, f))
                futures[i] = f
            else:
                host_idx.append(i)
        with self._stats_lock:
            self.host_fallback_blocks += len(host_idx)
        host_results = (
            self._host.encode([blocks[i] for i in host_idx], k, m) if host_idx else []
        )
        out: list = [None] * len(blocks)
        for j, i in enumerate(host_idx):
            out[i] = host_results[j]
        for i, f in enumerate(futures):
            if f is not None:
                out[i] = f.result(timeout=60)
        return out

    def reconstruct(self, shards, k, m, want):
        return self._host.reconstruct(shards, k, m, want)

    def reconstruct_batch(self, rows_batch, k, m, want, with_digests=False):
        """Degraded-GET / heal windows of full blocks with a uniform loss
        pattern run as ONE padded-batch device program (the served decode
        path the reference runs per block, cmd/erasure-decode.go:206,
        erasure-lowlevel-heal.go:31); tails and irregular batches fall back
        to the host codec, mirroring the encode-side split. The window
        crosses as arrays, on the caller's thread: one strided copy per
        surviving shard into a reused staging array, and the rebuilt rows
        come back as memoryviews over the array the batch came home in."""
        with tracing.span(
            "erasure.reconstruct", "erasure", blocks=len(rows_batch), k=k, m=m
        ):
            plan = uniform_recon_plan(rows_batch, k) if len(rows_batch) > 1 else None
            if plan is None or plan[2] != rs_matrix.shard_size(self.block_size, k):
                with self._stats_lock:
                    self.host_fallback_recon_blocks += len(rows_batch)
                return self._host.reconstruct_batch(rows_batch, k, m, want, with_digests)
            _, surv, s = plan
            self._ensure_worker(k, m)
            with tracing.stage("reconstruct-batch", "codec") as st:
                out, strided = run_device_reconstruct(
                    self._pipelines[(k, m)], self._recon_staging, rows_batch, k,
                    tuple(want), surv, s, with_digests,
                )
            with self._stats_lock:
                self.device_recon_seconds += st.wall
                self.recon_batches_run += 1
                self.blocks_reconstructed += len(rows_batch)
                self.recon_shards_packed += k
                self.recon_shards_strided += strided
            return out

    def digests_batch(self, chunks):
        """Deep-scan / heal verification batches run on the device
        (pipeline.verify_digests, the scanner's batched bitrot consumer --
        VERDICT r3 #9); small or ragged batches stay on the host."""
        if len(chunks) < 4 or len({len(c) for c in chunks}) != 1:
            with self._stats_lock:
                self.host_fallback_digest_chunks += len(chunks)
            return self._host.digests_batch(chunks)
        length = len(chunks[0])
        # Full-chunk lengths (ceil(block/k) for any plausible k) are the
        # steady-state production sizes: always device-eligible, never
        # counted against the cap, so one-off tail lengths can't lock the
        # hot path out of the compile budget.
        full_chunk = length in {-(-self.block_size // k) for k in range(1, 33)}
        if not full_chunk:
            with self._lock:
                if length not in self._verify_lens:
                    if length < (16 << 10) or len(self._verify_lens) >= 8:
                        # Tiny chunks or too many distinct lengths: the
                        # device compile costs more than it saves.
                        pass_to_host = True
                    else:
                        self._verify_lens.add(length)
                        pass_to_host = False
                else:
                    pass_to_host = False
            if pass_to_host:
                with self._stats_lock:
                    self.host_fallback_digest_chunks += len(chunks)
                return self._host.digests_batch(chunks)
        from ..models.pipeline import ErasurePipeline, Geometry
        from ..object.codec import bucket_batch

        key = "verify"
        with self._lock:
            pipe = self._pipelines.get(key)
            if pipe is None:
                # Geometry is irrelevant for pure digesting; any instance
                # provides the jitted verify step.
                pipe = self._pipelines[key] = ErasurePipeline(Geometry(1, 1))
        # Bucketed sub-batches (<= the largest bucket) so each chunk length
        # costs a bounded number of XLA compilations, however many chunks a
        # big part brings.
        out: list[bytes] = []
        cap = bucket_batch(len(chunks))
        for lo in range(0, len(chunks), cap):
            sub = chunks[lo : lo + cap]
            n_pad = bucket_batch(len(sub))
            arr = np.zeros((n_pad, 1, len(sub[0])), dtype=np.uint8)
            for i, c in enumerate(sub):
                arr[i, 0] = np.frombuffer(c, dtype=np.uint8)
            with tracing.stage("verify-batch", "codec") as st:
                digs = np.asarray(pipe.verify_digests(arr))  # [n_pad, 1, 32]
            with self._stats_lock:
                self.device_verify_seconds += st.wall
                self.verify_batches_run += 1
                self.digests_verified += len(sub)
            out.extend(digs[i, 0].tobytes() for i in range(len(sub)))
        return out

    # -- metrics surface ------------------------------------------------------

    def queue_depths(self) -> dict[str, int]:
        """Pending encode requests per worker queue (full + small paths)."""
        with self._lock:
            out = {}
            for key, q in self._queues.items():
                name = f"{key[0]}x{key[1]}"
                if len(key) > 2:
                    name += "-small"
                out[name] = q.qsize()
            return out

    def stats(self) -> dict:
        """Counter snapshot for the /metrics/node codec/device series."""
        with self._stats_lock:
            return {
                "blocks_encoded": self.blocks_encoded,
                "batches_run": self.batches_run,
                "blocks_padded": self.blocks_padded,
                "blocks_reconstructed": self.blocks_reconstructed,
                "recon_batches_run": self.recon_batches_run,
                "recon_shards_packed": self.recon_shards_packed,
                "recon_shards_strided": self.recon_shards_strided,
                "pack_copies": self.pack_copies,
                "encode_staging_allocated": self._encode_staging.allocated,
                "encode_staging_reused": self._encode_staging.reused,
                "digests_verified": self.digests_verified,
                "verify_batches_run": self.verify_batches_run,
                "small_blocks_encoded": self.small_blocks_encoded,
                "small_batches_run": self.small_batches_run,
                "small_blocks_padded": self.small_blocks_padded,
                "small_encode_seconds": self.small_encode_seconds,
                "small_queue_wait_block_seconds": self.small_queue_wait_block_seconds,
                "small_worker_idle_seconds": self.small_worker_idle_seconds,
                "small_worker_wall_seconds": self.small_worker_wall_seconds,
                "small_user_bytes": self.small_user_bytes,
                "double_buffered_batches": self.double_buffered_batches,
                "d2h_early_batches": self.d2h_early_batches,
                "mesh_devices": self.mesh_devices,
                "chip_blocks": list(self.chip_blocks),
                "mesh_blocks_even": self.mesh_blocks_even,
                "mesh_blocks_fullest": self.mesh_blocks_fullest,
                "mesh_chip_batches": self.mesh_chip_batches,
                "host_fallback_blocks": self.host_fallback_blocks,
                "host_fallback_recon_blocks": self.host_fallback_recon_blocks,
                "host_fallback_digest_chunks": self.host_fallback_digest_chunks,
                "device_encode_seconds": self.device_encode_seconds,
                "device_recon_seconds": self.device_recon_seconds,
                "device_verify_seconds": self.device_verify_seconds,
                "queue_wait_block_seconds": self.queue_wait_block_seconds,
                "worker_idle_seconds": self.worker_idle_seconds,
                "worker_wall_seconds": self.worker_wall_seconds,
                "h2d_bytes": self.h2d_bytes,
                "d2h_bytes": self.d2h_bytes,
                "encoded_user_bytes": self.encoded_user_bytes,
                "compiled_verify_lens": len(self._verify_lens),
            }

    def close(self) -> None:
        self._stop.set()
        with self._lock:
            threads = list(self._threads.values())
        for t in threads:
            try:
                t.join(timeout=1.0)
            except RuntimeError:  # raced a thread mid-start
                pass
