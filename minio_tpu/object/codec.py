"""Block codec service: the seam between the object layer and the math.

The object layer hands 1 MiB blocks to a BlockCodec and gets back shard bytes
plus bitrot digests. Implementations:

  * HostCodec  -- numpy GF tables + numpy HighwayHash; the low-latency
    fallback (the reference's always-on CPU SIMD analogue).
  * parallel/batching.BatchingDeviceCodec -- the cross-upload batching
    scheduler: blocks from concurrent requests aggregated into one device
    program (models/pipeline.py) -- the BASELINE.json north-star design.

Both produce bit-identical outputs (tests pin this), so the runtime can
install either.
"""

from __future__ import annotations

import abc

import numpy as np

from ..control import tracing
from ..control.sanitizer import san_lock
from ..ops import highwayhash as hh
from ..ops import rs_matrix, rs_ref


class BlockCodec(abc.ABC):
    """Encode/decode service for erasure blocks."""

    @abc.abstractmethod
    def encode(
        self, blocks: list[bytes], k: int, m: int
    ) -> list[tuple[list[bytes], list[bytes]]]:
        """For each input block: ([K+M shard chunks], [K+M digests])."""

    @abc.abstractmethod
    def reconstruct(
        self, shards: list[bytes | None], k: int, m: int, want: tuple[int, ...]
    ) -> list[bytes]:
        """Rebuild the `want` shard rows from available shards (None = lost)."""

    def reconstruct_batch(
        self,
        rows_batch: list[list[bytes | None]],
        k: int,
        m: int,
        want: tuple[int, ...],
        with_digests: bool = False,
    ) -> "list[tuple[list[bytes | memoryview], list[bytes] | None]]":
        """Rebuild `want` rows for MANY blocks sharing one present-mask.

        The batched analogue of `reconstruct` -- degraded GETs and heal
        rebuild whole windows of blocks with the same shards lost
        (reference per-block loop: cmd/erasure-decode.go:206,
        erasure-lowlevel-heal.go:31), so device codecs override this to run
        one [B, K, S] program instead of B round trips. Returns, per block,
        (rebuilt chunks, their bitrot digests or None when not requested).
        Input rows and rebuilt chunks are bytes-LIKE (buffer protocol): a
        device codec hands back read-only memoryviews over the one array
        its batch came home in, so consumers slice, join, write or frame
        them and must not assume `bytes`. The 32-byte digests are `bytes`.
        """
        from ..ops import bitrot

        out: list[tuple[list[bytes], list[bytes] | None]] = []
        for rows in rows_batch:
            chunks = self.reconstruct(rows, k, m, want)
            digests = [bitrot.digest_of(c) for c in chunks] if with_digests else None
            out.append((chunks, digests))
        return out

    def digests_batch(self, chunks: list[bytes]) -> list[bytes]:
        """Bitrot digests of many shard chunks (deep-scan / heal verify).

        Host codecs use the vectorized native hash; the batching device
        codec routes uniform full-chunk batches through the device
        verify_digests pipeline (the scanner's deep-scan consumer)."""
        from ..ops import bitrot

        return bitrot.digests_of_batch(chunks)

    def encode_frames(self, blocks: list[bytes], k: int, m: int) -> "list[bytes | memoryview]":
        """Per shard ROW: concatenated H(chunk)||chunk frames across blocks.

        This is the byte image appended to each drive's staged shard file
        (streaming-bitrot layout, cmd/bitrot-streaming.go:43-65). The default
        builds frames from encode()'s chunks+digests; HostCodec overrides
        with a single C hash+frame call per row. Rows are bytes-LIKE
        (buffer protocol): consumers write them to files/HTTP bodies and
        must not assume hashability or msgpack support."""
        encoded = self.encode(blocks, k, m)
        rows: list[bytes] = []
        for row in range(k + m):
            parts: list[bytes] = []
            for chunks, digests in encoded:
                parts.append(digests[row])
                parts.append(chunks[row])
            rows.append(b"".join(parts))
        return rows

    def encode_group(self, blocks: list, k: int, m: int) -> "EncodedGroup":
        """Scatter form of encode_frames: per-row IOVEC LISTS instead of
        joined row images, so the fan-out hands each drive its whole group
        as views (one os.writev) and never materializes row bytes. The
        concatenation of a row's iovecs is byte-identical to
        encode_frames()[row]. Also carries the data-row digest stream the
        fast etag hashes (block-major, rows 0..k-1)."""
        encoded = self.encode(blocks, k, m)
        iovecs: list[list] = []
        for row in range(k + m):
            vecs: list = []
            for chunks, digests in encoded:
                vecs.append(digests[row])
                vecs.append(chunks[row])
            iovecs.append(vecs)
        digest_stream = b"".join(
            digests[row] for chunks, digests in encoded for row in range(k)
        )
        return EncodedGroup(iovecs, digest_stream)


class EncodedGroup:
    """One encoded window, scatter layout.

    iovecs[row] is the buffer sequence whose concatenation is that drive's
    staged-file frame image for the group (digest||chunk per block). The
    views alias storage allocated per call and kept alive by the iovecs
    themselves, never the caller's input window -- so the PUT pipeline can
    recycle its pooled read buffer and encode group g+1 while group g's
    writes are still in flight. digest_stream is the concatenated data-row
    digests feeding the streaming etag."""

    __slots__ = ("iovecs", "digest_stream")

    def __init__(self, iovecs: list[list], digest_stream: bytes):
        self.iovecs = iovecs
        self.digest_stream = digest_stream

    def row_nbytes(self, row: int) -> int:
        return sum(len(v) for v in self.iovecs[row])


def _split_block(block: bytes, k: int) -> np.ndarray:
    return rs_matrix.split(np.frombuffer(block, dtype=np.uint8), k)


class HostCodec(BlockCodec):
    """Host CPU codec: C++/AVX2 kernels (native/minio_native.cpp) when the
    toolchain built them, numpy table lookups otherwise. Bit-identical either
    way (tests pin both against the reference golden vectors)."""

    def __init__(self, use_native: bool | None = None):
        from ..ops import native

        self._native = native if (use_native is None and native.available()) or use_native else None

    def _encode_one(self, shards: np.ndarray, m: int) -> np.ndarray:
        k = shards.shape[0]
        if self._native is not None:
            parity = self._native.rs_encode(shards, rs_matrix.parity_matrix(k, m))
            return np.concatenate([shards, parity], axis=0)
        return rs_ref.encode(shards, m)

    def _digests(self, shards: np.ndarray) -> np.ndarray:
        if self._native is not None:
            return self._native.hh256_batch(shards, hh.MAGIC_KEY)
        return hh.hash256_batch(shards)

    def encode(self, blocks, k, m):
        with tracing.span(
            "erasure.encode", "erasure", blocks=len(blocks), k=k, m=m, host=True
        ):
            out = []
            for block in blocks:
                shards = self._encode_one(_split_block(block, k), m)  # [K+M, S]
                digests = self._digests(shards)
                out.append(
                    (
                        [shards[i].tobytes() for i in range(k + m)],
                        [digests[i].tobytes() for i in range(k + m)],
                    )
                )
            return out

    def encode_frames(self, blocks, k, m):
        """Uniform block groups: split + parity are written straight into one
        [G, K+M, S] buffer (rs_encode's `out` view), then ONE strided
        hh256_frame C call per shard row hashes + interleaves in native code
        (native/minio_native.cpp:326) -- no per-shard Python loop, no
        np.stack / per-row ascontiguousarray copies of the group. Rows come
        back as memoryviews (buffer-protocol consumers only: drive appends /
        HTTP bodies)."""
        if (
            self._native is None
            or not blocks
            or len({len(b) for b in blocks}) != 1
            or len(blocks[0]) == 0  # split() rejects empty -- keep paths identical
        ):
            return super().encode_frames(blocks, k, m)
        with tracing.span(
            "erasure.encode_frames", "erasure", blocks=len(blocks), k=k, m=m, host=True
        ):
            pm = np.ascontiguousarray(rs_matrix.parity_matrix(k, m))
            s = rs_matrix.shard_size(len(blocks[0]), k)
            stacked = np.empty((len(blocks), k + m, s), dtype=np.uint8)
            for i, block in enumerate(blocks):
                flat = stacked[i, :k].reshape(-1)
                flat[: len(block)] = np.frombuffer(block, dtype=np.uint8)
                flat[len(block):] = 0  # zero-pad the tail shard (Split semantics)
                self._native.rs_encode(stacked[i, :k], pm, out=stacked[i, k:])
            return self._native.hh256_frame_rows(stacked, hh.MAGIC_KEY)

    def encode_group(self, blocks, k, m):
        """Native scatter path: one [G, K+M, S] buffer takes split + parity
        (rs_encode `out` views), ONE batched hash call digests every shard
        chunk ([G*(K+M), S] view -- ~6x cheaper than the per-row interleave
        in hh256_frame_rows, which also copies every chunk into joined row
        images), and the iovecs are views over that buffer: nothing is
        rejoined. Irregular groups (mixed sizes / no native kernels) fall
        back to the encode()-based default."""
        if (
            self._native is None
            or not blocks
            or len({len(b) for b in blocks}) != 1
            or len(blocks[0]) == 0
        ):
            return super().encode_group(blocks, k, m)
        with tracing.span(
            "erasure.encode_group", "erasure", blocks=len(blocks), k=k, m=m, host=True
        ):
            pm = np.ascontiguousarray(rs_matrix.parity_matrix(k, m))
            g = len(blocks)
            t = k + m
            s = rs_matrix.shard_size(len(blocks[0]), k)
            stacked = np.empty((g, t, s), dtype=np.uint8)
            for i, block in enumerate(blocks):
                flat = stacked[i, :k].reshape(-1)
                flat[: len(block)] = np.frombuffer(block, dtype=np.uint8)
                flat[len(block):] = 0
                self._native.rs_encode(stacked[i, :k], pm, out=stacked[i, k:])
            digests = self._native.hh256_batch(
                stacked.reshape(g * t, s), hh.MAGIC_KEY
            ).reshape(g, t, 32)
            iovecs = [
                [v for i in range(g) for v in (memoryview(digests[i, row]), memoryview(stacked[i, row]))]
                for row in range(t)
            ]
            return EncodedGroup(iovecs, digests[:, :k, :].tobytes())

    def reconstruct(self, shards, k, m, want):
        arrs: list[np.ndarray | None] = [
            np.frombuffer(s, dtype=np.uint8) if s is not None else None for s in shards
        ]
        if self._native is not None and any(s is not None for s in shards):
            present = tuple(s is not None for s in arrs)
            survivors = np.stack([a for a in arrs if a is not None][:k], axis=0)
            coeffs = rs_matrix.reconstruct_rows(k, m, present, tuple(want))
            rebuilt = self._native.rs_apply(survivors, coeffs)
            return [rebuilt[i].tobytes() for i in range(len(want))]
        rebuilt = rs_ref.reconstruct(arrs, k, m, data_only=False)
        return [rebuilt[i].tobytes() for i in want]

    def reconstruct_batch(self, rows_batch, k, m, want, with_digests=False):
        """Uniform windows rebuild with ONE matrix inversion and ONE C call:
        GF(2^8) is per-byte, so B blocks sharing a loss pattern concatenate
        along the byte axis into a [K, B*S] slab (the per-block default was
        256 inversions + 256 kernel calls per 256-block heal). Digests of
        the rebuilt rows batch into one hash call too."""
        plan = uniform_recon_plan(rows_batch, k) if len(rows_batch) > 1 else None
        if plan is None or self._native is None:
            return super().reconstruct_batch(rows_batch, k, m, want, with_digests)
        with tracing.span(
            "erasure.reconstruct", "erasure", blocks=len(rows_batch), k=k, m=m, host=True
        ):
            return self._reconstruct_batch_slab(
                rows_batch, k, m, want, with_digests, plan
            )

    def _reconstruct_batch_slab(self, rows_batch, k, m, want, with_digests, plan):
        present, surv, s = plan
        b = len(rows_batch)
        survivors = np.empty((k, b * s), dtype=np.uint8)
        for bi, rows in enumerate(rows_batch):
            for ki, j in enumerate(surv):
                survivors[ki, bi * s : (bi + 1) * s] = np.frombuffer(rows[j], dtype=np.uint8)
        coeffs = np.ascontiguousarray(rs_matrix.reconstruct_rows(k, m, present, tuple(want)))
        rebuilt = self._native.rs_apply(survivors, coeffs)  # [len(want), B*S]
        w = len(want)
        digests_np = None
        if with_digests:
            # [W, B*S] -> [W*B, S] chunk rows (row-major view), one hash call.
            digests_np = self._digests(rebuilt.reshape(w * b, s)).reshape(w, b, 32)
        out = []
        for bi in range(b):
            chunks = [rebuilt[wi, bi * s : (bi + 1) * s].tobytes() for wi in range(w)]
            digs = (
                [digests_np[wi, bi].tobytes() for wi in range(w)]
                if digests_np is not None
                else None
            )
            out.append((chunks, digs))
        return out


_RECON_BUCKETS = (1, 2, 4, 8, 16, 32, 64)


def bucket_batch(n: int) -> int:
    """Pad a batch count to a small fixed set of sizes so each (pattern,
    geometry) costs at most len(_RECON_BUCKETS) XLA compilations."""
    for b in _RECON_BUCKETS:
        if n <= b:
            return b
    return _RECON_BUCKETS[-1]


class ReconStaging:
    """Bounded free list of host staging arrays for the device reconstruct,
    one list per [b_pad, K, S] shape (the utils/bufpool.py idiom): acquire
    never blocks -- past `per_shape` free arrays a release drops its array,
    and an empty list allocates. At 12+4 a window of 16 blocks stages
    16.8 MB, so the default keeps at most 134 MB per shape. Arrays come
    back dirty: the pack overwrites every real slot and zeroes the pad."""

    def __init__(self, per_shape: int = 8):
        self.per_shape = per_shape
        self._lock = san_lock("ReconStaging._lock")
        self._free: dict[tuple[int, ...], list[np.ndarray]] = {}
        self.outstanding = 0
        # Acquires served from the free list, and those that allocated.
        self.reused = 0
        self.allocated = 0

    def acquire(self, shape: tuple[int, ...]) -> np.ndarray:
        with self._lock:
            self.outstanding += 1
            free = self._free.get(shape)
            if free:
                self.reused += 1
                return free.pop()
            self.allocated += 1
        return np.empty(shape, dtype=np.uint8)

    def release(self, arr: np.ndarray) -> None:
        """Recycle: only once the program that read `arr` has finished."""
        with self._lock:
            self.outstanding -= 1
            free = self._free.setdefault(arr.shape, [])
            if len(free) < self.per_shape:
                free.append(arr)

    def discard(self, arr: np.ndarray) -> None:
        """Give up `arr` without recycling it (a failed batch: the runtime
        may still be reading the array)."""
        with self._lock:
            self.outstanding -= 1

    def free_count(self) -> int:
        with self._lock:
            return sum(len(v) for v in self._free.values())


def _address(buf) -> int:
    return np.frombuffer(buf, dtype=np.uint8).__array_interface__["data"][0]


def strided_runs(views: list, s: int) -> "list[tuple[int, int, np.ndarray | None]]":
    """Split `views` (buffers of s bytes) into maximal runs of consecutive
    views at one constant stride >= s inside one exporter -- a read window's
    frames (stride DIGEST_LEN + S over the shard's blob), slices of a raw
    shard file or a PUT window's blocks (stride S) -- each as (start, stop,
    ONE [stop - start, s] strided array over the exporter). A view that
    joins no run (bytes: its own exporter; a non-contiguous view) is
    (i, i + 1, None): the caller copies that one alone. The arrays export
    the caller's buffer: drop them before the caller may release it."""
    keys = []  # (exporter, address) of a view that may join a run
    for v in views:
        ok = isinstance(v, memoryview) and v.c_contiguous and v.nbytes == s
        keys.append((v.obj, _address(v)) if ok else None)
    out: list[tuple[int, int, np.ndarray | None]] = []
    i = 0
    while i < len(views):
        if keys[i] is None:
            out.append((i, i + 1, None))
            i += 1
            continue
        base, addr = keys[i]
        stop, stride = i + 1, s
        if stop < len(views) and keys[stop] is not None and keys[stop][0] is base:
            stride = keys[stop][1] - addr
        if stride >= s:
            while (stop < len(views) and keys[stop] is not None and keys[stop][0] is base
                   and keys[stop][1] - keys[stop - 1][1] == stride):
                stop += 1
        whole = np.frombuffer(base, dtype=np.uint8)
        out.append((i, stop, np.ndarray(
            (stop - i, s), dtype=np.uint8, buffer=whole,
            offset=addr - _address(whole), strides=(stride, 1),
        )))
        i = stop
    return out


def _shard_window(rows_batch: list, j: int, s: int) -> np.ndarray | None:
    """Shard j's rows as ONE [B, S] strided array, when they are one run
    (strided_runs); None when the input is anything else (rows in buffers
    of their own, a row moved elsewhere): the caller packs those row by row."""
    runs = strided_runs([r[j] for r in rows_batch], s)
    return runs[0][2] if len(runs) == 1 else None


def pack_survivors(staging: np.ndarray, rows_batch: list, surv: list[int], s: int) -> int:
    """Fill staging[:B] ([b_pad, K, S]) with the surviving rows and zero the
    pad slots; returns how many of the K shards crossed as one strided copy
    (the rest went row by row)."""
    b = len(rows_batch)
    strided = 0
    for ki, j in enumerate(surv):
        window = _shard_window(rows_batch, j, s)
        if window is not None:
            staging[:b, ki, :] = window
            strided += 1
        else:
            for bi, rows in enumerate(rows_batch):
                staging[bi, ki] = np.frombuffer(rows[j], dtype=np.uint8)
    if b < staging.shape[0]:
        staging[b:] = 0
    return strided


def run_device_reconstruct(
    pipe,
    staging: ReconStaging,
    rows_batch: list[list[bytes | None]],
    k: int,
    want: tuple[int, ...],
    surv: list[int],
    chunk_size: int,
    with_digests: bool,
) -> "tuple[list[tuple[list[memoryview], list[bytes] | None]], int]":
    """Run a uniform rows_batch as one padded [B, K, S] device reconstruct
    program (the batching codec's served decode/heal path): the window
    crosses host -> device -> host as arrays, once. Returns the per-block
    results -- rebuilt rows as memoryviews over the one array the batch came
    home in -- and the number of shards packed by one strided copy."""
    b_real = len(rows_batch)
    b_pad = max(bucket_batch(b_real), b_real)  # never allocate under b_real
    present = tuple(r is not None for r in rows_batch[0])
    w = len(want)
    arr = staging.acquire((b_pad, k, chunk_size))
    try:
        with tracing.stage("recon-pack", "codec"):
            strided = pack_survivors(arr, rows_batch, surv, chunk_size)
        with tracing.stage("recon-h2d", "codec"):
            rebuilt, digests = pipe.reconstruct(
                arr, present, tuple(want), with_digests=with_digests
            )
        with tracing.stage("recon-device-wait", "codec"):
            rebuilt.block_until_ready()
        with tracing.stage("recon-d2h", "codec"):
            rebuilt_np = np.asarray(rebuilt)
            digests_np = np.asarray(digests) if with_digests else None
    except BaseException:
        staging.discard(arr)
        raise
    # The rebuilt rows have arrived, so the program has consumed its input:
    # only now may another batch overwrite the staging array.
    staging.release(arr)
    with tracing.stage("recon-unpack", "codec"):
        flat = memoryview(rebuilt_np.reshape(-1))
        rows = [flat[i * chunk_size : (i + 1) * chunk_size] for i in range(b_real * w)]
        out = [
            (
                rows[bi * w : (bi + 1) * w],
                [digests_np[bi, wi].tobytes() for wi in range(w)] if with_digests else None,
            )
            for bi in range(b_real)
        ]
    return out, strided


def uniform_recon_plan(
    rows_batch: list[list[bytes | None]], k: int
) -> tuple[tuple[bool, ...], list[int], int] | None:
    """Device-eligibility check for a batched reconstruct.

    Returns (present mask, first-K surviving row indices, chunk size) when
    every block in the batch lost the same shards and all surviving chunks
    share one length -- the shape a single [B, K, S] device program needs.
    None means the batch is irregular (mixed tails/patterns): host path.
    """
    present = tuple(r is not None for r in rows_batch[0])
    if sum(present) < k:
        return None
    sizes: set[int] = set()
    for rows in rows_batch:
        if tuple(r is not None for r in rows) != present:
            return None
        sizes.update(len(r) for r in rows if r is not None)
    if len(sizes) != 1:
        return None
    surv = [i for i, p in enumerate(present) if p][:k]
    return present, surv, sizes.pop()


_default: BlockCodec | None = None


def default_codec() -> BlockCodec:
    """Process-wide codec. Host for now; the server runtime installs the
    batching device codec at startup (see parallel/batching.py)."""
    global _default
    if _default is None:
        _default = HostCodec()
    return _default


def set_default_codec(codec: BlockCodec) -> None:
    global _default
    _default = codec
