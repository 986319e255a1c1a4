"""A degraded window crosses to the device as arrays, once.

BatchingDeviceCodec.reconstruct_batch on the CPU backend, against the numpy
oracles (ops/rs_ref, ops/highwayhash): one strided copy per surviving shard
into a reused staging array when the rows are views at a constant stride in
one exporter (a read window's frames, a raw shard file), row by row when they
are not; resident weights; rebuilt rows handed on as memoryviews. Then the
three consumers of those rows -- a degraded ranged GET, heal, and the legacy
whole-file join -- through the real object layer.
"""

from __future__ import annotations

import os
import sys
import threading

import numpy as np
import pytest

from minio_tpu.control import bufsan
from minio_tpu.object.codec import HostCodec, ReconStaging, pack_survivors
from minio_tpu.object.erasure import BLOCK_SIZE, DIGEST_LEN, _frame_shard, _join_block_rows, _parse_frames
from minio_tpu.ops import highwayhash as hh_host
from minio_tpu.ops import rs_ref
from minio_tpu.parallel.batching import BatchingDeviceCodec
from minio_tpu.utils import bufpool

S = 1000  # shard length of the synthetic windows: not a multiple of the 32 B hash packet
GEOMETRIES = [(12, 4), (4, 4), (2, 2)]
# (first M data rows, last data row + one parity, mixed) per geometry.
LOSSES = {
    (12, 4): [(0, 1, 2, 3), (11, 13), (0, 5, 13, 14)],
    (4, 4): [(0, 1, 2, 3), (3, 5), (1, 3, 5, 6)],
    (2, 2): [(0, 1), (1, 2), (0, 3)],
}
LOSS_CASES = [(k, m, lost) for (k, m) in GEOMETRIES for lost in LOSSES[(k, m)]]


def _encoded(k: int, m: int, b: int, *seed) -> np.ndarray:
    """[b, k+m, S] shards of b seeded blocks, by the oracle."""
    data = np.random.default_rng([k, m, b, *seed]).integers(0, 256, (b, k, S), dtype=np.uint8)
    return np.stack([rs_ref.encode(d, m) for d in data])


def _framed_rows(full: np.ndarray, lost) -> list[list]:
    """rows_batch as a read window hands it over: per surviving shard ONE
    blob of digest||chunk frames, rows sliced from it by _parse_frames."""
    b, t, s = full.shape
    per_shard = {}
    for j in range(t):
        if j in lost:
            continue
        chunks = [full[bi, j].tobytes() for bi in range(b)]
        blob = bytearray(_frame_shard(chunks, [bytes(DIGEST_LEN)] * b))
        per_shard[j] = [c for _, c in _parse_frames(blob, [s] * b)]
    return [[per_shard[j][bi] if j in per_shard else None for j in range(t)] for bi in range(b)]


def _check(results, full, k, m, lost, with_digests=False) -> None:
    """Every rebuilt row is rs_ref.reconstruct's bytes and a memoryview;
    digests are the host hash's."""
    for bi, (chunks, digests) in enumerate(results):
        shards = [None if j in lost else full[bi, j] for j in range(k + m)]
        want = rs_ref.reconstruct(shards, k, m, data_only=False)
        for slot, j in enumerate(lost):
            assert isinstance(chunks[slot], memoryview)
            assert bytes(chunks[slot]) == want[j].tobytes()
        if with_digests:
            got = np.frombuffer(b"".join(digests), np.uint8).reshape(-1, 32)
            np.testing.assert_array_equal(got, hh_host.hash256_batch(full[bi, list(lost)]))
            assert all(isinstance(d, bytes) for d in digests)
        else:
            assert digests is None


@pytest.fixture
def codec_for():
    made = []

    def make(k: int) -> BatchingDeviceCodec:
        c = BatchingDeviceCodec(block_size=k * S, max_batch=8, batch_timeout_s=0.002, mesh=None)
        made.append(c)
        return c

    yield make
    for c in made:
        c.close()


def _shard_counts(c: BatchingDeviceCodec) -> tuple[int, int]:
    st = c.stats()
    return st["recon_shards_packed"], st["recon_shards_strided"]


# -- (a) framed windows: one strided copy a shard --------------------------------


@pytest.mark.parametrize("with_digests", [False, True])
@pytest.mark.parametrize("k,m,lost", LOSS_CASES)
def test_framed_window_matches_oracle_and_packs_strided(codec_for, k, m, lost, with_digests):
    c = codec_for(k)
    full = _encoded(k, m, 6, *lost)
    results = c.reconstruct_batch(_framed_rows(full, lost), k, m, lost, with_digests=with_digests)
    assert len(results) == 6
    _check(results, full, k, m, lost, with_digests)
    assert _shard_counts(c) == (k, k)
    assert c.stats()["host_fallback_recon_blocks"] == 0
    assert c.stats()["blocks_reconstructed"] == 6


# -- (b) rows that are not equally spaced in one exporter go row by row ----------


@pytest.mark.parametrize("variant", ["bytes-rows", "moved-row"])
@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_irregular_shard_packs_row_wise_same_bytes(codec_for, k, m, variant):
    c = codec_for(k)
    lost = LOSSES[(k, m)][0]
    full = _encoded(k, m, 5, 7)
    rows = _framed_rows(full, lost)
    odd = next(j for j in range(k + m) if j not in lost)
    if variant == "bytes-rows":  # a test's or an inline payload's list of bytes
        for r in rows:
            r[odd] = bytes(r[odd])
    else:  # a hedged straggler: one row read into another buffer
        rows[3][odd] = memoryview(bytearray(rows[3][odd]))
    results = c.reconstruct_batch(rows, k, m, lost)
    _check(results, full, k, m, lost)
    assert _shard_counts(c) == (k, k - 1)


def test_raw_shard_file_rows_pack_strided():
    """The legacy whole-file layout: rows are slices of one raw blob, stride S."""
    full = _encoded(4, 4, 4, 9)
    blobs = [memoryview(full[:, j].tobytes()) for j in range(4)]
    rows = [[blobs[j][bi * S : (bi + 1) * S] for j in range(4)] for bi in range(4)]
    staging = np.full((4, 4, S), 0xAA, np.uint8)
    assert pack_survivors(staging, rows, [0, 1, 2, 3], S) == 4
    np.testing.assert_array_equal(staging, full[:, :4])


# -- (c) partial batches after a full one: pad slots zero on the way in ----------


@pytest.mark.parametrize("b_real", [3, 5, 9])
def test_partial_batch_after_full_batch_has_zero_pad(codec_for, monkeypatch, b_real):
    k, m, lost = 4, 4, (0, 2)
    c = codec_for(k)
    b_pad = {3: 4, 5: 8, 9: 16}[b_real]
    dirty = _encoded(k, m, b_pad, 1)  # fills every slot of the [b_pad, K, S] staging array
    c.reconstruct_batch(_framed_rows(dirty, lost), k, m, lost)
    pipe = c._pipelines[(k, m)]
    seen = []
    real = pipe.reconstruct

    def watched(survivors, *a, **kw):
        seen.append((survivors, survivors[b_real:].copy()))
        return real(survivors, *a, **kw)

    monkeypatch.setattr(pipe, "reconstruct", watched)
    full = _encoded(k, m, b_real, 2)
    results = c.reconstruct_batch(_framed_rows(full, lost), k, m, lost)
    _check(results, full, k, m, lost)
    (staging, pad), = seen
    assert staging.shape == (b_pad, k, S) and not pad.any()
    assert c._recon_staging.free_count() == 1  # the dirty array was reused, and is back


# -- (d) rebuilt views outlive their batch's staging array -----------------------


def test_views_survive_later_batches_and_staging_reuse(codec_for, monkeypatch):
    k, m, lost = 4, 4, (1, 3)
    c = codec_for(k)
    first = _encoded(k, m, 4, 100)
    held = c.reconstruct_batch(_framed_rows(first, lost), k, m, lost)
    pipe = c._pipelines[(k, m)]
    stagings = []
    real = pipe.reconstruct

    def watched(survivors, *a, **kw):
        stagings.append(survivors)
        return real(survivors, *a, **kw)

    monkeypatch.setattr(pipe, "reconstruct", watched)
    for n in range(1, 21):
        full = _encoded(k, m, 4, 100 + n)
        _check(c.reconstruct_batch(_framed_rows(full, lost), k, m, lost), full, k, m, lost)
    assert all(a is stagings[0] for a in stagings)  # one array, recycled 20 times
    _check(held, first, k, m, lost)


# -- (e) eight request threads through one codec ---------------------------------


@pytest.mark.race  # stressed under adversarial thread scheduling by tools/race_gate.py
def test_eight_threads_twenty_batches_each(codec_for):
    k, m, lost = 4, 4, (0, 1, 2)
    c = codec_for(k)
    errors_: list = []
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def worker(t: int) -> None:
        try:
            for n in range(20):
                full = _encoded(k, m, 4, t, n)
                results = c.reconstruct_batch(_framed_rows(full, lost), k, m, lost)
                for bi, (chunks, _) in enumerate(results):
                    for slot, j in enumerate(lost):
                        assert bytes(chunks[slot]) == full[bi, j].tobytes(), (t, n, bi, j)
        except BaseException as e:  # noqa: BLE001 - reported by the main thread
            errors_.append(e)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(8)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors_, errors_
    pool = c._recon_staging
    assert pool.outstanding == 0
    assert 1 <= pool.free_count() <= pool.per_shape
    assert c.stats()["recon_batches_run"] == 160
    assert _shard_counts(c) == (160 * k, 160 * k)


def test_staging_pool_bound_and_discard():
    pool = ReconStaging(per_shape=2)
    arrs = [pool.acquire((2, 3, 5)) for _ in range(4)]
    assert pool.outstanding == 4 and len({id(a) for a in arrs}) == 4
    for a in arrs[:3]:
        pool.release(a)
    pool.discard(arrs[3])  # a failed batch's array is never recycled
    assert pool.outstanding == 0 and pool.free_count() == 2
    assert pool.acquire((2, 3, 5)) is arrs[1] and pool.acquire((4, 3, 5)).shape == (4, 3, 5)


def test_failed_batch_discards_its_staging(codec_for, monkeypatch):
    k, m, lost = 2, 2, (0,)
    c = codec_for(k)
    full = _encoded(k, m, 2, 5)
    c.reconstruct_batch(_framed_rows(full, lost), k, m, lost)
    pipe = c._pipelines[(k, m)]

    def boom(*a, **kw):
        raise RuntimeError("device lost")

    monkeypatch.setattr(pipe, "reconstruct", boom)
    with pytest.raises(RuntimeError):
        c.reconstruct_batch(_framed_rows(full, lost), k, m, lost)
    assert c._recon_staging.outstanding == 0 and c._recon_staging.free_count() == 0


# -- the batch's life in the stage ledger ----------------------------------------


def test_recon_rows_are_declared_and_inside_the_outer_row(codec_for):
    """Each of the five stages of a reconstruct batch is a declared ledger
    row with one record a batch, and together they fit inside the unchanged
    outer row codec/reconstruct-batch."""
    from minio_tpu.control.perf import GLOBAL_PERF, STAGES

    def rows():
        return GLOBAL_PERF.ledger.snapshot()["stages"].get("codec", {})

    stages = ("recon-pack", "recon-h2d", "recon-device-wait", "recon-d2h", "recon-unpack")
    before = {s: (sum(h["counts"]), h["sum"]) for s, h in rows().items()}
    k, m, lost = 4, 4, (0, 1)
    c = codec_for(k)
    for n in range(3):
        c.reconstruct_batch(_framed_rows(_encoded(k, m, 4, n), lost), k, m, lost)
    after = rows()

    def delta(stage):
        n0, s0 = before.get(stage, (0, 0.0))
        return sum(after[stage]["counts"]) - n0, after[stage]["sum"] - s0

    inner = 0.0
    for stage in stages:
        assert ("codec", stage) in STAGES, stage
        count, seconds = delta(stage)
        assert count == 3, stage
        inner += seconds
    count, outer = delta("reconstruct-batch")
    assert count == 3 and inner <= outer
    assert abs(c.stats()["device_recon_seconds"] - outer) < 1e-6


# -- (f) weights are resident ----------------------------------------------------


def test_weights_of_a_loss_pattern_upload_once(codec_for):
    k, m, lost = 12, 4, (0, 5, 13, 14)
    c = codec_for(k)
    full = _encoded(k, m, 3, 11)
    rows = _framed_rows(full, lost)
    c.reconstruct_batch(rows, k, m, lost)
    pipe = c._pipelines[(k, m)]
    present = tuple(j not in lost for j in range(k + m))
    before = pipe._recon_weights.cache_info()
    w = pipe._recon_weights(present, lost)
    import jax

    assert isinstance(w, jax.Array) and w.shape == (8 * k, 8 * len(lost))
    for _ in range(10):
        c.reconstruct_batch(rows, k, m, lost)
    after = pipe._recon_weights.cache_info()
    assert after.misses == before.misses  # no upload after the first use
    assert after.hits == before.hits + 11
    assert pipe._recon_weights(present, lost) is w


# -- the consumers: bytes-like rows ----------------------------------------------


@pytest.mark.parametrize("need", [4 * S, 3 * S + 1, S, 17])
def test_join_block_rows_takes_memoryview_rows(need):
    full = _encoded(4, 4, 1, 3)[0]
    rows = [memoryview(full[j].tobytes()).toreadonly() for j in range(4)]
    want = b"".join(full[j].tobytes() for j in range(4))[:need]
    assert _join_block_rows(rows, 4, need) == want


def test_frame_shard_takes_memoryview_chunks():
    full = _encoded(4, 4, 3, 4)
    chunks = [memoryview(full[bi, 0].tobytes()).toreadonly() for bi in range(3)]
    digests = [bytes([bi]) * DIGEST_LEN for bi in range(3)]
    blob = _frame_shard(chunks, digests)
    assert isinstance(blob, bytes)
    for (d, c), bi in zip(_parse_frames(blob, [S] * 3), range(3)):
        assert bytes(d) == digests[bi] and bytes(c) == full[bi, 0].tobytes()


class _Recording:
    """Wraps a codec: keeps the types of the rows reconstruct_batch returned."""

    def __init__(self, inner):
        self.inner = inner
        self.row_types: list[type] = []
        self.batches: list[tuple[int, bool]] = []

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def reconstruct_batch(self, rows_batch, k, m, want, with_digests=False):
        out = self.inner.reconstruct_batch(rows_batch, k, m, want, with_digests)
        self.batches.append((len(rows_batch), with_digests))
        self.row_types += [type(c) for chunks, _ in out for c in chunks]
        return out


def _body(size: int, seed: int) -> bytes:
    return np.random.default_rng(seed).integers(0, 256, size, dtype=np.uint8).tobytes()


def _data_row_drives(layer, bucket, name, n, k):
    fi, _, _ = layer._read_quorum_fi(bucket, name, "")
    return [i for i, rot in enumerate(fi.erasure.distribution) if rot - 1 < k][:n]


@pytest.fixture(scope="module")
def degraded_store(tmp_path_factory):
    """An 8-drive 4+4 store with an 18 MiB object (a window of 16 full blocks
    and one of 2) and a 2 MiB + 777 B one, put by the host codec and read,
    two data drives offline, through a BatchingDeviceCodec."""
    from tests.harness import ErasureHarness

    h = ErasureHarness(tmp_path_factory.mktemp("degraded"), n_disks=8, codec=HostCodec())
    h.layer.make_bucket("b")
    bodies = {"big": _body(18 * BLOCK_SIZE, 1), "tail": _body(2 * BLOCK_SIZE + 777, 2)}
    for name, body in bodies.items():
        h.layer.put_object("b", name, body)
    batcher = BatchingDeviceCodec(block_size=BLOCK_SIZE, max_batch=8, batch_timeout_s=0.002)
    rec = _Recording(batcher)
    h.layer._codec = rec
    h.take_offline(*_data_row_drives(h.layer, "b", "big", 2, 4))
    yield h, rec, batcher, bodies
    batcher.close()


B = BLOCK_SIZE
RANGES = [
    # (offset, length, full blocks the device rebuilds: a window of one block is the host's)
    (3 * B + 1000, 5000, 0),        # inside a block
    (5 * B, B, 0),                  # exactly one block
    (7 * B - 10, 20, 2),            # across a block edge
    (2 * B + 5, 3 * B, 4),          # four blocks, ragged at both ends
    (16 * B - 4096, 4096, 0),       # ends at the window edge
    (16 * B, 4096, 0),              # starts at the window edge
    (14 * B + 1, 3 * B, 4),         # across the window edge: two blocks of each window
    (0, -1, 18),                    # the whole object: 16 + 2
    (18 * B - 1, 1, 0),             # the last byte
]


@pytest.mark.parametrize("offset,length,device_blocks", RANGES)
def test_degraded_ranged_get_returns_reference_bytes(degraded_store, offset, length, device_blocks):
    h, rec, batcher, bodies = degraded_store
    rec.row_types.clear()
    before = batcher.stats()
    san = bufsan.BufSanitizer()
    bufsan.arm(san)
    try:
        _, got = h.layer.get_object("b", "big", offset=offset, length=length)
    finally:
        bufsan.disarm()
    body = bodies["big"]
    assert got == (body[offset:] if length < 0 else body[offset : offset + length])
    after = batcher.stats()
    assert after["blocks_reconstructed"] - before["blocks_reconstructed"] == device_blocks
    packed = after["recon_shards_packed"] - before["recon_shards_packed"]
    assert after["recon_shards_strided"] - before["recon_shards_strided"] == packed
    if device_blocks:
        assert after["host_fallback_recon_blocks"] == before["host_fallback_recon_blocks"]
        assert rec.row_types and set(rec.row_types) == {memoryview}
    assert not san.findings, san.findings
    assert bufpool.shard_pool().outstanding() == 0


def test_irregular_window_still_falls_back_to_host(degraded_store):
    h, rec, batcher, bodies = degraded_store
    before = batcher.stats()
    _, got = h.layer.get_object("b", "tail")
    assert got == bodies["tail"]
    after = batcher.stats()
    assert after["host_fallback_recon_blocks"] - before["host_fallback_recon_blocks"] == 3
    assert after["blocks_reconstructed"] == before["blocks_reconstructed"]


@pytest.mark.parametrize("op", ["get", "heal"])
def test_legacy_whole_file_rows_pack_strided(tmp_path, op):
    """The whole-file layout: rows are views over each shard's one raw blob
    (stride S), so the device reconstruct packs them strided too; the join
    and heal's file image take the rebuilt views."""
    from minio_tpu.object.types import PutObjectOptions
    from tests.harness import ErasureHarness

    h = ErasureHarness(tmp_path, n_disks=8, codec=HostCodec())
    h.layer.make_bucket("b")
    body = _body(3 * BLOCK_SIZE, 8)
    h.layer.put_object("b", "legacy", body, PutObjectOptions(bitrot_algorithm="sha256"))
    victim = _data_row_drives(h.layer, "b", "legacy", 1, 4)[0]
    assert h.corrupt_shard(victim, "b", "legacy", at=10)
    batcher = BatchingDeviceCodec(block_size=BLOCK_SIZE, max_batch=8, batch_timeout_s=0.002)
    h.layer._codec = batcher
    try:
        if op == "heal":
            assert h.layer.heal_object("b", "legacy").disks_healed == 1
            h.layer._codec = HostCodec()
            assert h.layer.heal_object("b", "legacy", dry_run=True).disks_healed == 0
        _, got = h.layer.get_object("b", "legacy", offset=100, length=3 * BLOCK_SIZE - 150)
        assert got == body[100 : 3 * BLOCK_SIZE - 50]
        st = batcher.stats()
        assert st["blocks_reconstructed"] == 3 and st["host_fallback_recon_blocks"] == 0
        assert st["recon_shards_strided"] == st["recon_shards_packed"] == 4
    finally:
        batcher.close()


# -- heal -------------------------------------------------------------------------


def _part_files(h, disk_index: int, bucket: str, name: str) -> list[str]:
    out = []
    for root, _, files in os.walk(os.path.join(h.dirs[disk_index], bucket, name)):
        out += [os.path.join(root, f) for f in files if f.startswith("part.")]
    return sorted(out)


def test_heal_of_three_part_object_through_device_codec(tmp_path):
    """Heal rebuilds whole windows with digests on the device, takes the rows
    as views, frames them, and the healed shard files are the original
    files byte for byte."""
    from tests.harness import ErasureHarness

    h = ErasureHarness(tmp_path, n_disks=16, codec=HostCodec())
    h.layer.make_bucket("b")
    mp = h.layer.multipart
    up = mp.new_multipart_upload("b", "mp")
    bodies = [_body(5 * BLOCK_SIZE, 21), _body(5 * BLOCK_SIZE, 22), _body(2 * BLOCK_SIZE + 123, 23)]
    parts = [mp.put_object_part("b", "mp", up, n + 1, body) for n, body in enumerate(bodies)]
    mp.complete_multipart_upload("b", "mp", up, [(n + 1, p.etag) for n, p in enumerate(parts)])
    victims = _data_row_drives(h.layer, "b", "mp", 3, 12)
    originals = {}
    for i in victims:
        files = _part_files(h, i, "b", "mp")
        assert len(files) == 3
        for f in files:
            with open(f, "rb") as fh:
                originals[f] = fh.read()
            os.remove(f)
    batcher = BatchingDeviceCodec(block_size=BLOCK_SIZE, max_batch=8, batch_timeout_s=0.002)
    rec = _Recording(batcher)
    h.layer._codec = rec
    try:
        result = h.layer.heal_object("b", "mp")
        assert result.disks_healed == 3
        st = batcher.stats()
        # Parts 1 and 2: a window of 5 full blocks each on the device; part 3's
        # window ends in a short block and is the host codec's, as before.
        assert st["blocks_reconstructed"] == 10 and st["recon_batches_run"] == 2
        assert st["host_fallback_recon_blocks"] == 3
        assert st["recon_shards_strided"] == st["recon_shards_packed"] == 24
        assert (5, True) in rec.batches and memoryview in set(rec.row_types)
        for f, want in originals.items():
            with open(f, "rb") as fh:
                assert fh.read() == want, f
        h.layer._codec = HostCodec()
        _, got = h.layer.get_object("b", "mp")
        assert got == b"".join(bodies)
    finally:
        batcher.close()
