"""Batching device codec tests: bit-identical with host codec, under
concurrency."""

import threading
import time

import jax
import numpy as np
import pytest

from minio_tpu.object.codec import HostCodec, strided_runs
from minio_tpu.ops import rs_matrix
from minio_tpu.parallel import mesh as mesh_lib
from minio_tpu.parallel.batching import BatchingDeviceCodec
from tests import reference_store as ref

# Stressed under adversarial thread scheduling by tools/race_gate.py.
pytestmark = pytest.mark.race


BLOCK = 1 << 20


@pytest.fixture(scope="module")
def batcher():
    b = BatchingDeviceCodec(block_size=BLOCK, max_batch=8, batch_timeout_s=0.002)
    yield b
    b.close()


def test_single_block_matches_host(batcher):
    rng = np.random.default_rng(0)
    block = rng.integers(0, 256, BLOCK).astype(np.uint8).tobytes()
    dev = batcher.encode([block], 4, 2)
    host = HostCodec().encode([block], 4, 2)
    assert dev[0][0] == host[0][0]
    assert dev[0][1] == host[0][1]


def test_partial_block_falls_back_to_host(batcher):
    rng = np.random.default_rng(1)
    block = rng.integers(0, 256, 12345).astype(np.uint8).tobytes()
    dev = batcher.encode([block], 4, 2)
    host = HostCodec().encode([block], 4, 2)
    assert dev[0][0] == host[0][0]


def test_concurrent_requests_batched(batcher):
    rng = np.random.default_rng(2)
    blocks = [rng.integers(0, 256, BLOCK).astype(np.uint8).tobytes() for _ in range(6)]
    host = HostCodec().encode(blocks, 4, 2)
    results = [None] * 6

    def work(i):
        results[i] = batcher.encode([blocks[i]], 4, 2)[0]

    threads = [threading.Thread(target=work, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    for i in range(6):
        assert results[i] is not None, i
        assert results[i][0] == host[i][0], i
        assert results[i][1] == host[i][1], i


def test_mixed_sizes_one_call(batcher):
    rng = np.random.default_rng(3)
    blocks = [
        rng.integers(0, 256, BLOCK).astype(np.uint8).tobytes(),
        rng.integers(0, 256, 777).astype(np.uint8).tobytes(),
        rng.integers(0, 256, BLOCK).astype(np.uint8).tobytes(),
    ]
    dev = batcher.encode(blocks, 4, 2)
    host = HostCodec().encode(blocks, 4, 2)
    for i in range(3):
        assert dev[i][0] == host[i][0], i
        assert dev[i][1] == host[i][1], i


class TestDeviceReconstructServing:
    """The decode/heal serving path runs the batched device pipeline
    (VERDICT r3 #3): degraded GETs and heal must advance the reconstruct
    counters, not silently punt to the host codec."""

    def _harness(self, tmp_path):
        from tests.harness import ErasureHarness

        batcher = BatchingDeviceCodec(block_size=BLOCK, max_batch=8, batch_timeout_s=0.002)
        h = ErasureHarness(tmp_path, n_disks=16, codec=batcher)
        h.layer.make_bucket("b")
        return h, batcher

    def _data_row_drives(self, layer, bucket, name, n, k=12):
        """Indices of n drives whose shard row is a data row."""
        fi, _, _ = layer._read_quorum_fi(bucket, name, "")
        out = [i for i, rot in enumerate(fi.erasure.distribution) if rot - 1 < k]
        return out[:n]

    def test_degraded_get_runs_device_batch(self, tmp_path):
        h, batcher = self._harness(tmp_path)
        try:
            rng = np.random.default_rng(10)
            data = rng.integers(0, 256, 3 * BLOCK).astype(np.uint8).tobytes()
            h.layer.put_object("b", "obj", data)
            h.take_offline(*self._data_row_drives(h.layer, "b", "obj", 2))
            before = batcher.blocks_reconstructed
            _, got = h.layer.get_object("b", "obj")
            assert got == data
            assert batcher.blocks_reconstructed >= before + 3  # all 3 full blocks
            assert batcher.recon_batches_run >= 1
        finally:
            batcher.close()

    def test_heal_runs_device_batch(self, tmp_path):
        h, batcher = self._harness(tmp_path)
        try:
            rng = np.random.default_rng(11)
            data = rng.integers(0, 256, 3 * BLOCK).astype(np.uint8).tobytes()
            h.layer.put_object("b", "obj", data)
            deleted = 0
            for i in self._data_row_drives(h.layer, "b", "obj", 3):
                assert h.delete_shard(i, "b", "obj")
                deleted += 1
            assert deleted == 3
            before = batcher.blocks_reconstructed
            h.layer.heal_object("b", "obj")
            assert batcher.blocks_reconstructed >= before + 3
            _, got = h.layer.get_object("b", "obj")
            assert got == data
        finally:
            batcher.close()

    def test_degraded_tail_block_host_fallback_is_exact(self, tmp_path):
        """Tail blocks (irregular window) must still read back correctly."""
        h, batcher = self._harness(tmp_path)
        try:
            rng = np.random.default_rng(12)
            data = rng.integers(0, 256, 2 * BLOCK + 12345).astype(np.uint8).tobytes()
            h.layer.put_object("b", "obj", data)
            h.take_offline(*self._data_row_drives(h.layer, "b", "obj", 2))
            _, got = h.layer.get_object("b", "obj")
            assert got == data
        finally:
            batcher.close()


class TestSmallObjectBatching:
    """Cross-request coalescing of sub-block objects (MTPU_BATCH_WAIT_US):
    many concurrent small PUTs ride ONE device dispatch, bit-identical to
    the host codec."""

    def test_small_objects_coalesce_into_one_batch(self, monkeypatch):
        monkeypatch.setenv("MTPU_BATCH_WAIT_US", "20000")
        b = BatchingDeviceCodec(block_size=BLOCK, max_batch=8, batch_timeout_s=0.002)
        try:
            rng = np.random.default_rng(30)
            sizes = [5000, 9000, 40000, 123457]
            blocks = [rng.integers(0, 256, n).astype(np.uint8).tobytes() for n in sizes]
            host = HostCodec().encode(blocks, 4, 2)
            results = [None] * len(blocks)

            def work(i):
                results[i] = b.encode([blocks[i]], 4, 2)[0]

            threads = [threading.Thread(target=work, args=(i,)) for i in range(len(blocks))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30)
            for i in range(len(blocks)):
                assert results[i] is not None, i
                assert results[i][0] == host[i][0], i
                assert results[i][1] == host[i][1], i
            st = b.stats()
            assert st["small_blocks_encoded"] == len(blocks)
            # The 20 ms window must have coalesced 4 concurrent requests
            # into fewer dispatches than requests.
            assert 1 <= st["small_batches_run"] < len(blocks)
        finally:
            b.close()

    def test_small_path_disabled_when_wait_unset(self, monkeypatch):
        monkeypatch.delenv("MTPU_BATCH_WAIT_US", raising=False)
        b = BatchingDeviceCodec(block_size=BLOCK, max_batch=8, batch_timeout_s=0.002)
        try:
            assert b.small_wait_s is None or b.small_wait_s >= 0  # default on (500us)
        finally:
            b.close()
        monkeypatch.setenv("MTPU_BATCH_WAIT_US", "off")
        b2 = BatchingDeviceCodec(block_size=BLOCK, max_batch=8, batch_timeout_s=0.002)
        try:
            assert b2.small_wait_s is None
            rng = np.random.default_rng(31)
            block = rng.integers(0, 256, 12345).astype(np.uint8).tobytes()
            dev = b2.encode([block], 4, 2)
            host = HostCodec().encode([block], 4, 2)
            assert dev[0][0] == host[0][0]
            assert b2.stats()["small_blocks_encoded"] == 0  # host path served
        finally:
            b2.close()

    def test_tiny_objects_stay_on_host(self, monkeypatch):
        # Below _SMALL_MIN a device round-trip costs more than it saves.
        monkeypatch.setenv("MTPU_BATCH_WAIT_US", "1000")
        b = BatchingDeviceCodec(block_size=BLOCK, max_batch=8, batch_timeout_s=0.002)
        try:
            block = b"\x42" * 512
            dev = b.encode([block], 4, 2)
            host = HostCodec().encode([block], 4, 2)
            assert dev[0][0] == host[0][0]
            assert b.stats()["small_blocks_encoded"] == 0
        finally:
            b.close()


def test_mesh_and_double_buffer_counters():
    """Full-block batches at the production geometry report mesh fan-out and
    per-chip accounting (12+4 tiles the virtual 8-device mesh; 4+2 does not
    and runs single-device)."""
    b = BatchingDeviceCodec(block_size=BLOCK, max_batch=8, batch_timeout_s=0.002)
    try:
        rng = np.random.default_rng(40)
        blocks = [rng.integers(0, 256, BLOCK).astype(np.uint8).tobytes() for _ in range(4)]
        host = HostCodec().encode(blocks, 12, 4)
        for _ in range(3):
            dev = b.encode(blocks, 12, 4)
        for i in range(4):
            assert dev[i][0] == host[i][0], i
            assert dev[i][1] == host[i][1], i
        st = b.stats()
        assert st["mesh_devices"] >= 1
        if st["mesh_devices"] > 1:  # conftest forces 8 virtual devices
            # chip_blocks has one entry per data-parallel group.
            assert 1 <= len(st["chip_blocks"]) <= st["mesh_devices"]
            assert sum(st["chip_blocks"]) == st["blocks_encoded"]
        assert st["double_buffered_batches"] >= 0
    finally:
        b.close()


def test_scanner_deep_scan_runs_device_verify(tmp_path):
    """The scanner's sampled deep-check verifies bitrot through the batched
    device pipeline (VERDICT r3 #9): verify counters must advance."""
    from tests.harness import ErasureHarness
    from tests.test_control import _PoolsShim
    from minio_tpu.control.scanner import DataScanner

    batcher = BatchingDeviceCodec(block_size=BLOCK, max_batch=8, batch_timeout_s=0.002)
    try:
        h = ErasureHarness(tmp_path, n_disks=16, codec=batcher)
        h.layer.make_bucket("scanb")
        rng = np.random.default_rng(21)
        h.layer.put_object(
            "scanb", "obj", rng.integers(0, 256, 2 * BLOCK).astype(np.uint8).tobytes()
        )
        sc = DataScanner(_PoolsShim(h), heal_sample=1)  # deep-check everything
        sc.scan_cycle()
        assert batcher.verify_batches_run >= 1
        assert batcher.digests_verified >= 16  # at least one full row set
    finally:
        batcher.close()


# -- the life of a batch: ledger rows and counters ----------------------------

_BATCH_STAGES = ("worker-idle", "collect", "pack", "h2d", "device-wait", "d2h", "scatter")


def _codec_rows():
    from minio_tpu.control.perf import GLOBAL_PERF

    return GLOBAL_PERF.ledger.snapshot()["stages"].get("codec", {})


def _encode_some(codec, k, m, n_blocks, n_threads=3):
    rng = np.random.default_rng(42)
    blocks = [rng.integers(0, 256, BLOCK).astype(np.uint8).tobytes() for _ in range(n_blocks)]
    threads = [
        threading.Thread(target=codec.encode, args=(blocks[i::n_threads], k, m))
        for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def test_batch_life_rows_exist_after_an_encode():
    """Every stage of a full-block batch is a declared ledger row with at
    least one observation, and encode-batch stays beside them."""
    from minio_tpu.control.perf import STAGES

    before = {s: sum(h["counts"]) for s, h in _codec_rows().items()}
    codec = BatchingDeviceCodec(block_size=BLOCK, max_batch=8, batch_timeout_s=0.002)
    try:
        _encode_some(codec, 4, 2, 6)
    finally:
        codec.close()
    rows = _codec_rows()
    for stage in _BATCH_STAGES + ("queue-wait", "encode-batch"):
        assert ("codec", stage) in STAGES, stage
        assert sum(rows[stage]["counts"]) > before.get(stage, 0), stage
    # h2d and scatter burn the worker's core; their cpu is recorded.
    assert rows["pack"]["cpu"] > 0.0


_CLOSURE_SCRIPT = """
import json, threading
import numpy as np
from minio_tpu.control.perf import GLOBAL_PERF
from minio_tpu.parallel.batching import BatchingDeviceCodec

BLOCK = 1 << 20
STAGES = ("worker-idle", "collect", "pack", "h2d", "device-wait", "d2h", "scatter")

def stage_sum():
    rows = GLOBAL_PERF.ledger.snapshot()["stages"].get("codec", {})
    return sum(rows[s]["sum"] for s in STAGES if s in rows)

codec = BatchingDeviceCodec(block_size=BLOCK, max_batch=8, batch_timeout_s=0.002)
codec.encode([bytes(BLOCK)], 4, 2)  # compile outside the measured stretch
rng = np.random.default_rng(42)
blocks = [rng.integers(0, 256, BLOCK).astype(np.uint8).tobytes() for _ in range(9)]
threads = [threading.Thread(target=codec.encode, args=(blocks[i::3], 4, 2)) for i in range(3)]
for t in threads: t.start()
for t in threads: t.join()
codec.close()  # the worker's last iteration lands in the counters
st = codec.stats()
print(json.dumps({"stages": stage_sum(), "wall": st["worker_wall_seconds"],
                  "idle": st["worker_idle_seconds"],
                  "queue_wait": st["queue_wait_block_seconds"]}))
"""


def test_batch_stages_close_on_worker_wall():
    """idle + collect + pack + h2d + device-wait + d2h + scatter accounts
    for the worker loop's whole time: what is left is the bookkeeping
    between the stages. Run in a process of its own: the ledger is the
    process's, and any other live codec's worker idles into the same rows."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": root}
    done = subprocess.run([sys.executable, "-c", _CLOSURE_SCRIPT], env=env, cwd=root,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    got = json.loads(done.stdout.strip().splitlines()[-1])
    assert got["wall"] > 0 and 0 <= got["idle"] <= got["wall"]
    assert got["stages"] == pytest.approx(got["wall"], rel=0.05, abs=0.005)
    assert got["queue_wait"] > 0


def test_transfer_bytes_equal_the_shapes():
    """h2d_bytes / d2h_bytes are the padded arrays' bytes (up: the K data
    rows; back: the M parity rows and the K+M digests, not the data rows),
    the same counts land in the copy ledger as hops, and encoded_user_bytes
    counts only real blocks."""
    from minio_tpu.control.profiler import GLOBAL_PROFILER
    from minio_tpu.ops import rs_matrix

    k, m = 4, 2
    s = rs_matrix.shard_size(BLOCK, k)
    hops0 = GLOBAL_PROFILER.copy.snapshot()["hops"]
    codec = BatchingDeviceCodec(block_size=BLOCK, max_batch=8, batch_timeout_s=0.05)
    try:
        _encode_some(codec, k, m, 5)
        st = codec.stats()
    finally:
        codec.close()
    assert st["blocks_encoded"] == 5
    slots = st["blocks_padded"]
    assert st["h2d_bytes"] == slots * k * s
    assert st["d2h_bytes"] == slots * (m * s + 32 * (k + m))
    assert st["encoded_user_bytes"] == 5 * BLOCK
    hops1 = GLOBAL_PROFILER.copy.snapshot()["hops"]
    for hop, key in (("device-h2d", "h2d_bytes"), ("device-d2h", "d2h_bytes")):
        was = hops0.get(hop, {}).get("copied_bytes", 0)
        assert hops1[hop]["copied_bytes"] - was == st[key]
        assert hops1[hop]["moved_bytes"] == hops0.get(hop, {}).get("moved_bytes", 0)


def test_codec_programs_carry_names():
    """The jitted callables' names carry the geometry (a profiler trace's
    module line reads `jit_<name>`), and the halves of the programs carry
    `mtpu.*` scopes in their ops' metadata, down to the compiled HLO."""
    from minio_tpu.models import pipeline
    from minio_tpu.models.pipeline import ErasurePipeline, Geometry

    pipe = ErasurePipeline(Geometry(4, 2, BLOCK))
    x = np.zeros((2, 4, BLOCK // 4), np.uint8)
    enc = pipe._encode_fn.lower(x)
    assert "module @jit_mtpu_encode_hash_k4m2 " in enc.as_text()
    compiled = enc.compile().as_text()
    assert "mtpu.rs_encode" in compiled and "mtpu.hh256" in compiled
    par = pipe._parity_fn.lower(x).as_text(debug_info=True)
    assert "module @jit_mtpu_parity_k4m2 " in par and "mtpu.rs_parity_small" in par
    w = np.zeros((4 * 8, 2 * 8), np.int8)
    rec = pipeline._reconstruct_step.lower(x, w, None).as_text(debug_info=True)
    assert "module @jit_mtpu_reconstruct " in rec and "mtpu.rs_reconstruct" in rec


# -- a full-block batch packs runs of a caller's window into reused staging ---
#
# Blocks of 12 x 256 - 2 B at 12+4 (K*S = n + 2: the real slots have a tail
# to zero) and 4 x 768 B at 4+4 (K*S = n), so the CPU backend runs in
# milliseconds; every staging array starts out filled with 0xFF.

PACK_GEOMS = [(12, 4, 12 * 256 - 2), (4, 4, 4 * 768)]


def _pack_codec(k, m, n, b_pad=16):
    """A one-device codec whose free list holds one dirty [b_pad, K, S] array."""
    codec = BatchingDeviceCodec(block_size=n, max_batch=64, batch_timeout_s=0.25, mesh=None)
    staging = codec._encode_staging
    arr = staging.acquire((b_pad, k, rs_matrix.shard_size(n, k)))
    arr.fill(0xFF)
    staging.release(arr)
    return codec


def _window(seed, n_blocks, n):
    rng = np.random.default_rng(seed)
    win = bytearray(rng.integers(0, 256, n_blocks * n, dtype=np.uint8).tobytes())
    mv = memoryview(win)
    return win, [mv[i * n : (i + 1) * n] for i in range(n_blocks)]


def _host_of(blocks, k, m):
    return HostCodec().encode([bytes(b) for b in blocks], k, m)


def _watch_encode(codec, k, m, seen):
    """Record a copy of every array the pipeline is launched with."""
    codec._ensure_worker(k, m)
    pipe = codec._pipelines[(k, m)]
    real = pipe.encode

    def watched(arr):
        seen.append(np.array(arr))
        return real(arr)

    pipe.encode = watched


@pytest.mark.parametrize("b_real", [16, 13])
@pytest.mark.parametrize("k,m,n", PACK_GEOMS)
def test_one_window_is_one_copy_and_bytes_blocks_one_each(k, m, n, b_real):
    codec = _pack_codec(k, m, n)
    seen: list = []
    try:
        _watch_encode(codec, k, m, seen)
        win, blocks = _window(7, b_real, n)
        assert codec.encode(blocks, k, m) == _host_of(blocks, k, m)
        st = codec.stats()
        assert (st["batches_run"], st["blocks_encoded"], st["pack_copies"]) == (1, b_real, 1)
        assert (st["encode_staging_allocated"], st["encode_staging_reused"]) == (1, 1)
        flat = seen[0].reshape(16, -1)
        assert not flat[:, n:].any() and not flat[b_real:].any()  # tail and pad zeroed
        as_bytes = [bytes(b) for b in blocks]
        assert codec.encode(as_bytes, k, m) == _host_of(blocks, k, m)
        st = codec.stats()
        assert (st["batches_run"], st["pack_copies"]) == (2, 1 + b_real)
    finally:
        codec.close()


@pytest.mark.parametrize("k,m,n", PACK_GEOMS)
def test_two_windows_interleaved_in_one_batch_come_back_to_their_own(k, m, n):
    codec = _pack_codec(k, m, n)
    try:
        _, a = _window(11, 8, n)
        _, b = _window(12, 8, n)
        one_by_one = [blk for pair in zip(a, b) for blk in pair]  # a0 b0 a1 b1 ...
        assert codec.encode(one_by_one, k, m) == _host_of(one_by_one, k, m)
        assert codec.stats()["pack_copies"] == 16
        by_fours = a[:4] + b[:4] + a[4:] + b[4:]
        assert codec.encode(by_fours, k, m) == _host_of(by_fours, k, m)
        assert codec.stats()["pack_copies"] == 16 + 4
        # two requests at once, each its own window: every row comes home
        out: dict = {}
        start = threading.Barrier(2)

        def one(name, blocks):
            start.wait(30)
            out[name] = codec.encode(blocks, k, m)

        threads = [threading.Thread(target=one, args=x) for x in (("a", a), ("b", b))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert out["a"] == _host_of(a, k, m) and out["b"] == _host_of(b, k, m)
    finally:
        codec.close()


@pytest.mark.parametrize("k,m,n", PACK_GEOMS)
def test_staging_is_reused_back_only_after_resolve_and_discarded_on_failure(k, m, n):
    codec = BatchingDeviceCodec(block_size=n, max_batch=64, batch_timeout_s=0.25, mesh=None)
    staging = codec._encode_staging
    free_at_resolve: list = []
    real_resolve = codec._resolve_batch

    def resolve(rec):
        free_at_resolve.append(staging.free_count())
        real_resolve(rec)
        free_at_resolve.append(staging.free_count())

    codec._resolve_batch = resolve
    try:
        for seed in (21, 22):
            _, blocks = _window(seed, 16, n)
            assert codec.encode(blocks, k, m) == _host_of(blocks, k, m)
        st = codec.stats()
        assert (st["encode_staging_allocated"], st["encode_staging_reused"]) == (1, 1)
        assert free_at_resolve == [0, 1, 0, 1] and staging.outstanding == 0
        pipe = codec._pipelines[(k, m)]

        def boom(arr):
            raise RuntimeError("device lost")

        pipe.encode = boom
        _, blocks = _window(23, 16, n)
        with pytest.raises(RuntimeError):
            codec.encode(blocks, k, m)
        assert staging.outstanding == 0 and staging.free_count() == 0  # never recycled
    finally:
        codec.close()


@pytest.mark.parametrize("k,m,n", PACK_GEOMS)
def test_results_alias_neither_the_window_nor_the_staging_array(k, m, n):
    codec = _pack_codec(k, m, n)
    try:
        win, blocks = _window(31, 16, n)
        want = _host_of(blocks, k, m)
        got = codec.encode(blocks, k, m)
        win[:] = bytes(len(win))  # the caller reuses its window ...
        _, other = _window(32, 16, n)
        codec.encode(other, k, m)  # ... and the staging array packs again
        assert codec.stats()["encode_staging_reused"] == 2
        assert got == want
        assert all(type(c) is bytes for rows, digests in got for c in rows + digests)
        for b in blocks:  # nothing exports the window any more
            b.release()
        win.extend(b"x")
    finally:
        codec.close()


def test_strided_runs_split_at_every_break():
    win = bytearray(64)
    mv = memoryview(win)
    other = memoryview(bytearray(16))
    views = [mv[0:8], mv[8:16], mv[16:24], mv[40:48], mv[48:56], b"x" * 8, other[0:8],
             mv[56:64]]
    runs = strided_runs(views, 8)
    assert [(a, b) for a, b, _ in runs] == [(0, 3), (3, 5), (5, 6), (6, 7), (7, 8)]
    assert runs[2][2] is None and runs[1][2].strides == (8, 1)
    whole = np.frombuffer(win, np.uint8)
    assert np.shares_memory(runs[0][2], whole) and runs[0][2].shape == (3, 8)
    assert [r[2].shape for r in strided_runs([mv[0:8], mv[24:32], mv[48:56]], 8)] == [(3, 8)]
    assert strided_runs([mv[0:8], mv[48:56]], 8)[0][2].strides == (48, 1)
    # a step under s (overlap) or backwards joins nothing
    assert [(a, b) for a, b, _ in strided_runs([mv[8:16], mv[4:12], mv[0:8]], 8)] == [
        (0, 1), (1, 2), (2, 3)]
    del runs, whole


# -- a batch's copy home starts at its launch ---------------------------------


class _Recorded:
    """A device array that logs the start of its copy home."""

    def __init__(self, arr, tag, log):
        self._arr, self._tag, self._log = arr, tag, log

    def copy_to_host_async(self):
        self._log.append(("copy", self._tag))
        self._arr.copy_to_host_async()

    def block_until_ready(self):
        self._arr.block_until_ready()
        return self

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._arr, dtype=dtype)


def _log_batch_life(codec, k, m, log):
    """Log each batch's launch (numbered), the start of its outputs' copies,
    the bounds of every _dispatch_batch and the entry of its _resolve_batch."""
    codec._ensure_worker(k, m)
    pipe = codec._pipelines[(k, m)]
    encode, dispatch, resolve = pipe.encode, codec._dispatch_batch, codec._resolve_batch

    def launched(arr):
        n = sum(e[0] == "launch" for e in log)
        log.append(("launch", n))
        parity, digests = encode(arr)
        return _Recorded(parity, ("parity", n), log), _Recorded(digests, ("digests", n), log)

    def dispatched(*args):
        log.append(("dispatch-in",))
        rec = dispatch(*args)
        log.append(("dispatch-out",))
        return rec

    def resolved(rec):
        log.append(("resolve", rec[1]._tag[1]))
        resolve(rec)

    pipe.encode, codec._dispatch_batch, codec._resolve_batch = launched, dispatched, resolved


def _encode_queue_full(codec, k, m, blocks):
    """Encode `blocks` in one call with every block queued before the first
    batch launches, so that the worker finds the next batch waiting each time
    it comes back, up to the last."""
    q = codec._ensure_worker(k, m)
    gate = threading.Event()
    taken: list[int] = []
    dispatch = codec._dispatch_batch

    def held(pipe, kk, mm, batch):
        taken.append(len(batch))
        gate.wait(30)
        return dispatch(pipe, kk, mm, batch)

    codec._dispatch_batch = held
    out: dict = {}
    t = threading.Thread(target=lambda: out.setdefault("got", codec.encode(blocks, k, m)))
    t.start()
    deadline = time.monotonic() + 30
    while q.qsize() + sum(taken) < len(blocks) and time.monotonic() < deadline:
        time.sleep(0.001)
    gate.set()
    t.join(60)
    return out["got"]


def _mesh_of(shape):
    if shape is None:
        return None
    if jax.device_count() < 4:
        pytest.skip("needs conftest's virtual devices")
    return mesh_lib.make_mesh(4, shape)


@pytest.mark.parametrize("shape", [None, (2, 2, 1)], ids=["one-device", "mesh-2x2x1"])
@pytest.mark.parametrize("k,m,n", PACK_GEOMS)
def test_every_batch_starts_its_copy_home_inside_its_dispatch(k, m, n, shape):
    codec = BatchingDeviceCodec(block_size=n, max_batch=4, batch_timeout_s=0.25,
                                mesh=_mesh_of(shape))
    log: list = []
    try:
        _log_batch_life(codec, k, m, log)
        _, blocks = _window(41, 14, n)
        assert _encode_queue_full(codec, k, m, blocks) == _host_of(blocks, k, m)
        launches = [e[1] for e in log if e[0] == "launch"]
        assert launches == list(range(codec.stats()["batches_run"])) and len(launches) >= 4
        for b in launches:
            at = log.index(("launch", b))
            copies = [log.index(("copy", (name, b))) for name in ("parity", "digests")]
            into = max(i for i, e in enumerate(log[:at]) if e == ("dispatch-in",))
            out = log.index(("dispatch-out",), into)
            assert into < at < min(copies) and max(copies) < out
            assert max(copies) < log.index(("resolve", b))
    finally:
        codec.close()


@pytest.mark.parametrize("shape", [None, (2, 2, 1)], ids=["one-device", "mesh-2x2x1"])
@pytest.mark.parametrize("k,m,n", PACK_GEOMS)
def test_a_full_queue_resolves_early_copies_bit_exact(k, m, n, shape):
    codec = BatchingDeviceCodec(block_size=n, max_batch=4, batch_timeout_s=0.25,
                                mesh=_mesh_of(shape))
    try:
        _, blocks = _window(43, 18, n)
        got = _encode_queue_full(codec, k, m, blocks)
        for block, (rows, digests) in zip(blocks, got):
            assert (rows, digests) == ref.encode_block(bytes(block), k, m)
        st = codec.stats()
        assert st["double_buffered_batches"] >= 3
        assert 0 < st["d2h_early_batches"] <= st["double_buffered_batches"] <= st["batches_run"]
        assert st["blocks_encoded"] == len(blocks)
    finally:
        codec.close()


@pytest.mark.parametrize("k,m,n", PACK_GEOMS)
def test_a_lone_batch_resolved_at_once_counts_no_early_batch(k, m, n):
    codec = BatchingDeviceCodec(block_size=n, max_batch=64, batch_timeout_s=0.25, mesh=None)
    log: list = []
    try:
        _log_batch_life(codec, k, m, log)
        _, blocks = _window(47, 5, n)
        assert codec.encode(blocks, k, m) == _host_of(blocks, k, m)
        st = codec.stats()
        assert (st["batches_run"], st["double_buffered_batches"], st["d2h_early_batches"]) == (1, 0, 0)
        assert log.index(("copy", ("parity", 0))) < log.index(("resolve", 0))
    finally:
        codec.close()
