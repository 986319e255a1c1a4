"""The small-object path against a plain reference (tests/reference_store.py).

What a 4-drive 2+2 erasure set serving 64 KiB PUTs rests on: the batcher's
cross-request small queue, the inline ``xl.meta`` image, every 2-of-4 loss,
the K = M write quorum (3 of 4) and the op answers -- on seeded data, on the
CPU (``MINIO_TPU_CODEC=xla-cpu`` serves the device pipeline on jax's CPU
backend). The reference imports neither parallel/batching.py nor
object/erasure.py.
"""

from __future__ import annotations

import importlib.util
import itertools
import os
import shutil
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from minio_tpu import runtime
from minio_tpu.api.server import ThreadedServer
from minio_tpu.chaos.disk import FaultyDisk
from minio_tpu.chaos.faults import DRIVE_ERROR, FaultRegistry, FaultSpec
from minio_tpu.dist.node import Node
from minio_tpu.models.pipeline import ErasurePipeline, Geometry
from minio_tpu.object import codec as codec_mod
from minio_tpu.parallel.batching import BatchingDeviceCodec
from tests import reference_store as ref
from tests.s3client import S3TestClient

BLOCK = 1 << 20
GEOMETRIES = [(2, 2), (4, 4), (12, 4)]
# Both edges of the queue (4 KiB, one byte under a block), an odd length whose
# last shard is zero-padded, the cell's 64 KiB, and both sides of the inline limit.
SIZES = [4096, 4097, 10240, 65536, 102400, 131071, 131072, 1048575]
THREADS = 16


def _body(seed: int, n: int) -> bytes:
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8).tobytes()


# -- the small queue against (i) ------------------------------------------------


@pytest.fixture(scope="module", params=GEOMETRIES, ids=lambda g: f"{g[0]}+{g[1]}")
def queued(request):
    """Every size twice (other bytes), one request a thread, 16 threads at
    once through one codec's small queue: batches hold ragged lengths."""
    k, m = request.param
    codec = BatchingDeviceCodec(block_size=BLOCK, max_batch=64)
    codec.small_wait_s = 0.05  # hold long enough that the 16 threads meet in a batch
    bodies = [_body(1000 * k + i, SIZES[i % len(SIZES)]) for i in range(THREADS)]
    out: list = [None] * THREADS
    start = threading.Barrier(THREADS)

    def one(i: int) -> None:
        start.wait(30)
        out[i] = codec.encode([bodies[i]], k, m)[0]

    threads = [threading.Thread(target=one, args=(i,)) for i in range(THREADS)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(240)
        assert not any(t.is_alive() for t in threads)
    finally:
        codec.close()
    return SimpleNamespace(k=k, m=m, bodies=bodies, out=out, stats=codec.stats())


@pytest.mark.parametrize("slot", range(THREADS), ids=lambda i: f"{SIZES[i % len(SIZES)]}B-{i}")
def test_small_queue_rows_and_digests_equal_the_reference(queued, slot):
    rows, digests = queued.out[slot]
    want_rows, want_digests = ref.encode_block(queued.bodies[slot], queued.k, queued.m)
    assert [bytes(r) for r in rows] == want_rows  # true length: no byte of padding
    assert [bytes(d) for d in digests] == want_digests


def test_small_queue_counts_every_block_and_shares_batches(queued):
    st = queued.stats
    assert st["small_blocks_encoded"] == THREADS
    assert st["host_fallback_blocks"] == 0 and st["blocks_encoded"] == 0
    assert 1 <= st["small_batches_run"] < THREADS  # more than one request in some batch
    assert st["small_blocks_padded"] >= THREADS
    assert st["small_user_bytes"] == sum(len(b) for b in queued.bodies)
    # The seconds of a small batch are its own: a full batch's round trip
    # (device_encode_seconds / batches_run) never holds them.
    assert st["small_encode_seconds"] > 0.0 and st["device_encode_seconds"] == 0.0
    assert st["small_queue_wait_block_seconds"] > 0.0
    assert 0.0 <= st["small_worker_idle_seconds"] <= st["small_worker_wall_seconds"]


def test_small_batch_life_rows_are_declared_and_recorded(queued):
    from minio_tpu.control.perf import GLOBAL_PERF, STAGES

    rows = GLOBAL_PERF.ledger.snapshot()["stages"].get("codec", {})
    for stage in ("small-queue-wait", "small-worker-idle", "small-collect", "small-pack",
                  "encode-batch-small", "small-digest", "small-scatter"):
        assert ("codec", stage) in STAGES, stage
        assert sum(rows[stage]["counts"]) >= 1, stage
    assert rows["small-digest"]["cpu"] > 0.0  # the host hashes; the row keeps its cpu


# -- the served path at 2+2 over 4 drives ---------------------------------------

ROOT, SECRET, BUCKET = "smalladmin", "small-secret-key-1", "small"
DRIVES, K, M = 4, 2, 2
CELL_BYTES = 65536


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One node over 4 temp drives (default parity EC:2), its device pipeline
    on jax's CPU backend, behind a real socket."""
    tmp = tmp_path_factory.mktemp("small-2p2")
    dirs = [str(tmp / f"d{i}") for i in range(DRIVES)]
    old = os.environ.get("MINIO_TPU_CODEC")
    prev_codec = codec_mod._default  # the install replaces the process's default codec
    os.environ["MINIO_TPU_CODEC"] = "xla-cpu"
    node = Node(dirs, root_user=ROOT, root_password=SECRET)
    ts = ThreadedServer(SimpleNamespace(app=node.make_app()))
    try:
        url = ts.start()
        node.build()
        assert isinstance(node.codec, BatchingDeviceCodec)
        assert runtime.install_status()["geometry"] == [K, M]
        client = S3TestClient(url, ROOT, SECRET)
        assert client.make_bucket(BUCKET).status_code == 200
        layer = node.pools.pools[0].sets[0]
        yield SimpleNamespace(node=node, client=client, dirs=dirs, layer=layer, url=url)
    finally:
        ts.stop()
        node.close()
        runtime.shutdown_data_plane(node.codec)
        codec_mod._default = prev_codec  # later files of this worker get theirs back
        if old is None:
            os.environ.pop("MINIO_TPU_CODEC", None)
        else:
            os.environ["MINIO_TPU_CODEC"] = old


def _put(served, key: str, body: bytes) -> None:
    r = served.client.put_object(BUCKET, key, body)
    assert r.status_code == 200, r.text


def _lose(served, key: str, drives) -> None:
    for i in drives:
        shutil.rmtree(os.path.join(served.dirs[i], BUCKET, key))


@pytest.mark.parametrize("drive", range(DRIVES))
def test_inline_image_on_each_drive_equals_the_reference(served, drive):
    body = _body(41, CELL_BYTES)
    before = served.node.codec.stats()
    _put(served, "image.bin", body)
    after = served.node.codec.stats()
    assert after["small_blocks_encoded"] == before["small_blocks_encoded"] + 1
    assert after["host_fallback_blocks"] == before["host_fallback_blocks"]
    row = ref.hash_order(f"{BUCKET}/image.bin", DRIVES)[drive] - 1
    fi = served.layer.disks[drive].read_version(BUCKET, "image.bin")
    assert not fi.data_dir  # inline: the metadata write was the commit
    assert bytes(fi.inline_data) == ref.inline_shards(body, K, M)[row]
    got = served.client.get_object(BUCKET, "image.bin")
    assert got.status_code == 200 and got.content == body


@pytest.mark.parametrize("pair", list(itertools.combinations(range(DRIVES), 2)),
                         ids=lambda p: f"lost{p[0]}{p[1]}")
def test_get_is_exact_with_any_two_drives_lost(served, pair):
    key = f"lose-{pair[0]}{pair[1]}.bin"
    body = _body(50 + 4 * pair[0] + pair[1], CELL_BYTES)
    _put(served, key, body)
    _lose(served, key, pair)
    got = served.client.get_object(BUCKET, key)
    assert got.status_code == 200 and got.content == body


@pytest.mark.parametrize("keep", range(DRIVES), ids=lambda i: f"only{i}")
def test_three_drives_lost_is_an_error_never_wrong_bytes(served, keep):
    key = f"gone-{keep}.bin"
    _put(served, key, _body(70 + keep, CELL_BYTES))
    _lose(served, key, [i for i in range(DRIVES) if i != keep])
    got = served.client.get_object(BUCKET, key)
    assert got.status_code >= 400, got.status_code
    assert len(got.content) < CELL_BYTES  # an error document, not a body


SMALL_SERIES = [
    "minio_tpu_codec_small_blocks_encoded_total",
    "minio_tpu_codec_small_batches_total",
    "minio_tpu_codec_small_blocks_padded_total",
    "minio_tpu_codec_small_roundtrip_seconds_total",
    "minio_tpu_codec_small_queue_wait_block_seconds_total",
    'minio_tpu_codec_small_worker_seconds_total{state="idle"}',
    'minio_tpu_codec_small_worker_seconds_total{state="all"}',
    "minio_tpu_codec_small_user_bytes_total",
]


@pytest.fixture(scope="module")
def scraped(served) -> str:
    """The node's Prometheus exposition after one small PUT."""
    _put(served, "series.bin", _body(42, CELL_BYTES))
    r = served.client.request("GET", "/minio/v2/metrics/node")
    assert r.status_code == 200
    return r.text


def _sample(text: str, series: str) -> float:
    values = [float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
              if line.startswith(series + " ")]
    assert len(values) == 1, series
    return values[0]


def test_exposition_with_the_small_series_is_lint_clean(scraped):
    spec = importlib.util.spec_from_file_location(
        "metrics_lint", os.path.join(os.path.dirname(__file__), "..", "tools", "metrics_lint.py"))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    assert lint.validate_exposition(scraped) == [] and lint.lint_exposition(scraped) == []


@pytest.mark.parametrize("series", SMALL_SERIES)
def test_small_queue_series_are_exported(scraped, series):
    assert _sample(scraped, series) > 0.0


def test_full_batch_round_trip_keeps_its_own_seconds(scraped):
    # Sub-blocks only were sent to this node: a full batch's seconds stay 0.
    assert _sample(scraped, 'minio_tpu_codec_roundtrip_seconds_total{kernel="encode"}') == 0.0


# -- the write quorum at K = M: 3 of 4 ------------------------------------------


@pytest.fixture
def faulty(served):
    """The served layer's drives behind FaultyDisk over a private registry."""
    reg = FaultRegistry()
    plain = list(served.layer.disks)
    served.layer.disks = [FaultyDisk(d, reg) for d in plain]
    try:
        yield reg
    finally:
        served.layer.disks = plain


def _fail_commits(reg: FaultRegistry, served, drives) -> None:
    for i in drives:
        reg.arm(FaultSpec(kind=DRIVE_ERROR, target=served.dirs[i], ops=("write_metadata",)))


@pytest.mark.parametrize("had_previous", [False, True], ids=["new-key", "overwrite"])
@pytest.mark.parametrize("pair", [(0, 1), (1, 3), (2, 3)], ids=lambda p: f"fail{p[0]}{p[1]}")
def test_put_with_two_commits_failing_is_refused_and_never_read(served, faulty, pair, had_previous):
    key = f"quorum-{pair[0]}{pair[1]}-{int(had_previous)}.bin"
    previous, refused = _body(80, CELL_BYTES), _body(81, CELL_BYTES)
    if had_previous:
        _put(served, key, previous)
    _fail_commits(faulty, served, pair)
    r = served.client.put_object(BUCKET, key, refused)
    assert r.status_code >= 500, r.status_code  # 2 of 4 committed: under the quorum of 3
    faulty.disarm_all()
    got = served.client.get_object(BUCKET, key)
    if got.status_code == 200:
        assert had_previous and got.content == previous
    else:
        assert got.content != refused and got.status_code in (404, 503)


@pytest.mark.parametrize("drive", range(DRIVES), ids=lambda i: f"fail{i}")
def test_put_with_one_commit_failing_is_acknowledged_and_reads_back(served, faulty, drive):
    key = f"quorum-one-{drive}.bin"
    body = _body(90 + drive, CELL_BYTES)
    _fail_commits(faulty, served, [drive])
    _put(served, key, body)
    got = served.client.get_object(BUCKET, key)
    assert got.status_code == 200 and got.content == body


# -- op answers against (ii) ----------------------------------------------------

REPLAY_THREADS, REPLAY_OPS = 8, 400
REPLAY_SIZES = [1, 4095, 4096, 10240, 65536, 100000, 131071, 131072, 300000]


def _replay(client, model: ref.ModelStore, thread: int, seed: int, problems: list) -> None:
    rng = np.random.default_rng(seed * 100 + thread)
    keys = [f"replay-{seed}/t{thread}-k{j}" for j in range(4)]  # a thread owns its keys
    for step in range(REPLAY_OPS // REPLAY_THREADS):
        key = keys[int(rng.integers(len(keys)))]
        op = ("PUT", "GET", "HEAD", "DELETE")[int(rng.choice(4, p=[0.4, 0.3, 0.2, 0.1]))]
        if op == "PUT":
            body = _body(int(rng.integers(1 << 30)), int(rng.choice(REPLAY_SIZES)))
            r = client.put_object(BUCKET, key, body)
            got = (r.status_code, None, r.headers.get("ETag", "").strip('"'))
            want = model.put(key, body, got[2])
        elif op == "GET":
            r, want = client.get_object(BUCKET, key), model.get(key)
            ok = r.status_code == 200
            got = (r.status_code, r.content if ok else None,
                   r.headers.get("ETag", "").strip('"') if ok else None)
        elif op == "HEAD":
            r, want = client.head_object(BUCKET, key), model.head(key)
            ok = r.status_code == 200
            got = (r.status_code, int(r.headers["Content-Length"]) if ok else None,
                   r.headers.get("ETag", "").strip('"') if ok else None)
        else:
            r, want = client.delete_object(BUCKET, key), model.delete(key)
            got = (r.status_code, None, None)
        if got != want:
            problems.append(f"thread {thread} step {step} {op} {key}: "
                            f"{got[0]} {got[2]} != {want[0]} {want[2]}")


@pytest.mark.parametrize("seed", [3, 11])
def test_concurrent_small_ops_answer_as_the_model(served, seed):
    model, problems = ref.ModelStore(), []
    threads = [
        threading.Thread(target=_replay, args=(
            S3TestClient(served.url, ROOT, SECRET), model, t, seed, problems))
        for t in range(REPLAY_THREADS)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(240)
    assert not any(t.is_alive() for t in threads)
    assert problems == []


# -- the full block at 2+2: the programs the warm-up runs at 524,288 B shards ----


@pytest.mark.parametrize("batch", [1, 2])
def test_full_block_encode_and_reconstruct_at_2p2_equal_the_oracles(batch):
    pipe = ErasurePipeline(Geometry(K, M, BLOCK))
    blocks = [_body(200 + i, BLOCK) for i in range(batch)]
    data = np.stack([ref.split_block(b, K) for b in blocks])  # [B, 2, 524288]
    assert data.shape == (batch, K, BLOCK // K)
    parity, digests = pipe.encode(data)  # parity rows only; all K+M digests
    parity, digests = np.asarray(parity), np.asarray(digests)
    assert parity.shape == (batch, M, BLOCK // K)
    for i, block in enumerate(blocks):
        want_rows, want_digests = ref.encode_block(block, K, M)
        assert [parity[i, j].tobytes() for j in range(M)] == want_rows[K:]
        assert [digests[i, j].tobytes() for j in range(K + M)] == want_digests
    # Both data rows lost: rebuilt from the two parity rows alone.
    present = (False, False, True, True)
    rebuilt, rebuilt_digests = pipe.reconstruct(parity, present, (0, 1))
    assert np.array_equal(np.asarray(rebuilt), data)
    assert np.array_equal(np.asarray(rebuilt_digests), digests[:, :K])
