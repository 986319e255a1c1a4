"""The batcher and the served PUT path on a four-device codec mesh, against the
plain references (ops/rs_ref.py, the numpy HighwayHash of ops/highwayhash.py,
tests/reference_store.py), on conftest's virtual CPU devices.

What a four-chip host rests on: a batch's real blocks dealt round-robin to the
dp groups and read back by the same map (no block changes its bytes or its
owner), the counters of that dealing equal to hand arithmetic, the sharded
upload counted for every tp replica, a 16-drive node storing exactly the
reference's shard bytes through the mesh -- and one device moving none of it.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import shutil
import threading
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from minio_tpu import runtime
from minio_tpu.api.server import ThreadedServer
from minio_tpu.control.perf import GLOBAL_PERF, STAGES
from minio_tpu.dist.node import Node
from minio_tpu.object import codec as codec_mod
from minio_tpu.parallel import batching
from minio_tpu.parallel import mesh as mesh_lib
from minio_tpu.parallel.batching import BatchingDeviceCodec
from tests import reference_store as ref
from tests.s3client import S3TestClient

K, M = 12, 4
BLOCK = K * 256  # 3072 B blocks: 256 B shards, the references run in milliseconds
RAGGED = (1, 3, 5, 22, 37)
SHAPES = [(2, 2, 1), (4, 1, 1), (2, 1, 2), (1, 2, 2)]
NEW_COUNTERS = ("mesh_blocks_even", "mesh_blocks_fullest", "mesh_chip_batches")

needs_four = pytest.mark.skipif(jax.device_count() < 4,
                                reason="needs conftest's virtual devices")


def _blocks(seed: int, n: int, size: int = BLOCK) -> list[bytes]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size, dtype=np.uint8).tobytes() for _ in range(n)]


def _mesh_put_count() -> int:
    rows = GLOBAL_PERF.ledger.snapshot()["stages"].get("codec", {})
    return sum(rows["mesh-put"]["counts"]) if "mesh-put" in rows else 0


def _shape_id(shape) -> str:
    return "x".join(map(str, shape))


# -- the dealing itself ---------------------------------------------------------------


@pytest.mark.parametrize("dp", [1, 2, 4])
def test_deal_gives_every_group_its_share_and_one_device_the_old_order(dp):
    for b_real in range(1, 65):
        b_pad = -(-batching._bucket(b_real) // dp) * dp
        slots = batching._deal(b_real, b_pad, dp)
        per = b_pad // dp
        assert len(set(slots)) == b_real and all(0 <= s < b_pad for s in slots)
        groups = [sum(1 for s in slots if s // per == g) for g in range(dp)]
        assert max(groups) - min(groups) <= 1 and max(groups) == -(-b_real // dp)
        # block i sits in group i mod dp, behind the group's earlier blocks
        assert all(slots[i] // per == i % dp and slots[i] % per == i // dp
                   for i in range(b_real))
        if dp == 1:
            assert slots == list(range(b_real))  # block i in slot i, as without a mesh


# -- ragged batches, one at a time: the counters against hand arithmetic ------------------


def _window_blocks(seed: int, n: int) -> list[memoryview]:
    """n blocks as views into one window: one run, which the dealing splits
    across the dp groups."""
    mv = memoryview(bytearray(b"".join(_blocks(seed, n))))
    return [mv[i * BLOCK : (i + 1) * BLOCK] for i in range(n)]


@needs_four
@pytest.mark.parametrize("source", ["bytes", "window"])
@pytest.mark.parametrize("shape", SHAPES, ids=_shape_id)
def test_ragged_batches_on_a_mesh_equal_the_references_and_the_arithmetic(shape, source):
    dp, tp, sp = shape
    codec = BatchingDeviceCodec(block_size=BLOCK, max_batch=64, batch_timeout_s=0.25,
                                mesh=mesh_lib.make_mesh(4, shape))
    puts_before = _mesh_put_count()
    want = {"even": 0.0, "fullest": 0, "chip_batches": 0, "chips": [0] * dp, "h2d": 0,
            "d2h": 0, "padded": 0, "copies": 0}
    try:
        for n in RAGGED:
            blocks = (_blocks if source == "bytes" else _window_blocks)(100 + n, n)
            # one call, one thread: the hold gathers all n blocks into one batch
            got = codec.encode(blocks, K, M)
            for block, (rows, digests) in zip(blocks, got):
                want_rows, want_digests = ref.encode_block(bytes(block), K, M)
                assert rows == want_rows and digests == want_digests
            b_pad = -(-batching._bucket(n) // dp) * dp
            # a bytes block is a copy of its own; a window's run one a dp group
            want["copies"] += n if source == "bytes" else min(n, dp)
            want["even"] += n / dp
            want["fullest"] += -(-n // dp)
            want["chip_batches"] += min(n, dp) * tp * sp
            for g in range(dp):
                want["chips"][g] += len(range(g, n, dp))
            want["h2d"] += b_pad * K * (BLOCK // K) * tp  # every tp replica takes its copy
            want["d2h"] += b_pad * (M * (BLOCK // K) + 32 * (K + M))
            want["padded"] += b_pad
        st = codec.stats()
    finally:
        codec.close()
    assert st["batches_run"] == len(RAGGED) and st["blocks_encoded"] == sum(RAGGED)
    assert st["blocks_padded"] == want["padded"]
    assert st["mesh_devices"] == 4
    assert st["mesh_blocks_even"] == pytest.approx(want["even"])
    assert st["mesh_blocks_fullest"] == want["fullest"]
    assert st["mesh_chip_batches"] == want["chip_batches"]
    assert st["chip_blocks"] == want["chips"] and sum(st["chip_blocks"]) == sum(RAGGED)
    assert st["h2d_bytes"] == want["h2d"] and st["d2h_bytes"] == want["d2h"]
    assert st["pack_copies"] == want["copies"]
    assert ("codec", "mesh-put") in STAGES
    assert _mesh_put_count() - puts_before == len(RAGGED)  # one record a batch


# -- ragged requests from several threads at once: no block changes bytes or owner ---------


@needs_four
@pytest.mark.parametrize("shape", SHAPES[:2], ids=_shape_id)
def test_concurrent_ragged_requests_on_a_mesh_get_their_own_rows_back(shape):
    dp = shape[0]
    codec = BatchingDeviceCodec(block_size=BLOCK, max_batch=64, batch_timeout_s=0.02,
                                mesh=mesh_lib.make_mesh(4, shape))
    requests = [_blocks(500 + 10 * t + i, n) for t in range(4) for i, n in enumerate(RAGGED)]
    out: list = [None] * len(requests)
    start = threading.Barrier(len(requests))

    def one(i: int) -> None:
        start.wait(30)
        out[i] = codec.encode(requests[i], K, M)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(len(requests))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(240)
        assert not any(t.is_alive() for t in threads)
        st = codec.stats()
    finally:
        codec.close()
    for blocks, got in zip(requests, out):
        assert len(got) == len(blocks)
        for block, (rows, digests) in zip(blocks, got):
            want_rows, want_digests = ref.encode_block(block, K, M)
            assert rows == want_rows and digests == want_digests
    total = 4 * sum(RAGGED)
    assert st["blocks_encoded"] == total == sum(st["chip_blocks"])
    assert max(st["chip_blocks"]) - min(st["chip_blocks"]) <= st["batches_run"]
    assert total / dp == pytest.approx(st["mesh_blocks_even"])
    assert st["mesh_blocks_even"] <= st["mesh_blocks_fullest"] <= (
        st["mesh_blocks_even"] + st["batches_run"] * (dp - 1) / dp)
    assert st["batches_run"] <= st["mesh_chip_batches"] <= 4 * st["batches_run"]


# -- one device: nothing of it moves -----------------------------------------------------


def test_one_device_moves_none_of_the_mesh_counters():
    codec = BatchingDeviceCodec(block_size=BLOCK, max_batch=64, batch_timeout_s=0.25, mesh=None)
    puts_before = _mesh_put_count()
    padded = 0
    try:
        for n in RAGGED:
            blocks = _blocks(900 + n, n)
            got = codec.encode(blocks, K, M)
            for block, (rows, digests) in zip(blocks, got):
                want_rows, want_digests = ref.encode_block(block, K, M)
                assert rows == want_rows and digests == want_digests
            padded += batching._bucket(n)
        st = codec.stats()
    finally:
        codec.close()
    assert codec._pipelines[(K, M)].mesh is None
    assert all(st[name] == 0 for name in NEW_COUNTERS)
    assert st["chip_blocks"] == [] and st["mesh_devices"] == 1
    assert st["blocks_padded"] == padded
    assert st["h2d_bytes"] == padded * K * (BLOCK // K)  # each byte crosses once
    assert _mesh_put_count() == puts_before


# -- the served path: a 16-drive node on a four-device mesh ----------------------------


ROOT, SECRET, BUCKET = "meshadmin", "mesh-secret-key-01", "mesh"
DRIVES = 16
MIB = 1 << 20
# Five full blocks and a tail (one codec group through the mesh program, the tail
# through the small queue), and three full blocks exactly.
OBJECTS = {"five-and-a-bit.bin": (61, 5 * MIB + 4321), "three.bin": (62, 3 * MIB)}


def _body(seed: int, n: int) -> bytes:
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8).tobytes()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One node over 16 temp drives (default parity EC:4), its device pipeline
    on jax's CPU backend over the mesh factor_mesh picks for four devices, behind
    a real socket; the two objects PUT over HTTP."""
    if jax.device_count() < 4:
        pytest.skip("needs conftest's virtual devices")
    tmp = tmp_path_factory.mktemp("mesh-12p4")
    dirs = [str(tmp / f"d{i}") for i in range(DRIVES)]
    old_env = os.environ.get("MINIO_TPU_CODEC")
    prev_codec = codec_mod._default  # the install replaces the process's default codec
    prev_mesh = list(mesh_lib._codec_mesh_cache)
    # What codec_mesh() builds by itself on a four-chip host (the test process has eight).
    mesh_lib._codec_mesh_cache[:] = [mesh_lib.make_mesh(4)]
    os.environ["MINIO_TPU_CODEC"] = "xla-cpu"
    node = Node(dirs, root_user=ROOT, root_password=SECRET)
    ts = ThreadedServer(SimpleNamespace(app=node.make_app()))
    try:
        url = ts.start()
        node.build()
        assert isinstance(node.codec, BatchingDeviceCodec)
        install = runtime.install_status()
        assert install["geometry"] == [K, M]
        assert install["mesh"] == dict(zip(mesh_lib.AXES, mesh_lib.factor_mesh(4)))
        client = S3TestClient(url, ROOT, SECRET)
        assert client.make_bucket(BUCKET).status_code == 200
        before = node.codec.stats()
        bodies = {}
        for key, (seed, size) in OBJECTS.items():
            bodies[key] = _body(seed, size)
            r = client.put_object(BUCKET, key, bodies[key])
            assert r.status_code == 200, r.text
        layer = node.pools.pools[0].sets[0]
        yield SimpleNamespace(node=node, client=client, dirs=dirs, layer=layer, bodies=bodies,
                              before=before, after=node.codec.stats())
    finally:
        ts.stop()
        node.close()
        runtime.shutdown_data_plane(node.codec)
        codec_mod._default = prev_codec  # later files of this worker get theirs back
        mesh_lib._codec_mesh_cache[:] = prev_mesh
        if old_env is None:
            os.environ.pop("MINIO_TPU_CODEC", None)
        else:
            os.environ["MINIO_TPU_CODEC"] = old_env


@pytest.fixture(scope="module")
def images(served) -> dict[str, list[bytes]]:
    """Per object, what each shard row's file has to hold: digest || chunk per block."""
    return {key: ref.inline_shards(body, K, M) for key, body in served.bodies.items()}


def test_the_mesh_carried_every_full_block(served):
    moved = {name: served.after[name] - served.before[name]
             for name in ("blocks_encoded", "batches_run", "mesh_chip_batches",
                          "mesh_blocks_fullest", "mesh_blocks_even", "host_fallback_blocks")}
    assert moved["blocks_encoded"] == 8 and moved["host_fallback_blocks"] == 0
    assert served.after["mesh_devices"] == 4
    dp = mesh_lib.factor_mesh(4)[0]
    assert moved["mesh_blocks_even"] == pytest.approx(8 / dp)
    assert moved["batches_run"] <= moved["mesh_chip_batches"] <= 4 * moved["batches_run"]
    assert sum(served.after["chip_blocks"]) - sum(served.before["chip_blocks"]) == 8


@pytest.mark.parametrize("drive", range(DRIVES))
def test_shard_file_on_each_drive_equals_the_reference(served, images, drive):
    for key, body in served.bodies.items():
        row = ref.hash_order(f"{BUCKET}/{key}", DRIVES)[drive] - 1
        fi = served.layer.disks[drive].read_version(BUCKET, key)
        assert fi.data_dir and fi.size == len(body)
        assert (fi.erasure.data_blocks, fi.erasure.parity_blocks) == (K, M)
        assert fi.erasure.index == row + 1
        path = os.path.join(served.dirs[drive], BUCKET, key, fi.data_dir, "part.1")
        with open(path, "rb") as f:
            assert f.read() == images[key][row], (key, drive)


@pytest.mark.parametrize("key", sorted(OBJECTS))
def test_xl_meta_etag_is_the_md5_of_the_reference_data_row_digests(served, key):
    body = served.bodies[key]
    h = hashlib.md5()
    for off in range(0, len(body), MIB):
        _, digests = ref.encode_block(body[off: off + MIB], K, M)
        h.update(b"".join(digests[:K]))
    etags = {served.layer.disks[d].read_version(BUCKET, key).metadata["etag"]
             for d in range(DRIVES)}
    assert etags == {h.hexdigest()}


@pytest.mark.parametrize("key", sorted(OBJECTS))
def test_object_reads_back_whole_and_with_four_data_shards_gone(served, key):
    body = served.bodies[key]
    got = served.client.get_object(BUCKET, key)
    assert got.status_code == 200 and got.content == body
    order = ref.hash_order(f"{BUCKET}/{key}", DRIVES)
    victims = [d for d in range(DRIVES) if order[d] - 1 < K][:M]
    for d in victims:
        shutil.rmtree(os.path.join(served.dirs[d], BUCKET, key))
    before = served.node.codec.stats()
    got = served.client.get_object(BUCKET, key)
    assert got.status_code == 200 and got.content == body
    after = served.node.codec.stats()
    if len(body) % MIB == 0:  # a window with a short tail block is the host codec's
        assert after["blocks_reconstructed"] - before["blocks_reconstructed"] == len(body) // MIB
        assert after["host_fallback_recon_blocks"] == before["host_fallback_recon_blocks"]


MESH_SERIES = [
    "minio_tpu_codec_mesh_devices",
    'minio_tpu_codec_mesh_blocks_total{share="even"}',
    'minio_tpu_codec_mesh_blocks_total{share="fullest"}',
    "minio_tpu_codec_mesh_chip_batches_total",
    'minio_tpu_codec_chip_blocks_total{chip="0"}',
    'minio_tpu_codec_transfer_bytes_total{dir="h2d"}',
]


@pytest.fixture(scope="module")
def scraped(served) -> str:
    r = served.client.request("GET", "/minio/v2/metrics/node")
    assert r.status_code == 200
    return r.text


def test_exposition_with_the_mesh_series_is_lint_clean(scraped):
    spec = importlib.util.spec_from_file_location(
        "metrics_lint", os.path.join(os.path.dirname(__file__), "..", "tools", "metrics_lint.py"))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    assert lint.validate_exposition(scraped) == [] and lint.lint_exposition(scraped) == []


@pytest.mark.parametrize("series", MESH_SERIES)
def test_mesh_series_are_exported(scraped, series):
    values = [float(line.rsplit(" ", 1)[1]) for line in scraped.splitlines()
              if line.startswith(series + " ")]
    assert len(values) == 1 and values[0] > 0.0, series


def test_h2d_series_counts_the_tp_replicas(served):
    tp = mesh_lib.factor_mesh(4)[1]
    moved = served.after["h2d_bytes"] - served.before["h2d_bytes"]
    padded = served.after["blocks_padded"] - served.before["blocks_padded"]
    assert moved == padded * K * (-(-MIB // K)) * tp


# -- the cell that measures it -------------------------------------------------------


def test_the_four_chip_cell_is_in_the_manifest_as_data():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        man = json.load(f)
    cell = [w for w in man["workloads"] if w["name"] == "put64m-c8-chip4"]
    assert cell == [{**cell[0], "config": "ec12p4-d16-chip4", "traffic": "put64m-c8", "chips": 4}]
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1
    with open(os.path.join(root, "benchmark", "configs", "ec12p4-d16-chip4.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "benchmark", "configs", "ec12p4-d16-chip1.json")) as f:
        one = json.load(f)
    assert cfg["chips"] == 4 and cfg["env"] == {"MINIO_TPU_CODEC": "device"}
    assert cfg["guarantees"] == one["guarantees"]
    assert (cfg["drives"], cfg["data"], cfg["parity"], cfg["block_bytes"]) == (16, 12, 4, MIB)
    throughput = [m for m in man["end_to_end"] if m["name"] == "throughput"][0]
    assert throughput["workloads"][-1] == "put64m-c8-chip4"
    mine = [m for m in man["per_layer"] if m.get("workloads") == ["put64m-c8-chip4"]]
    assert len(mine) == 16 and all(m["name"].endswith(".chip4") for m in mine)
    assert not any("roofline" in m["name"] for m in mine)
    counters = set(BatchingDeviceCodec().stats()) | {"compiles", "cache_entries"}
    for m in mine:
        with open(os.path.join(root, "benchmark", "metrics", m["name"] + ".json")) as f:
            reader = json.load(f)["reader"]
        assert set(reader.get("numerator", []) + reader.get("denominator", [])) <= counters
        assert all(tuple(row.split("/")) in STAGES for row in reader.get("rows", []))
    # the cell measures the mesh the program picks: no benchmark file sets the shape
    for name in os.listdir(os.path.join(root, "benchmark", "configs")):
        with open(os.path.join(root, "benchmark", "configs", name)) as f:
            assert "MTPU_MESH_SHAPE" not in json.load(f).get("env", {}), name
