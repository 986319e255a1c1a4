"""A streamed GET crosses to a thread once per read window.

The object layer's stream (`erasure._WindowStream`) hands the S3 front every
chunk of the current read window in one `next_batch()` call; the front
(`S3Server._send_stream`) writes them to the socket back to back and counts
its hops and chunks. Covered here: the served bytes are the same for every
shape of GET, the hop counter says one hop per window, a stream without
`next_batch()` is drained in bounded batches, and a window's pooled buffers
are recycled exactly once whichever way the response ends.
"""

import time
import xml.etree.ElementTree as ET

import numpy as np
import pytest

from minio_tpu.api import server as server_mod
from minio_tpu.api.server import S3Server, ThreadedServer
from minio_tpu.control import bufsan
from minio_tpu.control.iam import IAMSys
from minio_tpu.control.metrics import MetricsSys
from minio_tpu.object import erasure
from minio_tpu.object.pools import ServerPools
from minio_tpu.object.sets import ErasureSets
from minio_tpu.utils import bufpool
from minio_tpu.utils.bufpool import BufferPool
from minio_tpu.utils.hashes import hash_order
from tests.harness import ErasureHarness
from tests.s3client import S3TestClient

AK, SK, BKT = "batchak", "batch-secret-key", "bat"
MIB = 1 << 20
NS = "{http://s3.amazonaws.com/doc/2006-03-01/}"
GEOMETRIES = {"12+4": 16, "4+4": 8}  # data+parity -> drives (default parity 4)


@pytest.fixture(scope="module", params=list(GEOMETRIES))
def stack(request, tmp_path_factory):
    drives = GEOMETRIES[request.param]
    hz = ErasureHarness(tmp_path_factory.mktemp("getbatch"), n_disks=drives)
    layer = ServerPools([ErasureSets(list(hz.drives), drives)])
    srv = S3Server(layer, IAMSys(AK, SK), check_skew=False)
    srv.metrics = MetricsSys()  # a Node wires this; the counters live there
    ts = ThreadedServer(srv)
    client = S3TestClient(ts.start(), AK, SK)
    assert client.make_bucket(BKT).status_code == 200
    yield {"client": client, "srv": srv, "hz": hz, "k": drives - 4, "drives": drives}
    ts.stop()


def _body(size: int, seed: int = 0) -> bytes:
    return np.random.default_rng(seed).integers(0, 256, size, dtype=np.uint8).tobytes()


def _put(stack, key: str, size: int, seed: int = 0) -> bytes:
    body = _body(size, seed)
    assert stack["client"].put_object(BKT, key, body).status_code == 200
    return body


def _counted(stack, get):
    """Run `get()` and return (its result, hops, chunks) of the one streamed
    response it causes. The client has its last byte before the server
    records the response, so wait for the record."""
    m = stack["srv"].metrics
    hops0, chunks0 = m.get_stream_hops, m.get_stream_chunks
    out = get()
    deadline = time.monotonic() + 10
    while m.get_stream_hops == hops0 and time.monotonic() < deadline:
        time.sleep(0.01)
    return out, m.get_stream_hops - hops0, m.get_stream_chunks - chunks0


def _lose_data_shards(stack, key: str, n: int) -> None:
    order = hash_order(f"{BKT}/{key}", stack["drives"])
    victims = [i for i in range(stack["drives"]) if order[i] - 1 < stack["k"]][:n]
    assert len(victims) == n
    for i in victims:
        assert stack["hz"].delete_shard(i, BKT, key)


# -- (a) the served bytes and the hop counter ---------------------------------


@pytest.mark.parametrize("size_mib", [10, 40])
def test_whole_get_is_exact_and_takes_a_hop_per_window(stack, size_mib):
    key = f"whole-{size_mib}"
    body = _put(stack, key, size_mib * MIB, seed=size_mib)
    r, hops, chunks = _counted(stack, lambda: stack["client"].get_object(BKT, key))
    assert r.status_code == 200 and r.headers["Content-Length"] == str(len(body))
    assert r.content == body
    # K row views a 1 MiB block: 120 / 40 chunks for 10 MiB at 12+4 / 4+4.
    assert chunks == size_mib * stack["k"]
    windows = -(-size_mib // erasure.GROUP_BLOCKS)
    # One hop per window and one that finds the end of the stream.
    assert windows + 1 <= hops <= windows + 2
    if size_mib == 10:
        assert hops <= 3


def test_ranged_get_mid_row_is_exact(stack):
    body = _put(stack, "ranged", 10 * MIB, seed=3)
    # Starts and ends inside a row of a block, 17 windows' rows apart.
    lo, hi = 3 * MIB + 12345, 9 * MIB + 54321
    r, hops, chunks = _counted(stack, lambda: stack["client"].get_object(
        BKT, "ranged", headers={"Range": f"bytes={lo}-{hi}"}))
    assert r.status_code == 206
    assert r.content == body[lo : hi + 1]
    assert hops <= 3 and chunks > hops


def test_part_number_get_is_exact(stack):
    client = stack["client"]
    r = client.request("POST", f"/{BKT}/mp", query=[("uploads", "")])
    uid = ET.fromstring(r.text).find(f"{NS}UploadId").text
    parts = [_body(5 * MIB + 7, seed=11), _body(2 * MIB + 1, seed=12)]
    etags = []
    for n, part in enumerate(parts, 1):
        r = client.request("PUT", f"/{BKT}/mp",
                           query=[("partNumber", str(n)), ("uploadId", uid)], body=part)
        etags.append(r.headers["ETag"].strip('"'))
    done = "<CompleteMultipartUpload>" + "".join(
        f"<Part><PartNumber>{n}</PartNumber><ETag>{e}</ETag></Part>"
        for n, e in enumerate(etags, 1)) + "</CompleteMultipartUpload>"
    assert client.request("POST", f"/{BKT}/mp", query=[("uploadId", uid)],
                          body=done.encode()).status_code == 200
    r, hops, _ = _counted(stack, lambda: client.get_object(
        BKT, "mp", query=[("partNumber", "2")]))
    assert r.status_code == 206 and r.content == parts[1]
    assert hops <= 3
    # The whole object crosses a part boundary: one stream, a window a part.
    r, hops, _ = _counted(stack, lambda: client.get_object(BKT, "mp"))
    assert r.content == parts[0] + parts[1]
    assert hops == 3


def test_inline_get_is_exact(stack):
    body = _put(stack, "inline", 50_000, seed=4)
    r, hops, chunks = _counted(stack, lambda: stack["client"].get_object(BKT, "inline"))
    assert r.content == body
    assert hops == 2 and 1 <= chunks <= stack["k"]


def test_degraded_get_is_exact(stack):
    body = _put(stack, "degraded", 10 * MIB, seed=5)
    _lose_data_shards(stack, "degraded", 4)
    r, hops, chunks = _counted(stack, lambda: stack["client"].get_object(BKT, "degraded"))
    assert r.status_code == 200 and r.content == body
    assert hops <= 3 and chunks >= 10


# -- (c) a stream without next_batch() ---------------------------------------


def _wait(cond, what: str, seconds: float = 10.0) -> None:
    deadline = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.01)


@pytest.fixture
def armed():
    san = bufsan.BufSanitizer()
    bufsan.arm(san)
    try:
        yield san
    finally:
        bufsan.disarm()


@pytest.mark.parametrize("size_mib", [10, 40])
def test_plain_generator_streams_in_bounded_batches(stack, monkeypatch, armed, size_mib):
    """A wrapper that hides next_batch(): at 40 MiB the 4 MiB batches
    straddle read windows, and a window closed while the batch still views
    it is discarded, never recycled under the views: exact bytes, nothing
    left outstanding, nothing for bufsan to find."""
    key = f"plain-{size_mib}"
    body = _put(stack, key, size_mib * MIB, seed=6)
    layer = stack["srv"].layer
    real = layer.get_object_stream
    biggest = []
    pool = bufpool.shard_pool()
    _wait(lambda: pool.outstanding() == 0, "shard pool busy before the test")

    def plain(*a, **kw):
        oi, it = real(*a, **kw)

        def gen():
            yield from it

        return oi, gen()

    real_pull = server_mod._pull_batch

    def watched_pull(it):
        batch = real_pull(it)
        biggest.append(sum(len(c) for c in batch))
        return batch

    monkeypatch.setattr(layer, "get_object_stream", plain, raising=False)
    monkeypatch.setattr(server_mod, "_pull_batch", watched_pull)
    r, hops, chunks = _counted(stack, lambda: stack["client"].get_object(BKT, key))
    assert r.content == body
    assert chunks == size_mib * stack["k"]
    # Batches that stop at the first chunk past 4 MiB, and the end: 10 MiB
    # in 3 + 1 hops.
    assert hops == len(biggest)
    if size_mib == 10:
        assert hops == 4
    assert max(biggest) < server_mod._PULL_BATCH_BYTES + MIB
    assert biggest[-1] == 0
    _wait(lambda: pool.outstanding() == 0, "the wrapped stream kept pooled buffers")
    assert not armed.findings, armed.findings


# -- (b) buffer lifetime ---------------------------------------------------------


class _FakeWindows:
    """(chunks, close) units over a private pool, as `_stream_part_range`
    makes them, with every close() counted per window."""

    def __init__(self, windows: int, window_bytes: int, fail_after: int | None = None):
        self.pool = BufferPool(window_bytes, 4, name="fake-get")
        self.windows, self.window_bytes, self.fail_after = windows, window_bytes, fail_after
        self.closes: list[int] = []
        self.ended = False

    def units(self):
        try:
            for w in range(self.windows):
                if w == self.fail_after:
                    raise erasure.errors.FileCorrupt("planted mid-stream failure")
                pb = self.pool.acquire()
                step = self.window_bytes // 8
                chunks = [pb.view(o, o + step) for o in range(0, self.window_bytes, step)]
                self.closes.append(0)

                def close(w=w, pb=pb, chunks=chunks):
                    self.closes[w] += 1
                    del chunks[:]
                    pb.release_or_discard()

                yield chunks, close
        finally:
            self.ended = True


def _serve_fake(stack, monkeypatch, fake: _FakeWindows, key: str):
    layer = stack["srv"].layer
    oi = layer.get_object_info(BKT, key)
    assert oi.size == fake.windows * fake.window_bytes
    monkeypatch.setattr(
        layer, "get_object_stream",
        lambda *a, **kw: (oi, erasure._WindowStream(fake.units())), raising=False)


def test_client_gone_mid_window_closes_each_window_once(stack, monkeypatch, armed):
    _put(stack, "gone", 32 * MIB, seed=7)
    fake = _FakeWindows(windows=4, window_bytes=8 * MIB)
    _serve_fake(stack, monkeypatch, fake, "gone")
    r = stack["client"].request("GET", f"/{BKT}/gone", stream=True)
    assert r.status_code == 200
    assert len(r.raw.read(4 * MIB)) == 4 * MIB  # half of the first window
    r.raw.close()
    r.close()
    stack["client"].session.close()
    _wait(lambda: fake.ended, "the abandoned stream was never closed")
    assert fake.closes and all(n == 1 for n in fake.closes), fake.closes
    assert len(fake.closes) < fake.windows  # the rest was never read
    assert fake.pool.outstanding() == 0
    assert not armed.findings, armed.findings


def test_read_failure_mid_stream_closes_each_window_once(stack, monkeypatch, armed):
    _put(stack, "fails", 16 * MIB, seed=8)
    fake = _FakeWindows(windows=2, window_bytes=8 * MIB, fail_after=1)
    _serve_fake(stack, monkeypatch, fake, "fails")
    aborted = []
    try:
        r = stack["client"].get_object(BKT, "fails")
        aborted.append(len(r.content))
    except Exception as e:  # noqa: BLE001 - the promised length never arrives
        aborted.append(type(e).__name__)
    stack["client"].session.close()
    _wait(lambda: fake.ended, "the failed stream was never closed")
    assert aborted[0] != 16 * MIB
    assert fake.closes == [1]
    assert fake.pool.outstanding() == 0
    assert not armed.findings, armed.findings


def test_real_stream_abandoned_mid_window_returns_its_buffers(stack, armed):
    """The served path over the real layer: a client that closes its socket
    half a window in leaves the shard pool's outstanding count where it was."""
    _put(stack, "abandon", 40 * MIB, seed=9)
    pool = bufpool.shard_pool()
    _wait(lambda: pool.outstanding() == 0, "shard pool busy before the test")
    r = stack["client"].request("GET", f"/{BKT}/abandon", stream=True)
    assert r.status_code == 200
    assert len(r.raw.read(8 * MIB)) == 8 * MIB
    r.raw.close()
    r.close()
    stack["client"].session.close()
    _wait(lambda: pool.outstanding() == 0, "an abandoned GET kept pooled buffers", 20.0)
    assert not armed.findings, armed.findings


# -- the stream object itself --------------------------------------------------


def _units_of(lists, closes):
    for i, chunks in enumerate(lists):
        closes.append(0)

        def close(i=i):
            closes[i] += 1

        yield list(chunks), close


def test_window_stream_iterates_and_batches_the_same_chunks():
    lists = [[b"a", b"b", b"c"], [b"d"], [b"e", b"f"]]
    closes: list[int] = []
    assert list(erasure._WindowStream(_units_of(lists, closes))) == [
        b"a", b"b", b"c", b"d", b"e", b"f"]
    assert closes == [1, 1, 1]

    closes = []
    ws = erasure._WindowStream(_units_of(lists, closes))
    assert ws.next_batch() == [b"a", b"b", b"c"]
    assert closes == [0]  # the window in hand stays open until more is asked
    assert ws.next_batch() == [b"d"]
    assert closes == [1, 0]
    assert ws.next_batch() == [b"e", b"f"]
    assert ws.next_batch() == [] and ws.next_batch() == []
    assert closes == [1, 1, 1]


@pytest.mark.parametrize("how", ["close", "drop", "raise"])
def test_window_stream_closes_the_window_in_hand_once(how):
    closes: list[int] = []

    def units():
        yield from _units_of([[b"a", b"b"]], closes)
        if how == "raise":
            raise erasure.errors.FileCorrupt("planted")
        yield from _units_of([[b"never"]], [])

    ws = erasure._WindowStream(units())
    assert ws.next_batch() == [b"a", b"b"]
    if how == "close":
        ws.close()
        ws.close()
    elif how == "drop":
        del ws
    else:
        with pytest.raises(erasure.errors.FileCorrupt):
            ws.next_batch()
        ws.close()
    assert closes == [1]
