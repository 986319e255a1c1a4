"""A streamed PUT body reaches its worker thread without a round trip a chunk.

`server._RequestBodyReader` pumps the body on the event loop into a queue
bounded by bytes; the object layer's thread drains the queue and never
crosses to the loop for a chunk. Covered here: the bytes arrive whole and in
order through read() and readinto(), a worker that does not read parks the
pump at the bound (and with it aiohttp's reading of the socket), a failure
of the body surfaces in the worker after what was queued, close() ends a
pump however the request ended, and served PUTs -- accepted, refused before
a byte was read, broken off by the client -- leave no pump behind. The GET
side's gathered write is held to its contract with a slow client.
"""

import asyncio
import threading
import time

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
from tests.harness import ErasureHarness
from tests.s3client import S3TestClient

AK, SK, BKT = "pumpak", "pump-secret-key", "pmp"
MIB = 1 << 20
BOUND = server_mod._BODY_QUEUE_BYTES
RECV_DEFAULT = 256 * 1024


# -- the reader alone, over a loop in a thread ---------------------------------


class _Content:
    """aiohttp's request.content as the reader uses it: readany()."""

    def __init__(self, chunks, fail_at=None, hang_at=None):
        self.chunks, self.fail_at, self.hang_at = chunks, fail_at, hang_at
        self.served = 0

    async def readany(self) -> bytes:
        await asyncio.sleep(0)
        if self.served == self.fail_at:
            raise ConnectionResetError("planted: the client went away")
        if self.served == self.hang_at:
            await asyncio.Event().wait()
        if self.served == len(self.chunks):
            return b""
        self.served += 1
        return self.chunks[self.served - 1]


class _Transport:
    max_size = RECV_DEFAULT


class _Request:
    def __init__(self, content, transport=None):
        self.content, self.transport = content, transport


@pytest.fixture
def loop():
    lp = asyncio.new_event_loop()
    t = threading.Thread(target=lp.run_forever, daemon=True)
    t.start()
    yield lp
    lp.call_soon_threadsafe(lp.stop)
    t.join(5)
    lp.close()


def _on_loop(loop, fn, *args):
    async def call():
        return fn(*args)

    return asyncio.run_coroutine_threadsafe(call(), loop).result(10)


def _reader(loop, content, transport=None):
    return _on_loop(
        loop, lambda: server_mod._RequestBodyReader(_Request(content, transport), loop))


def _chunks(n: int, size: int) -> list[bytes]:
    rng = np.random.default_rng(size)
    return [rng.integers(0, 256, size, dtype=np.uint8).tobytes() for _ in range(n)]


def _drain(reader, how: str, piece: int) -> bytes:
    out = bytearray()
    if how == "read":
        while True:
            b = reader.read(piece)
            if not b:
                return bytes(out)
            out += b
    buf = bytearray(piece)
    while True:
        n = reader.readinto(memoryview(buf))
        if not n:
            return bytes(out)
        out += buf[:n]


def _wait(cond, what: str, seconds: float = 10.0) -> None:
    deadline = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.005)


@pytest.mark.parametrize("how", ["read", "readinto"])
@pytest.mark.parametrize("chunk", [1000, 256 * 1024, 3 * MIB])
def test_pump_delivers_the_body_whole_and_in_order(loop, how, chunk):
    chunks = _chunks(max(3, (6 * MIB) // chunk), chunk)
    reader = _reader(loop, _Content(chunks))
    assert _drain(reader, how, 100_000) == b"".join(chunks)
    assert reader.read(10) == b"" and reader.readinto(memoryview(bytearray(10))) == 0
    _on_loop(loop, reader.close)


@pytest.mark.parametrize("chunk", [256 * 1024, MIB])
def test_pump_parks_at_the_byte_bound_until_the_worker_reads(loop, chunk):
    chunks = _chunks(4 * BOUND // chunk, chunk)
    content = _Content(chunks)
    reader = _reader(loop, content)
    _wait(lambda: reader._parked, "the pump never parked")
    time.sleep(0.05)  # parked: nothing more is taken from the body
    assert content.served == BOUND // chunk
    assert reader._queued == BOUND
    assert _drain(reader, "read", MIB) == b"".join(chunks)
    assert content.served == len(chunks)
    _on_loop(loop, reader.close)


def test_body_failure_reaches_the_worker_after_what_was_queued(loop):
    chunks = _chunks(3, 1000)
    reader = _reader(loop, _Content(chunks, fail_at=3))
    got = bytearray()
    with pytest.raises(ConnectionResetError, match="planted"):
        while True:
            got += reader.read(4096) or b""
            assert len(got) <= 3000
    assert bytes(got) == b"".join(chunks)
    _on_loop(loop, reader.close)


def test_close_wakes_a_waiting_worker_and_restores_the_recv_size(loop):
    transport = _Transport()
    reader = _reader(loop, _Content(_chunks(1, 1000), hang_at=1), transport)
    assert transport.max_size == BOUND  # a streamed body is read in larger pieces
    assert len(reader.read(4096)) == 1000
    raised = []

    def worker():
        try:
            reader.read(10)
        except ConnectionError as e:
            raised.append(e)

    t = threading.Thread(target=worker)
    t.start()
    time.sleep(0.05)
    assert t.is_alive()  # waiting for a chunk that never comes
    _on_loop(loop, reader.close)
    t.join(5)
    assert not t.is_alive() and len(raised) == 1
    assert transport.max_size == RECV_DEFAULT
    _wait(reader._task.done, "the pump outlived close()")
    _on_loop(loop, reader.close)  # a second close changes nothing


# -- _write_batch's choices ---------------------------------------------------------


class _Writer:
    def __init__(self):
        self.drains = 0

    async def drain(self):
        self.drains += 1


class _Resp:
    def __init__(self):
        self.written: list = []
        self._payload_writer = _Writer()

    async def write(self, chunk):
        self.written.append(chunk)


class _WTransport:
    def __init__(self, closing=False):
        self.closing, self.lines = closing, []

    def is_closing(self):
        return self.closing

    def writelines(self, chunks):
        self.lines.append(list(chunks))


@pytest.mark.parametrize("case", ["gathered", "one-chunk", "no-transport", "closing"])
def test_write_batch_gathers_all_but_the_first_chunk(case):
    batch = [b"a" * 10, b"b" * 20, b"c" * 30][: 1 if case == "one-chunk" else 3]
    resp = _Resp()
    transport = None if case == "no-transport" else _WTransport(closing=case == "closing")
    call = server_mod._write_batch(_Request(None, transport), resp, batch)
    if case in ("no-transport", "closing"):
        with pytest.raises(ConnectionResetError):
            asyncio.run(call)
        return
    asyncio.run(call)
    assert resp.written == batch[:1]  # aiohttp puts the headers before it
    assert transport.lines == ([batch[1:]] if case == "gathered" else [])
    assert resp._payload_writer.drains == 1


# -- served ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def stack(tmp_path_factory):
    hz = ErasureHarness(tmp_path_factory.mktemp("pump"), n_disks=8)
    layer = ServerPools([ErasureSets(list(hz.drives), 8)])
    srv = S3Server(layer, IAMSys(AK, SK), check_skew=False)
    srv.metrics = MetricsSys()
    ts = ThreadedServer(srv)
    endpoint = ts.start()
    client = S3TestClient(endpoint, AK, SK)
    assert client.make_bucket(BKT).status_code == 200
    yield {"client": client, "endpoint": endpoint, "ts": ts, "srv": srv}
    ts.stop()


def _body(size: int, seed: int) -> bytes:
    return np.random.default_rng(seed).integers(0, 256, size, dtype=np.uint8).tobytes()


def _pumps(stack) -> int:
    """Pump tasks alive on the serving loop."""
    loop = stack["ts"]._loop

    async def count():
        return sum(1 for t in asyncio.all_tasks()
                   if getattr(t.get_coro(), "__qualname__", "") == "_RequestBodyReader._pump")

    return asyncio.run_coroutine_threadsafe(count(), loop).result(10)


@pytest.mark.parametrize("size", [1, 70_000, 10 * MIB + 3, 40 * MIB])
def test_served_put_round_trips_and_leaves_no_pump(stack, size):
    body = _body(size, seed=size % 97)
    key = f"rt-{size}"
    assert stack["client"].put_object(BKT, key, body).status_code == 200
    assert stack["client"].get_object(BKT, key).content == body
    _wait(lambda: _pumps(stack) == 0, "a finished PUT left its pump running")


def test_refused_put_stops_its_pump_before_the_body_is_read(stack):
    """A PUT refused at its signature never reads the body: the pump that
    started with the request is stopped all the same, and the server goes
    on serving."""
    bad = S3TestClient(stack["endpoint"], AK, "not-the-secret")
    try:
        r = bad.put_object(BKT, "refused", _body(10 * MIB, seed=1))
        assert r.status_code == 403
    except Exception:  # noqa: BLE001 - the server may close on the unread body
        pass
    _wait(lambda: _pumps(stack) == 0, "a refused PUT left its pump running")
    body = _body(MIB, seed=2)
    assert stack["client"].put_object(BKT, "after-refusal", body).status_code == 200
    assert stack["client"].get_object(BKT, "after-refusal").content == body


def test_client_gone_mid_body_fails_the_put_and_stops_its_pump(stack):
    import socket
    from urllib.parse import urlparse

    u = urlparse(stack["endpoint"])
    s = socket.create_connection((u.hostname, u.port))
    s.sendall(
        f"PUT /{BKT}/half HTTP/1.1\r\nHost: {u.netloc}\r\n"
        f"Content-Length: {8 * MIB}\r\n\r\n".encode() + b"x" * MIB)
    time.sleep(0.1)
    s.close()
    _wait(lambda: _pumps(stack) == 0, "a PUT whose client left kept its pump")
    assert stack["client"].request("HEAD", f"/{BKT}/half").status_code in (403, 404)


def test_slow_client_gets_every_window_exact(stack):
    """The gathered write against a client that reads slowly: the loop waits
    until the socket has taken a window before the stream recycles it; the
    bytes are exact, one hop a window, nothing left outstanding and nothing
    for bufsan to find."""
    size = 40 * MIB
    body = _body(size, seed=5)
    assert stack["client"].put_object(BKT, "slow", body).status_code == 200
    pool = bufpool.shard_pool()
    _wait(lambda: pool.outstanding() == 0, "shard pool busy before the test")
    m = stack["srv"].metrics
    hops0 = m.get_stream_hops
    san = bufsan.BufSanitizer()
    bufsan.arm(san)
    try:
        r = stack["client"].request("GET", f"/{BKT}/slow", stream=True)
        assert r.status_code == 200
        got = bytearray()
        while True:
            piece = r.raw.read(MIB)
            if not piece:
                break
            got += piece
            time.sleep(0.004)
        _wait(lambda: m.get_stream_hops > hops0, "the response was never recorded")
        _wait(lambda: pool.outstanding() == 0, "a finished GET kept pooled buffers")
    finally:
        bufsan.disarm()
    assert bytes(got) == body
    windows = -(-size // (erasure.GROUP_BLOCKS * MIB))
    assert m.get_stream_hops - hops0 <= windows + 2
    assert not san.findings, san.findings
