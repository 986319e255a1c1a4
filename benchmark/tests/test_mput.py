"""The generator's multipart upload (op ``MPUT``), and that it moved nothing else.

Run by hand, like the rest of this directory:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_mput.py -q -p no:cacheprovider

The first half needs no server: what ``load_traffic`` refuses, that the standing
traffic files load as they did, and that ``Client.next_op`` deals every standing
cell the ops it dealt before ``MPUT`` existed (digests taken from the parent
commit's ``client_worker.py``, PR 33). The second half drives the client's
``mput`` against a server in this process on jax's CPU backend.
"""

import hashlib
import json
import os

import pytest

from benchmark.harness import client_worker, server, traffic

MIB = 1 << 20


def _digest(obj) -> str:
    return hashlib.sha256(json.dumps(obj, sort_keys=True).encode()).hexdigest()[:16]


# -- load_traffic ---------------------------------------------------------------


def _load_altered(tmp_path, monkeypatch, name, alter):
    doc = json.load(open(os.path.join(traffic.BENCH_DIR, "traffic", f"{name}.json")))
    alter(doc)
    monkeypatch.setattr(traffic, "BENCH_DIR", str(tmp_path))
    os.makedirs(tmp_path / "traffic", exist_ok=True)
    with open(tmp_path / "traffic" / f"{name}.json", "w") as f:
        json.dump(doc, f)
    return traffic.load_traffic(name)


@pytest.mark.parametrize("name,alter,match", [
    ("put64m-c8", lambda t: t.update(mix={"MPUT": 100}), "come together"),
    ("put64m-c8", lambda t: t.update(multipart={"part_bytes": 8 * MIB, "parts_in_flight": 8}),
     "come together"),
    ("mpput64m-p8m-c4", lambda t: t.pop("multipart"), "come together"),
    ("mpput64m-p8m-c4", lambda t: t["multipart"].update(part_bytes=MIB), "part_bytes"),
    ("mpput64m-p8m-c4", lambda t: t["multipart"].update(parts_in_flight=0), "parts_in_flight"),
    ("mpput64m-p8m-c4", lambda t: t["multipart"].update(part_bites=1), "multipart takes"),
    ("mpput64m-p8m-c4", lambda t: t.update(mix={"MPUT": 50, "GET": 50}), "pool"),
    # PR 37: a heal is HEAL alone, with its field, over a populated pool, after a long ramp
    ("heal64m-d3", lambda t: t.pop("heal"), "come together"),
    ("degraded-get64m-c8", lambda t: t.update(heal={"replace": [1], "poll_s": 0.05}),
     "come together"),
    ("heal64m-d3", lambda t: t.update(mix={"HEAL": 50, "GET": 50}), "HEAL alone"),
    ("heal64m-d3", lambda t: t["prepare"].append({"lose_shards": {"data": 1}}), "HEAL alone"),
    ("heal64m-d3", lambda t: t["heal"].update(replace=[1, 1]), "heal takes"),
    ("heal64m-d3", lambda t: t["heal"].update(replace=[0]), "heal takes"),
    ("heal64m-d3", lambda t: t["heal"].update(poll_s=0), "heal takes"),
    ("heal64m-d3", lambda t: t.update(ramp_s=t["typical_op_s"]), "typical ops"),
])
def test_load_traffic_refuses(tmp_path, monkeypatch, name, alter, match):
    with pytest.raises(ValueError, match=match):
        _load_altered(tmp_path, monkeypatch, name, alter)


def test_an_object_of_one_part_may_be_smaller_than_the_least_part(tmp_path, monkeypatch):
    def one_small_part(t):
        t.update(object_bytes=MIB)
        t["multipart"].update(part_bytes=MIB)

    assert _load_altered(tmp_path, monkeypatch, "mpput64m-p8m-c4", one_small_part)


@pytest.mark.parametrize("name,digest", [
    ("mixed10m-c20", "95c9ccaf8379b263"),
    ("degraded-get64m-c8", "5e39ef8635ca75fc"), ("get64m-c8", "cad096ba7bb40bbb"),
    ("put64k-c32", "3ebc78b4b57b3b98"),
])
def test_standing_traffic_files_load_to_what_they_did(name, digest):
    """sha256 of the dictionary the parent's file gave (PR 33), keys sorted."""
    assert _digest(traffic.load_traffic(name)) == digest


def test_put64m_c8_is_the_parents_file_with_the_sweep_and_the_pinned_allocator():
    """After the benchmark check refused `put64m-c8-ec4p4` as too noisy the file
    gained `reap_superseded` and `mallopt` with their notes (`assumed`, `tmpfs_why`);
    the rest, and so every op and body of every seed, is PR 33's."""
    t = traffic.load_traffic("put64m-c8")
    rest = {k: v for k, v in t.items()
            if k not in ("reap_superseded", "mallopt", "assumed", "tmpfs_why")}
    assert _digest(rest) == "b97b542f3202ff0f"
    assert set(t["assumed"]) == {"keys", "bodies", "reap_superseded", "mallopt"}


def test_the_new_cell_is_what_the_issue_states():
    t = traffic.Cell("mpput64m-p8m-c4").traffic
    assert t["mix"] == {"MPUT": 100} and t["clients"] == 4 and t["loop"] == "closed"
    assert t["object_bytes"] == 64 * MIB
    assert t["multipart"] == {"part_bytes": 8 * MIB, "parts_in_flight": 8}
    assert t["keys"] == {"kind": "ring", "per_client": 4}
    assert (t["typical_op_s"], t["ramp_s"], t["trace_seconds"]) == (2.3, 8.0, 5.0)
    assert t["check"] == {"readback_sample": 12, "degraded_sample": 4}
    assert t["tmpfs_bytes"] == traffic.load_traffic("put64m-c8")["tmpfs_bytes"] == 30064771072


# -- the op generator, without a server ---------------------------------------------


def _client(mod, t, seed, idx, object_bytes=4096):
    spec = {"client": idx, "clients": t["clients"], "seed": seed, "endpoint": "http://127.0.0.1:9",
            "bucket": "bench", "access": "a", "secret": "s", "region": "r",
            "object_bytes": object_bytes, "mix": t["mix"], "keys": t["keys"], "timeout_s": 1.0}
    if "multipart" in t:
        spec["multipart"] = t["multipart"]
    return mod.Client(spec)


def deal(mod, t, seed, idx, n=200):
    """The first n ops a client would send, each write answered 200 at once:
    [kind, key] and, for a write, the body slice it drew."""
    c = _client(mod, t, seed, idx)

    def write(key):
        body = c.rnd.randrange(mod.N_OFFSETS)
        c.state[key] = c.shas[body]
        return body

    if any("populate" in step for step in t.get("prepare", [])):
        for key in c.keys:
            write(key)
    out = []
    for _ in range(n):
        kind, key = c.next_op()
        if kind in ("PUT", "MPUT"):
            out.append([kind, key, write(key)])
        else:
            if kind == "DELETE":
                c.state[key] = None
            out.append([kind, key])
    return out


# sha256 of the parent's first 200 ops (PR 33's client_worker.py through `deal`), by
# traffic file and (seed, client).
PARENT_DEALS = {
    ("put64m-c8", 7, 0): "9a3acf594e5fd5a6", ("put64m-c8", 3000000809, 3): "eabf3a5815881c0c",
    ("mixed10m-c20", 7, 0): "f643d09ca96b4353", ("mixed10m-c20", 3000000809, 3): "e83d7170e9ee09d9",
    ("degraded-get64m-c8", 7, 0): "2b0375cdabc7f622",
    ("degraded-get64m-c8", 3000000809, 3): "31232b7e7c7a37c1",
    ("get64m-c8", 7, 0): "2b0375cdabc7f622", ("get64m-c8", 3000000809, 3): "31232b7e7c7a37c1",
    ("put64k-c32", 7, 0): "c7c406a8b14b3ce9", ("put64k-c32", 3000000809, 3): "74a9afe85e402e70",
}


@pytest.mark.parametrize("name,seed,idx", sorted(PARENT_DEALS))
def test_next_op_deals_every_standing_cell_the_ops_the_parent_dealt(name, seed, idx):
    ops = deal(client_worker, traffic.load_traffic(name), seed, idx)
    dealt = hashlib.sha256(json.dumps(ops).encode()).hexdigest()[:16]
    assert dealt == PARENT_DEALS[name, seed, idx]


def test_the_healer_deals_heals_of_the_bucket_and_draws_nothing_from_its_generator():
    t = traffic.load_traffic("heal64m-d3")
    spec_heal = {"replace_dirs": ["/nonexistent/d1"], "poll_s": 0.05, "objects": 32}
    c = _client(client_worker, t, 7, 0)
    c.heal_spec = spec_heal
    state = c.rnd.getstate()
    assert [c.next_op() for _ in range(5)] == [("HEAL", "bench")] * 5
    assert c.rnd.getstate() == state
    # the pool's owners: the same keys and bodies as degraded-get64m-c8's clients
    pool = _client(client_worker, traffic.load_traffic("degraded-get64m-c8"), 7, 0)
    assert c.keys == pool.keys and c.shas == pool.shas and c.offsets == pool.offsets


def test_mput_alone_cycles_the_ring_and_draws_the_body_a_put_would():
    t = traffic.load_traffic("mpput64m-p8m-c4")
    as_put = {**t, "mix": {"PUT": 100}}
    as_put.pop("multipart")
    mputs, puts = deal(client_worker, t, 11, 2, n=12), deal(client_worker, as_put, 11, 2, n=12)
    assert [op[0] for op in mputs] == ["MPUT"] * 12
    assert [op[1:] for op in mputs] == [op[1:] for op in puts]
    assert [op[1] for op in mputs[:5]] == [f"c002/k000{j}" for j in (0, 1, 2, 3, 0)]


def test_the_clients_own_account_of_an_upload():
    """Parts cut the slice a PUT would send; the ETag due is md5 of the parts' md5s."""
    t = {**traffic.load_traffic("mpput64m-p8m-c4"),
         "multipart": {"part_bytes": 1000, "parts_in_flight": 3}}
    c = _client(client_worker, t, 5, 1, object_bytes=2500)
    try:
        for body, rows, etag in zip(c.bodies, c.parts, c.mp_etags):
            assert [(a, b) for a, b, _, _ in rows] == [(0, 1000), (1000, 2000), (2000, 2500)]
            whole = bytes(body)
            assert b"".join(whole[a:b] for a, b, _, _ in rows) == whole
            for a, b, sha, md5 in rows:
                assert sha == hashlib.sha256(whole[a:b]).hexdigest()
                assert md5 == hashlib.md5(whole[a:b]).digest()
            assert etag == hashlib.md5(b"".join(r[3] for r in rows)).hexdigest() + "-3"
        assert c.part_conns.qsize() == 3
    finally:
        c.close()


# -- against a server in this process -------------------------------------------------

on_cpu = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS", "") != "cpu",
    reason="serves the device programs on jax's CPU backend: set JAX_PLATFORMS=cpu")


@pytest.fixture(scope="module")
def served():
    from benchmark.harness import run as bench_run

    cell = traffic.Cell("mpput64m-p8m-c4", rehearse=True)
    dep = server.Deployment(cell.config, rehearse=True)
    try:
        dep.start(cell.footprint_bytes())
        bench_run.make_bucket(dep)
        yield dep
    finally:
        dep.close()


@pytest.fixture
def uploader(served):
    """One client of three parts, the last a short one, two in flight."""
    t = {**traffic.load_traffic("mpput64m-p8m-c4"),
         "multipart": {"part_bytes": 5 * MIB, "parts_in_flight": 2}}
    spec = {"client": 0, "clients": 1, "seed": 21, "endpoint": served.endpoint,
            "bucket": server.BUCKET, "access": server.ACCESS, "secret": server.SECRET,
            "region": server.REGION, "object_bytes": 11 * MIB + 12345, "mix": t["mix"],
            "keys": t["keys"], "timeout_s": 120.0, "multipart": t["multipart"]}
    c = client_worker.Client(spec)
    yield c
    c.close()


@on_cpu
def test_mput_is_read_back_whole_with_the_etags_the_clients_arithmetic_gives(served, uploader):
    c = uploader
    before = served.snapshot()
    ok, nbytes, why = c.do("MPUT", c.keys[0])
    assert ok and nbytes == c.size, why
    assert len(c.part_ends) == 3 and not c.open_uploads
    i = c.shas.index(c.state[c.keys[0]])
    whole = bytes(c.bodies[i])
    md5s = [hashlib.md5(whole[a:a + 5 * MIB]).digest() for a in range(0, len(whole), 5 * MIB)]
    assert c.etags[c.keys[0]] == hashlib.md5(b"".join(md5s)).hexdigest() + "-3"
    # read back whole: sha256, length and the ETag, as the check's read-back does
    assert c.verify([c.keys[0]])["mismatches"] == 0
    assert hashlib.sha256(c.scratch).hexdigest() == hashlib.sha256(whole).hexdigest()
    # and across the part boundaries with four data shards gone
    served.lose_shards(c.keys[0], 4)
    assert c.verify([c.keys[0]])["mismatches"] == 0
    rows = {name: h["count"] - before["ledger"].get(name, {"count": 0})["count"]
            for name, h in served.snapshot()["ledger"].items()}
    assert rows["object/object.PutObjectPart"] == 3
    assert rows["object/object.CompleteMultipartUpload"] == 1
    assert rows["object/commit"] == 3 + 1  # the program gives both commits one row name
    # a wrong record is caught: the read-back holds the ETag and the bytes
    c.etags[c.keys[0]] = "0" * 32 + "-3"
    assert c.verify([c.keys[0]])["mismatches"] == 1


@on_cpu
def test_a_complete_that_names_a_wrong_part_etag_fails_the_op_and_is_aborted_at_drain(uploader):
    c, key = uploader, uploader.keys[1]
    i = 0
    status, _, _, data = c.s3.request("POST", c.path(key), query=(("uploads", ""),))
    assert status == 200
    upload_id = data.split(b"<UploadId>")[1].split(b"<")[0].decode()
    c.open_uploads.append((key, upload_id))
    etags = []
    for n, (a, b, sha, md5) in enumerate(c.parts[i], start=1):
        etag, why = c._send_part(key, upload_id, n, c.bodies[i][a:b], sha, md5)
        assert etag == md5.hex(), why
        etags.append(etag)
    ok, nbytes, why = c._complete(key, upload_id, [etags[0], "f" * 32, etags[2]], i)
    assert not ok and nbytes == 0 and "Complete: HTTP 400" in why
    assert key not in c.state and c.open_uploads == [(key, upload_id)]
    assert c.abort_open_uploads() == 1 and not c.open_uploads
    status, _, _, _ = c.s3.request("GET", c.path(key), query=(("uploadId", upload_id),))
    assert status == 404  # the upload is gone
