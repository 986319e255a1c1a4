#!/usr/bin/env python3
"""One closed-loop S3 client, as a process of its own.

The run's main process holds the chip and serves the store; a client in that
process would share its GIL and be read as a slow server. So every client is
this script in a subprocess. It imports neither jax nor anything of
``minio_tpu``: the SigV4 signing below is a copy of ``minio_tpu/api/auth.py``
``sign_request`` as ``minio_tpu/loadgen/target.py`` uses it (header-signed,
payload sha256 in ``x-amz-content-sha256``), on ``http.client`` with one
kept-alive connection.

Protocol: one JSON object per line on stdin, one per line on stdout.

    argv[1]            JSON spec: endpoint, keys, traffic, seed, client index
    -> {"ready": ...}  buffer made, sha256 of every body slice known
    {"cmd": "populate"}                -> {"populated": n, "failed": n}
    {"cmd": "run", "start", "t0", "t1"} -> {"ops": [...], "cpu_s", ...}
    {"cmd": "verify", "keys": [...]|null} -> {"verified": n, "mismatch": [...]}
    {"cmd": "live"}                    -> {"live": n}   keys with a body on record
    {"cmd": "exit"}

Times are ``time.monotonic()``: CLOCK_MONOTONIC is one clock for every process
of a host, so the parent's window and the clients' stamps agree.

The client is the reference of the comparison that decides ``correct``: it
owns its keys (no other client touches them), remembers for each the sha256
of the last body the server acknowledged or that the key was deleted, and
holds every answer (GET body, HEAD length, 404 after DELETE) to that record.

An ``MPUT`` is a whole multipart upload, the way an SDK sends a large object:
Create, every part (``multipart.parts_in_flight`` at once, each on a connection
of its own from a small pool of threads in this process), Complete. The client
keeps its own account of it: the md5 of each part, held against the ``ETag``
the UploadPart answered; ``md5(the parts' binary md5s) + "-N"``, held against
the Complete's ``ETag`` inside the op and against the GET's at read-back.
"""

from __future__ import annotations

import datetime
import hashlib
import hmac
import http.client
import json
import math
import queue
import random
import re
import sys
import time
import urllib.parse
from concurrent.futures import ThreadPoolExecutor

ALGORITHM = "AWS4-HMAC-SHA256"
EMPTY_SHA256 = hashlib.sha256(b"").hexdigest()
N_OFFSETS = 8  # body slices per client; sha256 of each is made before the window
OFFSET_SPAN = 1 << 20  # slices start inside the buffer's first MiB
MIX_SUM = 100  # a mix is shares of 100


# -- SigV4 (copy of minio_tpu/api/auth.py, signing side only) -------------------


def _hmac(key: bytes, msg: str) -> bytes:
    return hmac.new(key, msg.encode(), hashlib.sha256).digest()


def _signing_key(secret: str, date: str, region: str) -> bytes:
    k = _hmac(("AWS4" + secret).encode(), date)
    k = _hmac(k, region)
    k = _hmac(k, "s3")
    return _hmac(k, "aws4_request")


def _uri_encode(s: str, encode_slash: bool = True) -> str:
    return urllib.parse.quote(s, safe=("" if encode_slash else "/") + "-_.~")


def sign(access: str, secret: str, region: str, method: str, host: str, path: str,
         query: list[tuple[str, str]], payload_sha256: str) -> dict[str, str]:
    """Headers of a header-signed request, ``host`` included."""
    amz_date = datetime.datetime.now(datetime.timezone.utc).strftime("%Y%m%dT%H%M%SZ")
    date = amz_date[:8]
    headers = {"host": host, "x-amz-date": amz_date, "x-amz-content-sha256": payload_sha256}
    signed = sorted(headers)
    canon_query = "&".join(
        f"{k}={v}" for k, v in sorted((_uri_encode(k), _uri_encode(v)) for k, v in query)
    )
    creq = "\n".join([
        method.upper(),
        _uri_encode(path, encode_slash=False),
        canon_query,
        "".join(f"{h}:{' '.join(headers[h].split())}\n" for h in signed),
        ";".join(signed),
        payload_sha256,
    ])
    scope = f"{date}/{region}/s3/aws4_request"
    sts = "\n".join([ALGORITHM, amz_date, scope, hashlib.sha256(creq.encode()).hexdigest()])
    sig = hmac.new(_signing_key(secret, date, region), sts.encode(), hashlib.sha256).hexdigest()
    headers["authorization"] = (
        f"{ALGORITHM} Credential={access}/{scope}, "
        f"SignedHeaders={';'.join(signed)}, Signature={sig}"
    )
    return headers


class S3Conn:
    """One kept-alive connection; reconnects once when the server closed it."""

    def __init__(self, endpoint: str, access: str, secret: str, region: str, timeout_s: float):
        u = urllib.parse.urlparse(endpoint)
        self.host = u.netloc
        self.access, self.secret, self.region = access, secret, region
        self.timeout_s = timeout_s
        self.conn: http.client.HTTPConnection | None = None

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None

    def request(self, method: str, path: str, body=b"", body_sha256: str = EMPTY_SHA256,
                into: bytearray | None = None,
                query: tuple[tuple[str, str], ...] = ()) -> tuple[int, int, dict, bytes]:
        """(status, body length, headers, body). A 200 GET body is read into
        ``into`` when given (no per-op allocation); other bodies are returned."""
        headers = sign(self.access, self.secret, self.region, method, self.host, path,
                       list(query), body_sha256)
        headers["content-length"] = str(len(body))
        url = urllib.parse.quote(path)
        if query:
            url += "?" + "&".join(f"{_uri_encode(k)}={_uri_encode(v)}" for k, v in query)
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(self.host, timeout=self.timeout_s)
            try:
                self.conn.request(method, url, body=body, headers=headers)
                resp = self.conn.getresponse()
                break
            except (http.client.RemoteDisconnected, BrokenPipeError, ConnectionResetError):
                self.close()  # a kept-alive connection the server dropped while idle
                if attempt:
                    raise
        hdrs = {k.lower(): v for k, v in resp.getheaders()}
        if into is not None and resp.status == 200:
            view, n = memoryview(into), 0
            while n < len(into):
                got = resp.readinto(view[n:])
                if not got:
                    break
                n += got
            rest = resp.read()  # more than expected is a wrong answer too
            return resp.status, n + len(rest), hdrs, b""
        data = resp.read()
        return resp.status, len(data), hdrs, data


# -- the client ----------------------------------------------------------------


def client_keys(keys: dict, idx: int, n: int) -> list[str]:
    """The keys client `idx` of `n` owns: a ring of its own, or its share of
    the pool (object j belongs to client j mod n)."""
    if keys["kind"] == "ring":
        return [f"c{idx:03d}/k{j:04d}" for j in range(keys["per_client"])]
    if keys["kind"] == "pool":
        return [f"c{idx:03d}/o{j:05d}" for j in range(keys["objects"]) if j % n == idx]
    raise ValueError(f"unknown keys.kind {keys['kind']!r}")


class Client:
    def __init__(self, spec: dict):
        import numpy as np

        self.spec = spec
        self.idx, self.n = spec["client"], spec["clients"]
        self.bucket = spec["bucket"]
        self.size = spec["object_bytes"]
        self.s3 = S3Conn(spec["endpoint"], spec["access"], spec["secret"], spec["region"],
                         spec["timeout_s"])
        seed = spec["seed"]
        rng = np.random.default_rng([seed, self.idx, 1])
        self.buf = rng.bytes(self.size + OFFSET_SPAN)
        self.rnd = random.Random(seed * 1_000_003 + self.idx)
        offs = sorted(self.rnd.sample(range(OFFSET_SPAN), N_OFFSETS))
        view = memoryview(self.buf)
        self.bodies = [view[o:o + self.size] for o in offs]
        self.shas = [hashlib.sha256(b).hexdigest() for b in self.bodies]
        self.scratch = bytearray(self.size)
        self.keys = client_keys(spec["keys"], self.idx, self.n)
        self.ring_next = 0
        self.state: dict[str, str | None] = {}  # key -> sha256 acknowledged, None = deleted
        self.deck: list[str] = []
        mix = spec["mix"]
        if sum(mix.values()) != MIX_SUM:
            raise ValueError(f"mix must sum to {MIX_SUM}, got {mix}")
        # The smallest deck with the exact mix (45/30/15/10 is 9/6/3/2 of 20):
        # a client sends some twenty ops in a window, so a deck of a hundred
        # would leave the mix of a window to the seed.
        unit = math.gcd(*mix.values())
        self.deck_ops = [op for op, share in sorted(mix.items()) for _ in range(share // unit)]
        # Traffic of one kind of write cycles the ring; what a mix puts back
        # where nothing is left to read is its own kind of write.
        self.ring_op = next(iter(mix)) if set(mix) in ({"PUT"}, {"MPUT"}) else None
        self.write_op = "MPUT" if "MPUT" in mix and "PUT" not in mix else "PUT"
        self.etags: dict[str, str] = {}  # key -> ETag due at read-back (multipart objects)
        self.open_uploads: list[tuple[str, str]] = []  # (key, upload id) no Complete closed
        self.part_ends: list[float] = []  # when each UploadPart of a run answered 200
        self.mp = spec.get("multipart")
        if self.mp:
            self._prepare_parts()

    def _prepare_parts(self) -> None:
        """The client's own account of every upload it can send: for each body
        slice its parts' (start, end, sha256 to sign, md5), and the ETag the
        object is due. Plus the part senders: a thread and a connection each."""
        part = int(self.mp["part_bytes"])
        self.parts, self.mp_etags = [], []
        for body in self.bodies:
            cuts = [(a, min(a + part, self.size)) for a in range(0, self.size, part)]
            rows = [(a, b, hashlib.sha256(body[a:b]).hexdigest(), hashlib.md5(body[a:b]).digest())
                    for a, b in cuts]
            self.parts.append(rows)
            self.mp_etags.append(
                hashlib.md5(b"".join(r[3] for r in rows)).hexdigest() + f"-{len(rows)}")
        n = max(1, min(int(self.mp["parts_in_flight"]), len(self.parts[0])))
        spec = self.spec
        self.part_conns: queue.SimpleQueue = queue.SimpleQueue()
        for _ in range(n):
            self.part_conns.put(S3Conn(spec["endpoint"], spec["access"], spec["secret"],
                                       spec["region"], spec["timeout_s"]))
        self.part_pool = ThreadPoolExecutor(max_workers=n, thread_name_prefix="part")

    def close(self) -> None:
        self.s3.close()
        if self.mp:
            self.part_pool.shutdown(wait=True)
            while not self.part_conns.empty():
                self.part_conns.get().close()

    def path(self, key: str) -> str:
        return f"/{self.bucket}/{key}"

    # -- single ops; each returns (ok, nbytes, detail) ---------------------------

    def put(self, key: str) -> tuple[bool, int, str]:
        i = self.rnd.randrange(N_OFFSETS)
        status, _, _, data = self.s3.request("PUT", self.path(key), self.bodies[i], self.shas[i])
        if status != 200:
            self._void(key)
            return False, 0, f"PUT {key}: HTTP {status} {data[:200]!r}"
        self.state[key] = self.shas[i]
        self.etags.pop(key, None)
        return True, self.size, ""

    def _void(self, key: str) -> None:
        """Unknown whether a write landed: the key's record is void until the next one."""
        self.state.pop(key, None)
        self.etags.pop(key, None)

    def _send_part(self, key: str, upload_id: str, number: int, body, sha: str,
                   md5: bytes) -> tuple[str, str]:
        """One UploadPart on a connection of its own: (the ETag answered, "")
        or ("", what went wrong). The ETag is held to the client's own md5."""
        conn = self.part_conns.get()
        try:
            status, _, hdrs, data = conn.request(
                "PUT", self.path(key), body, sha,
                query=(("partNumber", str(number)), ("uploadId", upload_id)))
        except (OSError, http.client.HTTPException) as e:
            conn.close()
            return "", f"part {number}: {type(e).__name__}: {e}"
        finally:
            self.part_conns.put(conn)
        if status != 200:
            return "", f"part {number}: HTTP {status} {data[:200]!r}"
        etag = hdrs.get("etag", "").strip('"')
        self.part_ends.append(time.monotonic())
        if etag != md5.hex():
            return "", f"part {number}: ETag {etag!r}, the part's md5 is {md5.hex()}"
        return etag, ""

    def mput(self, key: str) -> tuple[bool, int, str]:
        """Create, the parts (at most `parts_in_flight` at once), Complete.
        It counts when the Complete answered 200 with the ETag due, and only then."""
        i = self.rnd.randrange(N_OFFSETS)
        body, rows = self.bodies[i], self.parts[i]
        self._void(key)
        status, _, _, data = self.s3.request("POST", self.path(key), query=(("uploads", ""),))
        found = re.search(rb"<UploadId>([^<]+)</UploadId>", data) if status == 200 else None
        if not found:
            return False, 0, f"MPUT {key}: Create: HTTP {status} {data[:200]!r}"
        upload_id = found.group(1).decode()
        self.open_uploads.append((key, upload_id))
        sent = [self.part_pool.submit(self._send_part, key, upload_id, n, body[a:b], sha, md5)
                for n, (a, b, sha, md5) in enumerate(rows, start=1)]
        answers = [f.result() for f in sent]
        bad = [why for _, why in answers if why]
        if bad:
            return False, 0, f"MPUT {key}: {bad[0]}"
        return self._complete(key, upload_id, [etag for etag, _ in answers], i)

    def _complete(self, key: str, upload_id: str, etags: list[str],
                  i: int) -> tuple[bool, int, str]:
        """The Complete of an upload of body slice `i`, naming `etags` in order."""
        doc = ("<CompleteMultipartUpload>" + "".join(
            f"<Part><PartNumber>{n}</PartNumber><ETag>&quot;{e}&quot;</ETag></Part>"
            for n, e in enumerate(etags, start=1)) + "</CompleteMultipartUpload>").encode()
        status, _, _, data = self.s3.request(
            "POST", self.path(key), doc, hashlib.sha256(doc).hexdigest(),
            query=(("uploadId", upload_id),))
        if status != 200:
            return False, 0, f"MPUT {key}: Complete: HTTP {status} {data[:200]!r}"
        self.open_uploads.remove((key, upload_id))
        found = re.search(rb"<ETag>(?:&quot;|&#34;|\")?([0-9a-f-]+)", data)
        if not found or found.group(1).decode() != self.mp_etags[i]:
            return False, 0, (f"MPUT {key}: Complete answered ETag "
                              f"{found.group(1) if found else data[:200]!r}, "
                              f"due {self.mp_etags[i]}")
        self.state[key] = self.shas[i]
        self.etags[key] = self.mp_etags[i]
        return True, self.size, ""

    def abort_open_uploads(self) -> int:
        """At drain, outside the window: uploads no Complete closed are aborted."""
        n = 0
        for key, upload_id in self.open_uploads:
            try:
                self.s3.request("DELETE", self.path(key), query=(("uploadId", upload_id),))
            except (OSError, http.client.HTTPException):
                self.s3.close()
            n += 1
        self.open_uploads.clear()
        return n

    def get(self, key: str) -> tuple[bool, int, str]:
        want = self.state.get(key)
        status, n, hdrs, data = self.s3.request("GET", self.path(key), into=self.scratch)
        if want is None:
            ok = status == 404
            return ok, 0, "" if ok else f"GET {key}: HTTP {status}, the key was deleted"
        if status != 200:
            return False, 0, f"GET {key}: HTTP {status} {data[:200]!r}"
        if n != self.size:
            return False, 0, f"GET {key}: {n} bytes, stored {self.size}"
        if hashlib.sha256(self.scratch).hexdigest() != want:
            return False, 0, f"GET {key}: sha256 differs from the acknowledged PUT"
        due = self.etags.get(key)
        if due is not None and hdrs.get("etag", "").strip('"') != due:
            return False, 0, f"GET {key}: ETag {hdrs.get('etag')!r}, the upload's parts give {due}"
        return True, n, ""

    def stat(self, key: str) -> tuple[bool, int, str]:
        want = self.state.get(key)
        status, _, hdrs, _ = self.s3.request("HEAD", self.path(key))
        if want is None:
            ok = status == 404
            return ok, 0, "" if ok else f"HEAD {key}: HTTP {status}, the key was deleted"
        if status != 200 or int(hdrs.get("content-length", -1)) != self.size:
            return False, 0, (f"HEAD {key}: HTTP {status} content-length "
                              f"{hdrs.get('content-length')}, stored {self.size}")
        return True, 0, ""

    def delete(self, key: str) -> tuple[bool, int, str]:
        status, _, _, data = self.s3.request("DELETE", self.path(key))
        if status not in (200, 204):
            self._void(key)
            return False, 0, f"DELETE {key}: HTTP {status} {data[:200]!r}"
        self.state[key] = None
        self.etags.pop(key, None)
        return True, 0, ""

    # -- the op generator --------------------------------------------------------

    def next_op(self) -> tuple[str, str]:
        """(kind, key). Traffic of PUTs alone, or of MPUTs alone, cycles the
        ring. A mix is dealt from a shuffled deck that holds it exactly, so
        every seed sends the same mix in another order; reads and deletes take
        a present key, a PUT or MPUT re-creates a deleted key first and
        overwrites otherwise."""
        if self.ring_op:
            key = self.keys[self.ring_next % len(self.keys)]
            self.ring_next += 1
            return self.ring_op, key
        if not self.deck:
            self.deck = list(self.deck_ops)
            self.rnd.shuffle(self.deck)
        kind = self.deck.pop()
        present = [k for k in self.keys if self.state.get(k) is not None]
        absent = [k for k in self.keys if self.state.get(k) is None]
        if kind in ("PUT", "MPUT"):
            return kind, self.rnd.choice(absent or present)
        if not present or (kind == "DELETE" and len(present) <= len(self.keys) // 2):
            # Nothing to read, or half the share already deleted: put one back.
            return self.write_op, self.rnd.choice(absent)
        return kind, self.rnd.choice(present)

    def do(self, kind: str, key: str) -> tuple[bool, int, str]:
        try:
            return {"PUT": self.put, "MPUT": self.mput, "GET": self.get, "STAT": self.stat,
                    "DELETE": self.delete}[kind](key)
        except (OSError, http.client.HTTPException) as e:
            self.s3.close()
            if kind in ("PUT", "MPUT", "DELETE"):
                self._void(key)
            return False, 0, f"{kind} {key}: {type(e).__name__}: {e}"

    # -- commands ----------------------------------------------------------------

    def populate(self) -> dict:
        failed = []
        for key in self.keys:
            if self.state.get(key) is None:
                ok, _, detail = self.put(key)
                if not ok:
                    failed.append(detail)
        return {"populated": len(self.keys), "failed": len(failed), "errors": failed[:5]}

    def run(self, start: float, t0: float, t1: float) -> dict:
        """Closed loop from `start` (this client's place in the ramp) until the
        op in flight at `t1` has answered."""
        ops, errors = [], []
        self.part_ends = []
        while time.monotonic() < start:
            time.sleep(min(0.005, max(0.0, start - time.monotonic())))
        began = time.monotonic()
        cpu0 = time.process_time()
        cpu_w0 = None
        while True:
            now = time.monotonic()
            if cpu_w0 is None and now >= t0:
                cpu_w0 = (now, time.process_time())
            if now >= t1:
                break
            kind, key = self.next_op()
            a = time.monotonic()
            ok, nbytes, detail = self.do(kind, key)
            b = time.monotonic()
            ops.append([kind, key, a, b, nbytes, ok])
            if not ok and len(errors) < 5:
                errors.append(detail)
        cpu_w1 = (time.monotonic(), time.process_time())
        if cpu_w0 is None:
            cpu_w0 = (began, cpu0)
        out = {
            "ops": ops, "errors": errors, "late_s": began - start,
            "cpu_s": cpu_w1[1] - cpu_w0[1], "cpu_wall_s": cpu_w1[0] - cpu_w0[0],
        }
        if self.mp:
            out["part_ends"] = self.part_ends
            out["uploads_aborted"] = self.abort_open_uploads()
        return out

    def verify(self, keys: list[str] | None) -> dict:
        """GET the given keys (all with a record, when None) and hold each to
        the record: the acknowledged bytes, or 404 after a DELETE."""
        mismatch = []
        todo = [k for k in (self.keys if keys is None else keys) if k in self.state]
        for key in todo:
            ok, _, detail = self.do("GET", key)
            if not ok:
                mismatch.append(detail)
        return {"verified": len(todo), "mismatch": mismatch[:5], "mismatches": len(mismatch),
                "live": sum(1 for k in todo if self.state.get(k) is not None)}


def main(argv: list[str]) -> int:
    spec = json.loads(argv[1])
    t = time.monotonic()
    client = Client(spec)

    def say(obj: dict) -> None:
        sys.stdout.write(json.dumps(obj) + "\n")
        sys.stdout.flush()

    say({"ready": client.idx, "keys": len(client.keys), "prep_s": time.monotonic() - t})
    for line in sys.stdin:
        cmd = json.loads(line)
        what = cmd["cmd"]
        if what == "populate":
            say(client.populate())
        elif what == "run":
            say(client.run(cmd["start"], cmd["t0"], cmd["t1"]))
        elif what == "verify":
            say(client.verify(cmd.get("keys")))
        elif what == "live":
            say({"live": sum(1 for v in client.state.values() if v is not None)})
        elif what == "exit":
            break
        else:
            say({"error": f"unknown command {what!r}"})
    client.close()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
