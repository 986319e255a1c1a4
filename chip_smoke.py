#!/usr/bin/env python3
"""Chip smoke: the served S3 path through the device codec, on the chip.

Starts the normal server entry (`python -m minio_tpu server`, 16 local drives,
12+4, 1 MiB blocks, default codec mode and fsync mode -- BASELINE.md config 4's
deployment) as ONE subprocess, waits until the server itself reports the warmed
device codec serving on a TPU, and drives signed SigV4 PUT / GET / degraded GET
/ heal through it. Every object is read back and compared by sha256; the
server's own counters must show that the device did the work. Then the server
is started a second time on the same drives: its set-up must add no entry to
the compile cache, and it must serve what the first one stored.

One process per chip: this process never imports jax -- the chip belongs to
the server it starts (whose probe child opens and releases the chip before
the server opens it).

Exit 0 and a last stdout line
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
only when every phase passed on a TPU. Any failed phase, any exception, a
codec that never took over (a kernel the oracle-compared warm-up refused), a
failed native build, or no TPU: non-zero exit and no result line. Nothing
printed here is a throughput: seconds are set-up times, counts are counters.

    python chip_smoke.py                        the contract run, on the chip
    JAX_PLATFORMS=cpu python chip_smoke.py --allow-cpu --scale tiny
                                                sandbox dry run of the same
                                                phases on jax's CPU backend
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

MIB = 1 << 20
BLOCK = MIB  # the erasure block: full blocks take the fused device program
SMALL_MIN = 4 << 10  # below this a tail stays on the host codec (batching.py)
DRIVES, DATA_ROWS, PARITY = 16, 12, 4
BUCKET = "chip-smoke"
ADMIN = "/mtpu/admin/v1"
ACCESS, SECRET = "chipsmokeadmin", "chipsmoke-secret-key"
# Env that would move the server off the deployment this smoke pins.
_PINNED_ENV = (
    "MINIO_TPU_CODEC", "MTPU_WORKERS",
    "MTPU_MESH_SHAPE", "MTPU_BATCH_WAIT_US", "MTPU_FSYNC", "MTPU_PROBE_CACHE",
    "MTPU_MEMCACHE_MB", "MTPU_FAST_ETAG",
)
DEADLINE_S = 1150  # the contract allows 1200 s, compilation included
SETUP_TIMEOUT_S = 780  # one server start, to the codec takeover, cold cache

# (serial objects, concurrent streams x puts x size, 1 MiB objects, ragged sizes)
SCALES = {
    "full": {
        "serial": (8, 128 * MIB),
        "concurrent": (8, 4, 16 * MIB),
        "one_mib": 16,
        "ragged": (128 * MIB + 300 * 1024, 5 * MIB + 1, 64 * 1024),
        "restart_put": 16 * MIB,
    },
    "tiny": {
        "serial": (3, 17 * MIB),
        "concurrent": (4, 2, 2 * MIB),
        "one_mib": 4,
        "ragged": (2 * MIB + 300 * 1024, 1 * MIB + 1, 64 * 1024),
        "restart_put": 2 * MIB,
    },
}


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


T0 = time.monotonic()


# -- the server subprocess -----------------------------------------------------


class Server:
    """One `python -m minio_tpu server` subprocess in its own process group."""

    def __init__(self, data_dir: str, log_path: str, allow_cpu: bool):
        self.data_dir = data_dir
        self.log_path = log_path
        self.allow_cpu = allow_cpu
        self.proc: subprocess.Popen | None = None
        self.port = 0
        self.started = 0.0

    def start(self) -> str:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        env = {k: v for k, v in os.environ.items() if k not in _PINNED_ENV}
        env["MINIO_ROOT_USER"], env["MINIO_ROOT_PASSWORD"] = ACCESS, SECRET
        env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
        if self.allow_cpu:
            env["MINIO_TPU_CODEC"] = "xla-cpu"
            env["JAX_PLATFORMS"] = "cpu"
        self.started = time.monotonic()
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                [
                    sys.executable, "-m", "minio_tpu", "server",
                    os.path.join(self.data_dir, "d{1...%d}" % DRIVES),
                    "--address", f"127.0.0.1:{self.port}", "--json",
                ],
                stdout=log, stderr=subprocess.STDOUT, env=env, cwd=HERE,
                start_new_session=True,
            )
        return f"http://127.0.0.1:{self.port}"

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def log_tail(self, n: int = 60) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""

    def stop(self) -> None:
        """SIGTERM, then SIGKILL the whole group (probe children included)."""
        p = self.proc
        if p is None:
            return
        if p.poll() is None:
            p.terminate()
            try:
                p.wait(30)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(p.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        p.wait(10)
        self.proc = None


# -- the admin surface ---------------------------------------------------------

_FALLBACK = 'minio_tpu_codec_host_fallback_total{kind="encode"}'
_SMALL = "minio_tpu_codec_small_blocks_encoded_total"
_SAMPLE = re.compile(r"^(\w+(?:\{[^}]*\})?)\s+(\S+)$")


def scrape(target) -> dict[str, float]:
    r = target.request("GET", ADMIN + "/metrics")
    check(r.status_code == 200, f"metrics scrape: HTTP {r.status_code}")
    out = {}
    for line in r.text.splitlines():
        m = _SAMPLE.match(line)
        if m and not line.startswith("#"):
            out[m.group(1)] = float(m.group(2))
    return out


def wait_for_takeover(srv: Server, target, allow_cpu: bool) -> tuple[dict, float]:
    """Block until the server reports the device codec serving; returns its
    takeover report and the seconds since the server process was started.
    Fails as soon as the server says the device will not serve."""
    deadline = time.monotonic() + SETUP_TIMEOUT_S
    online = False
    while True:
        check(srv.alive(), "server exited during set-up:\n" + srv.log_tail())
        check(time.monotonic() < deadline,
              f"device codec did not take over within {SETUP_TIMEOUT_S}s:\n" + srv.log_tail())
        try:
            r = target.request("GET", ADMIN + "/perf")
        except OSError:  # requests' errors are OSErrors: not listening yet
            time.sleep(0.25)
            continue
        if r.status_code != 200:  # listening, node still building
            time.sleep(0.25)
            continue
        if not online:
            online = True
            say(f"server online after {time.monotonic() - srv.started:.1f}s")
        probe = r.json()["probe"]
        inst = probe["install"]
        if inst["state"] == "serving":
            return inst, time.monotonic() - srv.started
        check(inst["state"] != "failed",
              f"device codec install failed: {inst.get('reason')}\n" + srv.log_tail())
        if not allow_cpu and probe["done"]:
            check(probe["ok"],
                  f"no TPU: the server's probe child reports platform "
                  f"{probe['platform']!r}, error {probe['error']!r}\n{probe['detail']}")
        check(inst["state"] in ("pending", "none"),
              f"server serves on the host codec: {inst.get('reason')}")
        time.sleep(0.5)


# -- traffic -------------------------------------------------------------------


class Objects:
    """Bodies from the seed, remembered as (size, sha256) only."""

    def __init__(self, target, seed: int):
        import numpy as np

        self.target = target
        self.rng = np.random.default_rng(seed)
        self.known: dict[str, tuple[int, str]] = {}
        self.full_blocks = 0  # == BLOCK bytes: fused device program
        self.small_blocks = 0  # [SMALL_MIN, BLOCK): small-object device queue
        self.host_tails = 0  # < SMALL_MIN: host codec
        self._lock = threading.Lock()  # put() runs on the concurrent streams

    def body(self, size: int) -> bytes:
        return self.rng.bytes(size)

    def put(self, key: str, body: bytes) -> None:
        res = self.target.put(BUCKET, key, body)
        check(res.ok, f"PUT {key} ({len(body)} B) failed: {res.error_class}")
        digest = hashlib.sha256(body).hexdigest()
        full, tail = divmod(len(body), BLOCK)
        with self._lock:
            self.known[key] = (len(body), digest)
            self.full_blocks += full
            if tail >= SMALL_MIN:
                self.small_blocks += 1
            elif tail:
                self.host_tails += 1

    def verify(self, key: str) -> None:
        size, digest = self.known[key]
        r = self.target.request("GET", f"/{BUCKET}/{key}")
        if r.status_code != 200:  # r.text runs charset detection: errors only
            raise SmokeFailure(f"GET {key}: HTTP {r.status_code} {r.text[:200]}")
        body = r.content
        check(len(body) == size, f"GET {key}: {len(body)} bytes, stored {size}")
        check(hashlib.sha256(body).hexdigest() == digest, f"GET {key}: sha256 differs")


def shard_dirs(data_dir: str, key: str) -> list[tuple[int, str]]:
    """(shard row, object directory) per drive, in drive order: drive i holds
    row hash_order(bucket/key)[i]-1 (object/erasure.py put_object)."""
    from minio_tpu.utils.hashes import hash_order

    dist = hash_order(f"{BUCKET}/{key}", DRIVES)
    return [
        (dist[i] - 1, os.path.join(data_dir, f"d{i + 1}", BUCKET, key))
        for i in range(DRIVES)
    ]


def lose(dirs: list[str]) -> None:
    for d in dirs:
        check(os.path.isdir(d), f"expected shard directory {d}")
        shutil.rmtree(d)


def heal(target, key: str) -> None:
    r = target.request("POST", ADMIN + "/heal",
                       body=json.dumps({"bucket": BUCKET, "prefix": key}).encode())
    if r.status_code != 200:
        raise SmokeFailure(f"heal start: HTTP {r.status_code} {r.text[:200]}")
    seq = r.json()["healSequence"]
    deadline = time.monotonic() + 300
    while True:
        st = target.request("GET", f"{ADMIN}/heal/{seq}").json()
        if not st["running"]:
            break
        check(time.monotonic() < deadline, f"heal sequence {seq} still running after 300s")
        time.sleep(0.2)
    check(st["failed"] == 0 and st["healed"] >= 1, f"heal sequence ended {st}")


# -- the run -------------------------------------------------------------------


def run(args) -> dict:
    try:
        from minio_tpu import jaxenv
        from minio_tpu.loadgen.target import S3Target
        from minio_tpu.ops import native
    except ImportError as e:
        raise SmokeFailure(f"not a checkout of the repo (cannot import minio_tpu): {e}") from e
    check("jax" not in sys.modules, "the smoke's own process imported jax")

    scale = SCALES[args.scale]
    why = native.build()  # -march=native: always for the machine it runs on
    check(why is None, f"native host kernels failed to build: {why}")
    check(native.available(), "native host kernels built but did not load")
    say("native host kernels built on this machine")

    cache_dir = jaxenv.compile_cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    cache0 = set(os.listdir(cache_dir))
    log_dir = os.path.join(HERE, "chiprun_out", "chip_smoke")
    os.makedirs(log_dir, exist_ok=True)
    data_dir = tempfile.mkdtemp(prefix="chip-smoke-", dir=HERE)
    srv = Server(data_dir, os.path.join(log_dir, "server-cold.log"), args.allow_cpu)
    summary: dict = {"seed": args.seed, "scale": args.scale, "compile_cache": cache_dir,
                     "compile_cache_entries_before": len(cache0)}
    try:
        # -- first start: cold unless the machine kept a cache ------------------
        target = S3Target([srv.start()], ACCESS, SECRET, timeout_s=600)
        inst, cold_s = wait_for_takeover(srv, target, args.allow_cpu)
        want = "cpu" if args.allow_cpu else "tpu"
        check(inst["platform"] == want,
              f"device codec serves on {inst['platform']!r}, wanted {want!r}")
        m = scrape(target)
        if not args.allow_cpu:
            check(m.get('minio_tpu_device_probe_ok{platform="tpu"}') == 1,
                  "no minio_tpu_device_probe_ok{platform=\"tpu\"} 1 in the server's metrics")
        check(m.get(f'minio_tpu_device_codec_serving{{platform="{want}"}}') == 1,
              "minio_tpu_device_codec_serving is not 1")
        check("minio_tpu_codec_blocks_encoded_total" in m, "no minio_tpu_codec_* series")
        check(m.get("minio_tpu_native_codec_available") == 1,
              "the server runs without its native host kernels")
        cache1 = set(os.listdir(cache_dir))
        summary.update(
            platform=inst["platform"], device_kind=inst["device_kind"],
            device_count=inst["device_count"], jax=inst["jax"], jaxlib=inst["jaxlib"],
            libtpu=inst["libtpu"], geometry=inst["geometry"], mesh=inst["mesh"],
            rs_kernel=inst["kernels"]["rs"]["serving"],
            rs_selection=inst["kernels"]["rs"]["detail"],
            hash_kernel=inst["kernels"]["hash"]["serving"],
            hash_selection=inst["kernels"]["hash"]["detail"],
            cold_setup_seconds=round(cold_s, 1),
            cold_codec_setup_seconds=inst["setup_seconds"],
            cold_warm_programs=inst["warm"]["programs"],
            cold_warm_seconds=inst["warm"]["seconds"],
            cold_cache_entries_added=len(cache1 - cache0),
        )
        say(f"device codec serving on {inst['platform']} ({inst['device_kind']} x "
            f"{inst['device_count']}) after {cold_s:.1f}s; rs={summary['rs_kernel']} "
            f"hash={summary['hash_kernel']}")

        target.ensure_bucket(BUCKET)
        base = scrape(target)
        objs = Objects(target, args.seed)

        n, size = scale["serial"]
        serial = [f"serial-{i}" for i in range(n)]
        for key in serial:
            objs.put(key, objs.body(size))
        say(f"PUT {n} x {size // MIB} MiB serial")

        streams, puts, size = scale["concurrent"]
        bodies = [[objs.body(size) for _ in range(puts)] for _ in range(streams)]

        def stream(si: int) -> None:
            for pi, body in enumerate(bodies[si]):
                objs.put(f"conc-{si}-{pi}", body)

        with ThreadPoolExecutor(streams) as pool:
            for f in [pool.submit(stream, si) for si in range(streams)]:
                f.result()
        del bodies
        say(f"PUT {streams} streams x {puts} x {size // MIB} MiB concurrent")

        for i in range(scale["one_mib"]):
            objs.put(f"one-{i}", objs.body(MIB))
        say(f"PUT {scale['one_mib']} x 1 MiB")

        # Ragged sizes: tails >= 4 KiB take the small-object device queue, and
        # the host codec encodes the sub-4 KiB tails and nothing more. Counted
        # over this phase alone, because the data scanner also writes through
        # the codec: two tiny documents as each 60 s cycle ends. A window that
        # caught those is taken once more; two in a row cannot both.
        for attempt in ("ragged", "ragged-again"):
            before, tails0, small0 = scrape(target), objs.host_tails, objs.small_blocks
            for i, size in enumerate(scale["ragged"]):
                objs.put(f"{attempt}-{i}", objs.body(size))
            after = scrape(target)
            fallback = int(after[_FALLBACK] - before[_FALLBACK])
            small = int(after[_SMALL] - before[_SMALL])
            tails, smalls = objs.host_tails - tails0, objs.small_blocks - small0
            if fallback == tails and small == smalls:
                break
            say(f"{attempt}: host codec encoded {fallback} blocks for {tails} sub-4 KiB "
                f"tails, small queue {small} for {smalls}")
        check(fallback == tails,
              f"host codec encoded {fallback} blocks, {tails} sub-4 KiB tails were sent")
        check(small == smalls and small > 0,
              f"small-object device queue encoded {small} blocks, {smalls} were sent")
        summary["ragged"] = {"sizes": scale["ragged"], "host_tails_sent": tails,
                             "host_fallback_encode": fallback,
                             "small_blocks_sent": smalls, "small_blocks_encoded": small}
        say(f"PUT ragged {scale['ragged']}: host codec encoded the {tails} sub-4 KiB "
            f"tail(s) only, small device queue the {smalls} others")

        for key in objs.known:
            objs.verify(key)
        say(f"GET {len(objs.known)} objects: all byte-identical")

        # -- degraded read: 4 data rows of two large objects gone --------------
        degraded_blocks = 0
        for key in serial[:2]:
            rows = shard_dirs(data_dir, key)
            lose([d for row, d in rows if row < DATA_ROWS][:PARITY])
            objs.verify(key)
            degraded_blocks += objs.known[key][0] // BLOCK
        say("degraded GET of 2 objects with 4 data shards lost each: byte-identical")

        # -- heal: lose 3, heal, then read with 4 OTHER drives gone ------------
        key = serial[2]
        rows = shard_dirs(data_dir, key)
        data_dirs = [d for row, d in rows if row < DATA_ROWS]
        lose(data_dirs[:3])
        heal(target, key)
        check(all(os.path.isdir(d) for d in data_dirs[:3]), "heal left a lost shard missing")
        lose(data_dirs[3:3 + PARITY])
        objs.verify(key)
        say("heal of 3 lost shards, then GET with 4 other shards lost: byte-identical")

        # -- the device did the work -------------------------------------------
        end = scrape(target)

        def delta(name: str) -> int:
            return int(end.get(name, 0) - base.get(name, 0))

        encoded = delta("minio_tpu_codec_blocks_encoded_total")
        recon = delta("minio_tpu_codec_blocks_reconstructed_total")
        check(encoded >= objs.full_blocks,
              f"device encoded {encoded} full blocks, {objs.full_blocks} were sent")
        check(recon >= degraded_blocks,
              f"device reconstructed {recon} blocks, degraded reads needed {degraded_blocks}")
        summary["counters"] = {
            "full_blocks_sent": objs.full_blocks, "blocks_encoded": encoded,
            "encode_batches": delta("minio_tpu_codec_encode_batches_total"),
            "batch_occupancy": end.get("minio_tpu_codec_batch_occupancy"),
            "small_blocks_encoded": delta(_SMALL),
            "degraded_blocks_needed": degraded_blocks, "blocks_reconstructed": recon,
            "recon_batches": delta("minio_tpu_codec_recon_batches_total"),
            "digests_verified": delta("minio_tpu_codec_digests_verified_total"),
            "host_fallback_encode": delta(_FALLBACK),
            "host_fallback_reconstruct":
                delta('minio_tpu_codec_host_fallback_total{kind="reconstruct"}'),
            "scanner_cycles": delta("minio_tpu_scanner_cycles_completed_total"),
            "double_buffered_batches":
                delta("minio_tpu_codec_double_buffered_batches_total"),
        }
        mesh_devices = int(end.get("minio_tpu_codec_mesh_devices", 1))
        chips = {k: int(v) for k, v in end.items()
                 if k.startswith("minio_tpu_codec_chip_blocks_total")}
        summary["counters"].update(mesh_devices=mesh_devices, chip_blocks=chips)
        if inst["device_count"] > 1:
            check(mesh_devices == inst["device_count"],
                  f"mesh spans {mesh_devices} of {inst['device_count']} devices")
            check(chips and all(v > 0 for v in chips.values()),
                  f"a chip of the mesh encoded nothing: {chips}")
        errs = {k: v for k, v in end.items()
                if k.startswith("minio_tpu_http_requests_total") and 'status="5' in k}
        check(not errs, f"the server answered 5xx: {errs}")
        say("counters: " + json.dumps(summary["counters"]))

        # -- second start: same drives, warm compile cache ---------------------
        srv.stop()
        cache2 = set(os.listdir(cache_dir))
        srv.log_path = os.path.join(log_dir, "server-warm.log")
        target2 = S3Target([srv.start()], ACCESS, SECRET, timeout_s=600)
        inst2, warm_s = wait_for_takeover(srv, target2, args.allow_cpu)
        cache3 = set(os.listdir(cache_dir))
        summary.update(
            warm_setup_seconds=round(warm_s, 1),
            warm_codec_setup_seconds=inst2["setup_seconds"],
            warm_warm_seconds=inst2["warm"]["seconds"],
            warm_cache_entries_added=len(cache3 - cache2),
            compile_cache_entries_after=len(cache3),
        )
        check(cache3 <= cache2,
              f"the second start added {len(cache3 - cache2)} compile-cache entries: "
              f"{sorted(cache3 - cache2)[:5]}")
        check(inst2["compile_cache"]["entries_added"] == 0,
              "the second start's codec set-up wrote compile-cache entries")
        objs.target = target2
        objs.verify(serial[-1])
        objs.verify("ragged-0")
        objs.put("after-restart", objs.body(scale["restart_put"]))
        objs.verify("after-restart")
        say(f"second start took over after {warm_s:.1f}s with no new compile-cache "
            "entry; reads the first start's objects and serves new ones")
        return summary
    finally:
        srv.stop()
        shutil.rmtree(data_dir, ignore_errors=True)


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument("--seed", type=int, default=0, help="object bodies are made from it")
    ap.add_argument("--scale", choices=sorted(SCALES), default="full",
                    help="full: the contract's sizes; tiny: the same phases at toy size")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="sandbox dry run: serve the device pipeline on jax's CPU "
                         "backend (MINIO_TPU_CODEC=xla-cpu). Not a chip result.")
    args = ap.parse_args(argv)

    def on_deadline(signum, frame):
        raise SmokeFailure(f"not finished after {DEADLINE_S}s")

    signal.signal(signal.SIGALRM, on_deadline)
    signal.alarm(DEADLINE_S)
    try:
        summary = run(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    except Exception:  # noqa: BLE001 - boundary: any exception fails the smoke
        traceback.print_exc()
        print("chip_smoke: FAILED on an exception", file=sys.stderr, flush=True)
        return 1
    finally:
        signal.alarm(0)
    summary["total_seconds"] = round(time.monotonic() - T0, 1)
    print("chip_smoke summary (set-up seconds and counters; no throughput):")
    for k, v in summary.items():
        print(f"  {k}: {json.dumps(v) if isinstance(v, (dict, list)) else v}")
    print(json.dumps({
        "ok": True,
        "device": {"platform": summary["platform"], "kind": summary["device_kind"],
                   "count": summary["device_count"]},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
