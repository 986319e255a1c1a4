"""One run of one cell: set-up, ramp, window, check, result line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process holds the chip and serves the store; the clients are processes of
their own (client_worker.py). The last line of standard output is the result;
the lines before it say what a refused run needs to be read: the server's
install report, the ramp's lateness, op counts by type, bytes on the drives.
"""

from __future__ import annotations

import time

T_IMPORT = time.monotonic()  # as near to process start as Python gets; see process_age

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

from . import check, readers, trace, window  # noqa: E402
from .client_worker import S3Conn, client_keys  # noqa: E402 - imports no jax
from .server import (  # noqa: E402
    ACCESS, BUCKET, REGION, SECRET, SHM, Deployment, SetupError,
)
from .traffic import ROOT, Cell  # noqa: E402

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "client_worker.py")
REAPER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "reaper.py")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")  # git-ignored; emptied by every traced run
CONTROLS = ("parity-1",)


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_IMPORT:7.2f}s] {msg}", flush=True)


def process_age() -> float:
    """Seconds this process had lived when T_IMPORT was taken (interpreter
    start-up and the imports above it), from /proc; 0 where /proc is silent."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age_now = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return max(0.0, age_now - (time.monotonic() - T_IMPORT))
    except (OSError, ValueError, IndexError):
        return 0.0


class Clients:
    """The client processes of a run and the line protocol with them."""

    def __init__(self, cell: Cell, seed: int, endpoint: str):
        self.procs = []
        for i in range(cell.clients):
            spec = cell.client_spec(i, seed, endpoint, BUCKET, ACCESS, SECRET, REGION)
            self.procs.append(subprocess.Popen(
                [sys.executable, WORKER, json.dumps(spec)],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, bufsize=1,
            ))

    def _read(self, i: int) -> dict:
        line = self.procs[i].stdout.readline()
        if not line:
            raise SetupError(f"client {i} exited (code {self.procs[i].poll()})")
        return json.loads(line)

    def send(self, i: int, cmd: dict) -> None:
        self.procs[i].stdin.write(json.dumps(cmd) + "\n")
        self.procs[i].stdin.flush()

    def gather(self, which=None) -> list[dict]:
        return [self._read(i) for i in (range(len(self.procs)) if which is None else which)]

    def ask_all(self, cmd: dict) -> list[dict]:
        for i in range(len(self.procs)):
            self.send(i, cmd)
        return self.gather()

    def close(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.stdin.write('{"cmd": "exit"}\n')
                    p.stdin.flush()
                    p.stdin.close()
                except (OSError, ValueError):
                    pass
        for p in self.procs:
            try:
                p.wait(10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(10)
            if p.stdout:
                p.stdout.close()


class Reaper:
    """The sweep of superseded data directories (reaper.py), from the ramp's
    first op until the drain's last."""

    def __init__(self, dep: Deployment, keep: int, every_s: float):
        self.proc = subprocess.Popen(
            [sys.executable, REAPER, BUCKET, str(keep), str(every_s), *dep.drive_dirs],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)

    def close(self) -> dict | None:
        """Stop the sweep and wait for it: what it removed, or None."""
        try:
            self.proc.stdin.close()  # the sweep ends when its stdin does
        except OSError:
            pass
        try:
            self.proc.wait(30)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait(10)
        out = self.proc.stdout.read()
        self.proc.stdout.close()
        try:
            return json.loads(out.strip().splitlines()[-1])
        except (ValueError, IndexError):
            return None


def sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.25))


def owner_of(key: str) -> int:
    return int(key[1:4])  # client_worker.py names keys c<client>/...


def run_window(cell: Cell, dep: Deployment, clients: Clients, seed: int, seconds: float,
               traced: bool, t_process: float, describe_to: str | None = None) -> dict:
    """Prepare, ramp, window, drain, check: everything of a run that follows
    the server's start. `clients` were started beside it (their bodies are made
    while the codec warms up) and are stopped here. Returns the numbers; prints
    the log lines."""
    t = cell.traffic
    degraded: set[str] = set()  # keys the prepare step left short of data shards
    reaper = None
    try:
        ready = clients.gather()
        say(f"{len(ready)} clients ready; slowest body preparation "
            f"{max(r['prep_s'] for r in ready):.2f}s")
        for step in t.get("prepare", []):
            if "populate" in step:
                done = clients.ask_all({"cmd": "populate"})
                bad = sum(d["failed"] for d in done)
                if bad:
                    raise SetupError(f"populate: {bad} PUTs failed: "
                                     f"{[e for d in done for e in d['errors']][:3]}")
                say(f"populated {sum(d['populated'] for d in done)} objects")
            elif "lose_shards" in step:
                for key in pool_keys(cell):
                    dep.lose_shards(key, int(step["lose_shards"]["data"]))
                    degraded.add(key)
                say(f"removed {step['lose_shards']['data']} data shards of "
                    f"{len(degraded)} objects")

        before = dep.snapshot()
        if "reap_superseded" in t:
            reaper = Reaper(dep, int(t["reap_superseded"]["keep"]),
                            float(t["reap_superseded"]["every_s"]))
        go = time.monotonic() + 0.25
        t0 = go + float(t["ramp_s"])
        t1 = t0 + seconds
        for i in range(cell.clients):
            clients.send(i, {"cmd": "run", "t0": t0, "t1": t1,
                             "start": go + i / cell.clients * float(t["typical_op_s"])})
        setup_s = go - t_process
        say(f"set-up done after {setup_s:.2f}s; ramp {t['ramp_s']}s, window {seconds}s")

        sleep_until(t0)
        snap_a, cpu_a = dep.snapshot(), time.process_time()
        traced_pair, trace_wall = None, 0.0
        if traced:
            tr_s = min(float(t.get("trace_seconds", 3.0)), seconds / 2)
            sleep_until(t0 + (seconds - tr_s) / 2)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            trace.start(TRACE_DIR)
            ta = dep.snapshot()
            sleep_until(ta["t"] + tr_s)
            tb = dep.snapshot()
            trace.stop()
            traced_pair, trace_wall = (ta, tb), tb["t"] - ta["t"]
            say(f"traced {trace_wall:.2f}s; stop_trace took {time.monotonic() - tb['t']:.2f}s")
        sleep_until(t1)
        snap_b, cpu_b = dep.snapshot(), time.process_time()

        results = clients.gather()
        if reaper is not None:
            swept, reaper = reaper.close(), None
            say(f"superseded data directories removed from ramp to drain: {json.dumps(swept)}")
        after = dep.snapshot()
        device = dep.device()  # the peak is read before the check drives the device again

        ops = [op for r in results for op in r["ops"]]
        a, b = snap_a["t"], snap_b["t"]
        late = [r["late_s"] for r in results]
        say(f"ramp lateness per client (s): max {max(late):.4f} "
            + " ".join(f"{x:.3f}" for x in late))
        say("ops by type (touching the window / ended inside / failed ramp to drain): "
            + json.dumps(window.counts_by_kind(ops, a, b)))
        if cell.part_bytes:
            say(f"UploadParts answered 200 from ramp to drain: "
                f"{sum(len(r['part_ends']) for r in results)}; uploads no Complete closed, "
                f"aborted at drain: {sum(r['uploads_aborted'] for r in results)}")
        for r in results:
            for e in r["errors"]:
                say(f"client error: {e}")
        say(f"server process used {(cpu_b - cpu_a) / (b - a):.2f} cores over the window "
            f"({len(os.sched_getaffinity(0))} cpus to run on)")

        numbers, stored, live = run_check(cell, dep, clients, ops, (a, b), seed, before, after,
                                          degraded)
    finally:
        if reaper is not None:
            reaper.close()
        clients.close()

    # -- numbers ---------------------------------------------------------------------
    e2e = window.end_to_end(ops, a, b)
    e2e["setup_s"] = setup_s
    user_live = live * int(t["object_bytes"])
    facts = {
        **op_facts(ops, a, b, [end for r in results for end in r.get("part_ends", ())]),
        "client_cpu": (sum(r["cpu_s"] for r in results)
                       / (sum(r["cpu_wall_s"] for r in results) / len(results))),
        "stored_per_user_byte": stored / user_live if user_live else None,
        "warmup_s": (dep.install.get("warm") or {}).get("seconds"),
    }
    src = {"window": (snap_a, snap_b), "traced": traced_pair, "trace": {}, "facts": facts,
           "geometry": cell.geometry, "block_bytes": cell.config["block_bytes"],
           "device_kind": device["kind"], "lost_data": cell.lost_data}
    if traced:
        xplane = trace.find_xplane(TRACE_DIR)
        if describe_to:
            with open(describe_to, "w") as f:
                f.write("\n".join(trace.describe(xplane)) + "\n")
        src["trace"] = reduced = trace.reduce(
            trace.load(xplane), cell.geometry[2], wall_s=trace_wall,
            idle_chips=device["count"] if device["platform"] != "cpu" else 0)
        if reduced:
            reduced["span_s"] = trace_wall  # the host's clock frames the traced window
            device["busy_s"], device["window_s"] = reduced["busy_s"], trace_wall
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return {
        "ops": ops, "window": (a, b),
        "numbers": numbers, "e2e": e2e, "src": src, "device": device,
        "attempted": len(ops), "failed": int(numbers["ops_failed"]),
        "compiles_in_window": (snap_b["compiles"] - snap_a["compiles"]
                               + snap_b["cache_entries"] - snap_a["cache_entries"]),
    }


def op_facts(ops: list, a: float, b: float, part_ends: list[float]) -> dict:
    """What the window [a, b) holds of the clients' ops, for the per-layer
    readers to divide by. A completed MPUT is one op, one of `puts_ended`, its
    bytes in `put_MiB`: a PUT-path metric reads per object and per MiB under
    either kind of write. `part_ends`: when each UploadPart answered 200."""
    inside = window.ended_inside(ops, a, b)
    puts = [op for op in ops if op[window.KIND] in check.WRITES]
    gets = [op for op in ops if op[window.KIND] == "GET"]
    return {
        "ops_ended": len(inside),
        "puts_ended": len([op for op in inside if op[window.KIND] in check.WRITES]),
        "mputs_ended": len([op for op in inside if op[window.KIND] == "MPUT"]),
        "parts_ended": sum(a <= end < b for end in part_ends),
        "put_MiB": window.prorated_rate(puts, a, b, by_bytes=True) * (b - a) / window.MIB,
        "gets_ended": len([op for op in inside if op[window.KIND] == "GET"]),
        "get_MiB": window.prorated_rate(gets, a, b, by_bytes=True) * (b - a) / window.MIB,
    }


def pool_keys(cell: Cell) -> list[str]:
    """Every key of the cell's ring or pool, client by client."""
    return [key for i in range(cell.clients)
            for key in client_keys(cell.traffic["keys"], i, cell.clients)]


def run_check(cell: Cell, dep: Deployment, clients: Clients, ops: list,
              win: tuple[float, float], seed: int, before: dict, after: dict,
              degraded: set[str]):
    """The comparison that decides `correct` (harness/check.py), once the window
    has closed: (numbers compared, bytes on the drives, live objects).
    `degraded` are the keys the prepare step left short of data shards."""
    t, (a, b) = cell.traffic, win
    t_check = time.monotonic()
    numbers: dict[str, float] = {"ops_failed": sum(1 for op in ops if not op[window.OK])}
    chk = t.get("check", {})
    stored = dep.stored_bytes()
    live = sum(v["live"] for v in clients.ask_all({"cmd": "live"}))
    back = verify_keys(clients, check.readback_sample(
        ops, seed, int(chk.get("readback_sample", 12))))
    numbers["readback_mismatch"] = sum(v["mismatches"] for v in back)
    read_back = sum(v["verified"] for v in back)
    n_degraded = int(chk.get("degraded_sample", 4))
    sample, drawn_from = check.degraded_sample(ops, a, b, seed, n_degraded), "PUT in the window"
    if check.wrote_nothing(ops) and cell.populates:
        sample, drawn_from = check.pool_sample(pool_keys(cell), seed, n_degraded), "populated pool"
    lost_all = int(cell.config["guarantees"]["drives_lost_tolerated"])
    for key in sample:
        dep.lose_shards(key, lost_all)
    deg = verify_keys(clients, sample)
    numbers["degraded_mismatch"] = sum(v["mismatches"] for v in deg)
    numbers["degraded_short"] = max(1, len(sample)) - sum(v["verified"] for v in deg)
    block = cell.config["block_bytes"]
    codec = {name: after["codec"].get(name, 0) - before["codec"].get(name, 0)
             for name in ("blocks_encoded", "blocks_reconstructed", "host_fallback_recon_blocks")}
    sent_blocks = check.full_blocks_put(ops, block, cell.part_bytes)
    got_blocks = check.degraded_blocks_got(ops, degraded, block)
    numbers["device_blocks_missing"] = (
        max(0, sent_blocks - codec["blocks_encoded"])
        + max(0, got_blocks - codec["blocks_reconstructed"]))
    for v in back + deg:
        for e in v["mismatch"]:
            say(f"check: {e}")
    say(f"check took {time.monotonic() - t_check:.2f}s: read back {read_back} keys, "
        f"{len(sample)} more ({drawn_from}) with {lost_all} data shards removed; device "
        f"encoded {codec['blocks_encoded']} blocks, clients' acknowledged PUTs held "
        f"{sent_blocks}")
    if cell.part_bytes:
        checked = dep.snapshot()
        recon = {name: checked["codec"].get(name, 0) - after["codec"].get(name, 0)
                 for name in ("blocks_reconstructed", "host_fallback_recon_blocks")}
        say(f"the check's degraded read-back, across part boundaries: device reconstructed "
            f"{recon['blocks_reconstructed']} blocks, host_fallback_recon_blocks moved by "
            f"{recon['host_fallback_recon_blocks']}")
    if degraded:
        say(f"device reconstructed {codec['blocks_reconstructed']} blocks ramp to drain "
            f"(blocks_reconstructed), acknowledged GETs of the {len(degraded)} keys the prepare "
            f"step degraded held {got_blocks}; host_fallback_recon_blocks moved by "
            f"{codec['host_fallback_recon_blocks']}")
    say(f"drives hold {stored} bytes under {dep.root} for {live} live objects; "
        f"{shutil.disk_usage(SHM).free} bytes free on {SHM}")
    return numbers, stored, live


def verify_keys(clients: Clients, keys: list[str]) -> list[dict]:
    """Have each key's owner read it back and hold it to its record."""
    by_owner: dict[int, list[str]] = {}
    for key in keys:
        by_owner.setdefault(owner_of(key), []).append(key)
    for i, mine in by_owner.items():
        clients.send(i, {"cmd": "verify", "keys": mine})
    return clients.gather(list(by_owner))


def result_line(cell: Cell, out: dict, traced: bool, rehearse: bool, control: str | None) -> dict:
    correct, compared = check.decide(out["numbers"])
    metrics: dict[str, dict] = {}
    if rehearse:
        pass  # a CPU run prints no number under the name of a device metric
    elif not traced:
        for m in cell.end_to_end:
            if m["name"] in out["e2e"]:
                metrics[m["name"]] = {"value": out["e2e"][m["name"]], "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            value = readers.read(m, out["src"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": correct, "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": out["device"]}
    reduced = out["src"]["trace"]
    if traced and reduced and not rehearse:
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    if rehearse:
        line["rehearsal"] = True
    if control:
        line["control"] = control
    line["compared"] = compared
    return line


def make_bucket(dep: Deployment) -> None:
    status, _, _, body = S3Conn(dep.endpoint, ACCESS, SECRET, REGION, 30).request(
        "PUT", f"/{BUCKET}")
    if status not in (200, 409):
        raise SetupError(f"cannot create the bucket: HTTP {status} {body[:200]!r}")


def execute(args, deployment_hook=None) -> tuple[int, dict | None]:
    """A whole run. `deployment_hook(dep)` is for benchmark/tests: it breaks the
    timed path underneath after the server started."""
    t_process = T_IMPORT - process_age()
    cell = Cell(args.workload, rehearse=args.rehearse)
    parity = cell.config["parity"] - 1 if args.control == "parity-1" else None
    dep = Deployment(cell.config, rehearse=args.rehearse, parity=parity)
    clients = Clients(cell, args.seed, dep.endpoint)
    line = None
    try:
        dep.start(cell.footprint_bytes(), cell.traffic.get("mallopt"))
        import jax

        if not args.rehearse and jax.device_count() != cell.chips:
            raise SetupError(f"the cell asks for {cell.chips} chip(s), jax has {jax.device_count()}")
        inst = dep.install
        say("install: " + json.dumps({k: inst.get(k) for k in (
            "state", "platform", "device_kind", "device_count", "geometry", "mesh", "warm",
            "compile_cache", "setup_seconds")}))
        say("kernels: " + json.dumps(inst.get("kernels")))
        make_bucket(dep)
        if deployment_hook is not None:
            deployment_hook(dep)
        out = run_window(cell, dep, clients, args.seed, args.seconds, bool(args.trace),
                         t_process, args.describe_trace)
        line = result_line(cell, out, bool(args.trace), args.rehearse, args.control)
        if args.dump_ops:
            with open(args.dump_ops, "w") as f:
                json.dump({"window": out["window"], "ops": out["ops"]}, f)
        if out["compiles_in_window"]:
            say(f"WARNING: {out['compiles_in_window']} compilations inside the window")
    except SetupError as e:
        print(f"benchmark: cannot run: {e}", file=sys.stderr, flush=True)
        return 2, None
    finally:
        clients.close()
        dep.close()
    return 0, line


def parse(argv: list[str]):
    ap = argparse.ArgumentParser(prog="benchmark/run.py", description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox rehearsal on jax's CPU backend (MINIO_TPU_CODEC=xla-cpu): "
                         "checks correctness and control flow, prints no metric")
    ap.add_argument("--control", choices=CONTROLS, default=None,
                    help="run the control of the output check (not a measurement): the "
                         "deployment with one parity shard fewer than its file states")
    ap.add_argument("--describe-trace", metavar="FILE", default=None,
                    help="with --trace 1: also write the trace's planes, lines and first "
                         "events to FILE, to look at by hand")
    ap.add_argument("--dump-ops", metavar="FILE", default=None,
                    help="also write every op (kind, key, start, end, bytes, ok) and the "
                         "window's edges to FILE, to study a window length offline")
    return ap.parse_args(argv)


def main(argv: list[str]) -> int:
    args = parse(argv)
    rc, line = execute(args)
    if line is None:
        return rc or 1
    for name, c in line["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
