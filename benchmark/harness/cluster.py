"""The system under test as a cluster: N nodes, a process each.

MinIO's distributed deployment, ``minio server http://host{1...4}/mnt/disk{1...4}``,
on one host: every node is given the same ordered endpoint list and its own
URL, opens its own drives directly and the others' through storage REST, and
holds the namespace lock with the other nodes' lockers. Here a node is
``harness/node_proc.py`` in a process of its own, on a loopback port of its
own. The first ``chips`` nodes of the configuration hold a chip each and serve
``MINIO_TPU_CODEC=device`` (its install raises unless jax opened the TPU; on a
host of several chips libtpu's variables, ``chip_env``, give process n chip n
alone); the others hold none and serve storage, locks and healthy reads with
the host codec, which is all such a node does while no client sends to it. The
run's own process is the coordinator: it never imports jax, so it holds no chip.

``ClusterDeployment`` has ``Deployment``'s interface, so the run, the check and
the readers are the ones of a one-node cell:

* ``drive_dirs`` are the set's drives in endpoint order, node by node and drive
  by drive (``n<n>d<j>``): set position i is on node i // (drives a node).
  ``hash_order`` and every shard helper work on them as on one node's.
* ``snapshot()`` sums the nodes' ledgers, codec counters and front counters
  row by row (``merge_snapshots``).
* ``device()`` reports the chips together: their count, the fullest one's peak,
  and each chip node's under ``nodes``.
* a traced run traces every chip node's chip over the same sub-window; each
  reduces its own trace, ``merge_traces`` takes the mean busy time (idle is the
  mean of the chips) and sums the codec programs' device time.
* client i sends to chip node i mod ``chips`` (``warp --host`` with those
  hosts); the check reads a key back through a node other than its writer's
  (``readback_endpoint``) and rebuilds a degraded key on a chip node
  (``rebuild_endpoint``).
"""

from __future__ import annotations

import json
import os
import queue
import shutil
import socket
import subprocess
import sys
import threading
import time

from .server import PINNED_ENV, PREFIX, SHM, Deployment, SetupError

NODE_PROC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "node_proc.py")
# A node that holds no chip: the host codec, and jax (which the harness's node
# imports) kept off the accelerator another node holds.
HOST_NODE_ENV = {"MINIO_TPU_CODEC": "host", "JAX_PLATFORMS": "cpu"}
LISTEN_TIMEOUT_S = 300.0  # interpreter, imports, self-test, the native kernels' first build
INSTALL_TIMEOUT_S = 1000.0  # format consensus and the warm-up; a checkout's first compiles
ASK_TIMEOUT_S = 120.0


def chip_env(chip: int, port: int) -> dict[str, str]:
    """libtpu's variables for a process that holds chip `chip` of the host
    alone: a one-chip slice of its own, with a port of its own. libtpu's lock
    file (/tmp/libtpu_lockfile) admits one process a host, not one a chip, so
    the four are let past it (as jax's own multi-process tests do); that no
    two of them share a chip is held by ``TPU_VISIBLE_CHIPS`` and checked at
    set-up (``_hold_to_the_layout``: no accelerator device file held twice)."""
    return {
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_PORT": str(port),
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _is_number(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def merge_snapshots(snaps: list[dict]) -> dict:
    """The nodes' snapshots as one node's: ledger rows, codec counters, front
    counters and compile events summed row by row; compile-cache entries are
    one directory's, which every node reads (the largest count)."""
    ledger: dict[str, dict[str, float]] = {}
    codec: dict[str, float] = {}
    front: dict[str, float] = {}
    for s in snaps:
        for row, v in s["ledger"].items():
            acc = ledger.setdefault(row, {"count": 0, "wall_s": 0.0, "cpu_s": 0.0})
            for field in acc:
                acc[field] += v[field]
        for group, acc in (("codec", codec), ("front", front)):
            for name, v in s[group].items():
                if _is_number(v):
                    acc[name] = acc.get(name, 0) + v
    return {
        "t": max(s["t"] for s in snaps),
        "ledger": ledger,
        "codec": codec,
        "front": front,
        "cache_entries": max(s["cache_entries"] for s in snaps),
        "compiles": sum(s["compiles"] for s in snaps),
    }


def merge_traces(reduced: list[dict], wall_s: float) -> dict:
    """Each chip node's ``trace.reduce`` of its own chip as one reduction: busy
    time the mean over the chips, the codec programs' device time summed (no
    block is shared between chips, so the blocks' least time over it stays a
    sound roofline share), the top device ops summed by name, the longest idle
    gaps of any chip (named with their node). A node whose trace held nothing
    reads {}; where every node's does, so does the merge."""
    if not any(reduced):
        return {}
    n = len(reduced)
    by_name: dict[str, float] = {}
    gaps = []
    for i, r in enumerate(reduced):
        for name, s in r.get("device_ops", []):
            by_name[name] = by_name.get(name, 0.0) + s
        gaps += [[f"n{i + 1} {name}", s] for name, s in r.get("idle_gaps", [])]
    gaps.sort(key=lambda g: -g[1])
    return {
        "devices": sum(r.get("devices", 0) for r in reduced),
        "span_s": wall_s,
        "busy_s": sum(r.get("busy_s", 0.0) for r in reduced) / n,
        "codec_s": sum(r.get("codec_s", 0.0) for r in reduced),
        "codec_runs": sum(r.get("codec_runs", 0) for r in reduced),
        "device_ops": [[name, s] for name, s in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": gaps[:10],
    }


class NodeProc:
    """One node process and the line protocol with it. Its answers are read
    by a thread into a queue, its standard error is passed on line by line
    under its name."""

    def __init__(self, index: int, spec: dict, env: dict[str, str]):
        self.index = index
        self.name = f"n{index + 1}"
        self.proc = subprocess.Popen(
            [sys.executable, NODE_PROC, json.dumps(spec)], env=env, text=True, bufsize=1,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        self.answers: queue.Queue = queue.Queue()
        self.threads = [threading.Thread(target=self._read, daemon=True, name=f"{self.name}-out"),
                        threading.Thread(target=self._pass_on, daemon=True,
                                         name=f"{self.name}-err")]
        for t in self.threads:
            t.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            self.answers.put(line)
        self.answers.put(None)

    def _pass_on(self) -> None:
        for line in self.proc.stderr:
            sys.stderr.write(f"[{self.name}] {line}")

    def send(self, cmd: dict) -> None:
        try:
            self.proc.stdin.write(json.dumps(cmd) + "\n")
            self.proc.stdin.flush()
        except (OSError, ValueError) as e:
            raise SetupError(f"node {self.name} is gone (code {self.proc.poll()}): {e}") from e

    def answer(self, timeout_s: float) -> dict:
        try:
            line = self.answers.get(timeout=timeout_s)
        except queue.Empty:
            raise SetupError(f"node {self.name} did not answer in {timeout_s:.0f}s") from None
        if line is None:
            raise SetupError(f"node {self.name} exited (code {self.proc.wait()})")
        out = json.loads(line)
        if "error" in out:
            raise SetupError(f"node {self.name}: {out['error']}")
        return out

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.send({"cmd": "close"})
                self.proc.stdin.close()
            except (SetupError, OSError, ValueError):
                pass
        try:
            self.proc.wait(30)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait(10)
        for t in self.threads:
            t.join(5)
        for f in (self.proc.stdout, self.proc.stderr):
            if f:
                f.close()

    def cpu_seconds(self) -> float:
        """User + system time of the node process so far, from /proc."""
        try:
            with open(f"/proc/{self.proc.pid}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
            return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")
        except (OSError, ValueError, IndexError):
            return 0.0


class ClusterDeployment(Deployment):
    """A configuration with ``nodes`` > 1, running: one node process per node,
    the first ``chips`` of them a chip each (``rehearse``: jax's CPU backend,
    as ``Deployment``'s)."""

    def __init__(self, config: dict, rehearse: bool = False, parity: int | None = None):
        self.config = config
        self.rehearse = rehearse
        self.nodes = int(config["nodes"])
        self.chip_nodes = int(config["chips"])
        self.drives = int(config["drives"])
        self.parity = int(config["parity"]) if parity is None else parity
        per_node = self.drives // self.nodes
        self.root = os.path.join(SHM, f"{PREFIX}{os.getpid()}")
        self.drive_dirs = [os.path.join(self.root, f"n{n + 1}d{j + 1}")
                           for n in range(self.nodes) for j in range(per_node)]
        self.urls = [f"http://127.0.0.1:{free_port()}" for _ in range(self.nodes)]
        self.endpoints = [self.urls[i // per_node] + d for i, d in enumerate(self.drive_dirs)]
        self.endpoint = self.urls[0]
        self.procs: list[NodeProc] = []
        self.install: dict = {}

    # -- start -------------------------------------------------------------------

    def start(self, need_bytes: int, mallopt: dict[str, int] | None = None) -> None:
        self.make_drives(need_bytes)
        base = {k: v for k, v in os.environ.items() if k not in PINNED_ENV}
        base.update(self.config.get("env", {}))
        for n in range(self.nodes):
            env = dict(base)
            if n >= self.chip_nodes:
                env.update(HOST_NODE_ENV)
            elif self.rehearse:
                env.update({"MINIO_TPU_CODEC": "xla-cpu", "JAX_PLATFORMS": "cpu"})
            elif self.chip_nodes > 1:
                env.update(chip_env(n, free_port()))
            spec = {"endpoints": self.endpoints, "url": self.urls[n],
                    "port": int(self.urls[n].rsplit(":", 1)[1]), "mallopt": mallopt or {},
                    "parity": self.parity if self.parity != self.config["parity"] else None}
            self.procs.append(NodeProc(n, spec, env))
        # Every node serves its storage, lock and peer REST before any builds:
        # the first node formats the others' drives through it. It warms its
        # codec before it formats (Node.build), and a node that finds no format
        # waits 30 s for one, then fails; so the first builds alone, and the
        # others once the set is formatted.
        for p in self.procs:
            p.answer(LISTEN_TIMEOUT_S)
        answers = self._ask_each([{"cmd": "install"}], self.procs[:1], INSTALL_TIMEOUT_S)
        answers += self._ask_each([{"cmd": "install"}] * (self.nodes - 1), self.procs[1:],
                                  INSTALL_TIMEOUT_S)
        self._hold_to_the_layout(answers)
        chips = answers[:self.chip_nodes]
        first = chips[0]["install"]
        self.install = {
            **first,
            "device_count": sum(a["device"]["count"] for a in chips),
            "warm": {**(first.get("warm") or {}),
                     "seconds": max((a["install"].get("warm") or {}).get("seconds") or 0.0
                                    for a in chips)},
            "nodes": [{"node": p.name, "state": a["install"]["state"],
                       "platform": a["install"]["platform"],
                       "device_count": a["install"]["device_count"],
                       "warm": a["install"].get("warm"), "chip": a["device"]["chip"]}
                      for p, a in zip(self.procs, answers)],
        }

    @property
    def chip_procs(self) -> list[NodeProc]:
        return self.procs[:self.chip_nodes]

    def _hold_to_the_layout(self, answers: list[dict]) -> None:
        """Every chip node serves the device codec at the set's geometry on one
        device of the platform the run wants, no two share a chip, and every
        other node serves the host codec."""
        seen_files: dict[str, str] = {}
        for p, a in zip(self.procs, answers):
            inst, dev = a["install"], a["device"]
            if p.index >= self.chip_nodes:
                if inst.get("state") != "host":
                    raise SetupError(f"node {p.name} holds no chip and serves {inst}, "
                                     "not the host codec")
                continue
            self.hold_install(inst, f"node {p.name}: ")
            if dev["count"] != 1 or inst.get("device_count") != 1:
                raise SetupError(f"node {p.name}: jax has {dev['count']} devices, a node holds one")
            for f in dev["chip"]["dev_files"]:
                if f in seen_files:
                    raise SetupError(f"nodes {seen_files[f]} and {p.name} both hold {f}")
                seen_files[f] = p.name

    def check_chips(self, chips: int) -> None:
        if self.rehearse:
            return
        count = self.install.get("device_count")
        if count != chips:
            raise SetupError(f"the cell asks for {chips} chip(s), the nodes hold {count}")

    def _ask_each(self, cmds: list[dict], procs: list[NodeProc],
                  timeout_s: float = ASK_TIMEOUT_S) -> list[dict]:
        for p, cmd in zip(procs, cmds):
            p.send(cmd)
        return [p.answer(timeout_s) for p in procs]

    # -- clients ------------------------------------------------------------------

    def client_endpoint(self, client: int) -> str:
        return self.urls[client % self.chip_nodes]

    def readback_endpoint(self, client: int) -> str | None:
        """A node other than the one client `client` writes through, the
        clients of one writer spread over the others."""
        writer = client % self.chip_nodes
        step = 1 + (client // self.chip_nodes) % (self.nodes - 1)
        return self.urls[(writer + step) % self.nodes]

    def rebuild_endpoint(self, client: int) -> str | None:
        """A chip node, where a degraded read of client `client`'s keys is
        rebuilt on the device: the next chip node after its writer's, or the
        writer itself where it is the only one."""
        return self.urls[(client + 1) % self.chip_nodes]

    def plant(self, node: int, fault: str) -> None:
        """benchmark/tests: call `fault` ("module:function") inside node `node`."""
        self.procs[node].send({"cmd": "plant", "fault": fault})
        self.procs[node].answer(ASK_TIMEOUT_S)

    # -- sources the per-layer readers take ----------------------------------------

    def snapshot(self) -> dict:
        t = time.monotonic()
        snaps = self._ask_each([{"cmd": "snapshot"}] * self.nodes, self.procs)
        return {**merge_snapshots(snaps), "t": t}

    def cpu_seconds(self) -> float:
        return sum(p.cpu_seconds() for p in self.procs)

    def device(self) -> dict:
        devs = self._ask_each([{"cmd": "device"}] * self.chip_nodes, self.chip_procs)
        return {"platform": devs[0]["platform"], "kind": devs[0]["kind"],
                "count": sum(d["count"] for d in devs),
                "memory_peak_bytes": max(d["memory_peak_bytes"] for d in devs),
                "nodes": [{"node": p.name, "memory_peak_bytes": d["memory_peak_bytes"],
                           "chip": d["chip"]} for p, d in zip(self.chip_procs, devs)]}

    def trace_start(self, log_dir: str) -> None:
        self._ask_each([{"cmd": "trace-start", "dir": os.path.join(log_dir, p.name)}
                        for p in self.chip_procs], self.chip_procs)

    def trace_stop(self) -> None:
        self._ask_each([{"cmd": "trace-stop"}] * self.chip_nodes, self.chip_procs)

    def trace_reduce(self, log_dir: str, shard_len: int, wall_s: float, idle_chips: int,
                     describe_to: str | None) -> dict:
        per_node = self._ask_each([
            {"cmd": "trace-reduce", "dir": os.path.join(log_dir, p.name), "shard_len": shard_len,
             "wall_s": wall_s, "idle_chips": 1 if idle_chips else 0,
             "describe_to": f"{describe_to}.{p.name}" if describe_to else None}
            for p in self.chip_procs], self.chip_procs, timeout_s=300.0)
        return merge_traces([a["reduced"] for a in per_node], wall_s)

    # -- stop --------------------------------------------------------------------

    def close(self) -> None:
        for p in self.procs:
            p.close()
        shutil.rmtree(self.root, ignore_errors=True)
