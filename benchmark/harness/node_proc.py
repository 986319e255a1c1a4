#!/usr/bin/env python3
"""One node of a cluster cell, as a process of its own.

``harness/cluster.py`` starts one of these per node. Each is a ``Deployment``
(``harness/server.py``) of one node of the cluster: the traffic file's
``mallopt`` pinned in its own process, the self-test, ``Node(endpoints,
url=...)`` over the cluster's one ordered endpoint list, ``make_app`` on its
own loopback port, then, once the coordinator has seen every node listen,
``node.build()`` (format consensus across the nodes; on a chip node the device
codec warmed, on the others the host codec) and the scanner. ``MINIO_TPU_CODEC``
and, on a host of several chips, the libtpu variables that give it one chip
(``cluster.chip_env``) are in its environment from the start. Its drives are
made and removed by the coordinator.

Protocol: one JSON object per line on stdin, one per line on the protocol
channel (the process's stdout; whatever the program prints goes to stderr).

    argv[1]                        JSON spec: endpoints, url, port, parity, mallopt
    -> {"listening": url}          HTTP up, storage/lock/peer REST reachable
    {"cmd": "install"}             -> {"install": {...}, "device": {...}}
                                      node.build() done, the scanner started
    {"cmd": "snapshot"}            -> Deployment.snapshot()'s fields
    {"cmd": "device"}              -> Deployment.device()'s fields and "chip"
    {"cmd": "trace-start", "dir"}  -> {"ok": true}   jax.profiler of this chip
    {"cmd": "trace-stop"}          -> {"ok": true}
    {"cmd": "trace-reduce", "dir", "shard_len", "wall_s", "idle_chips", "describe_to"}
                                   -> {"reduced": trace.reduce_dir(...)}, the trace removed
    {"cmd": "plant", "fault": "module:function"}  -> {"ok": true}
                                      benchmark/tests: calls the function here
    {"cmd": "close"}               -> {"closed": true}, then the process exits

An error answers {"error": "..."}; stdin's end is a ``close``.
"""

from __future__ import annotations

import importlib
import json
import os
import shutil
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import trace  # noqa: E402 - imports no jax
from benchmark.harness.server import Deployment  # noqa: E402

INSTALL_KEYS = ("state", "platform", "device_kind", "device_count", "geometry", "mesh", "warm",
                "compile_cache", "setup_seconds", "kernels", "reason")


class NodeProcess(Deployment):
    """One node of the cluster in this process: its drives are the
    coordinator's, its port and URL the spec's."""

    def __init__(self, spec: dict):
        self.spec = spec
        self.port = int(spec["port"])
        self.root = None  # the coordinator makes and removes the drives
        self.node = None
        self._stop = threading.Event()
        self._http: threading.Thread | None = None
        self.install: dict = {}
        self.compile_events: list[float] = []

    def listen_and_report(self) -> dict:
        spec = self.spec
        if spec.get("mallopt"):
            self.pin_allocator(spec["mallopt"])
        self.listen(spec["endpoints"], spec["url"], spec.get("parity"))
        return {"listening": spec["url"]}

    def install_and_report(self) -> dict:
        self.build()
        if self.install.get("state") == "host":
            self.node.scanner.start()  # a node with no chip scans its drives all the same
        return {"install": {k: self.install.get(k) for k in INSTALL_KEYS},
                "device": self.device()}

    def device(self) -> dict:
        """What jax reports of this process's one device, with the witness of
        which chip of the host it is: the chip the variables gave it and the
        accelerator device files the process holds open."""
        import jax

        held = set()
        for fd in os.listdir("/proc/self/fd"):
            try:
                target = os.readlink(f"/proc/self/fd/{fd}")
            except OSError:
                continue  # the listing's own descriptor, or one closed meanwhile
            # a chip's own file; /dev/vfio/vfio is the container every process opens
            if target.startswith(("/dev/accel", "/dev/vfio/")) and target != "/dev/vfio/vfio":
                held.add(target)
        return {**super().device(),
                "chip": {"visible": os.environ.get("TPU_VISIBLE_CHIPS"),
                         "ids": [d.id for d in jax.devices()], "dev_files": sorted(held)}}


def reduce_trace(cmd: dict) -> dict:
    """This chip's trace of the traced sub-window, reduced here (the
    coordinator imports no jax), then removed."""
    reduced = trace.reduce_dir(cmd["dir"], int(cmd["shard_len"]), float(cmd["wall_s"]),
                               int(cmd["idle_chips"]), cmd.get("describe_to"))
    shutil.rmtree(cmd["dir"], ignore_errors=True)
    return reduced


def plant(fault: str) -> None:
    module, _, fn = fault.partition(":")
    getattr(importlib.import_module(module), fn)()


def main(argv: list[str]) -> int:
    # The protocol keeps the real stdout; anything the program prints goes to stderr.
    proto = os.fdopen(os.dup(1), "w", buffering=1)
    os.dup2(2, 1)
    sys.stdout = sys.stderr

    def say(obj: dict) -> None:
        proto.write(json.dumps(obj) + "\n")
        proto.flush()

    node = NodeProcess(json.loads(argv[1]))
    try:
        say(node.listen_and_report())
        for line in sys.stdin:
            cmd = json.loads(line)
            what = cmd["cmd"]
            try:
                if what == "install":
                    say(node.install_and_report())
                elif what == "snapshot":
                    say(node.snapshot())
                elif what == "device":
                    say(node.device())
                elif what == "trace-start":
                    trace.start(cmd["dir"])
                    say({"ok": True})
                elif what == "trace-stop":
                    trace.stop()
                    say({"ok": True})
                elif what == "trace-reduce":
                    say({"reduced": reduce_trace(cmd)})
                elif what == "plant":
                    plant(cmd["fault"])
                    say({"ok": True})
                elif what == "close":
                    break
                else:
                    say({"error": f"unknown command {what!r}"})
            except Exception as e:  # noqa: BLE001 - the coordinator decides what it means
                say({"error": f"{what}: {type(e).__name__}: {e}"})
    except Exception as e:  # noqa: BLE001 - boundary: the coordinator reads the line
        say({"error": f"start: {type(e).__name__}: {e}"})
    node.close()
    say({"closed": True})
    return 0


if __name__ == "__main__":
    rc = main(sys.argv)
    sys.stderr.flush()
    os._exit(rc)  # a thread of the served program that will not end must not hold the exit
