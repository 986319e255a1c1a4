#!/usr/bin/env python3
"""Can four processes on one four-chip host each hold a chip of their own?

Run by hand on a four-chip host (it exits non-zero where a process fails):

    python3 benchmark/tests/probe_chips.py [--without-allow-multiple]

Starts four processes at once, process i with the libtpu variables that the
Cloud TPU docs give for one process per chip on a single host
(``harness/cluster.py chip_env``): ``TPU_VISIBLE_CHIPS=i``,
``TPU_CHIPS_PER_PROCESS_BOUNDS=1,1,1``, ``TPU_PROCESS_BOUNDS=1,1,1`` and a
``TPU_PROCESS_PORT`` of its own. Each prints what jax reports (device count,
id, coords, kind), the accelerator device files it holds open, and runs one
small program while the other three hold theirs. ``--without-allow-multiple``
leaves out ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``, to see whether libtpu's lock file
admits the four without it. This process never imports jax.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness.cluster import chip_env  # noqa: E402 - imports no jax

CHILD = r"""
import json, os, time
import jax, jax.numpy as jnp
devs = jax.devices()
x = jnp.ones((2048, 2048), jnp.float32)
s = float((x @ x).sum())
time.sleep(8)  # the four hold their chips at once
held = set()
for fd in os.listdir("/proc/self/fd"):
    try:
        target = os.readlink(f"/proc/self/fd/{fd}")
    except OSError:
        continue  # the listing's own descriptor
    if target.startswith("/dev/") and "null" not in target:
        held.add(target)
held = sorted(held)
print(json.dumps({"count": jax.device_count(), "local": jax.local_device_count(),
                  "ids": [d.id for d in devs], "coords": [list(getattr(d, "coords", ()))
                                                          for d in devs],
                  "kind": devs[0].device_kind, "platform": devs[0].platform,
                  "sum": s, "dev_files": held,
                  "peak": (devs[0].memory_stats() or {}).get("peak_bytes_in_use"),
                  "attrs": {k: repr(getattr(devs[0], k)) for k in dir(devs[0])
                            if not k.startswith("_") and not callable(getattr(devs[0], k))}}))
"""


def main(argv: list[str]) -> int:
    procs = []
    for i in range(4):
        env = {**os.environ, **chip_env(i, 8476 + i)}
        if "--without-allow-multiple" in argv:
            env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD")
        procs.append(subprocess.Popen([sys.executable, "-c", CHILD], env=env,
                                      stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    rc = 0
    deadline = time.monotonic() + 150.0  # a process that waits on another's chip hangs
    for i, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=max(1.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            p.kill()
            out, err = p.communicate()
        print(f"process {i}: exit {p.returncode}")
        print("  stdout: " + (out.strip().splitlines() or ["(none)"])[-1])
        if p.returncode:
            rc = 1
            print("  stderr tail: " + json.dumps(err.strip().splitlines()[-6:]))
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
