#!/usr/bin/env python3
"""Removes the data directories an overwritten key leaves behind, as a process
of its own (it imports nothing of the program and shares no GIL with it).

The program leaves the previous data directory of every overwritten key on the
drives (PERF.md, Open questions). On memory-backed drives that is the machine's
memory: a ring of keys overwritten for a minute at 250 MiB/s of 4+4 holds 30 GB
where 4 GiB are live, and the run ends at the machine's 40 GiB limit. A
traffic file that overwrites a ring therefore asks for this sweep:

    "reap_superseded": {"keep": 2, "every_s": 1.0}

Under every ``<drive>/<bucket>/<client>/<key>/`` the ``keep`` newest data
directories stay (the one ``xl.meta`` names and its predecessors, by the time
they were written); older ones are removed. The sweep runs from the ramp's
first op to the drain's last and is stopped before the check reads anything.

    argv: <bucket> <keep> <every_s> <drive dir>...      stdin closes -> exit
"""

from __future__ import annotations

import os
import select
import shutil
import sys
import time


def superseded(key_dir: str, keep: int) -> list[str]:
    """The data directories of one key on one drive that are older than its
    `keep` newest, oldest first."""
    dirs = []
    try:
        with os.scandir(key_dir) as it:
            for e in it:
                if e.is_dir(follow_symlinks=False):
                    dirs.append((e.stat(follow_symlinks=False).st_mtime_ns, e.path))
    except OSError:
        return []  # the key was removed under the scan
    dirs.sort()
    return [path for _, path in dirs[:max(0, len(dirs) - keep)]]


def sweep(drive_dirs: list[str], bucket: str, keep: int) -> tuple[int, int]:
    """One pass over every key of every drive: (directories removed, bytes)."""
    removed = freed = 0
    for drive in drive_dirs:
        top = os.path.join(drive, bucket)
        try:
            clients = [e.path for e in os.scandir(top) if e.is_dir(follow_symlinks=False)]
        except OSError:
            continue
        for client in clients:
            try:
                keys = [e.path for e in os.scandir(client) if e.is_dir(follow_symlinks=False)]
            except OSError:
                continue
            for key_dir in keys:
                for path in superseded(key_dir, keep):
                    try:
                        freed += sum(e.stat().st_size for e in os.scandir(path) if e.is_file())
                    except OSError:
                        pass
                    shutil.rmtree(path, ignore_errors=True)
                    removed += 1
    return removed, freed


def main(argv: list[str]) -> int:
    bucket, keep, every_s, drives = argv[1], int(argv[2]), float(argv[3]), argv[4:]
    removed = freed = sweeps = 0
    while True:
        t = time.monotonic()
        n, b = sweep(drives, bucket, keep)
        removed, freed, sweeps = removed + n, freed + b, sweeps + 1
        left = every_s - (time.monotonic() - t)
        if select.select([sys.stdin], [], [], max(0.0, left))[0]:
            break  # the parent closed stdin (or wrote): the window has drained
    print(f'{{"sweeps": {sweeps}, "removed": {removed}, "freed_bytes": {freed}}}', flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
