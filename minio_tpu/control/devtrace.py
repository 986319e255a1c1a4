"""Why is the chip idle: a profiler trace reduced to gaps with the host's names.

`load` reads the ``.xplane.pb`` that ``jax.profiler`` wrote (its own
``ProfileData``; imported there, this package imports no jax) into plain
tuples ``(name, start_ns, dur_ns)``. Everything else is arithmetic on those
tuples. With the annotator of control/tracing.py installed (runtime.py, beside
the device codec), every span and stage of the program is an event of the host
plane in the same nanoseconds as the device's ops, so an idle gap of the device
can be put down to what the host was doing meanwhile.

`summarize` gives, per device: busy and idle seconds of the window (first to
last device op unless given), device seconds and executions per program (the
module line; codec programs are named ``jit_mtpu_*``), and the N longest idle
gaps, each with the host annotations that overlap it by overlap seconds and the
share of the gap that no annotation covers.
"""

from __future__ import annotations

import re

from .perf import DYNAMIC_STAGE_LAYERS, STAGES

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# A host event is one of ours when it is named "<layer>/<stage>".
LAYERS = frozenset({layer for layer, _ in STAGES} | DYNAMIC_STAGE_LAYERS)
_RUN_SUFFIX = re.compile(r"\(\d+\)$")  # "jit_mtpu_encode_hash_k12m4(1234)"

Event = tuple  # (name, start_ns, dur_ns)


def load(xplane_path: str) -> dict:
    """{"devices": {plane: {"ops": [Event], "modules": [Event]}},
    "host": [Event]} -- host events are the program's annotations only."""
    from jax.profiler import ProfileData

    devices: dict[str, dict[str, list]] = {}
    host: list[Event] = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            rows: dict[str, list] = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    rows[key] = [(e.name, int(e.start_ns), int(e.duration_ns))
                                 for e in line.events]
            if rows["ops"] or rows["modules"]:
                devices[plane.name] = rows
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, int(e.start_ns), int(e.duration_ns))
                         for e in line.events
                         if e.name.partition("/")[0] in LAYERS and "/" in e.name]
    return {"devices": devices, "host": host}


def merge(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: list[list[int]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_and_gaps(ops: list[Event], w0: int, w1: int) -> tuple[int, list[tuple[int, int]]]:
    """Busy nanoseconds inside [w0, w1] (union of the op intervals, clipped)
    and the idle gaps there as (start_ns, end_ns)."""
    busy = merge([(max(s, w0), min(s + d, w1)) for _, s, d in ops])
    gaps, edge = [], w0
    for a, b in busy:
        if a > edge:
            gaps.append((edge, a))
        edge = b
    if w1 > edge:
        gaps.append((edge, w1))
    return sum(b - a for a, b in busy), gaps


def programs(modules: list[Event], w0: int, w1: int) -> dict[str, dict]:
    """Device seconds and executions per program inside the window."""
    out: dict[str, dict] = {}
    for name, s, d in modules:
        ns = min(s + d, w1) - max(s, w0)
        if ns > 0:
            row = out.setdefault(_RUN_SUFFIX.sub("", name), {"seconds": 0.0, "executions": 0})
            row["seconds"] += ns / 1e9
            row["executions"] += 1
    return out


def attribute(gap: tuple[int, int], host: list[Event]) -> dict:
    """One idle gap put down to the host annotations that overlap it."""
    g0, g1 = gap
    by_name: dict[str, int] = {}
    covered = []
    for name, s, d in host:
        a, b = max(s, g0), min(s + d, g1)
        if b > a:
            by_name[name] = by_name.get(name, 0) + (b - a)
            covered.append((a, b))
    union = sum(b - a for a, b in merge(covered))
    return {
        "start_s": g0 / 1e9,
        "seconds": (g1 - g0) / 1e9,
        "host": [[n, ns / 1e9] for n, ns in sorted(by_name.items(), key=lambda kv: -kv[1])],
        "uncovered_share": 1.0 - union / (g1 - g0),
    }


def summarize(trace: dict, top: int = 10, window_ns: tuple[int, int] | None = None) -> dict:
    """The whole reduction: what the admin profile's devtrace.json holds."""
    host = trace["host"]
    out: dict = {"host_annotations": len(host), "devices": {}}
    for plane, rows in trace["devices"].items():
        ops = rows["ops"]
        if not ops:
            continue
        w0, w1 = window_ns or (min(s for _, s, _ in ops), max(s + d for _, s, d in ops))
        busy, gaps = busy_and_gaps(ops, w0, w1)
        gaps.sort(key=lambda g: g[0] - g[1])
        out["devices"][plane] = {
            "window_s": (w1 - w0) / 1e9,
            "busy_s": busy / 1e9,
            "idle_share": 1.0 - busy / (w1 - w0) if w1 > w0 else 0.0,
            "programs": programs(rows["modules"], w0, w1),
            "idle_gaps": [attribute(g, host) for g in gaps[:top]],
        }
    return out
