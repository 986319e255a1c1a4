"""From a profiler trace to device busy time, idle gaps and codec time.

Two halves. `load` reads the ``.xplane.pb`` that ``jax.profiler`` wrote, with
jax's own ``ProfileData``, into plain tuples. Everything after it is arithmetic
on those tuples and is tested on a synthetic list (benchmark/tests).

A device event is ``(name, start_ns, dur_ns, dims)``: ``dims`` are the integers
of the shapes in the op's name (its HLO text on a TPU), else ``()``. Codec programs are
found by the shard length the geometry fixes (a dimension of the codec's
inputs and outputs), never by XLA's numbered fusion names: a module execution
is the codec's when an op inside it carries that dimension.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DIMS = re.compile(r"\[([0-9,\s]+)\]")


def start(log_dir: str) -> None:
    """Start a trace of this process: device tracing on, the Python call
    tracer off (it would log every call of a twenty-connection server)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def dims_of(name: str) -> tuple[int, ...]:
    """The integers of every [..] shape in an op's name: on a TPU the name is
    the op's whole HLO text, operands and result with their shapes."""
    return tuple(int(x) for group in _DIMS.findall(name)
                 for x in group.replace(" ", "").split(",") if x)


def load(xplane_path: str) -> dict[str, dict[str, list]]:
    """{device plane name: {"ops": [...], "modules": [...]}} for every plane of
    an accelerator (``/device:TPU:n``). Times are the trace's own nanoseconds."""
    from jax.profiler import ProfileData

    out: dict[str, dict[str, list]] = {}
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        rows: dict[str, list] = {"ops": [], "modules": []}
        for line in plane.lines:
            if line.name == OPS_LINE:
                rows["ops"] = [(e.name, int(e.start_ns), int(e.duration_ns), dims_of(e.name))
                               for e in line.events]
            elif line.name == MODULES_LINE:
                rows["modules"] = [(e.name, int(e.start_ns), int(e.duration_ns), ())
                                   for e in line.events]
        if rows["ops"] or rows["modules"]:
            out[plane.name] = rows
    return out


def describe(xplane_path: str, limit: int = 6) -> list[str]:
    """Planes, lines and a few events with their stats: what to look at by
    hand before trusting the reduction on a new jax or a new chip."""
    from jax.profiler import ProfileData

    text = []
    for plane in ProfileData.from_file(xplane_path).planes:
        lines = list(plane.lines)
        text.append(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines[:40]:
            events = list(line.events)
            text.append(f"  LINE {line.name!r} events={len(events)}")
            for e in events[:limit]:
                try:
                    stats = {k: str(v)[:120] for k, v in e.stats}
                except (TypeError, ValueError):
                    stats = {}
                text.append(f"    {e.name!r} start={e.start_ns} dur={e.duration_ns} {stats}")
    return text


def reduce_dir(log_dir: str, shard_len: int, wall_s: float, idle_chips: int,
               describe_to: str | None = None) -> dict:
    """``reduce`` of the trace this process wrote under `log_dir`; with
    `describe_to`, ``describe``'s lines written there too."""
    xplane = find_xplane(log_dir)
    if describe_to:
        with open(describe_to, "w") as f:
            f.write("\n".join(describe(xplane)) + "\n")
    return reduce(load(xplane), shard_len, wall_s=wall_s, idle_chips=idle_chips)


# -- arithmetic on event tuples ------------------------------------------------


def merge(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Union of [start, end) intervals as a sorted list of disjoint ones."""
    out: list[list[int]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_and_gaps(events: list, w0: int, w1: int) -> tuple[int, list[tuple[int, str]]]:
    """Busy nanoseconds of one device inside [w0, w1] (the union of its op
    intervals, clipped), and every idle gap there as (length_ns, name), the
    name being the op that ended before the gap and the op that began after."""
    clipped = []
    for name, s, d, _ in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            clipped.append((a, b, name))
    clipped.sort()
    busy = sum(b - a for a, b in merge([(a, b) for a, b, _ in clipped]))
    gaps, edge, last = [], w0, "window-start"
    for a, b, name in clipped:
        if a > edge:
            gaps.append((a - edge, f"{label(last)}--{label(name)}"))
        if b > edge:
            edge, last = b, name
    if w1 > edge:
        gaps.append((w1 - edge, f"{label(last)}--window-end"))
    return busy, gaps


def codec_module_ns(ops: list, modules: list, shard_len: int, w0: int, w1: int) -> tuple[int, int]:
    """(device nanoseconds, executions) of the module executions inside
    [w0, w1] that are the codec's: those with an op that carries the shard
    length as a dimension. A module cut by the window's edge counts for the
    part inside."""
    marks = sorted(s for _, s, _, dims in ops if shard_len in dims)
    total = runs = 0
    for _, s, d, _ in modules:
        i = bisect.bisect_left(marks, s)
        if i < len(marks) and marks[i] < s + d:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                total += b - a
                runs += 1
    return total, runs


_HLO = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])")


def label(name: str) -> str:
    """A short name for an op. On a TPU an op's name is its whole HLO text,
    '%fusion.173 = u8[64,87382,4]{...} fusion(...)': keep the instruction and
    its result shape, so that one fusion at two batch sizes is two rows."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def top_ops(events: list, w0: int, w1: int, n: int = 10) -> list[list]:
    """The n ops with most device time inside the window, in seconds."""
    by_name: dict[str, int] = {}
    for name, s, d, _ in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            key = label(name)
            by_name[key] = by_name.get(key, 0) + (b - a)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def reduce(devices: dict[str, dict[str, list]], shard_len: int,
           window_ns: tuple[int, int] | None = None, idle_chips: int = 0,
           wall_s: float = 0.0) -> dict:
    """The whole reduction, averaged over the devices used. `window_ns` is the
    traced window on the trace's clock; when the caller cannot place it there
    (host and trace clocks differ), the window is from the first device event
    to the last, and `window_s` of the caller's own clock is kept beside it.

    A chip that ran nothing in the slice has no plane in the trace at all (a
    healthy GET verifies on the host), so the trace cannot tell an idle chip
    from no trace: the caller can. Where it says the process held
    `idle_chips` accelerator chips while it traced `wall_s` seconds, a trace
    with no device op reads busy 0 over that time and one gap, the whole
    slice; otherwise (0: no accelerator, as in a rehearsal) it reads {}."""
    starts = [s for d in devices.values() for _, s, _, _ in d["ops"]]
    ends = [s + n for d in devices.values() for _, s, n, _ in d["ops"]]
    if not starts:
        if not idle_chips:
            return {}
        return {"devices": idle_chips, "span_s": wall_s, "busy_s": 0.0, "codec_s": 0.0,
                "codec_runs": 0, "device_ops": [],
                "idle_gaps": [["window-start--window-end", wall_s]]}
    w0, w1 = window_ns or (min(starts), max(ends))
    busy_ns, codec_ns, codec_runs, gaps, all_ops = [], [], 0, [], []
    for dev in devices.values():
        busy, dev_gaps = busy_and_gaps(dev["ops"], w0, w1)
        busy_ns.append(busy)
        gaps += dev_gaps
        ns, runs = codec_module_ns(dev["ops"], dev["modules"], shard_len, w0, w1)
        codec_ns.append(ns)
        codec_runs += runs
        all_ops += dev["ops"]
    n = len(devices)
    gaps.sort(key=lambda g: -g[0])
    return {
        "devices": n,
        "span_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "codec_s": sum(codec_ns) / n / 1e9,
        "codec_runs": codec_runs,
        "device_ops": top_ops(all_ops, w0, w1),
        "idle_gaps": [[name, ns / 1e9] for ns, name in gaps[:10]],
    }
