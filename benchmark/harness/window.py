"""The arithmetic of the measured window.

An op is ``[kind, key, start, end, nbytes, ok]`` on the shared monotonic
clock. A rate is all the correct work over all the time of the window: each
correct op contributes its weight (bytes, or 1) times the share of its own
[start, end] that lies inside [t0, t1]. An op that began in the ramp or ended
in the drain counts for the part inside; a window in which the server stalled
reads low, not empty. Nothing here is a median of pieces.
"""

from __future__ import annotations

MIB = 1 << 20
KIND, KEY, START, END, NBYTES, OK = range(6)


def share_inside(start: float, end: float, t0: float, t1: float) -> float:
    """The share of [start, end] inside [t0, t1]; an instant op is in or out."""
    if end <= start:
        return 1.0 if t0 <= start < t1 else 0.0
    return max(0.0, min(end, t1) - max(start, t0)) / (end - start)


def prorated_rate(ops: list, t0: float, t1: float, by_bytes: bool) -> float:
    """Correct work per second of the window, prorated at its edges."""
    if t1 <= t0:
        raise ValueError("empty window")
    work = 0.0
    for op in ops:
        if op[OK]:
            work += (op[NBYTES] if by_bytes else 1.0) * share_inside(op[START], op[END], t0, t1)
    return work / (t1 - t0)


def ended_inside(ops: list, t0: float, t1: float) -> list:
    return [op for op in ops if t0 <= op[END] < t1]


def percentile(values: list[float], q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    if not values:
        raise ValueError("no values")
    v = sorted(values)
    pos = q / 100.0 * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def end_to_end(ops: list, t0: float, t1: float) -> dict[str, float]:
    """Every end-to-end number the window can give; the cell's manifest
    entries pick the ones it reports. Latencies are of all ops that ended
    inside the window, failed ones too."""
    out = {
        "throughput": prorated_rate(ops, t0, t1, by_bytes=True) / MIB,
        "ops_rate": prorated_rate(ops, t0, t1, by_bytes=False),
    }
    lat = [(op[END] - op[START]) * 1e3 for op in ended_inside(ops, t0, t1)]
    if lat:
        out["lat_p50"] = percentile(lat, 50)
        out["lat_p95"] = percentile(lat, 95)
    return out


def counts_by_kind(ops: list, t0: float, t1: float) -> dict[str, dict[str, int]]:
    out: dict[str, dict[str, int]] = {}
    for op in ops:
        row = out.setdefault(op[KIND], {"touching": 0, "ended_inside": 0, "failed": 0})
        if op[END] > t0 and op[START] < t1:
            row["touching"] += 1
        if t0 <= op[END] < t1:
            row["ended_inside"] += 1
        if not op[OK]:
            row["failed"] += 1
    return out
