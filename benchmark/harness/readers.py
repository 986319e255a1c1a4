"""The per-layer readers: a small fixed set, driven by a metric's data file.

A metric is ``benchmark/metrics/<name>.json``; its ``reader`` names one of the
kinds below and that kind's parameters, so a later PR adds a metric as data
wherever the source already exists. A reader that finds nothing to read
returns None and the harness leaves the metric out of the line; it never
returns 0 for a share.

Sources (``src``), all taken by the harness, none computed by the program
beyond its own counters and ledger:

    src["window"]  = (snapshot at window start, snapshot at window end)
    src["traced"]  = (snapshot at trace start, snapshot at trace stop)
    src["trace"]   = trace.reduce(...) of the traced sub-window, or {}
    src["facts"]   = numbers of the window the harness counted itself
    src["geometry"] = (K, M, shard length); src["block_bytes"]; src["device_kind"]
    src["lost_data"] = data shards the traffic file's `prepare` removed per object

kinds:
    ledger   rows (layer/stage) summed, field wall_s|cpu_s|count, times scale,
             over a fact (per) or 1
    counter  numerator / denominator, each a list of codec counters, S3-front
             counters or snapshot counters summed and differenced over the
             window; a missing denominator is 1
    trace    value: idle_share | codec_ms_per_GiB | codec_roofline (the codec
             programs' device time over the blocks encoded in the traced
             slice) | recon_ms_per_GiB | recon_roofline (the same over the
             blocks reconstructed there: in a cell whose window writes nothing
             every program found by the shard length is the reconstruct program)
    process  value: a fact by name (client_cpu, stored_per_user_byte, warmup_s)
"""

from __future__ import annotations

from . import roofline

GIB = 1 << 30
KINDS = {
    "ledger": {"kind", "rows", "field", "scale", "per"},
    "counter": {"kind", "numerator", "denominator", "scale"},
    "trace": {"kind", "value"},
    "process": {"kind", "value"},
}


def validate(name: str, reader: dict) -> None:
    kind = reader.get("kind")
    if kind not in KINDS:
        raise ValueError(f"metric {name}: unknown reader kind {kind!r}")
    extra = set(reader) - KINDS[kind]
    if extra:
        raise ValueError(f"metric {name}: unknown reader fields {sorted(extra)}")


def _delta(pair: tuple[dict, dict], group: str, name: str, field: str | None = None) -> float | None:
    a, b = pair
    va, vb = a[group].get(name), b[group].get(name)
    if vb is None:
        return None
    if field is not None:
        return vb[field] - (va[field] if va else 0.0)
    return vb - (va or 0)


def _counter(pair: tuple[dict, dict], name: str) -> float | None:
    """A codec counter, a counter of the S3 front, or a counter of the
    snapshot itself (compiles, cache_entries), differenced."""
    for group in ("codec", "front"):
        if name in pair[1].get(group, {}):
            return _delta(pair, group, name)
    if name in pair[1] and isinstance(pair[1][name], (int, float)):
        return pair[1][name] - pair[0][name]
    return None


def read_ledger(reader: dict, src: dict) -> float | None:
    field = reader.get("field", "wall_s")
    parts = [_delta(src["window"], "ledger", row, field) for row in reader["rows"]]
    if all(p is None for p in parts):
        return None
    total = sum(p for p in parts if p is not None)
    per = reader.get("per")
    if per is None:
        return total * reader.get("scale", 1.0)
    denom = src["facts"].get(per)
    if not denom:
        return None
    return total * reader.get("scale", 1.0) / denom


def read_counter(reader: dict, src: dict) -> float | None:
    num = [_counter(src["window"], n) for n in reader["numerator"]]
    if any(v is None for v in num):
        return None
    den_names = reader.get("denominator")
    if not den_names:
        return sum(num) * reader.get("scale", 1.0)
    den = [_counter(src["window"], n) for n in den_names]
    if any(v is None for v in den) or not sum(den):
        return None
    return sum(num) / sum(den) * reader.get("scale", 1.0)


def read_trace(reader: dict, src: dict) -> float | None:
    tr = src.get("trace") or {}
    if not tr or not tr.get("span_s"):
        return None
    value = reader["value"]
    if value == "idle_share":
        return 100.0 * (1.0 - tr["busy_s"] / tr["span_s"])
    if value not in ("codec_ms_per_GiB", "codec_roofline", "recon_ms_per_GiB", "recon_roofline"):
        raise ValueError(f"unknown trace value {value!r}")
    recon = value.startswith("recon")
    blocks = _counter(src["traced"], "blocks_reconstructed" if recon else "blocks_encoded")
    if not blocks or not tr.get("codec_s"):
        return None  # no full block went through, or no codec program was found
    k, m, shard_len = src["geometry"]
    if value.endswith("_ms_per_GiB"):
        user_gib = blocks * src["block_bytes"] / GIB
        return tr["codec_s"] * 1e3 / user_gib
    if recon:
        if not src.get("lost_data"):
            return None  # the traffic file lost no shards: nothing says how many rows a block rebuilt
        least, _bound = roofline.recon_least_seconds(
            int(blocks), k, int(src["lost_data"]), shard_len, src["device_kind"])
    else:
        least, _bound = roofline.least_seconds(int(blocks), k, m, shard_len, src["device_kind"])
    return 100.0 * least / tr["codec_s"]


def read_process(reader: dict, src: dict) -> float | None:
    return src["facts"].get(reader["value"])


READ = {"ledger": read_ledger, "counter": read_counter, "trace": read_trace,
        "process": read_process}


def read(metric: dict, src: dict) -> float | None:
    reader = metric["reader"]
    value = READ[reader["kind"]](reader, src)
    return None if value is None else float(value)
