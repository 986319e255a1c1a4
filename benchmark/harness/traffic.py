"""A cell from its data files.

``BENCHMARK.json`` names a cell's configuration and traffic mix; the files are
found by those names:

    benchmark/configs/<config>.json     the deployment
    benchmark/traffic/<traffic>.json    the traffic mix, parameters of the one
                                        generator (client_worker.py)
    benchmark/metrics/<metric>.json     a per-layer metric and its reader

An unknown field in any of them is an error: a typo must not silently run the
default. Adding a cell, a configuration or a metric with an existing reader is
adding files and manifest entries, never code.
"""

from __future__ import annotations

import json
import os

from . import check, readers, roofline
from .server import MALLOPT_PARAMS

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

CONFIG_FIELDS = {
    "name", "source", "deployment", "drives", "parity", "data", "block_bytes",
    "chips", "drive_medium", "drive_medium_why", "env", "guarantees", "reduced",
    "assumed", "device_state", "nodes",
}
TRAFFIC_FIELDS = {
    "name", "source", "reduced", "assumed", "why", "loop", "clients", "object_bytes",
    "mix", "keys", "prepare", "typical_op_s", "ramp_s", "trace_seconds", "check",
    "op_timeout_s", "tmpfs_bytes", "tmpfs_why", "multipart", "reap_superseded", "mallopt",
    "heal",
}
METRIC_FIELDS = {"name", "unit", "better", "layer", "moves", "source", "workloads",
                 "reader", "what"}
PREPARE_STEPS = {"populate", "lose_shards"}
CHECK_FIELDS = {"readback_sample", "degraded_sample", "reference_sample"}
MULTIPART_FIELDS = {"part_bytes", "parts_in_flight"}
REAP_FIELDS = {"keep", "every_s"}
HEAL_FIELDS = {"replace", "poll_s"}
HEAL_RAMP = 1.5  # a heal traffic's ramp, in typical ops: the first heal ends before the window
MALLOPT_MAX = 2**31 - 1  # mallopt takes an int
OPS = {*check.WRITES, "GET", "STAT", "DELETE", "HEAL"}
MIN_PART_BYTES = 5 << 20  # S3's least part, the last excepted (the program holds a Complete to it)


def _load(path: str, fields: set[str], what: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    extra = set(doc) - fields
    if extra:
        raise ValueError(f"{what} {path}: unknown fields {sorted(extra)}")
    return doc


def manifest() -> dict:
    with open(MANIFEST) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    cfg = _load(os.path.join(BENCH_DIR, "configs", f"{name}.json"), CONFIG_FIELDS, "configuration")
    for key in ("name", "source", "drives", "parity", "data", "block_bytes", "chips",
                "drive_medium", "guarantees"):
        if key not in cfg:
            raise ValueError(f"configuration {name}: missing {key!r}")
    if cfg["name"] != name:
        raise ValueError(f"configuration file {name}.json names itself {cfg['name']!r}")
    if cfg["data"] + cfg["parity"] != cfg["drives"]:
        raise ValueError(f"configuration {name}: data + parity != drives")
    nodes = cfg.get("nodes", 1)
    if (not isinstance(nodes, int) or nodes < 1 or cfg["drives"] % nodes
            or (nodes > 1 and cfg["chips"] not in (1, nodes))):
        raise ValueError(f"configuration {name}: nodes must divide drives, and a cluster "
                         "(nodes > 1) holds one chip, on its first node, or one a node "
                         "(harness/cluster.py)")
    if cfg["drive_medium"] != "tmpfs":
        raise ValueError(f"configuration {name}: drive_medium {cfg['drive_medium']!r}: only "
                         "tmpfs has a harness; a real-disk medium is a later benchmark PR")
    return cfg


def load_traffic(name: str) -> dict:
    t = _load(os.path.join(BENCH_DIR, "traffic", f"{name}.json"), TRAFFIC_FIELDS, "traffic mix")
    for key in ("name", "source", "why", "loop", "clients", "object_bytes", "mix", "keys",
                "typical_op_s", "ramp_s", "tmpfs_bytes"):
        if key not in t:
            raise ValueError(f"traffic mix {name}: missing {key!r}")
    if t["name"] != name:
        raise ValueError(f"traffic file {name}.json names itself {t['name']!r}")
    if t["loop"] != "closed":
        raise ValueError(f"traffic mix {name}: loop {t['loop']!r}: the generator is closed-loop; "
                         "an open loop at a fixed rate is a later benchmark PR")
    if set(t["mix"]) - OPS or sum(t["mix"].values()) != 100:
        raise ValueError(f"traffic mix {name}: mix must share 100 among {sorted(OPS)}")
    if t["keys"].get("kind") not in ("ring", "pool"):
        raise ValueError(f"traffic mix {name}: keys.kind must be ring or pool")
    # a ring of keys takes one kind of write and nothing else
    if set(t["mix"]) not in ({w} for w in check.WRITES) and t["keys"]["kind"] != "pool":
        raise ValueError(f"traffic mix {name}: reads and deletes need keys.kind pool")
    if ("MPUT" in t["mix"]) != ("multipart" in t):
        raise ValueError(f"traffic mix {name}: MPUT in the mix and the field multipart "
                         "come together or not at all")
    if "multipart" in t:
        mp = t["multipart"]
        if set(mp) != MULTIPART_FIELDS:
            raise ValueError(f"traffic mix {name}: multipart takes {sorted(MULTIPART_FIELDS)}")
        several = t["object_bytes"] > mp["part_bytes"]
        if mp["parts_in_flight"] < 1 or (several and mp["part_bytes"] < MIN_PART_BYTES):
            raise ValueError(f"traffic mix {name}: multipart wants parts_in_flight >= 1 and "
                             f"part_bytes >= {MIN_PART_BYTES} where an object has more than one")
    if ("HEAL" in t["mix"]) != ("heal" in t):
        raise ValueError(f"traffic mix {name}: HEAL in the mix and the field heal "
                         "come together or not at all")
    if "heal" in t:
        h = t["heal"]
        rep = h.get("replace")
        if (set(h) != HEAL_FIELDS or not isinstance(rep, list) or not rep
                or len(set(rep)) != len(rep)
                or not all(isinstance(d, int) and d >= 1 for d in rep) or h["poll_s"] <= 0):
            raise ValueError(f"traffic mix {name}: heal takes {sorted(HEAL_FIELDS)}: distinct "
                             "drive numbers from 1, and poll_s > 0")
        steps = [next(iter(step)) for step in t.get("prepare", [])]
        if (t["mix"] != {"HEAL": 100} or t["keys"]["kind"] != "pool"
                or steps != ["populate"]):
            raise ValueError(f"traffic mix {name}: a heal is HEAL alone over a pool that "
                             "`populate` put, and nothing else prepared")
        if t["ramp_s"] < HEAL_RAMP * t["typical_op_s"]:
            raise ValueError(f"traffic mix {name}: a heal's ramp_s is at least {HEAL_RAMP} "
                             "typical ops")
    if "reap_superseded" in t:
        reap = t["reap_superseded"]
        if (set(reap) != REAP_FIELDS or reap["keep"] < 2 or reap["every_s"] <= 0
                or t["keys"]["kind"] != "ring"):
            raise ValueError(f"traffic mix {name}: reap_superseded takes {sorted(REAP_FIELDS)}, "
                             "keep >= 2 (the version xl.meta names and the one before), "
                             "every_s > 0, and a ring of keys")
    pins = t.get("mallopt", {"M_TOP_PAD": 0})
    if not pins or set(pins) - set(MALLOPT_PARAMS) or not all(
            isinstance(v, int) and 0 <= v <= MALLOPT_MAX for v in pins.values()):
        raise ValueError(f"traffic mix {name}: mallopt pins some of {sorted(MALLOPT_PARAMS)} "
                         f"to whole numbers from 0 to {MALLOPT_MAX}")
    for step in t.get("prepare", []):
        if len(step) != 1 or set(step) - PREPARE_STEPS:
            raise ValueError(f"traffic mix {name}: unknown prepare step {step}")
    if set(t.get("check", {})) - CHECK_FIELDS:
        raise ValueError(f"traffic mix {name}: unknown check fields")
    if t["ramp_s"] < t["typical_op_s"]:
        raise ValueError(f"traffic mix {name}: ramp_s is shorter than typical_op_s")
    return t


def load_metric(name: str) -> dict:
    m = _load(os.path.join(BENCH_DIR, "metrics", f"{name}.json"), METRIC_FIELDS, "metric")
    if m.get("name") != name:
        raise ValueError(f"metric file {name}.json names itself {m.get('name')!r}")
    readers.validate(name, m["reader"])
    return m


class Cell:
    """One entry of ``workloads`` with everything it names, resolved."""

    def __init__(self, workload: str, man: dict | None = None, rehearse: bool = False):
        man = man or manifest()
        rows = [w for w in man["workloads"] if w["name"] == workload]
        if not rows:
            raise ValueError(f"workload {workload!r} is not in BENCHMARK.json "
                             f"({[w['name'] for w in man['workloads']]})")
        self.entry = rows[0]
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.config = load_config(self.entry["config"])
        self.traffic = load_traffic(self.entry["traffic"])
        if rehearse:
            self._shrink()
        if self.replaced and (max(self.replaced) >= self.config["drives"] or len(self.replaced)
                              > self.config["guarantees"]["drives_lost_tolerated"]):
            raise ValueError(f"cell {workload}: replaces drives the configuration does not "
                             "have, or more than it tolerates losing")
        if self.config["chips"] != self.chips:
            raise ValueError(f"cell {workload}: chips {self.chips} but configuration "
                             f"{self.config['name']} is laid out for {self.config['chips']}")
        self.end_to_end = [m for m in man["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        self.per_layer = []
        for m in man["per_layer"]:
            if workload in m.get("workloads", [workload]):
                self.per_layer.append({**load_metric(m["name"]), **m})
        k, m_ = self.config["data"], self.config["parity"]
        self.geometry = (k, m_, roofline.shard_bytes(self.config["block_bytes"], k))

    def _shrink(self) -> None:
        """The sandbox rehearsal's tiny sizes: the same files, control flow and
        check, on a CPU that encodes a few MiB a second."""
        t = self.traffic
        if "multipart" in t:
            # The least upload with more than one part: S3's 5 MiB, then a short last one.
            t["object_bytes"] = MIN_PART_BYTES + (3 << 19)
            t["multipart"] = {"part_bytes": MIN_PART_BYTES, "parts_in_flight":
                              min(2, int(t["multipart"]["parts_in_flight"]))}
        else:
            t["object_bytes"] = min(int(t["object_bytes"]), 2 << 20)
        t["clients"] = min(int(t["clients"]), 4)
        if t["keys"]["kind"] == "pool":
            t["keys"] = {"kind": "pool", "objects": 3 * t["clients"]}
        t["ramp_s"], t["typical_op_s"] = 1.0, 0.5
        t["tmpfs_bytes"] = int(t["tmpfs_bytes"]) // 100

    @property
    def nodes(self) -> int:
        """Node processes of the deployment: 1 runs in the run's process
        (harness/server.py), more as a cluster (harness/cluster.py)."""
        return int(self.config.get("nodes", 1))

    @property
    def clients(self) -> int:
        return int(self.traffic["clients"])

    @property
    def runners(self) -> int:
        """Clients that run the closed loop. A heal is one sequence at a time
        (`mc admin heal` runs one): client 0 heals, the others put the pool
        and hold its records, as the clients of a read-only cell do."""
        return 1 if "heal" in self.traffic else self.clients

    @property
    def replaced(self) -> list[int]:
        """0-based indices of the drives a heal traffic replaces before every op."""
        return [d - 1 for d in self.traffic.get("heal", {}).get("replace", [])]

    @property
    def populates(self) -> bool:
        return any("populate" in step for step in self.traffic.get("prepare", []))

    @property
    def lost_data(self) -> int:
        """Data shards the `prepare` step removes from every object, or 0."""
        return max((int(step["lose_shards"]["data"]) for step in self.traffic.get("prepare", [])
                    if "lose_shards" in step), default=0)

    def footprint_bytes(self) -> int:
        """Room the drives need under /dev/shm: the traffic file states it, for
        the wider geometry of its cells (the file says why); the rehearsal's
        tiny objects need a hundredth."""
        return int(self.traffic["tmpfs_bytes"])

    def client_spec(self, idx: int, seed: int, endpoint: str, bucket: str,
                    access: str, secret: str, region: str,
                    drive_dirs: list[str] | None = None) -> dict:
        t = self.traffic
        spec = {
            "client": idx, "clients": self.clients, "seed": seed, "endpoint": endpoint,
            "bucket": bucket, "access": access, "secret": secret, "region": region,
            "object_bytes": int(t["object_bytes"]), "mix": t["mix"], "keys": t["keys"],
            "timeout_s": float(t.get("op_timeout_s", 120.0)),
        }
        if "multipart" in t:
            spec["multipart"] = t["multipart"]
        if "heal" in t:
            spec["heal"] = {"replace_dirs": [drive_dirs[d] for d in self.replaced],
                            "poll_s": float(t["heal"]["poll_s"]), "objects": int(t["keys"]["objects"])}
        return spec

    @property
    def part_bytes(self) -> int | None:
        """The size of an MPUT's parts (the last takes the rest), or None."""
        return int(self.traffic["multipart"]["part_bytes"]) if "multipart" in self.traffic else None
