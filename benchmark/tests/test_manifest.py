"""BENCHMARK.json and the files it names agree."""

import json
import os
import re

import pytest

from benchmark.harness import readers, run, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    return traffic.manifest()


def test_keys_and_limits(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert os.path.getsize(traffic.MANIFEST) <= 64 * 1024
    assert isinstance(man["run_seconds"], int) and 1 <= man["run_seconds"] <= 51
    assert man["paths"] == ["benchmark"]
    assert all(isinstance(w, str) and 1 <= len(w) <= 200 for w in man["command"])


def test_names_units_and_sources(man):
    names = []
    for m in man["end_to_end"] + man["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        names.append(m["name"])
    assert len(names) == len(set(names))
    for m in man["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in [m["name"] for m in man["end_to_end"]]
    for c in man["configs"] + man["workloads"]:
        assert NAME.match(c["name"])
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"] and "\t" not in c["why"]
    for c in man["configs"]:
        assert 1 <= len(c["source"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16


def test_every_name_resolves(man):
    configs = {c["name"]: c for c in man["configs"]}
    used = set()
    pairs = set()
    for w in man["workloads"]:
        cell = traffic.Cell(w["name"], man)
        assert w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
        used.add(w["config"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.samefile(os.path.join(traffic.ROOT, configs[w["config"]]["file"]),
                                os.path.join(traffic.BENCH_DIR, "configs", w["config"] + ".json"))
        e2e = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer, f"{w['name']} reports no per-layer metric"
        for m in cell.per_layer:
            assert m["moves"] in e2e, f"{m['name']} moves {m['moves']}, not reported in {w['name']}"
    assert used == set(configs)
    files = [c["file"] for c in man["configs"]]
    assert len(files) == len(set(files))


def test_every_data_file_on_disk_loads_and_read_only_cells_have_a_pool_to_check(man):
    """A traffic or configuration file no cell names yet (a cell left out for
    a later PR) still has to load with no unknown field."""
    for kind, load in (("traffic", traffic.load_traffic), ("configs", traffic.load_config)):
        for f in sorted(os.listdir(os.path.join(traffic.BENCH_DIR, kind))):
            assert f.endswith(".json")
            assert load(f[:-5])["name"] == f[:-5]
    for w in man["workloads"]:
        cell = traffic.Cell(w["name"], man)
        if not {"PUT", "MPUT"} & set(cell.traffic["mix"]):
            # nothing is PUT in the window: the degraded sample needs the populated pool
            assert cell.populates, w["name"]
        assert cell.lost_data <= cell.config["guarantees"]["drives_lost_tolerated"]
        footprint = (cell.traffic["keys"].get("objects", 0) * cell.traffic["object_bytes"]
                     * cell.config["drives"] / cell.config["data"])
        assert cell.footprint_bytes() >= footprint


def test_read_only_pair_differs_in_the_loss_alone():
    healthy = traffic.load_traffic("get64m-c8")
    degraded = traffic.load_traffic("degraded-get64m-c8")
    assert degraded["prepare"] == healthy["prepare"] + [{"lose_shards": {"data": 4}}]
    # typical_op_s is each file's own measured op (it spreads the clients' starts)
    for key in ("loop", "clients", "object_bytes", "mix", "keys", "ramp_s",
                "trace_seconds", "check", "op_timeout_s", "tmpfs_bytes"):
        assert healthy[key] == degraded[key], key


def test_config_file_states_what_the_manifest_says(man):
    for c in man["configs"]:
        cfg = traffic.load_config(c["name"])
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["guarantees"]["drives_lost_tolerated"] == cfg["parity"]


def test_metric_files_match_manifest_entries(man):
    cells = {w["name"] for w in man["workloads"]}
    layers = set()
    for entry in man["per_layer"]:
        m = traffic.load_metric(entry["name"])
        for key in ("unit", "better", "layer", "moves", "source"):
            assert m[key] == entry[key], (entry["name"], key)
        assert m.get("workloads") == entry.get("workloads")
        assert set(entry.get("workloads", [])) <= cells
        assert set(entry) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        readers.validate(entry["name"], m["reader"])
        layers.add(entry["layer"])
        if entry["name"].endswith("_roofline") or "mfu" in entry["name"]:
            assert entry["unit"] == "%"
    on_disk = {f[:-5] for f in os.listdir(os.path.join(traffic.BENCH_DIR, "metrics"))}
    assert on_disk == {e["name"] for e in man["per_layer"]}
    assert all("\n" not in layer and len(layer) <= 200 for layer in layers)


CELLS_AND_CHIPS = {  # the manifest as it stands: the cells in order, each one's chips
    "put64m-c8": 1, "put64m-c8-ec4p4": 1, "mixed10m-c20": 1, "degraded-get64m-c8": 1,
    "put64k-c32-ec2p2": 1, "mpput64m-p8m-c4": 1, "put64m-c8-chip4": 4, "heal64m-d3": 1,
    "put64m-c8-n4": 1,
}


def test_the_cells_and_the_chips_each_asks_for(man):
    assert {w["name"]: w["chips"] for w in man["workloads"]} == CELLS_AND_CHIPS
    assert [w["name"] for w in man["workloads"]] == list(CELLS_AND_CHIPS)
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert four <= max(1, len(man["workloads"]) // 2)


def test_the_multipart_cell_its_metrics_and_the_facts_they_divide_by(man):
    """The multipart cell on one chip; `throughput` in six cells (with the
    four-node cluster and the four-chip PUT); the PUT-path metrics the multipart path feeds take
    the cell, five new ones read it alone; every ledger reader's `per` is a fact
    the harness counts."""
    cell_name = "mpput64m-p8m-c4"
    assert CELLS_AND_CHIPS[cell_name] == 1
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert e2e["throughput"]["workloads"] == ["put64m-c8", "put64m-c8-ec4p4",
                                              "degraded-get64m-c8", cell_name,
                                              "put64m-c8-n4", "put64m-c8-chip4"]
    cell = traffic.Cell(cell_name, man)
    assert (cell.entry["config"], cell.entry["traffic"]) == ("ec12p4-d16-chip1", cell_name)
    assert [m["name"] for m in cell.end_to_end] == ["throughput", "setup_s"]
    mine = {m["name"]: m for m in cell.per_layer}
    put_path = {m["name"] for m in traffic.Cell("put64m-c8", man).per_layer}
    new = {"mp_front_ms_per_upload", "mp_part_ms_per_part", "mp_commit_ms_per_upload",
           "mp_complete_ms_per_upload", "mp_drive_calls_per_upload"}
    assert set(mine) == put_path | new
    assert {"codec_roofline", "device_idle", "compiles_in_window", "blocks_per_batch"} <= set(mine)
    for name in new:
        assert mine[name]["workloads"] == [cell_name] and mine[name]["moves"] == "throughput"
        assert mine[name]["reader"]["kind"] == "ledger"
    assert mine["mp_drive_calls_per_upload"]["reader"]["field"] == "count"
    facts = run.op_facts([], 0.0, 1.0, [])
    assert {"mputs_ended", "parts_ended", "puts_ended", "put_MiB"} <= set(facts)
    for w in man["workloads"]:
        for m in traffic.Cell(w["name"], man).per_layer:
            per = m["reader"].get("per")
            assert per is None or per in facts, (m["name"], per)


HEAL_METRICS = {  # PR 37: what each reads, by reader kind
    "heal_verify_ms_per_MiB": "ledger", "chunks_per_verify_batch": "counter",
    "recon_roundtrip_ms.heal": "counter", "blocks_per_recon_batch.heal": "counter",
    "recon_host_ms_per_MiB.heal": "ledger", "heal_drive_ms_per_MiB": "ledger",
    "heal_dev_ms_per_GiB": "trace", "heal_roofline": "trace", "device_idle.heal": "trace",
    "compiles_in_window.heal": "counter",
}


def test_the_heal_cell_its_metrics_and_the_facts_they_divide_by(man):
    """PR 37: `heal64m-d3` on one chip; `heal_rate` lists it alone and it reports
    `heal_rate` and `setup_s`; the heal metrics read it alone and move
    `heal_rate`; `warmup_s` takes it; every `per` is a fact `op_facts` counts."""
    cell_name = "heal64m-d3"
    cell = traffic.Cell(cell_name, man)
    assert (cell.entry["config"], cell.entry["traffic"], cell.chips) == (
        "ec12p4-d16-chip1", cell_name, 1)
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert e2e["heal_rate"]["workloads"] == [cell_name]
    assert (e2e["heal_rate"]["unit"], e2e["heal_rate"]["better"],
            e2e["heal_rate"]["source"]) == ("MiB/s", "higher", "host_clock")
    assert [m["name"] for m in cell.end_to_end] == ["heal_rate", "setup_s"]
    mine = {m["name"]: m for m in cell.per_layer}
    assert set(mine) == set(HEAL_METRICS) | {"warmup_s"}
    facts = run.op_facts([], 0.0, 1.0, [])
    assert {"heals_ended", "heal_MiB"} <= set(facts)
    for name, kind in HEAL_METRICS.items():
        m = mine[name]
        assert m["workloads"] == [cell_name] and m["moves"] == "heal_rate", name
        assert m["reader"]["kind"] == kind
        per = m["reader"].get("per")
        assert per is None or per in facts
    assert mine["heal_roofline"]["unit"] == "%"
    t = cell.traffic
    assert t["mix"] == {"HEAL": 100} and t["heal"]["replace"] == [1, 2, 3]
    assert t["heal"]["poll_s"] == 0.05 and t["keys"] == {"kind": "pool", "objects": 32}
    assert t["ramp_s"] >= 1.5 * t["typical_op_s"] and cell.runners == 1
    # degraded-get64m-c8's pool: the same clients, keys, bodies and sizes
    pool = traffic.load_traffic("degraded-get64m-c8")
    for key in ("clients", "object_bytes", "keys", "tmpfs_bytes"):
        assert t[key] == pool[key], key


CLUSTER_METRICS = {  # what each reads, by reader kind
    "device_idle.n4": "trace", "codec_dev_ms_per_GiB.n4": "trace", "codec_roofline.n4": "trace",
    "blocks_per_batch.n4": "counter", "batcher_idle.n4": "counter",
    "codec_host_ms_per_MiB.n4": "ledger", "put_host_ms_per_MiB.n4": "ledger",
    "rpc_ms_per_put.n4": "ledger", "rpc_calls_per_put.n4": "ledger",
    "lock_rpc_ms_per_put.n4": "ledger",
    "compiles_in_window.n4": "counter", "warmup_s.n4": "process",
}


def test_the_cluster_cell_its_configuration_and_metrics(man):
    """`put64m-c8-n4`, four nodes of four drives, the first of them on the
    host's one chip, under `put64m-c8`'s traffic file unchanged; `throughput`
    and `setup_s`; the
    `.n4` metrics read it alone; the internode ones read the `rpc-peer` rows
    by prefix, the lock client's a subset of them."""
    cell_name = "put64m-c8-n4"
    cell = traffic.Cell(cell_name, man)
    assert (cell.entry["config"], cell.entry["traffic"], cell.chips, cell.nodes) == (
        "ec12p4-n4d4-chip1", "put64m-c8", 1, 4)
    cfg = cell.config
    assert (cfg["drives"], cfg["data"], cfg["parity"], cfg["block_bytes"], cfg["chips"]) == (
        16, 12, 4, 1 << 20, 1)
    assert cfg["env"] == {"MINIO_TPU_CODEC": "device"} and "MTPU_MESH_SHAPE" in cfg["assumed"]
    assert {"node_lost_tolerated", "write_quorum", "namespace_lock"} <= set(cfg["guarantees"])
    assert set(cfg["reduced"]) == {"hosts", "network", "drive_medium", "concurrency", "chips"}
    entry = [c for c in man["configs"] if c["name"] == cfg["name"]][0]
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert cell.traffic == traffic.load_traffic("put64m-c8")
    assert [m["name"] for m in cell.end_to_end] == ["throughput", "setup_s"]
    mine = {m["name"]: m for m in cell.per_layer}
    assert set(mine) == set(CLUSTER_METRICS)
    facts = run.op_facts([], 0.0, 1.0, [])
    for name, kind in CLUSTER_METRICS.items():
        m = mine[name]
        assert m["workloads"] == [cell_name] and m["reader"]["kind"] == kind, name
        assert m["moves"] == ("setup_s" if name == "warmup_s.n4" else "throughput")
        per = m["reader"].get("per")
        assert per is None or per in facts
    rpc = mine["rpc_ms_per_put.n4"]["reader"]["prefixes"]
    assert rpc == ["rpc-peer/"]
    assert all(p.startswith(rpc[0]) for p in mine["lock_rpc_ms_per_put.n4"]["reader"]["prefixes"])
    assert mine["codec_roofline.n4"]["unit"] == "%"
    assert mine["codec_roofline.n4"]["reader"]["value"] == "codec_roofline"


@pytest.mark.parametrize("change, loads", [
    ({}, True), ({"chips": 4}, True),  # one chip on the first node, or one a node
    ({"nodes": 3}, False), ({"chips": 2}, False), ({"nodes": 0}, False)])
def test_a_cluster_configuration_holds_one_chip_or_one_a_node(tmp_path, monkeypatch, change,
                                                              loads):
    cfg = traffic.load_config("ec12p4-n4d4-chip1")
    monkeypatch.setattr(traffic, "BENCH_DIR", str(tmp_path))
    os.makedirs(tmp_path / "configs")
    with open(tmp_path / "configs" / "ec12p4-n4d4-chip1.json", "w") as f:
        json.dump({**cfg, **change}, f)
    if loads:
        assert traffic.load_config("ec12p4-n4d4-chip1")["chips"] == change.get("chips", 1)
        return
    with pytest.raises(ValueError, match="nodes must divide drives"):
        traffic.load_config("ec12p4-n4d4-chip1")


def test_unknown_fields_are_errors(tmp_path, monkeypatch):
    monkeypatch.setattr(traffic, "BENCH_DIR", str(tmp_path))
    os.makedirs(tmp_path / "traffic")
    good = json.load(open(os.path.join(os.path.dirname(traffic.HERE), "traffic", "put64m-c8.json")))
    with open(tmp_path / "traffic" / "put64m-c8.json", "w") as f:
        json.dump({**good, "cleints": 9}, f)
    with pytest.raises(ValueError, match="unknown fields"):
        traffic.load_traffic("put64m-c8")
    with pytest.raises(ValueError, match="unknown reader"):
        readers.validate("x", {"kind": "guess"})
    with pytest.raises(ValueError, match="unknown reader fields"):
        readers.validate("x", {"kind": "trace", "value": "idle_share", "fudge": 2})


def test_files_under_paths_are_named_from_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(traffic.BENCH_DIR):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), traffic.ROOT)
            assert ok.match(rel), rel
