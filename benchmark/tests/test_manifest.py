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


def test_the_multipart_cell_its_metrics_and_the_facts_they_divide_by(man):
    """PR 34: six cells, all on one chip; `throughput` in four; the PUT-path
    metrics the multipart path feeds take the cell, five new ones read it alone;
    every ledger reader's `per` is a fact the harness counts."""
    cell_name = "mpput64m-p8m-c4"
    assert [w["name"] for w in man["workloads"]][-1] == cell_name
    assert len(man["workloads"]) == 6 and all(w["chips"] == 1 for w in man["workloads"])
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert e2e["throughput"]["workloads"] == ["put64m-c8", "put64m-c8-ec4p4",
                                              "degraded-get64m-c8", cell_name]
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
