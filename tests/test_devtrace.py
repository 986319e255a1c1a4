"""control/devtrace.py: the arithmetic that puts a device's idle gaps down to
what the host was doing, on a synthetic event list (the loader is exercised
against a real jax.profiler trace in tests/test_observability.py)."""

import pytest

from minio_tpu.control import devtrace

MS = 1_000_000  # ns


def _ops(*spans):
    return [(f"op{i}", a * MS, (b - a) * MS) for i, (a, b) in enumerate(spans)]


def test_merge_unions_overlapping_and_drops_empty():
    assert devtrace.merge([(5, 9), (0, 3), (2, 4), (7, 7), (9, 12)]) == [(0, 4), (5, 12)]


def test_busy_and_gaps_clip_to_the_window():
    ops = _ops((0, 10), (5, 20), (50, 60), (90, 120))
    busy, gaps = devtrace.busy_and_gaps(ops, 10 * MS, 100 * MS)
    assert busy == (10 + 10 + 10) * MS
    assert gaps == [(20 * MS, 50 * MS), (60 * MS, 90 * MS)]
    busy, gaps = devtrace.busy_and_gaps(_ops((20, 30)), 0, 40 * MS)
    assert gaps == [(0, 20 * MS), (30 * MS, 40 * MS)]


def test_programs_by_name_strip_the_run_id():
    modules = [("jit_mtpu_encode_hash_k12m4(101)", 0, 4 * MS),
               ("jit_mtpu_encode_hash_k12m4(102)", 10 * MS, 4 * MS),
               ("jit_mtpu_reconstruct(7)", 20 * MS, 2 * MS),
               ("jit_outside(9)", 200 * MS, 2 * MS)]
    got = devtrace.programs(modules, 0, 100 * MS)
    assert set(got) == {"jit_mtpu_encode_hash_k12m4", "jit_mtpu_reconstruct"}
    assert got["jit_mtpu_encode_hash_k12m4"] == {"seconds": pytest.approx(0.008), "executions": 2}


def test_gap_attribution_by_overlap_and_uncovered_share():
    """A 100 ms gap: the batcher idles through 60 ms of it, a request waits
    for its window through 40 ms that overlap the idling by 20 ms, and an
    annotation outside the gap does not count. 20 ms has no name."""
    gap = (100 * MS, 200 * MS)
    host = [("codec/worker-idle", 90 * MS, 70 * MS),      # [90,160): 60 inside
            ("object/window-wait", 140 * MS, 40 * MS),    # [140,180): 40 inside
            ("api/body-fill", 150 * MS, 5 * MS),          # [150,155): nested
            ("object/encode", 300 * MS, 50 * MS)]         # outside
    got = devtrace.attribute(gap, host)
    assert got["seconds"] == pytest.approx(0.1)
    assert got["host"] == [["codec/worker-idle", pytest.approx(0.06)],
                           ["object/window-wait", pytest.approx(0.04)],
                           ["api/body-fill", pytest.approx(0.005)]]
    assert got["uncovered_share"] == pytest.approx(0.2)  # [180, 200) of 100
    assert devtrace.attribute(gap, [])["uncovered_share"] == 1.0


def test_summarize_ranks_the_longest_gaps_per_device():
    trace = {
        "devices": {"/device:TPU:0": {
            "ops": _ops((0, 10), (40, 50), (150, 160), (170, 200)),
            "modules": [("jit_mtpu_encode_hash_k12m4(1)", 0, 10 * MS)],
        }},
        "host": [("codec/worker-idle", 50 * MS, 100 * MS)],
    }
    got = devtrace.summarize(trace, top=2)
    dev = got["devices"]["/device:TPU:0"]
    assert got["host_annotations"] == 1
    assert dev["window_s"] == pytest.approx(0.2) and dev["busy_s"] == pytest.approx(0.06)
    assert dev["idle_share"] == pytest.approx(0.7)
    assert [g["seconds"] for g in dev["idle_gaps"]] == [pytest.approx(0.1), pytest.approx(0.03)]
    assert dev["idle_gaps"][0]["host"] == [["codec/worker-idle", pytest.approx(0.1)]]
    assert dev["idle_gaps"][0]["uncovered_share"] == pytest.approx(0.0)
    assert dev["idle_gaps"][1]["uncovered_share"] == 1.0
    assert dev["programs"]["jit_mtpu_encode_hash_k12m4"]["executions"] == 1


def test_host_events_are_the_programs_own_layers():
    assert "codec" in devtrace.LAYERS and "background" in devtrace.LAYERS
    assert "runtime" in devtrace.LAYERS and "storage" in devtrace.LAYERS
