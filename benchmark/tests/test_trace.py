"""The trace reduction, on a small synthetic event list."""

import pytest

from benchmark.harness import trace

S = 87382


def ev(name, start, dur, dims=()):
    return (name, start, dur, dims)


OPS = [
    ev("fusion.1", 100, 50, (64, 12, S)),   # codec module A
    ev("copy.2", 140, 30),                  # overlaps fusion.1 by 10
    ev("fusion.9", 400, 100),               # a module that is not the codec's
    ev("fusion.1", 700, 100, (64, 12, S)),  # codec module B, cut by the window's end
]
MODULES = [ev("jit_encode", 100, 70), ev("jit_other", 400, 100), ev("jit_encode", 700, 100)]


def test_merge_unions_overlaps():
    assert trace.merge([(0, 10), (5, 20), (30, 40), (40, 45), (50, 50)]) == [(0, 20), (30, 45)]


def test_busy_is_the_union_and_gaps_are_named_by_their_neighbours():
    busy, gaps = trace.busy_and_gaps(OPS, 0, 750)
    # 100..170 (two ops overlapping), 400..500, 700..750 (clipped)
    assert busy == 70 + 100 + 50
    assert sorted(gaps, reverse=True) == [
        (230, "copy.2--fusion.9"), (200, "fusion.9--fusion.1"), (100, "window-start--fusion.1")]


def test_idle_share_and_codec_time():
    out = trace.reduce({"/device:TPU:0": {"ops": OPS, "modules": MODULES}}, S, (0, 750))
    assert out["span_s"] == pytest.approx(750e-9)
    assert out["busy_s"] == pytest.approx(220e-9)
    assert 1 - out["busy_s"] / out["span_s"] == pytest.approx(1 - 220 / 750)
    # Codec modules are the two with an op that carries the shard length; the
    # second is cut at the window's end: 70 + 50.
    assert out["codec_s"] == pytest.approx(120e-9)
    assert out["codec_runs"] == 2
    assert out["idle_gaps"][0] == ["copy.2--fusion.9", pytest.approx(230e-9)]
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(100e-9)]


def test_label_keeps_instruction_and_result_shape():
    hlo = ("%fusion.173 = u8[64,87382,4]{1,2,0:T(4,128)(4,1)S(1)} fusion(u8[64,12,87382]{2,1,0} "
           "%p), kind=kLoop")
    assert trace.label(hlo) == "fusion.173 u8[64,87382,4]"
    tup = "%copy-start.21 = (s8[96,32]{0,1}, s8[96,32]{0,1}, u32[]{:S(2)}) copy-start(...)"
    assert trace.label(tup) == "copy-start.21 s8[96,32]"
    assert trace.label("window-start") == "window-start"


def test_two_devices_average():
    one = {"ops": [ev("a", 0, 100, (S,))], "modules": [ev("m", 0, 100)]}
    two = {"ops": [ev("a", 0, 50, (S,))], "modules": [ev("m", 0, 50)]}
    out = trace.reduce({"d0": one, "d1": two}, S, (0, 100))
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx(75e-9)
    assert out["codec_s"] == pytest.approx(75e-9)


def test_nothing_to_read_returns_nothing():
    assert trace.reduce({}, S) == {}
    assert trace.reduce({"d": {"ops": [], "modules": []}}, S) == {}
    out = trace.reduce({"d": {"ops": [ev("x", 0, 10)], "modules": [ev("m", 0, 10)]}}, S)
    assert out["codec_s"] == 0 and out["codec_runs"] == 0


def test_no_device_op_on_a_chip_the_process_holds_is_an_idle_chip_not_a_missing_reading():
    """A healthy GET verifies on the host: the trace of such a slice has no
    device plane at all (my chip run, PR 28), and the caller says a chip was held."""
    out = trace.reduce({}, S, idle_chips=1, wall_s=5.0)
    assert out["busy_s"] == 0.0 and out["span_s"] == 5.0 and out["devices"] == 1
    assert out["device_ops"] == [] and out["idle_gaps"] == [["window-start--window-end", 5.0]]
    assert out["codec_s"] == 0 and out["codec_runs"] == 0
    # the hint changes nothing for a slice that has ops
    one = {"d": {"ops": OPS, "modules": MODULES}}
    assert trace.reduce(one, S, (0, 750), idle_chips=1, wall_s=5.0) == trace.reduce(one, S, (0, 750))
