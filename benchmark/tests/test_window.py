"""The prorated-rate arithmetic, on synthetic op lists."""

import pytest

from benchmark.harness import run, window

MIB = 1 << 20


def op(kind, start, end, nbytes=0, ok=True, key="c000/k0000"):
    return [kind, key, start, end, nbytes, ok]


def test_ops_wholly_inside_count_whole():
    ops = [op("PUT", 1.0, 2.0, 64 * MIB), op("PUT", 3.0, 5.0, 64 * MIB)]
    assert window.prorated_rate(ops, 0.0, 10.0, by_bytes=True) == pytest.approx(12.8 * MIB)
    assert window.prorated_rate(ops, 0.0, 10.0, by_bytes=False) == pytest.approx(0.2)


def test_edges_are_prorated():
    # Began 1 s before the window and ended 1 s into it: half its bytes count.
    # Began 2 s before the end and ended 2 s after: half again.
    ops = [op("PUT", -1.0, 1.0, 64 * MIB), op("PUT", 8.0, 12.0, 64 * MIB)]
    assert window.prorated_rate(ops, 0.0, 10.0, by_bytes=True) == pytest.approx(6.4 * MIB)
    assert window.prorated_rate(ops, 0.0, 10.0, by_bytes=False) == pytest.approx(0.1)


def test_stalled_window_reads_low_not_empty():
    # The server stalled: one op spans the whole window and more. No op ended
    # inside, yet the window's share of the work is counted, and it is small.
    ops = [op("PUT", -5.0, 15.0, 64 * MIB)]
    assert window.prorated_rate(ops, 0.0, 10.0, by_bytes=True) == pytest.approx(3.2 * MIB)
    e2e = window.end_to_end(ops, 0.0, 10.0)
    assert e2e["throughput"] == pytest.approx(3.2)
    assert "lat_p50" not in e2e  # no op ended inside: no latency is made up


def test_failed_ops_carry_no_work_but_count_in_latency():
    ops = [op("GET", 1.0, 2.0, 10 * MIB, ok=False), op("GET", 2.0, 3.0, 10 * MIB)]
    assert window.prorated_rate(ops, 0.0, 10.0, by_bytes=False) == pytest.approx(0.1)
    assert len(window.ended_inside(ops, 0.0, 10.0)) == 2


def test_ops_outside_count_nothing_and_instant_ops_are_in_or_out():
    ops = [op("STAT", -3.0, -1.0), op("STAT", 11.0, 12.0), op("STAT", 4.0, 4.0),
           op("STAT", 10.0, 10.0)]
    assert window.prorated_rate(ops, 0.0, 10.0, by_bytes=False) == pytest.approx(0.1)


def test_rate_is_work_over_the_whole_window_not_a_median_of_pieces():
    # 9 s of steady work then a 1 s stall: a median of per-second chunks would
    # hide the stall, the rate over the whole window shows it.
    ops = [op("PUT", float(i), float(i + 1), MIB) for i in range(9)]
    assert window.prorated_rate(ops, 0.0, 10.0, by_bytes=True) == pytest.approx(0.9 * MIB)


def test_percentiles_interpolate_like_numpy():
    values = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert window.percentile(values, 50) == 30.0
    assert window.percentile(values, 95) == pytest.approx(48.0)
    assert window.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        window.percentile([], 50)


def test_latencies_are_of_ops_that_ended_inside():
    ops = [op("GET", -1.0, 0.5), op("GET", 1.0, 1.1), op("GET", 9.5, 10.5)]
    e2e = window.end_to_end(ops, 0.0, 10.0)
    assert e2e["lat_p50"] == pytest.approx((1500.0 + 100.0) / 2)


def test_counts_by_kind():
    ops = [op("PUT", -1.0, 1.0), op("PUT", 2.0, 3.0, ok=False), op("GET", 11.0, 12.0)]
    c = window.counts_by_kind(ops, 0.0, 10.0)
    assert c["PUT"] == {"touching": 2, "ended_inside": 2, "failed": 1}
    assert c["GET"] == {"touching": 0, "ended_inside": 0, "failed": 0}


def test_an_mput_that_straddles_the_edge_is_prorated_like_any_op_and_counts_as_a_put():
    # One upload wholly inside, one that began 1 s before the end and ended 1 s after it
    # (half its bytes count), one that failed; parts are counted by when each answered.
    ops = [op("MPUT", 2.0, 3.0, 64 * MIB), op("MPUT", 9.0, 11.0, 64 * MIB),
           op("MPUT", 4.0, 5.0, 0, ok=False), op("PUT", 5.0, 6.0, 64 * MIB)]
    e2e = window.end_to_end(ops, 0.0, 10.0)
    assert e2e["throughput"] == pytest.approx((64 + 32 + 64) / 10.0)
    assert e2e["ops_rate"] == pytest.approx(0.25)
    facts = run.op_facts(ops, 0.0, 10.0, part_ends=[2.5, 2.9, 9.9, 10.0, 10.5, -0.1])
    assert facts["mputs_ended"] == 2 and facts["puts_ended"] == 3  # the failed one ended inside too
    assert facts["ops_ended"] == 3 and facts["parts_ended"] == 3
    assert facts["put_MiB"] == pytest.approx(64 + 32 + 64) and facts["get_MiB"] == 0
    assert window.counts_by_kind(ops, 0.0, 10.0)["MPUT"] == {
        "touching": 3, "ended_inside": 2, "failed": 1}
