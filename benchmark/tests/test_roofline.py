"""The roofline's bytes for the two geometries of the first cells."""

import pytest

from benchmark.harness import readers, roofline


def test_shard_lengths():
    assert roofline.shard_bytes(1 << 20, 12) == 87382
    assert roofline.shard_bytes(1 << 20, 4) == 262144


def test_block_bytes():
    # 12 x 87382 read; 4 x 87382 parity + 16 digests of 32 B written
    assert roofline.block_bytes_moved(12, 4, 87382) == 1048584 + 349528 + 512 == 1398624
    # 4 x 262144 read; 4 x 262144 parity + 8 digests written
    assert roofline.block_bytes_moved(4, 4, 262144) == 1048576 + 1048576 + 256 == 2097408


def test_least_seconds_is_hbm_bound_on_v5e():
    t, bound = roofline.least_seconds(1024, 12, 4, 87382, "TPU v5 lite")
    assert bound == "hbm"
    assert t == pytest.approx(1024 * 1398624 / 819e9)
    t44, bound44 = roofline.least_seconds(1024, 4, 4, 262144, "TPU v5 lite")
    assert bound44 == "hbm" and t44 == pytest.approx(1024 * 2097408 / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        roofline.peaks("cpu")


def test_trace_readers_never_return_zero_for_a_share():
    pair = ({"codec": {"blocks_encoded": 0}}, {"codec": {"blocks_encoded": 1024}})
    src = {"traced": pair, "trace": {"span_s": 3.0, "busy_s": 0.3, "codec_s": 0.15},
           "geometry": (12, 4, 87382), "block_bytes": 1 << 20, "device_kind": "TPU v5 lite"}
    assert readers.read_trace({"value": "idle_share"}, src) == pytest.approx(90.0)
    assert readers.read_trace({"value": "codec_ms_per_GiB"}, src) == pytest.approx(150.0)
    assert readers.read_trace({"value": "codec_roofline"}, src) == pytest.approx(
        100 * (1024 * 1398624 / 819e9) / 0.15)
    src["trace"]["codec_s"] = 0.0  # no codec program found: nothing, not 0 %
    assert readers.read_trace({"value": "codec_roofline"}, src) is None
    assert readers.read_trace({"value": "idle_share"}, {"trace": {}}) is None
