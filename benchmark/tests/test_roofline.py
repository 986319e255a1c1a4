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


def test_recon_bytes():
    # 12 x 87382 survivors read, 4 x 87382 rebuilt rows written: 16 x 87382
    assert roofline.recon_bytes_moved(12, 4, 87382, False) == 16 * 87382 == 1398112
    # heal asks for the rebuilt rows' digests, a GET does not
    assert roofline.recon_bytes_moved(12, 4, 87382, True) == 1398112 + 4 * 32
    assert roofline.recon_bytes_moved(4, 2, 262144, False) == 6 * 262144
    t, bound = roofline.recon_least_seconds(1024, 12, 4, 87382, "TPU v5 lite")
    assert bound == "hbm" and t == pytest.approx(1024 * 1398112 / 819e9)


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


def test_recon_readers_read_the_blocks_reconstructed_and_the_rows_the_traffic_lost():
    pair = ({"codec": {"blocks_reconstructed": 64, "blocks_encoded": 0}},
            {"codec": {"blocks_reconstructed": 64 + 1024, "blocks_encoded": 0}})
    src = {"traced": pair, "trace": {"span_s": 5.0, "busy_s": 0.1, "codec_s": 0.05},
           "geometry": (12, 4, 87382), "block_bytes": 1 << 20, "device_kind": "TPU v5 lite",
           "lost_data": 4}
    assert readers.read_trace({"value": "recon_ms_per_GiB"}, src) == pytest.approx(50.0)
    assert readers.read_trace({"value": "recon_roofline"}, src) == pytest.approx(
        100 * (1024 * 1398112 / 819e9) / 0.05)
    assert readers.read_trace({"value": "codec_roofline"}, src) is None  # nothing was encoded
    src["lost_data"] = 0  # the traffic file lost nothing: no count of rebuilt rows, no share
    assert readers.read_trace({"value": "recon_roofline"}, src) is None
    with pytest.raises(ValueError):
        readers.read_trace({"value": "guess"}, src)


def test_an_idle_slice_reads_100_percent_idle():
    src = {"trace": {"span_s": 5.0, "busy_s": 0.0, "codec_s": 0.0}}
    assert readers.read_trace({"value": "idle_share"}, src) == 100.0


def test_counter_reader_finds_the_s3_fronts_counters():
    pair = ({"codec": {}, "front": {"get_stream_hops": 10, "get_stream_chunks": 100}},
            {"codec": {}, "front": {"get_stream_hops": 14, "get_stream_chunks": 868}})
    reader = {"numerator": ["get_stream_chunks"], "denominator": ["get_stream_hops"]}
    assert readers.read_counter(reader, {"window": pair}) == pytest.approx(192.0)
    old = ({"codec": {}}, {"codec": {}})  # a snapshot without the group reads nothing
    assert readers.read_counter(reader, {"window": old}) is None
