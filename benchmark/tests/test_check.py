"""The arithmetic of the output check, and the shard-losing helper, without a server."""

import os

import pytest

from benchmark.harness import check, server

MIB = 1 << 20


def op(kind, key, start, end, nbytes=0, ok=True):
    return [kind, key, start, end, nbytes, ok]


def test_pool_sample_is_drawn_from_the_seed_and_holds_the_size_asked_for():
    keys = [f"c{i % 8:03d}/o{i:05d}" for i in range(32)]
    a, b = check.pool_sample(keys, 7, 4), check.pool_sample(list(reversed(keys)), 7, 4)
    assert a == b and len(a) == 4 and set(a) <= set(keys)
    assert check.pool_sample(keys, 8, 4) != a
    assert sorted(check.pool_sample(keys[:3], 7, 4)) == sorted(keys[:3])
    assert check.pool_sample([], 7, 4) == []


def test_a_run_wrote_nothing_only_if_no_put_or_delete_was_sent():
    gets = [op("GET", "c000/o00000", 1, 2, 64 * MIB), op("STAT", "c000/o00000", 2, 3)]
    assert check.wrote_nothing(gets) and check.wrote_nothing([])
    assert not check.wrote_nothing(gets + [op("PUT", "c000/o00008", 3, 4, 64 * MIB, ok=False)])
    assert not check.wrote_nothing(gets + [op("DELETE", "c000/o00008", 3, 4)])


def test_degraded_blocks_got_counts_acknowledged_gets_of_degraded_keys_until_rewritten():
    degraded = {"c000/o00000", "c001/o00001"}
    ops = [
        op("GET", "c000/o00000", 1, 2, 64 * MIB),
        op("GET", "c000/o00000", 2, 3, 0, ok=False),       # failed: not acknowledged
        op("GET", "c001/o00001", 1, 2, 2 * MIB + 5),        # the tail is no full block
        op("GET", "c002/o00002", 1, 2, 64 * MIB),           # never degraded
        op("STAT", "c000/o00000", 3, 4),
        op("PUT", "c001/o00001", 2, 3, 2 * MIB, ok=False),  # may have landed: whole again
        op("GET", "c001/o00001", 3, 4, 2 * MIB),
    ]
    assert check.degraded_blocks_got(ops, degraded, MIB) == 64 + 2
    assert check.degraded_blocks_got(ops, set(), MIB) == 0
    assert check.full_blocks_put(ops, MIB) == 0  # the one PUT was not acknowledged


def test_an_mput_is_a_write_and_holds_the_full_blocks_of_its_parts():
    ops = [
        op("MPUT", "c000/k0000", 1, 2, 64 * MIB),             # 8 parts of 8 MiB: 64 blocks
        op("MPUT", "c000/k0001", 2, 3, 0, ok=False),          # no Complete: nothing is due
        op("MPUT", "c000/k0002", 3, 9, 64 * MIB),
        op("PUT", "c000/k0003", 4, 5, 3 * MIB + 7),
    ]
    assert check.full_blocks_put(ops, MIB, 8 * MIB) == 64 + 64 + 3
    # parts of 5.5 MiB, the last 2.25: each part is a stream of its own, tails are no full blocks
    odd = [op("MPUT", "c000/k0000", 1, 2, 13 * MIB + (MIB >> 2))]
    assert check.full_blocks_put(odd, MIB, 5 * MIB + (MIB >> 1)) == 5 + 5 + 2
    assert not check.wrote_nothing(ops[1:2])
    assert check.readback_sample(ops, 3, 12)[0] == "c000/k0002"  # the newest acknowledged write
    assert sorted(check.readback_sample(ops, 3, 12)) == ["c000/k0000", "c000/k0002", "c000/k0003"]
    # the degraded sample: uploads whose Complete answered inside the window
    assert sorted(check.degraded_sample(ops, 0.0, 8.0, 3, 4)) == ["c000/k0000", "c000/k0003"]
    assert check.degraded_blocks_got(
        [op("GET", "c000/k0000", 1, 2, 64 * MIB), op("MPUT", "c000/k0000", 2, 3, 64 * MIB),
         op("GET", "c000/k0000", 3, 4, 64 * MIB)], {"c000/k0000"}, MIB) == 64


def test_every_limit_is_zero_and_a_number_not_read_fails():
    good = dict.fromkeys(check.LIMITS, 0)
    assert list(check.LIMITS) == ["ops_failed", "readback_mismatch", "degraded_mismatch",
                                  "degraded_short", "device_blocks_missing"]
    assert check.decide(good)[0] is True
    assert check.decide({**good, "device_blocks_missing": 64})[0] is False
    assert check.decide({k: v for k, v in good.items() if k != "degraded_short"})[0] is False


@pytest.fixture
def stored(tmp_path):
    """A 12+4 deployment's directories with one object placed on every drive."""
    dep = server.Deployment({"drives": 16, "parity": 4})
    dep.drive_dirs = [str(tmp_path / f"d{i + 1}") for i in range(16)]
    key = "c003/o00011"
    for _, d in dep.shard_dirs(key):
        os.makedirs(d)
    return dep, key


def test_lose_shards_called_twice_removes_nothing_more(stored):
    dep, key = stored
    first = dep.lose_shards(key, 4)
    rows = {d: row for row, d in dep.shard_dirs(key)}
    assert len(first) == 4 and all(rows[d] < 12 for d in first)
    assert [d for _, d in dep.shard_dirs(key) if not os.path.isdir(d)] == first
    assert dep.lose_shards(key, 4) == first
    assert sum(os.path.isdir(d) for _, d in dep.shard_dirs(key)) == 12
    with pytest.raises(FileNotFoundError):
        dep.lose_shards(key, 2)  # more are gone than asked for: nothing brings them back


def test_lose_shards_still_refuses_an_object_that_is_not_where_the_placement_says(stored):
    dep, key = stored
    victims = [d for row, d in dep.shard_dirs(key) if row < 12][:4]
    other = next(d for _, d in dep.shard_dirs(key) if d not in victims)
    os.rmdir(other)
    with pytest.raises(FileNotFoundError):
        dep.lose_shards(key, 4)
    assert all(os.path.isdir(d) for d in victims)  # refused before anything was removed
