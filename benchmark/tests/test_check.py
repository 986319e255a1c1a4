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


def test_a_heal_cell_is_held_to_five_numbers_more_and_its_blocks():
    heals = [op("HEAL", "bench", 1, 6, 2048 * MIB), op("HEAL", "bench", 6, 11, 0, ok=False),
             op("HEAL", "bench", 11, 16, 2048 * MIB)]
    assert check.healed_blocks(heals, MIB) == 2 * 2048
    assert check.healed_blocks(heals + [op("GET", "c000/o00000", 1, 2, 64 * MIB)], MIB) == 4096
    assert check.limits(False) == check.LIMITS
    held = check.limits(True)
    assert set(held) - set(check.LIMITS) == set(check.HEAL_LIMITS) and all(
        v == 0 for v in held.values())
    numbers = {name: 0 for name in held}
    assert check.decide(numbers, held)[0] is True
    assert check.decide({**numbers, "reference_mismatch": 1}, held)[0] is False
    missing = dict(numbers)
    missing.pop("host_fallback_digest_chunks")
    assert check.decide(missing, held)[0] is False  # a number not read fails
    assert check.decide(missing)[0] is True  # a cell that heals nothing is not held to it


def test_lose_shards_passes_over_the_spare_drives(tmp_path):
    """The heal cell's degraded sample loses its data shards outside the
    replaced drives, so the healed rows are among the survivors."""
    dep = server.Deployment.__new__(server.Deployment)
    dep.drives, dep.parity = 16, 4
    dep.drive_dirs = [str(tmp_path / f"d{i + 1}") for i in range(16)]
    key = "c000/o00003"
    for row, d in dep.shard_dirs(key):
        os.makedirs(d)
    victims = dep.lose_shards(key, 4, spare=(0, 1, 2))
    rows = dict((d, row) for row, d in dep.shard_dirs(key))
    assert len(victims) == 4 and all(rows[v] < 12 for v in victims)
    assert not set(victims) & set(dep.drive_dirs[i] + "/bench/" + key for i in range(3))
    assert all(os.path.isdir(dep.drive_dirs[i] + "/bench/" + key) for i in range(3))
    assert dep.lose_shards(key, 4, spare=(0, 1, 2)) == victims  # nothing more the second time


def test_a_cluster_is_held_to_its_shard_files_and_samples_them_from_the_window():
    """A cell of several nodes is held to `reference_mismatch` besides
    the five; its sample is of keys whose last acknowledged write was a PUT
    that ended inside the window (an MPUT's parts lay out otherwise)."""
    held = check.limits(False, cluster=True)
    assert set(held) - set(check.LIMITS) == {"reference_mismatch"}
    assert check.decide(dict.fromkeys(held, 0), held)[0] is True
    assert check.decide({**dict.fromkeys(check.LIMITS, 0)}, held)[0] is False  # not read: fails
    ops = [op("PUT", "c000/k0000", 1, 2, 64 * MIB), op("PUT", "c001/k0000", 2, 3, 64 * MIB),
           op("MPUT", "c002/k0000", 2, 4, 64 * MIB), op("PUT", "c003/k0000", 9, 12, 64 * MIB),
           op("PUT", "c004/k0000", 3, 5, 64 * MIB, ok=False)]
    sample = check.reference_sample(ops, 0.0, 10.0, 7, 8)
    assert sample[0] == "c001/k0000"  # the newest PUT that ended inside
    assert sorted(sample) == ["c000/k0000", "c001/k0000"]
    assert check.reference_sample(ops, 0.0, 10.0, 7, 1) == ["c001/k0000"]
    assert check.reference_sample([], 0.0, 10.0, 7, 2) == []


def test_the_newest_shard_file_is_the_last_put_s_where_the_sweep_keeps_two(tmp_path):
    dep = server.Deployment.__new__(server.Deployment)
    dep.drive_dirs = [str(tmp_path / "n1d1")]
    key = "c000/k0001"
    assert dep.newest_shard_file(0, key) is None
    paths = []
    for i, name in enumerate(("b-older", "a-newer")):
        d = tmp_path / "n1d1" / "bench" / key / name
        os.makedirs(d)
        (d / "part.1").write_bytes(b"x")
        os.utime(d / "part.1", (1000 + i, 1000 + i))
        paths.append(str(d / "part.1"))
    assert dep.newest_shard_file(0, key) == paths[1]
    assert dep.shard_file(0, key) is None  # the heal's helper wants exactly one
