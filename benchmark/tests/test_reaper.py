"""The sweep of superseded data directories (harness/reaper.py) on a made-up
tree, and the two traffic fields `reap_superseded` and `mallopt`: no server, no chip."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import reaper, traffic

from .test_mput import _load_altered


def _key_dir(tmp_path, drive: int, key: str, versions: int) -> str:
    """<drive>/bench/c000/<key>/ with xl.meta and `versions` data directories,
    the later the newer."""
    kd = tmp_path / f"d{drive}" / "bench" / "c000" / key
    kd.mkdir(parents=True)
    (kd / "xl.meta").write_bytes(b"m" * 300)
    for v in range(versions):
        dd = kd / f"{v:08d}-data"
        dd.mkdir()
        (dd / "part.1").write_bytes(b"x" * 1000)
        os.utime(dd, ns=(10**9 * (100 + v), 10**9 * (100 + v)))
    return str(kd)


def test_superseded_names_all_but_the_newest_by_the_time_written(tmp_path):
    kd = _key_dir(tmp_path, 1, "k0000", 5)
    old = reaper.superseded(kd, 2)
    assert [os.path.basename(p) for p in old] == ["00000000-data", "00000001-data", "00000002-data"]
    assert reaper.superseded(kd, 5) == [] and reaper.superseded(kd, 9) == []
    assert reaper.superseded(str(tmp_path / "gone"), 2) == []


def test_sweep_leaves_the_newest_two_and_everything_that_is_no_data_directory(tmp_path):
    drives = []
    for d in (1, 2):
        _key_dir(tmp_path, d, "k0000", 4)
        _key_dir(tmp_path, d, "k0001", 1)
        drives.append(str(tmp_path / f"d{d}"))
    assert reaper.sweep(drives, "bench", 2) == (4, 4000)
    for d in (1, 2):
        kd = tmp_path / f"d{d}" / "bench" / "c000" / "k0000"
        assert sorted(os.listdir(kd)) == ["00000002-data", "00000003-data", "xl.meta"]
        assert os.listdir(tmp_path / f"d{d}" / "bench" / "c000" / "k0001") != []
    assert reaper.sweep(drives, "bench", 2) == (0, 0)  # a second pass finds nothing
    assert reaper.sweep([str(tmp_path / "no-such-drive")], "bench", 2) == (0, 0)


def test_the_process_sweeps_until_its_stdin_closes_and_says_what_it_removed(tmp_path):
    _key_dir(tmp_path, 1, "k0000", 3)
    p = subprocess.Popen([sys.executable, reaper.__file__, "bench", "2", "0.05",
                          str(tmp_path / "d1")], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                         text=True)
    p.stdin.close()
    assert p.wait(20) == 0
    said = json.loads(p.stdout.read())
    p.stdout.close()
    assert said["removed"] == 1 and said["freed_bytes"] == 1000 and said["sweeps"] >= 1


@pytest.mark.parametrize("name,alter,match", [
    ("put64m-c8", lambda t: t["reap_superseded"].update(keep=1), "keep >= 2"),
    ("put64m-c8", lambda t: t["reap_superseded"].update(every_s=0), "every_s > 0"),
    ("put64m-c8", lambda t: t["reap_superseded"].update(older_than_s=3), "reap_superseded takes"),
    ("mixed10m-c20", lambda t: t.update(reap_superseded={"keep": 2, "every_s": 1.0}), "a ring"),
    ("put64m-c8", lambda t: t.update(mallopt={}), "mallopt pins"),
    ("put64m-c8", lambda t: t.update(mallopt={"M_ARENA_MAX": 1}), "mallopt pins"),
    ("put64m-c8", lambda t: t.update(mallopt={"M_TOP_PAD": 2**31}), "mallopt pins"),
    ("put64m-c8", lambda t: t.update(mallopt={"M_TOP_PAD": "64M"}), "mallopt pins"),
])
def test_load_traffic_refuses(tmp_path, monkeypatch, name, alter, match):
    with pytest.raises(ValueError, match=match):
        _load_altered(tmp_path, monkeypatch, name, alter)


def test_put64m_c8_asks_for_the_sweep_and_the_pinned_allocator_and_no_other_file_does():
    t = traffic.load_traffic("put64m-c8")
    assert t["reap_superseded"] == {"keep": 2, "every_s": 1.0}
    assert t["mallopt"] == {"M_MMAP_THRESHOLD": 32 << 20, "M_TRIM_THRESHOLD": 2**31 - 1,
                            "M_TOP_PAD": 64 << 20}
    for other in ("mixed10m-c20", "degraded-get64m-c8", "put64k-c32", "mpput64m-p8m-c4"):
        assert not {"reap_superseded", "mallopt"} & set(traffic.load_traffic(other))


def test_pin_allocator_calls_mallopt_for_each_name_it_is_given():
    """In a child: the pin is for good in the process it is made in."""
    code = ("from benchmark.harness import server\n"
            "server.Deployment.pin_allocator({'M_TOP_PAD': 1 << 20, 'M_MMAP_THRESHOLD': 1 << 20,"
            " 'M_TRIM_THRESHOLD': 2**31 - 1})\n"
            "print('pinned')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         cwd=traffic.ROOT, timeout=60)
    assert out.returncode == 0 and out.stdout.strip() == "pinned", out.stderr
