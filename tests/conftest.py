"""Test configuration: force an 8-device virtual CPU mesh before jax is used.

The chip is the serving platform; tests exercise the same code on a virtual
multi-device CPU platform so sharding/collective paths are covered without
hardware (mirrors the reference's in-process multi-disk harness philosophy,
/root/reference/cmd/test-utils_test.go:199). Pallas kernels run in interpret
mode here; tests/test_tpu_aot.py compiles them for a v5e without a chip.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Durability barriers off by default in tests: /tmp is a real filesystem
# here, and ~900 tests x fsync-per-commit would dominate the suite's wall
# clock without covering anything the crash tests (tests/test_crash.py,
# tools/crashcheck.py) don't already pin under MTPU_FSYNC=commit. Tests
# that exercise the barriers set the mode explicitly.
os.environ.setdefault("MTPU_FSYNC", "never")

# Recovery re-probe daemons off by default: tests that install a host codec
# in auto mode must not leave a timer thread re-probing (and re-installing a
# device codec) behind later tests' backs. Recovery tests set this per-test.
os.environ.setdefault("MTPU_PROBE_RECOVERY_S", "0")

# Flight-recorder trigger thread off by default: hundreds of tests build
# throwaway nodes, and an armed SLO watcher would dump diagnostic bundles to
# /tmp whenever a test intentionally provokes errors. The span ring and the
# manual/fanout capture paths stay live; flight tests arm the thread
# explicitly (tests/test_flight.py).
os.environ.setdefault("MTPU_FLIGHT", "0")

import jax

jax.config.update("jax_platforms", "cpu")

from minio_tpu import jaxenv  # noqa: E402

# Persistent compile cache: the suite re-jits the same kernels every run.
jaxenv.enable_compile_cache()


# -- child-process hygiene (round-2 verdict: one pytest run orphaned 11 wedged
# probe children). A session fixture snapshots our child PIDs at start and
# asserts the table is clean at exit; probe children are killed as process
# groups by runtime.probe_device, so anything left is a real leak.

import subprocess  # noqa: E402
import sys  # noqa: E402

import pytest  # noqa: E402

# -- race-stress mode (the `buildscripts/race.sh` analogue, tools/race_gate.py):
# MINIO_TPU_RACE=1 shrinks the interpreter's thread switch interval ~1000x so
# the scheduler interleaves threads at nearly every bytecode boundary. Latent
# check-then-act races in the quorum writers, batching queues, lock refresh
# loops, and pubsub hubs become orders of magnitude more likely to fire while
# the assertions stay exactly the same.
if os.environ.get("MINIO_TPU_RACE") == "1":
    sys.setswitchinterval(2e-6)


def pytest_configure(config):
    # Tier-1 runs `-m "not slow"`; the full chaos matrix (tools/chaos_check.py)
    # includes slow scenarios.
    config.addinivalue_line(
        "markers", "slow: long-running scenario tests excluded from tier-1"
    )
    # tools/race_gate.py discovers its file list from this marker.
    config.addinivalue_line(
        "markers", "race: concurrency-sensitive tests rerun by tools/race_gate.py"
    )


def _child_pids() -> set[int]:
    try:
        out = subprocess.run(
            ["ps", "-o", "pid=,ppid=,args=", "-e"],
            capture_output=True,
            text=True,
            timeout=10,
        ).stdout
    except Exception:  # pragma: no cover - ps unavailable
        return set()
    me = os.getpid()
    procs = []
    for line in out.splitlines():
        parts = line.split(None, 2)
        if len(parts) >= 2:
            procs.append((int(parts[0]), int(parts[1]), parts[2] if len(parts) > 2 else ""))
    # Transitive children of this process, excluding the ps we just ran.
    children: set[int] = set()
    added = True
    roots = {me}
    while added:
        added = False
        for pid, ppid, _ in procs:
            if ppid in roots | children and pid not in children and pid != me:
                children.add(pid)
                added = True
    return {
        pid
        for pid in children
        for p, pp, args in procs
        if p == pid and "ps -o" not in args and "<defunct>" not in args
    }


@pytest.fixture(scope="session", autouse=True)
def _no_leaked_threads():
    """Stop every Node's background workers at session end. Tests build
    in-process clusters ad hoc and rarely own their teardown; without this
    the daemon threads (replication workers, MRF heal, disk-heal monitor)
    pile up across the session and mtpusan's leaked-thread detector --
    which runs at interpreter exit, after this hook -- reports every one."""
    yield
    try:
        from minio_tpu.dist.node import Node

        Node.close_all()
    except Exception:  # pragma: no cover - teardown must not mask failures
        pass


@pytest.fixture(scope="session", autouse=True)
def _no_leaked_children():
    yield
    # Reap the probe process groups eagerly (atexit would fire later anyway;
    # the assert below must not race it).
    try:
        from minio_tpu import runtime as _rt

        _rt._reap_live_probes()
    except Exception:
        pass
    import time as _time

    for _ in range(20):  # allow daemon-thread subprocesses a moment to die
        leftover = _child_pids()
        if not leftover:
            break
        _time.sleep(0.25)
    assert not leftover, f"test suite leaked child processes: {sorted(leftover)}"
