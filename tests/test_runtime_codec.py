"""Served traffic routes through the installed data-plane codec.

Round-2 wiring (VERDICT #2): the server boot installs the batching device
codec via runtime.install_data_plane_codec, and a PutObject through the
object layer demonstrably runs the device pipeline (the reference's
equivalent always-on fast codec, cmd/erasure-coding.go:63).
"""

from __future__ import annotations

import numpy as np
import pytest

from minio_tpu import runtime
from minio_tpu.object import codec as codec_mod
from minio_tpu.object.codec import HostCodec
from minio_tpu.parallel.batching import BatchingDeviceCodec

from .harness import ErasureHarness


@pytest.fixture(autouse=True)
def _restore_default_codec():
    prev = codec_mod._default
    yield
    codec_mod.set_default_codec(prev) if prev is not None else None
    codec_mod._default = prev


def test_install_host_mode():
    codec = runtime.install_data_plane_codec(mode="host")
    assert isinstance(codec, HostCodec)
    assert codec_mod.default_codec() is codec


def test_install_auto_falls_back_without_device(monkeypatch):
    monkeypatch.setattr(runtime, "probe_device", lambda t: runtime.ProbeResult(None, error="x"))
    codec = runtime.install_data_plane_codec(mode="auto")
    assert isinstance(codec, HostCodec)


def test_install_auto_cpu_platform_uses_host(monkeypatch):
    monkeypatch.setattr(runtime, "probe_device", lambda t: runtime.ProbeResult("cpu"))
    codec = runtime.install_data_plane_codec(mode="auto")
    assert isinstance(codec, HostCodec)


@pytest.fixture
def _fake_tpu(monkeypatch):
    """The probe child and this process both report a TPU: the install runs
    its device branch on the CPU backend (the short warm-up plan)."""
    monkeypatch.setattr(runtime, "probe_device", lambda t: runtime.ProbeResult("tpu", "fake", 1))
    monkeypatch.setattr(runtime, "_open_backend", lambda: "tpu")


def test_install_auto_accelerator_uses_batching(_fake_tpu):
    codec = runtime.install_data_plane_codec(mode="auto")
    try:
        assert isinstance(codec, BatchingDeviceCodec)
        # Warmed and oracle-checked before it serves, and the takeover is
        # exported: nothing about the device install is silent.
        inst = runtime.probe_summary()["install"]
        assert inst["state"] == "serving" and inst["codec"] == "BatchingDeviceCodec"
        assert inst["warm"]["programs"] >= 4 and inst["geometry"] == [12, 4]
        assert set(inst["kernels"]) == {"rs", "hash"}
        assert codec.blocks_encoded == 0  # warm-up is not served traffic
    finally:
        runtime.shutdown_data_plane(codec)


def test_install_device_mode_raises_without_accelerator():
    """MINIO_TPU_CODEC=device on a host whose jax opens the CPU must raise,
    not serve XLA-on-CPU under the device codec's name."""
    prev = codec_mod._default
    with pytest.raises(RuntimeError, match="'tpu' backend but jax opened 'cpu'"):
        runtime.install_data_plane_codec(mode="device")
    assert codec_mod._default is prev


def test_install_auto_raises_when_parent_opens_another_backend(monkeypatch):
    """The probe child saw a chip but this process's jax fell back to the
    CPU: the synchronous install raises instead of hiding the device."""
    monkeypatch.setattr(runtime, "probe_device", lambda t: runtime.ProbeResult("tpu"))
    with pytest.raises(RuntimeError, match="jax opened 'cpu'"):
        runtime.install_data_plane_codec(mode="auto")


def test_install_rejects_unknown_mode():
    with pytest.raises(ValueError, match="MINIO_TPU_CODEC"):
        runtime.install_data_plane_codec(mode="gpu")


def test_warmup_failure_keeps_host_codec_and_is_exported(_fake_tpu, monkeypatch):
    """A device that fails (or disagrees with the oracle) at warm-up never
    takes over in the background install; the reason is exported."""
    def boom(self, k, m):
        raise RuntimeError("device encode program disagrees with the host codec")

    monkeypatch.setattr(BatchingDeviceCodec, "warm", boom)
    host = codec_mod.HostCodec()
    codec_mod.set_default_codec(host)
    runtime._takeover("tpu", (12, 4))
    assert codec_mod.default_codec() is host
    inst = runtime.probe_summary()["install"]
    assert inst["state"] == "failed" and "disagrees" in inst["reason"]
    with pytest.raises(RuntimeError, match="disagrees"):
        runtime.install_data_plane_codec(mode="auto")  # synchronous: raises


def _wrong_digests(rows):
    from minio_tpu.ops import highwayhash_jax as hhj

    return hhj.hash256_batch(rows) ^ np.uint8(1)


def _refuses_to_lower(rows):
    raise NotImplementedError("Mosaic: unsupported op")


@pytest.mark.parametrize(
    "broken,reason", [(_wrong_digests, "disagrees with the host codec"),
                      (_refuses_to_lower, "Mosaic: unsupported op")])
def test_broken_hash_kernel_fails_the_real_warmup(_fake_tpu, monkeypatch, broken, reason):
    """No boot-time probe stands between a broken kernel and traffic: the
    oracle-compared warm-up does, and the host codec keeps serving."""
    from minio_tpu.models import pipeline

    monkeypatch.setattr(pipeline, "hash_batch_fn", lambda: broken)
    host = codec_mod.HostCodec()
    codec_mod.set_default_codec(host)
    runtime._takeover("tpu", (4, 2))
    assert codec_mod.default_codec() is host
    inst = runtime.probe_summary()["install"]
    assert inst["state"] == "failed" and reason in inst["reason"]


def test_prefork_worker_never_opens_the_device(monkeypatch):
    """MTPU_WORKERS: N processes on one chip. Workers install the host codec
    without probing and say why; device mode there is an error."""
    from minio_tpu.api.prefork import WORKER_ENV

    def no_probe(t):
        raise AssertionError("a pre-fork worker must not probe")

    monkeypatch.setenv(WORKER_ENV, "1")
    monkeypatch.setattr(runtime, "probe_device", no_probe)
    monkeypatch.setattr(runtime, "_open_backend", no_probe)
    for background in (False, True):
        codec = runtime.install_data_plane_codec(mode="auto", background=background)
        assert isinstance(codec, HostCodec)
        inst = runtime.probe_summary()["install"]
        assert inst["state"] == "host" and "pre-fork worker" in inst["reason"]
    with pytest.raises(RuntimeError, match="MTPU_WORKERS"):
        runtime.install_data_plane_codec(mode="device")


def test_put_object_runs_device_pipeline(tmp_path):
    """A served PutObject routes its full blocks through the batching
    pipeline when the device codec is installed -- even on a layer built
    before the install (lazy default-codec resolution)."""
    hz = ErasureHarness(tmp_path, n_disks=8)  # built while HostCodec is default
    codec = runtime.install_data_plane_codec(mode="xla-cpu", geometry=(4, 4))
    try:
        assert isinstance(codec, BatchingDeviceCodec)
        assert hz.layer.codec is codec
        rng = np.random.default_rng(7)
        body = rng.integers(0, 256, (1 << 20) + 4096, dtype=np.uint8).tobytes()
        hz.layer.make_bucket("b")
        hz.layer.put_object("b", "o", body)
        assert codec.blocks_encoded >= 1
        assert codec.batches_run >= 1
        _, got = hz.layer.get_object("b", "o")
        assert got == body
    finally:
        runtime.shutdown_data_plane(codec)


def test_background_upgrade_reaches_serving_layer(tmp_path, monkeypatch):
    """Auto+background install: boot serves on HostCodec, and when the probe
    lands on an accelerator the layer's lazy codec resolution picks up the
    batching codec for subsequent traffic -- including layers built by
    Node.build() before the upgrade landed."""
    import threading

    from minio_tpu.dist.node import Node

    probe_started = threading.Event()
    probe_release = threading.Event()

    def slow_probe(timeout):
        probe_started.set()
        probe_release.wait(10)
        return runtime.ProbeResult("tpu")

    monkeypatch.setattr(runtime, "probe_device", slow_probe)
    monkeypatch.setattr(runtime, "_open_backend", lambda: "tpu")
    monkeypatch.setenv("MINIO_TPU_CODEC", "auto")
    endpoints = [str(tmp_path / f"d{i}") for i in range(4)]
    node = Node(endpoints, root_user="a" * 8, root_password="b" * 12).build()
    try:
        assert isinstance(node.codec, HostCodec)  # boot never blocked
        layer = node.pools.pools[0].sets[0]
        assert isinstance(layer.codec, HostCodec)
        assert probe_started.wait(5)
        probe_release.set()
        deadline = 60  # the takeover follows the geometry's warm-up
        import time

        t0 = time.monotonic()
        while not isinstance(codec_mod.default_codec(), BatchingDeviceCodec):
            assert time.monotonic() - t0 < deadline, "upgrade never landed"
            time.sleep(0.05)
        # The SAME layer object now serves through the device codec.
        assert isinstance(layer.codec, BatchingDeviceCodec)
    finally:
        runtime.shutdown_data_plane(node.codec)


def test_node_build_installs_codec(tmp_path, monkeypatch):
    """Node.build() installs the data-plane codec at boot and the layer
    serves through it."""
    from minio_tpu.dist.node import Node

    monkeypatch.setenv("MINIO_TPU_CODEC", "xla-cpu")
    endpoints = [str(tmp_path / f"d{i}") for i in range(4)]
    node = Node(endpoints, root_user="a" * 8, root_password="b" * 12).build()
    try:
        assert isinstance(node.codec, BatchingDeviceCodec)
        assert codec_mod.default_codec() is node.codec
        layer = node.pools
        rng = np.random.default_rng(9)
        body = rng.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes()
        layer.make_bucket("bkt")
        layer.put_object("bkt", "obj", body)
        assert node.codec.blocks_encoded >= 1
        _, got = layer.get_object("bkt", "obj")
        assert got == body
    finally:
        runtime.shutdown_data_plane(node.codec)


# -- probe verdict transitions (fallback / recovery) --------------------------


@pytest.fixture
def _probe_cache_file(tmp_path, monkeypatch):
    path = str(tmp_path / "probe.json")
    monkeypatch.setenv("MTPU_PROBE_CACHE", path)
    monkeypatch.setattr(runtime, "_last_transition", None)
    return path


def test_probe_store_records_fallback_and_recovery(_probe_cache_file):
    import json

    runtime._store_probe_file(runtime.ProbeResult("tpu", "v5e"))
    with open(_probe_cache_file) as f:
        assert json.load(f)["transition"] is None  # first verdict: no flip

    runtime._store_probe_file(runtime.ProbeResult(None, error="wedged"))
    with open(_probe_cache_file) as f:
        doc = json.load(f)
    assert doc["transition"]["kind"] == "fallback"
    assert doc["transition"]["from"] == "tpu" and doc["transition"]["to"] is None

    runtime._store_probe_file(runtime.ProbeResult("tpu", "v5e"))
    with open(_probe_cache_file) as f:
        doc = json.load(f)
    assert doc["transition"]["kind"] == "recovery"
    assert [t["kind"] for t in doc["transitions"]] == ["fallback", "recovery"]
    # the accessor surfaces the latest flip (bench JSON reads this)
    assert runtime.probe_transition()["kind"] == "recovery"


def test_probe_transition_read_from_file_by_fresh_process(_probe_cache_file, monkeypatch):
    runtime._store_probe_file(runtime.ProbeResult("tpu"))
    runtime._store_probe_file(runtime.ProbeResult(None, error="x"))
    # Simulate a fresh process: no in-memory transition, only the file.
    monkeypatch.setattr(runtime, "_last_transition", None)
    t = runtime.probe_transition()
    assert t is not None and t["kind"] == "fallback"


def test_probe_same_verdict_is_not_a_transition(_probe_cache_file):
    import json

    runtime._store_probe_file(runtime.ProbeResult(None, error="a"))
    runtime._store_probe_file(runtime.ProbeResult("cpu"))  # fail -> cpu: still not ok
    runtime._store_probe_file(runtime.ProbeResult(None, error="b"))
    with open(_probe_cache_file) as f:
        doc = json.load(f)
    assert doc["transitions"] == [] and doc["transition"] is None


def test_probe_transition_counts(monkeypatch):
    monkeypatch.setattr(runtime, "_transition_counts", {"fallback": 0, "recovery": 0})
    monkeypatch.setattr(runtime, "_last_transition", None)
    runtime._note_transition(
        runtime._transition_between("tpu", runtime.ProbeResult(None, error="x"))
    )
    runtime._note_transition(
        runtime._transition_between(None, runtime.ProbeResult("tpu"))
    )
    runtime._note_transition(None)  # same-verdict: no flip, no count
    assert runtime.probe_transition_counts() == {"fallback": 1, "recovery": 1}


# -- periodic recovery re-probe (BENCH r04-r05 wedge: CPU-parked node) ---------


def test_recovery_reprobe_reinstalls_device_codec(monkeypatch):
    """A node that booted onto the host codec (failed probe) re-acquires the
    device on the recovery cadence without a restart."""
    import time

    verdicts = [runtime.ProbeResult(None, error="wedged at boot")]

    def probe(t):
        return verdicts.pop(0) if verdicts else runtime.ProbeResult("tpu")

    monkeypatch.setattr(runtime, "probe_device", probe)
    monkeypatch.setattr(runtime, "_open_backend", lambda: "tpu")
    monkeypatch.setenv("MTPU_PROBE_RECOVERY_S", "0.05")
    codec = runtime.install_data_plane_codec(mode="auto")
    try:
        assert isinstance(codec, HostCodec)  # boot verdict: fall back
        t0 = time.monotonic()
        while not isinstance(codec_mod.default_codec(), BatchingDeviceCodec):
            assert time.monotonic() - t0 < 60, "recovery re-probe never landed"
            time.sleep(0.02)
        # The daemon exits after the swap: one recovery, then done.
        t = runtime._reprobe_thread
        if t is not None:
            t.join(timeout=5)
            assert not t.is_alive()
    finally:
        runtime.shutdown_data_plane(codec_mod._default)


def test_recovery_reprobe_disabled_by_env(monkeypatch):
    monkeypatch.setenv("MTPU_PROBE_RECOVERY_S", "0")
    monkeypatch.setattr(runtime, "probe_device", lambda t: runtime.ProbeResult(None, error="x"))
    monkeypatch.setattr(runtime, "_reprobe_thread", None)
    codec = runtime.install_data_plane_codec(mode="auto")
    assert isinstance(codec, HostCodec)
    assert runtime._reprobe_thread is None  # no daemon armed


def test_recovery_reprobe_stops_on_shutdown(monkeypatch):
    """shutdown_data_plane stops a still-waiting recovery daemon (the probe
    keeps failing, so only the stop event can end it)."""
    monkeypatch.setattr(runtime, "probe_device", lambda t: runtime.ProbeResult(None, error="x"))
    monkeypatch.setenv("MTPU_PROBE_RECOVERY_S", "30")
    codec = runtime.install_data_plane_codec(mode="auto")
    assert isinstance(codec, HostCodec)
    t = runtime._reprobe_thread
    assert t is not None and t.is_alive()
    runtime.shutdown_data_plane(codec)
    t.join(timeout=5)
    assert not t.is_alive()


def test_probe_summary_shape(monkeypatch):
    monkeypatch.setenv("MTPU_PROBE_RECOVERY_S", "0")
    s = runtime.probe_summary()
    assert set(s) >= {"done", "ok", "platform", "cached", "install", "error",
                      "detail", "transition", "transition_counts", "recovery"}
    assert s["recovery"]["interval_s"] == 0.0
    assert set(s["transition_counts"]) == {"fallback", "recovery"}
