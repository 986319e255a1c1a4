"""The whole run on jax's CPU backend at tiny sizes: the output check passes on
the sound program, and comes out false for the control and for each fault the
cells can have, planted underneath the timed path.

Run by hand (about ten minutes; each case boots a server in this process):

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_rehearsal.py -q -p no:cacheprovider

These skip the harness's look for a chip (``--rehearse``) and drive the rest of
a run: clients as processes, ramp, window, drain, read-back, degraded read-back.
Of the faults the builder's contract lists, "the exchange between chips left
out" has no cell here: every cell takes one chip.
"""

import argparse
import os

import numpy as np
import pytest

from benchmark.harness import run as bench_run
from benchmark.harness import server, traffic

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS", "") != "cpu",
    reason="the rehearsal serves the device programs on jax's CPU backend: set JAX_PLATFORMS=cpu",
)


def args(workload, seed, seconds=8.0, control=None):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds, trace=0,
                              rehearse=True, control=control, describe_trace=None, dump_ops=None)


MP_SECONDS = 40.0  # an upload of two parts compiles its groups' programs on a slow CPU first


def numbers(line):
    return {k: v["value"] for k, v in line["compared"].items()}


def test_sound_program_is_correct_and_prints_no_device_metric():
    rc, line = bench_run.execute(args("mixed10m-c20", 11))
    assert rc == 0 and line["correct"] is True, line
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert all(v == 0 for v in numbers(line).values())
    assert list(line)[-1] == "compared"
    assert not [d for d in os.listdir(server.SHM) if d == f"{server.PREFIX}{os.getpid()}"]


def test_control_one_parity_shard_fewer_is_not_correct():
    """The step that would tempt a later PR: 13+3 writes less and encodes
    faster, and nothing shows until four drives are lost."""
    rc, line = bench_run.execute(args("put64m-c8", 12, seconds=20.0, control="parity-1"))
    assert rc == 0 and line["correct"] is False
    n = numbers(line)
    assert n["degraded_mismatch"] >= 1
    assert n["ops_failed"] == 0 and n["readback_mismatch"] == 0


def _break_encode(mutate):
    """Plant a fault where the device's answer is produced: the pipeline's
    encode, which returns (parity [B, M, S], digests [B, K+M, 32]) since PR 30."""
    from minio_tpu.models import pipeline

    orig = pipeline.ErasurePipeline.encode

    def broken(self, data_shards):
        parity, digests = orig(self, data_shards)
        parity, digests = np.array(parity), np.array(digests)
        mutate(self, parity, digests)
        return parity, digests

    pipeline.ErasurePipeline.encode = broken
    return lambda: setattr(pipeline.ErasurePipeline, "encode", orig)


def _run_broken(workload, seed, mutate, seconds):
    """The fault goes in once the server has started: the install's own
    warm-up holds every program to the host codec and refuses a wrong one, so a
    fault that is there from the start never serves. The window is long
    enough for a first PUT that compiles its batch's program on a slow CPU
    (run alone, no earlier case has compiled it) to end inside."""
    restore = []
    try:
        return bench_run.execute(args(workload, seed, seconds=seconds),
                                 deployment_hook=lambda dep: restore.append(_break_encode(mutate)))
    finally:
        for r in restore:
            r()


# one PUT a request; an upload's parts (with the window each needs on a slow CPU)
ENCODING_CELLS = [("put64m-c8", 20.0), ("mpput64m-p8m-c4", MP_SECONDS)]


@pytest.mark.parametrize("workload,seconds", ENCODING_CELLS)
def test_fault_parity_altered_where_it_is_produced(workload, seconds):
    """One parity byte of every block flipped, and the row's digest made to
    match: bitrot verification passes, only the bytes read back can tell."""
    from minio_tpu.ops.highwayhash import hash256

    def mutate(pipe, parity, digests):
        k = pipe.geom.data
        parity[:, 0, 0] ^= 0x5A
        for b in range(parity.shape[0]):
            digests[b, k] = np.frombuffer(hash256(parity[b, 0].tobytes()), dtype=np.uint8)

    rc, line = _run_broken(workload, 13, mutate, seconds)
    assert rc == 0 and line["correct"] is False
    assert numbers(line)["degraded_mismatch"] >= 1


@pytest.mark.parametrize("workload,seconds", ENCODING_CELLS)
def test_fault_half_of_the_batch_left_out(workload, seconds):
    """The second half of every device batch comes back without parity."""

    def mutate(pipe, parity, digests):
        half = max(1, parity.shape[0] // 2)
        parity[half:] = 0
        if parity.shape[0] == 1:
            parity[:] = 0

    rc, line = _run_broken(workload, 14, mutate, seconds)
    assert rc == 0 and line["correct"] is False
    assert numbers(line)["degraded_mismatch"] >= 1


def test_fault_put_returns_the_state_unchanged():
    """A PUT over an existing key is acknowledged and stores nothing."""
    from minio_tpu.utils import errors
    from minio_tpu.object.erasure import ErasureObjects

    orig = ErasureObjects.put_object

    def unchanged(self, bucket, object_name, data, opts=None):
        try:
            info = self.get_object_info(bucket, object_name)
        except errors.ObjectNotFound:
            return orig(self, bucket, object_name, data, opts)
        if hasattr(data, "read"):
            while data.read(1 << 20):
                pass
        return info

    ErasureObjects.put_object = unchanged
    try:
        rc, line = bench_run.execute(args("mixed10m-c20", 15, seconds=10.0))
    finally:
        ErasureObjects.put_object = orig
    assert rc == 0 and line["correct"] is False
    n = numbers(line)
    assert n["ops_failed"] + n["readback_mismatch"] >= 1


def test_multipart_cell_is_correct_on_the_sound_program():
    rc, line = bench_run.execute(args("mpput64m-p8m-c4", 22, seconds=MP_SECONDS))
    assert rc == 0 and line["correct"] is True, line
    assert all(v == 0 for v in numbers(line).values()), numbers(line)
    assert line["attempted"] >= 4 and line["metrics"] == {}


def test_multipart_cell_control_one_parity_shard_fewer_is_not_correct():
    rc, line = bench_run.execute(args("mpput64m-p8m-c4", 23, seconds=MP_SECONDS,
                                      control="parity-1"))
    assert rc == 0 and line["correct"] is False
    n = numbers(line)
    assert n["degraded_mismatch"] >= 1
    assert n["ops_failed"] == 0 and n["readback_mismatch"] == 0


def test_fault_complete_answers_200_and_commits_nothing():
    """The step that returns its state unchanged: a Complete over an existing
    key answers 200 with the ETag due, and the key keeps what it held."""
    import hashlib

    from minio_tpu.object.multipart import MultipartManager
    from minio_tpu.object.types import ObjectInfo
    from minio_tpu.utils import errors

    orig = MultipartManager._complete_multipart_upload

    def hollow(self, bucket, object_name, upload_id, parts):
        try:
            self.eo.get_object_info(bucket, object_name)
        except errors.ObjectNotFound:
            return orig(self, bucket, object_name, upload_id, parts)
        have = {p.number: p for p in self.list_parts(bucket, object_name, upload_id, 0, 10_000)}
        infos = [have[n] for n, _ in parts]
        etag = hashlib.md5(b"".join(bytes.fromhex(p.etag) for p in infos)).hexdigest()
        return ObjectInfo(bucket=bucket, name=object_name, size=sum(p.size for p in infos),
                          etag=f"{etag}-{len(infos)}")

    MultipartManager._complete_multipart_upload = hollow
    try:
        rc, line = bench_run.execute(args("mpput64m-p8m-c4", 24, seconds=MP_SECONDS))
    finally:
        MultipartManager._complete_multipart_upload = orig
    assert rc == 0 and line["correct"] is False
    n = numbers(line)
    assert n["ops_failed"] == 0  # every Complete answered as a sound one would
    assert n["readback_mismatch"] + n["degraded_mismatch"] >= 1


def test_fault_get_answer_altered():
    """One byte of every GET's answer flipped on its way out."""
    from minio_tpu.object.erasure import ErasureObjects

    orig = ErasureObjects.get_object_stream

    def altered(self, *a, **kw):
        return _flip_first_byte(orig(self, *a, **kw))

    ErasureObjects.get_object_stream = altered
    try:
        rc, line = bench_run.execute(args("mixed10m-c20", 16))
    finally:
        ErasureObjects.get_object_stream = orig
    assert rc == 0 and line["correct"] is False
    assert numbers(line)["ops_failed"] >= 1


def _flip_first_byte(out):
    """get_object_stream returns (info, iterator of chunks): flip the first
    byte of the first chunk."""
    info, body = out

    def gen():
        first = True
        for chunk in body:
            if first and len(chunk):
                c = bytearray(chunk)
                c[0] ^= 0xFF
                chunk, first = bytes(c), False
            yield chunk

    return info, gen()


def _execute_under(workload, seed, alter, deployment_hook=None, **kw):
    """A whole rehearsal of a cell whose traffic `alter(traffic)` replaces
    before it is shrunk to the rehearsal's sizes: traffic no cell of the
    manifest has."""
    cell_cls = bench_run.Cell

    class Altered(cell_cls):
        def _shrink(self):
            self.traffic = alter(self.traffic)
            super()._shrink()

    bench_run.Cell = Altered
    try:
        return bench_run.execute(args(workload, seed, **kw), deployment_hook=deployment_hook)
    finally:
        bench_run.Cell = cell_cls


def test_lose_shards_prepare_step_removes_data_shards_and_the_run_is_correct():
    """`prepare: [{populate}, {lose_shards: {data: 4}}]` under reads alone:
    every object reads through reconstruct, the degraded sample comes from the
    populated pool as it stands, and the device is held to its reconstructs."""
    seen = {}

    def hook(dep):
        orig = dep.lose_shards

        def counting(key, data):
            victims = orig(key, data)
            seen.setdefault(key, []).append(victims)
            return victims

        dep.lose_shards = counting

    def reads_under_loss(t):
        t["prepare"] = [{"populate": {}}, {"lose_shards": {"data": 4}}]
        t["mix"] = {"GET": 70, "STAT": 30}
        return t

    rc, line = _execute_under("mixed10m-c20", 17, reads_under_loss, deployment_hook=hook)
    assert rc == 0 and line["correct"] is True, line
    assert all(v == 0 for v in numbers(line).values()), numbers(line)
    assert len(seen) >= 12 and all(len(v[0]) == 4 for v in seen.values())
    # The check lost the same four shards of its sample a second time: no error,
    # nothing more removed.
    again = [v for v in seen.values() if len(v) == 2]
    assert len(again) == 8 and all(v[0] == v[1] for v in again)


def _execute_traffic(traffic_name, seed, **kw):
    """A whole run of `degraded-get64m-c8`'s cell under the named traffic file:
    `get64m-c8.json` is data no cell names yet (PERF.md, Open questions)."""
    return _execute_under("degraded-get64m-c8", seed,
                          lambda _t: traffic.load_traffic(traffic_name), **kw)


@pytest.mark.parametrize("traffic_name", ["get64m-c8", "degraded-get64m-c8"])
def test_read_only_traffic_is_correct_with_a_sample_from_the_populated_pool(traffic_name):
    rc, line = _execute_traffic(traffic_name, 18)
    assert rc == 0 and line["correct"] is True, line
    assert all(v == 0 for v in numbers(line).values()), numbers(line)
    assert line["attempted"] > 0 and line["metrics"] == {}


@pytest.mark.parametrize("traffic_name", ["get64m-c8", "degraded-get64m-c8"])
def test_control_is_not_correct_under_read_only_traffic(traffic_name):
    """13+3: healthy reads all answer and the check's sample, four data shards
    lost, does not; with the loss in `prepare` every GET fails."""
    rc, line = _execute_traffic(traffic_name, 19, control="parity-1")
    assert rc == 0 and line["correct"] is False
    n = numbers(line)
    assert n["degraded_mismatch"] >= 1
    if traffic_name == "get64m-c8":
        assert n["ops_failed"] == 0
    else:
        assert n["ops_failed"] == line["attempted"] > 0


def test_fault_reconstruct_falls_back_to_the_host_codec():
    """Every degraded read decoded by the host codec: each byte is right, and
    the device did none of the work the cell is there to time."""
    from minio_tpu.parallel.batching import BatchingDeviceCodec

    orig = BatchingDeviceCodec.reconstruct_batch

    def on_host(self, rows_batch, k, m, want, with_digests=False):
        with self._stats_lock:
            self.host_fallback_recon_blocks += len(rows_batch)
        return self._host.reconstruct_batch(rows_batch, k, m, want, with_digests)

    def hook(dep):
        BatchingDeviceCodec.reconstruct_batch = on_host

    try:
        rc, line = bench_run.execute(args("degraded-get64m-c8", 20), deployment_hook=hook)
    finally:
        BatchingDeviceCodec.reconstruct_batch = orig
    assert rc == 0 and line["correct"] is False
    n = numbers(line)
    assert n["device_blocks_missing"] >= 2 * (line["attempted"] - n["ops_failed"]) > 0
    assert n["ops_failed"] == 0 and n["degraded_mismatch"] == 0 and n["degraded_short"] == 0


def test_fault_reconstructed_row_altered_where_it_is_produced():
    """One byte of the first rebuilt row of every block flipped on its way
    back from the device: a degraded GET's body is wrong."""
    from minio_tpu.models import pipeline

    orig = pipeline.ErasurePipeline.reconstruct

    def altered(self, survivors, present, want, with_digests=True):
        rebuilt, digests = orig(self, survivors, present, want, with_digests=with_digests)
        rebuilt = np.array(rebuilt)
        rebuilt[:, 0, 0] ^= 0x5A
        return rebuilt, digests

    def hook(dep):
        pipeline.ErasurePipeline.reconstruct = altered

    try:
        rc, line = bench_run.execute(args("degraded-get64m-c8", 21), deployment_hook=hook)
    finally:
        pipeline.ErasurePipeline.reconstruct = orig
    assert rc == 0 and line["correct"] is False
    n = numbers(line)
    assert n["ops_failed"] >= 1 and n["degraded_mismatch"] >= 1
