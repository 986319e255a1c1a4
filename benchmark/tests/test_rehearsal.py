"""The whole run on jax's CPU backend at tiny sizes: the output check passes on
the sound program, and comes out false for the control and for each fault the
cells can have, planted underneath the timed path.

Run by hand (about ten minutes; each case boots a server in this process):

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_rehearsal.py -q -p no:cacheprovider

These skip the harness's look for a chip (``--rehearse``) and drive the rest of
a run: clients as processes, ramp, window, drain, read-back, degraded read-back.
The exchange between nodes is the cluster cell's: four node processes, the
first serving the device programs on jax's CPU backend and every client, the
others the host codec, 12 of 16 shards over storage REST; its fault is a node
whose shard writes to the others answer OK and write nothing.
"""

import argparse
import os

import numpy as np
import pytest

from benchmark.harness import check, server, traffic
from benchmark.harness import run as bench_run
from benchmark.tests import faults

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


def _run_broken(workload, seed, mutate, seconds):
    """The fault goes in once the server has started: the install's own
    warm-up holds every program to the host codec and refuses a wrong one, so a
    fault that is there from the start never serves. The window is long
    enough for a first PUT that compiles its batch's program on a slow CPU
    (run alone, no earlier case has compiled it) to end inside."""
    restore = []
    try:
        return bench_run.execute(args(workload, seed, seconds=seconds),
                                 deployment_hook=lambda dep: restore.append(
                                     faults.break_encode(mutate)))
    finally:
        for r in restore:
            r()


# one PUT a request; an upload's parts (with the window each needs on a slow CPU)
ENCODING_CELLS = [("put64m-c8", 20.0), ("mpput64m-p8m-c4", MP_SECONDS)]


@pytest.mark.parametrize("workload,seconds", ENCODING_CELLS)
def test_fault_parity_altered_where_it_is_produced(workload, seconds):
    """One parity byte of every block flipped, and the row's digest made to
    match: bitrot verification passes, only the bytes read back can tell."""
    rc, line = _run_broken(workload, 13, faults.alter_parity, seconds)
    assert rc == 0 and line["correct"] is False
    assert numbers(line)["degraded_mismatch"] >= 1


@pytest.mark.parametrize("workload,seconds", ENCODING_CELLS)
def test_fault_half_of_the_batch_left_out(workload, seconds):
    """The second half of every device batch's real blocks comes back without
    parity (the half is of the real blocks, not of the padded batch: a fault
    that fell on padding slots alone would leave nothing to find)."""
    rc, line = _run_broken(workload, 14, faults.leave_out_half, seconds)
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

        def counting(key, data, **kw):
            victims = orig(key, data, **kw)
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


# -- the heal cell (PR 37) ----------------------------------------------------------

HEAL = "heal64m-d3"


def _heal_run(seed, patch=None, control=None):
    """A whole rehearsal of the heal cell; `patch()` plants a fault once set-up
    is done (at the window's first snapshot: the install's warm-up and the
    set-up's one-object heal would refuse it, and the run would print no
    result) and returns what undoes it."""
    undo = []

    def hook(dep):
        snapshot = dep.snapshot

        def planting():
            if not undo:
                undo.append(patch())
            return snapshot()

        dep.snapshot = planting

    try:
        return bench_run.execute(args(HEAL, seed, control=control),
                                 deployment_hook=hook if patch else None)
    finally:
        for u in undo:
            u()


def _replace(owner, name, new):
    orig = getattr(owner, name)
    setattr(owner, name, new)
    return lambda: setattr(owner, name, orig)


def test_heal_cell_is_correct_on_the_sound_program():
    rc, line = _heal_run(31)
    assert rc == 0 and line["correct"] is True, line
    n = numbers(line)
    assert set(n) == set(check.limits(True)) and all(v == 0 for v in n.values()), n
    assert line["attempted"] >= 2 and line["failed"] == 0 and line["metrics"] == {}


def test_heal_cell_control_one_parity_shard_fewer_is_not_correct():
    """13+3 heals its three rows too: only the plain reference (12+4) and the
    degraded read-back (12 survivors of 13 needed) can tell."""
    rc, line = _heal_run(32, control="parity-1")
    assert rc == 0 and line["correct"] is False
    n = numbers(line)
    assert n["reference_mismatch"] >= 1 and n["degraded_mismatch"] >= 1
    assert n["ops_failed"] == 0 and n["healed_shard_mismatch"] == 0


def _flip_first_byte_of_every_block(rebuilt):
    rebuilt[:, 0, 0] ^= 0x5A


def _zero_the_second_half_of_the_batch(rebuilt):
    rebuilt[max(1, rebuilt.shape[0] // 2):] = 0


@pytest.mark.parametrize("mutate", [_flip_first_byte_of_every_block,
                                    _zero_the_second_half_of_the_batch])
def test_heal_fault_rebuilt_rows_altered_where_the_heal_writes_them(mutate):
    """Where the reconstruct + digests program hands a heal's batch back (heal
    asks for digests, a GET does not): one byte of the first rebuilt row of
    every block flipped, or the second half of the batch left out (zeros); the
    digests left as the program made them."""
    from minio_tpu.models import pipeline

    orig = pipeline.ErasurePipeline.reconstruct

    def altered(self, survivors, present, want, with_digests=True):
        rebuilt, digests = orig(self, survivors, present, want, with_digests=with_digests)
        if with_digests:
            import jax.numpy as jnp

            rows = np.array(rebuilt)
            mutate(rows)
            rebuilt = jnp.asarray(rows)  # the caller waits on it as on the program's own
        return rebuilt, digests

    rc, line = _heal_run(33, lambda: _replace(pipeline.ErasurePipeline, "reconstruct", altered))
    assert rc == 0 and line["correct"] is False
    n = numbers(line)
    assert n["healed_shard_mismatch"] >= 1 and n["reference_mismatch"] >= 1


def test_heal_fault_half_of_every_verify_batch_left_out():
    """The second half of every device verify batch comes back as zero
    digests: sound rows read as rotten, a heal finds fewer than K good rows
    and fails, or rebuilds rows it need not."""
    from minio_tpu.models import pipeline

    orig = pipeline.ErasurePipeline.verify_digests

    def half(self, shards):
        digs = np.array(orig(self, shards))
        digs[max(1, digs.shape[0] // 2):] = 0
        return digs

    rc, line = _heal_run(37, lambda: _replace(pipeline.ErasurePipeline, "verify_digests", half))
    assert rc == 0 and line["correct"] is False


def test_heal_fault_reports_done_and_writes_nothing():
    """Every object reported healed (its missing drives counted), nothing written."""
    from minio_tpu.object.erasure import ErasureObjects

    orig = ErasureObjects.heal_object

    def hollow(self, bucket, object_name, version_id="", dry_run=False):
        res = orig(self, bucket, object_name, version_id, dry_run=True)
        res.after_drive_state = list(res.before_drive_state)
        return res

    rc, line = _heal_run(34, lambda: _replace(ErasureObjects, "heal_object", hollow))
    assert rc == 0 and line["correct"] is False
    n = numbers(line)
    assert n["ops_failed"] == 0  # every sequence answered as a sound one would
    assert n["healed_shard_mismatch"] >= 1 and n["reference_mismatch"] >= 1
    assert n["degraded_mismatch"] >= 1


def test_heal_fault_every_reconstruct_on_the_host_codec():
    from minio_tpu.parallel.batching import BatchingDeviceCodec

    def on_host(self, rows_batch, k, m, want, with_digests=False):
        with self._stats_lock:
            self.host_fallback_recon_blocks += len(rows_batch)
        return self._host.reconstruct_batch(rows_batch, k, m, want, with_digests)

    rc, line = _heal_run(35, lambda: _replace(BatchingDeviceCodec, "reconstruct_batch", on_host))
    assert rc == 0 and line["correct"] is False
    n = numbers(line)
    assert n["host_fallback_recon_blocks"] >= 1 and n["device_blocks_missing"] >= 1
    assert n["healed_shard_mismatch"] == 0 and n["reference_mismatch"] == 0


def test_heal_fault_every_verify_on_the_host_codec():
    from minio_tpu.parallel.batching import BatchingDeviceCodec

    def on_host(self, chunks):
        with self._stats_lock:
            self.host_fallback_digest_chunks += len(chunks)
        return self._host.digests_batch(chunks)

    rc, line = _heal_run(36, lambda: _replace(BatchingDeviceCodec, "digests_batch", on_host))
    assert rc == 0 and line["correct"] is False
    n = numbers(line)
    assert n["host_fallback_digest_chunks"] >= 1
    assert n["healed_shard_mismatch"] == 0 and n["reference_mismatch"] == 0


# -- the cluster cell ---------------------------------------------------------------

CLUSTER = "put64m-c8-n4"


def test_cluster_cell_is_correct_on_the_sound_program():
    """Four node processes; each key read back through another node than its
    writer's; every shard file of the sample as the plain reference has it;
    one chip, on the node every client sends to."""
    rc, line = bench_run.execute(args(CLUSTER, 41, seconds=10.0))
    assert rc == 0 and line["correct"] is True, line
    n = numbers(line)
    assert set(n) == set(check.limits(False, cluster=True)) and all(v == 0 for v in n.values()), n
    assert line["attempted"] >= 4 and line["failed"] == 0 and line["metrics"] == {}
    assert line["device"]["count"] == 1 and [n["node"] for n in line["device"]["nodes"]] == ["n1"]
    assert not [d for d in os.listdir(server.SHM) if d == f"{server.PREFIX}{os.getpid()}"]


def test_cluster_cell_control_one_parity_shard_fewer_is_not_correct():
    rc, line = bench_run.execute(args(CLUSTER, 42, seconds=10.0, control="parity-1"))
    assert rc == 0 and line["correct"] is False
    n = numbers(line)
    assert n["reference_mismatch"] >= 1 and n["degraded_mismatch"] >= 1
    assert n["ops_failed"] == 0 and n["readback_mismatch"] == 0


def test_cluster_fault_remote_shard_writes_answer_ok_and_write_nothing():
    """Planted in node n1 once it serves: its PUTs' 12 remote shards go nowhere."""
    rc, line = bench_run.execute(
        args(CLUSTER, 43, seconds=10.0),
        deployment_hook=lambda dep: dep.plant(0, "benchmark.tests.faults:hollow_remote_writes"))
    assert rc == 0 and line["correct"] is False
    n = numbers(line)
    assert n["ops_failed"] + n["readback_mismatch"] + n["reference_mismatch"] >= 1


@pytest.mark.parametrize("fault,caught_by", [
    ("parity_altered", ("degraded_mismatch", "reference_mismatch")),
    ("half_of_the_batch_left_out", ("degraded_mismatch", "reference_mismatch")),
    ("put_returns_the_state_unchanged", ("readback_mismatch", "ops_failed")),
])
def test_cluster_fault_planted_in_one_node(fault, caught_by):
    """The one-node cells' faults, planted in the chip node n1, which takes
    every PUT: the check reads across nodes and still sees them."""
    rc, line = bench_run.execute(
        args(CLUSTER, 44, seconds=12.0),
        deployment_hook=lambda dep: dep.plant(0, f"benchmark.tests.faults:{fault}"))
    assert rc == 0 and line["correct"] is False
    n = numbers(line)
    assert sum(n[name] for name in caught_by) >= 1, n
