"""Faults planted underneath the timed path, for test_rehearsal.py: in the
run's own process (one node), or inside a node process of a cluster cell
(harness/node_proc.py ``plant`` calls a function here by name once the node
serves)."""

import numpy as np


def break_encode(mutate):
    """Plant a fault where the device's answer is produced: the pipeline's
    encode, which returns (parity [B, M, S], digests [B, K+M, 32]).
    `mutate(pipe, parity, digests, data)` edits the answer in place. Returns
    what undoes it."""
    from minio_tpu.models import pipeline

    orig = pipeline.ErasurePipeline.encode

    def broken(self, data_shards):
        parity, digests = orig(self, data_shards)
        parity, digests = np.array(parity), np.array(digests)
        mutate(self, parity, digests, np.asarray(data_shards))
        return parity, digests

    pipeline.ErasurePipeline.encode = broken
    return lambda: setattr(pipeline.ErasurePipeline, "encode", orig)


def alter_parity(pipe, parity, digests, data):
    """One parity byte of every block flipped, and the row's digest made to
    match: bitrot verification passes, only the bytes read back can tell."""
    from minio_tpu.ops.highwayhash import hash256

    k = pipe.geom.data
    parity[:, 0, 0] ^= 0x5A
    for b in range(parity.shape[0]):
        digests[b, k] = np.frombuffer(hash256(parity[b, 0].tobytes()), dtype=np.uint8)


def leave_out_half(pipe, parity, digests, data):
    """The second half of the batch's real blocks (padding slots hold zeros
    and are not counted) comes back without parity; a batch of one loses it."""
    real = [b for b in range(data.shape[0]) if data[b].any()] or list(range(data.shape[0]))
    parity[real[len(real) // 2:]] = 0


def parity_altered() -> None:
    break_encode(alter_parity)


def half_of_the_batch_left_out() -> None:
    break_encode(leave_out_half)


def hollow_remote_writes() -> None:
    """This node's shard writes to the other nodes' drives answer OK and write
    nothing: the storage REST client's two shard-writing calls return at once."""
    from minio_tpu.dist.storage_rest import RemoteDrive

    def nothing(self, volume: str, path: str, data: bytes) -> None:
        return None

    RemoteDrive.create_file = nothing
    RemoteDrive.append_file = nothing


def put_returns_the_state_unchanged() -> None:
    """A PUT over an existing key is acknowledged and stores nothing."""
    from minio_tpu.object.erasure import ErasureObjects
    from minio_tpu.utils import errors

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
