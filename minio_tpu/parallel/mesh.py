"""Device mesh construction for the storage data plane.

Parallel axes (the TPU mapping of the reference's parallelism inventory,
SURVEY.md section 2.4):
  * dp -- across block batches (independent uploads / heal scans), the
    analogue of object-level parallelism across erasure sets;
  * tp -- across shard streams (the reference writes K+M shards concurrently,
    cmd/erasure-encode.go:29-70: `parallelWriter`); bitrot hashing shards
    this axis;
  * sp -- across shard byte ranges (sequence/long-object parallelism): the
    erasure matmul is pointwise in the byte axis so it runs sp-sharded with
    no collectives, and the encode->hash boundary is an all-to-all reshard
    (sp <-> tp), the storage equivalent of sequence-parallel attention
    re-gathering.

Multi-host: the same mesh spans hosts via jax.distributed; ICI carries the
sp/tp all-to-alls, DCN only carries control traffic (dist/ package).
"""

from __future__ import annotations

import math
import os
import threading

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("dp", "tp", "sp")


def factor_mesh(n: int) -> tuple[int, int, int]:
    """Split n devices into (dp, tp, sp), preferring dp >= tp >= sp."""
    best = (n, 1, 1)
    best_score = None
    for dp in range(1, n + 1):
        if n % dp:
            continue
        rest = n // dp
        for tp in range(1, rest + 1):
            if rest % tp:
                continue
            sp = rest // tp
            # Prefer balanced meshes with dp the largest axis.
            score = (abs(math.log(max(dp, 1) / max(tp, 1))) + abs(math.log(max(tp, 1) / max(sp, 1))),)
            if dp >= tp >= sp and (best_score is None or score < best_score):
                best, best_score = (dp, tp, sp), score
    return best


def make_mesh(n_devices: int | None = None, shape: tuple[int, int, int] | None = None) -> Mesh:
    devices = jax.devices()
    n = n_devices or len(devices)
    if shape is None:
        shape = factor_mesh(n)
    assert shape[0] * shape[1] * shape[2] == n, (shape, n)
    dev_array = np.array(devices[:n]).reshape(shape)
    return Mesh(dev_array, AXES)


def mesh_shape_from_env(n: int) -> tuple[int, int, int] | None:
    """Parse MTPU_MESH_SHAPE for n devices.

    Accepted: "dp,tp,sp" (must multiply to n), "auto"/"" (factor_mesh),
    "off"/"0"/"1" (disable the codec mesh entirely -> None from
    codec_mesh). A malformed or mismatched value falls back to auto rather
    than refusing to serve.
    """
    raw = os.environ.get("MTPU_MESH_SHAPE", "").strip().lower()
    if raw in ("off", "0", "1"):
        return None
    if raw in ("", "auto"):
        return factor_mesh(n)
    try:
        parts = tuple(int(p) for p in raw.split(","))
    except ValueError:
        return factor_mesh(n)
    if len(parts) != 3 or any(p < 1 for p in parts):
        return factor_mesh(n)
    if parts[0] * parts[1] * parts[2] != n:
        return factor_mesh(n)
    return parts


_CODEC_MESH_LOCK = threading.Lock()
_codec_mesh_cache: list = []  # [Mesh | None] once resolved


def codec_mesh() -> Mesh | None:
    """The mesh BatchingDeviceCodec fans encode batches over: all local
    devices, shaped by MTPU_MESH_SHAPE (default factor_mesh). None on
    single-device hosts or when MTPU_MESH_SHAPE=off -- callers then run the
    plain single-device pipeline. Cached: device enumeration and mesh
    construction happen once per process."""
    with _CODEC_MESH_LOCK:
        if not _codec_mesh_cache:
            n = len(jax.devices())
            shape = mesh_shape_from_env(n) if n > 1 else None
            _codec_mesh_cache.append(make_mesh(n, shape) if shape else None)
        return _codec_mesh_cache[0]


def data_spec() -> P:
    """[B, K, S] input blocks: batch over dp, bytes over sp."""
    return P("dp", None, "sp")


def digest_spec() -> P:
    """[B, nshards, 32] digests: batch over dp, streams over sp then tp.

    sp is MAJOR on the stream axis because the encode->hash all-to-all
    (lax.all_to_all over sp, models/pipeline.py) deals stream blocks to sp
    peers first; each peer then slices its tp share locally.
    """
    return P("dp", ("sp", "tp"), None)


def parity_spec() -> P:
    """[B, M, S] parity shards leaving the device: match data layout."""
    return P("dp", None, "sp")


def data_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, data_spec())
