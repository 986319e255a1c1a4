"""Ahead-of-time compile of the serving programs for a TPU v5e, without a chip.

The installed libtpu can describe a `v5e:2x2` topology and compile for it on a
CPU-only host (jax.experimental.topologies). That catches what interpret mode
on the CPU cannot: a Mosaic lowering refusal, a VMEM overflow, a shard_map the
TPU compiler rejects -- for the programs a v5e serves (XLA bit-matmul RS, Pallas
hash): encode + hash at the production shape (12+4, 1 MiB blocks -> 87,382 B
shards, one 16-block codec group) and for the three served geometries at the
warm-up's largest batch (64), whose outputs are parity + digests only; the
small queue's parity program; the mesh step of a four-chip host at the
warm-up's largest batch; and the two reconstruct programs,
heal (with digests) and degraded GET (without). Each program's temporaries and
outputs are printed. It proves nothing about execution or bit-exactness on
silicon: that is chip_smoke.py's job.

Runs in a subprocess: the kernels pick interpret mode and unroll depth from
jaxenv.on_tpu() at TRACE time, so compiling them for the chip in the test
process would leave TPU-flavoured traces in jit caches the CPU tests share.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, SingleDeviceSharding

from minio_tpu import jaxenv

jaxenv.on_tpu = lambda: True  # trace the kernels as the chip would

from minio_tpu.models import pipeline
from minio_tpu.ops import fused, rs, rs_matrix
from minio_tpu.ops import highwayhash_pallas as hhp
from minio_tpu.parallel import mesh as mesh_lib

K, M, BATCH = 12, 4, 16
S = rs_matrix.shard_size(1 << 20, K)
assert S == 87382

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
devs = topo.devices
assert len(devs) == 4 and devs[0].platform == "tpu"
one = SingleDeviceSharding(devs[0])


def sds(shape, dtype=jnp.uint8, sharding=one):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def fused_step(k, m, batch, s):
    step = fused.make_step(rs.RSCodec(k, m).encode, hhp.hash256_batch)
    return lambda: jax.jit(step).lower(sds((batch, k, s)))


def recon_step(hash_fn):
    # The first four data rows lost, rebuilt from the next twelve.
    w = sds((K * 8, 4 * 8), jnp.int8)
    return lambda: pipeline._reconstruct_step.lower(sds((BATCH, K, S)), w, hash_fn)


def mesh_step():
    # What codec_mesh() builds by itself on a four-chip host, factor_mesh(4),
    # at the largest batch bucket the cell put64m-c8-chip4 is served with, its
    # input placed as the batcher's sharded upload places it. Built now,
    # before the compile threads.
    shape = mesh_lib.factor_mesh(4)
    assert shape == (2, 2, 1), shape
    mesh = Mesh(np.array(devs).reshape(shape), mesh_lib.AXES)
    pipe = pipeline.ErasurePipeline(pipeline.Geometry(K, M), mesh=mesh)
    return lambda: pipe._encode_fn.lower(sds((64, K, S), sharding=mesh_lib.data_sharding(mesh)))


assert pipeline.hash_batch_fn() is hhp.hash256_batch
programs = {
    "fused 12+4 x16": fused_step(K, M, BATCH, S),
    # The accepted cells' geometries at the warm-up's largest batch.
    "fused 12+4 x64": fused_step(12, 4, 64, S),
    "fused 4+4 x64": fused_step(4, 4, 64, 262144),
    # The smallest erasure set, 2+2 (524,288 B shards): four shard rows are
    # fewer than a tile's sublanes, and without the barrier in
    # fused.make_step this program wants 18 GB of HBM.
    "fused 2+2 x64": fused_step(2, 2, 64, 524288),
    # The small-object queue's parity-only program for 64 KiB objects at 2+2.
    "parity 2+2 x64": lambda: jax.jit(rs.RSCodec(2, 2).encode).lower(sds((64, 2, 32768))),
    "mesh (2,2,1) x64": mesh_step(),
    "reconstruct + digests (heal)": recon_step(hhp.hash256_batch),
    "reconstruct (degraded GET)": recon_step(None),
}
UNHASHED = {"parity 2+2 x64", "reconstruct (degraded GET)"}


def build(item):
    name, make = item
    t0 = time.time()
    lowered = make()
    text = lowered.as_text()
    compiled = lowered.compile()
    assert compiled is not None
    # The hash is the one Mosaic kernel; RS is XLA's own fusions.
    assert ("tpu_custom_call" in text) == (name not in UNHASHED), f"{name}: Mosaic kernels"
    mem = compiled.memory_analysis()
    if name.startswith("fused"):
        # The encode + hash program hands back what the host lacks: M parity
        # rows and K+M digests a block, not the K data rows it was given.
        (b, k, s), = (a.shape for a in lowered.in_avals[0])
        parity, digests = lowered.out_info
        m = parity.shape[1]
        assert parity.shape == (b, m, s) and digests.shape == (b, k + m, 32), name
        assert mem.output_size_in_bytes <= 1.02 * b * (m * s + 32 * (k + m)), (
            name, mem.output_size_in_bytes)
    return name, time.time() - t0, mem.temp_size_in_bytes, mem.output_size_in_bytes


with ThreadPoolExecutor(len(programs)) as pool:
    for name, dt, temp, out in pool.map(build, programs.items()):
        print(f"AOT_OK {name} {dt:.1f}s temporaries {temp} B outputs {out} B", flush=True)
print("AOT_DONE", len(programs))
"""


def test_serving_programs_compile_for_v5e():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)  # one host device is enough; the mesh is the topology's
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=600,
    )
    if proc.returncode != 0 and "get_topology_desc" in proc.stderr and "AOT_OK" not in proc.stdout:
        pytest.skip("this libtpu cannot describe a v5e topology without a chip")
    assert proc.returncode == 0, proc.stdout[-2000:] + "\n" + proc.stderr[-6000:]
    assert "AOT_DONE 8" in proc.stdout, proc.stdout
    print(proc.stdout)  # each program's temporaries and outputs, under -s / on failure
