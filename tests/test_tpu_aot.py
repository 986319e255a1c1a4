"""Ahead-of-time compile of the serving programs for a TPU v5e, without a chip.

The installed libtpu can describe a `v5e:2x2` topology and compile for it on a
CPU-only host (jax.experimental.topologies). That catches what interpret mode
on the CPU cannot: a Mosaic lowering refusal, a VMEM overflow, a shard_map the
TPU compiler rejects -- at the production shape (12+4, 1 MiB blocks -> 87,382 B
shards, one 16-block codec group), for the kernel pairs the boot-time selection can
serve with, and the three served geometries' encode + hash program at the
warm-up's largest batch (64), whose outputs are parity + digests only. Each
program's temporaries and outputs are printed. It proves nothing about execution or bit-exactness
on silicon: that is chip_smoke.py's job.

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
import os, sys, time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from minio_tpu import jaxenv

jaxenv.on_tpu = lambda: True  # trace the kernels as the chip would

from minio_tpu.models import pipeline
from minio_tpu.ops import bitmatrix, fused, rs, rs_matrix
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


def fused_step(rs_impl, hash_impl):
    return fused._fused_cached(K, M, rs_impl, hash_impl).lower(sds((BATCH, K, S)))


# Heal of the first four data rows: reconstruct fused with the digest hash.
lost = (0, 1, 2, 3)
present = tuple(j not in lost for j in range(K + M))
coeffs = rs_matrix.reconstruct_rows(K, M, present, lost)


def recon_xla():
    from minio_tpu.ops import highwayhash_jax as hhj

    w = rs_matrix.bit_expand(coeffs)
    return pipeline._reconstruct_step.lower(
        sds((BATCH, K, S)), sds(w.shape, jnp.int8), hhj.hash256_batch
    )


def recon_pallas():
    from minio_tpu.ops import highwayhash_pallas as hhp

    return pipeline._reconstruct_sched_step.lower(
        sds((BATCH, K, S)), bitmatrix.schedule_for_coeffs(coeffs), hhp.hash256_batch
    )


def mesh_step(rs_impl):
    # What codec_mesh() builds by itself on a four-chip host: factor_mesh(4).
    # The kernel pair is fixed by the env here, not by the boot-time race
    # (which needs a device), and resolved now, before the compile threads.
    os.environ["MINIO_TPU_RS"], os.environ["MINIO_TPU_HASH"] = rs_impl, "pallas"
    shape = mesh_lib.factor_mesh(4)
    assert shape == (2, 2, 1), shape
    mesh = Mesh(np.array(devs).reshape(shape), mesh_lib.AXES)
    pipe = pipeline.ErasurePipeline(pipeline.Geometry(K, M), mesh=mesh)
    assert pipe.rs_impl == rs_impl
    return lambda: pipe._encode_fn.lower(
        sds((BATCH, K, S), sharding=NamedSharding(mesh, mesh_lib.data_spec()))
    )


programs = {
    "fused pallas+pallas": lambda: fused_step("pallas", "pallas"),
    "fused xla+xla": lambda: fused_step("xla", "xla"),
    # xla+pallas is the pair the boot-time selection picked on a v5e
    # (CHANGES.md, PR 21).
    "fused xla+pallas": lambda: fused_step("xla", "pallas"),
    "reconstruct xla": recon_xla,
    "reconstruct pallas": recon_pallas,
    "mesh (2,2,1) pallas+pallas": mesh_step("pallas"),
    "mesh (2,2,1) xla+pallas": mesh_step("xla"),
    # The smallest erasure set, 2+2 (524,288 B shards), at the warm-up's
    # largest batch: four shard rows are fewer than a tile's sublanes, and
    # without the barrier in fused.make_step this program wants 18 GB of HBM.
    "fused xla+pallas 2+2 x64": lambda: fused._fused_cached(2, 2, "xla", "pallas").lower(
        sds((64, 2, 524288))
    ),
    # The accepted cells' geometries at the warm-up's largest batch, as the
    # boot-time selection serves them.
    "fused xla+pallas 12+4 x64": lambda: fused._fused_cached(12, 4, "xla", "pallas").lower(
        sds((64, 12, S))
    ),
    "fused xla+pallas 4+4 x64": lambda: fused._fused_cached(4, 4, "xla", "pallas").lower(
        sds((64, 4, 262144))
    ),
    # The small-object queue's parity-only program for 64 KiB objects at 2+2.
    "parity xla 2+2 x64": lambda: jax.jit(rs.RSCodec(2, 2).encode).lower(sds((64, 2, 32768))),
}


def build(item):
    name, make = item
    t0 = time.time()
    lowered = make()
    text = lowered.as_text()
    compiled = lowered.compile()
    assert compiled is not None
    if "pallas" in name:
        assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel in the lowering"
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
    assert "AOT_DONE 11" in proc.stdout, proc.stdout
    print(proc.stdout)  # each program's temporaries and outputs, under -s / on failure
