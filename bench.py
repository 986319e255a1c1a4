"""Benchmark: erasure codec throughput, 12+4 @ 1 MiB blocks (BASELINE.md).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

  value       = device Reed-Solomon encode GiB/s over a BATCH-block batch,
                data-bytes counted (the reference benchmark convention,
                cmd/erasure-encode_test.go b.SetBytes).
  vs_baseline = value / CPU-AVX2 GiB/s measured on this machine with the
                native C++ kernel (native/minio_native.cpp) across all cores
                -- the stand-in for klauspost/reedsolomon's AVX2 path, same
                nibble-table algorithm the Go assembly uses.

Extra fields carry the secondary BASELINE configs: fused encode+hash,
decode/reconstruct with 4 missing data shards (BASELINE.md #2), and the CPU
numbers each is measured against.

This is a device measurement: without an accelerator it prints the probe's
evidence to stderr and exits 1, and a phase that raises fails the run. The
probe child opens the chip and exits before this process touches jax (one
process per chip). The cell benchmark (ROADMAP Queue 1 item 1) replaces this
file.

Run on the chip: chiprun -- python bench.py
"""

from __future__ import annotations

import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

K, M = 12, 4
BLOCK = int(os.environ.get("BENCH_BLOCK", str(1 << 20)))
# Aggregate throughput batch: 512 x 1 MiB blocks in flight (the batching
# runtime's cross-upload fan-in, SURVEY.md section 7 step 2). Dispatch
# overhead dominates small batches.
BATCH = int(os.environ.get("BENCH_BATCH", "512"))
SHARD = -(-BLOCK // K)
ITERS = 16
PROBE_TIMEOUT_S = int(os.environ.get("BENCH_PROBE_TIMEOUT_S", "180"))

# 4 missing data shards: rows 0..3 lost, rebuilt from shards 4..15.
MISSING = (0, 1, 2, 3)
PRESENT = tuple(i not in MISSING for i in range(K + M))


def cpu_encode_gibs(blocks: np.ndarray) -> float:
    """Multi-core AVX2 encode throughput (data GiB/s)."""
    from minio_tpu.ops import native, rs_matrix

    if not native.available():
        raise RuntimeError("native host kernels did not build: no CPU baseline")
    pm = np.ascontiguousarray(rs_matrix.parity_matrix(K, M))
    pool = ThreadPoolExecutor(max_workers=os.cpu_count() or 1)

    def enc(i):
        native.rs_encode(blocks[i], pm)

    list(pool.map(enc, range(len(blocks))))  # warmup
    t0 = time.perf_counter()
    n_iters = max(4, ITERS // 2)
    for _ in range(n_iters):
        list(pool.map(enc, range(len(blocks))))
    dt = time.perf_counter() - t0
    return len(blocks) * BLOCK * n_iters / dt / (1 << 30)


def cpu_decode_gibs(blocks: np.ndarray) -> float:
    """Multi-core reconstruct-4-missing throughput (data GiB/s)."""
    from minio_tpu.ops import native, rs_matrix

    if not native.available():
        raise RuntimeError("native host kernels did not build: no CPU baseline")
    coeffs = np.ascontiguousarray(rs_matrix.reconstruct_rows(K, M, PRESENT, MISSING))
    # Survivors: first K present rows of the encoded block.
    pm = np.ascontiguousarray(rs_matrix.parity_matrix(K, M))
    surv = []
    for i in range(len(blocks)):
        full = np.concatenate([blocks[i], native.rs_encode(blocks[i], pm)], axis=0)
        surv.append(np.ascontiguousarray(full[[j for j in range(K + M) if PRESENT[j]][:K]]))
    pool = ThreadPoolExecutor(max_workers=os.cpu_count() or 1)

    def rec(i):
        native.rs_apply(surv[i], coeffs)

    list(pool.map(rec, range(len(blocks))))  # warmup
    t0 = time.perf_counter()
    n_iters = max(4, ITERS // 2)
    for _ in range(n_iters):
        list(pool.map(rec, range(len(blocks))))
    dt = time.perf_counter() - t0
    return len(blocks) * BLOCK * n_iters / dt / (1 << 30)


FUSED_BATCH = 64  # the fused encode+hash probe stays at the hash's sweet spot

# Object-layer end-to-end benches (BASELINE.md configs #4 and #5). Sizes are
# env-tunable so constrained bench machines can shrink them; defaults keep
# the full run under a few minutes on local disk.
PUT_OBJECTS = int(os.environ.get("BENCH_PUT_OBJECTS", "32"))
PUT_SIZE = int(os.environ.get("BENCH_PUT_SIZE", str(128 << 20)))  # 128 MiB
HEAL_BYTES = int(os.environ.get("BENCH_HEAL_GB", "10")) << 30
CONCURRENT_PUTS = 8
CONCURRENT_SIZE = 16 << 20


def _stage_breakdown(
    snap: dict,
    phase: str,
    leaves: tuple[str, ...],
    nested: tuple[str, ...] = (),
    aliases: dict[str, str] | None = None,
) -> dict:
    """Per-stage share of a bench phase from a perf-ledger snapshot.

    `leaves` are DISJOINT object-layer stages; "other" is the end-to-end
    root-span total minus the leaf sums, so the stage sums equal the
    measured end-to-end time by construction (an honest remainder, not a
    fudge factor -- it is the unattributed pipeline cost the ISSUE wants
    localized).

    `nested` stages ride INSIDE a leaf (drive-sync barriers fire under the
    commit span's rename fan-out, and under shard-fanout in always mode), so
    they are reported with their share of the end-to-end wall but excluded
    from the leaf sum -- adding them would double-count the same seconds.

    `aliases` maps a REPORTED row name onto the ledger stage actually
    recorded (drive-read -> the metered read_file_into histogram): the row
    set keeps the copy-ledger hop vocabulary without minting duplicate
    stage keys."""
    from minio_tpu.control.perf import quantile

    stages = snap.get("stages", {})
    obj = stages.get("object", {})
    stor = stages.get("storage", {})
    api = stages.get("api", {})

    def _hist(name: str) -> dict | None:
        src = (aliases or {}).get(name, name)
        return obj.get(src) or stor.get(src) or api.get(src)
    root = stages.get("bench", {}).get(phase)
    e2e_s = root["sum"] if root else 0.0
    n = sum(root["counts"]) if root else 0
    rows: dict[str, dict] = {}
    leaf_total = 0.0

    def _row(h: dict) -> dict:
        return {
            "total_ms": round(h["sum"] * 1e3, 1),
            # Wall-vs-cpu attribution (thread_time deltas recorded alongside
            # the span walls): cpu_ms ~= total_ms means the stage burns the
            # core; cpu_ms << total_ms means it waits (GIL, device, disk).
            "cpu_ms": round(h.get("cpu", 0.0) * 1e3, 1),
            "count": sum(h["counts"]),
            "p50_ms": round(quantile(h["counts"], 0.5) * 1e3, 3),
            "share": round(h["sum"] / e2e_s, 3) if e2e_s else 0.0,
        }

    for name in leaves:
        h = _hist(name)
        if not h:
            continue
        leaf_total += h["sum"]
        rows[name] = _row(h)
    for name in nested:
        h = _hist(name)
        if not h:
            continue
        r = _row(h)
        # Barriers fan out across all 16 drives concurrently, so the summed
        # stage wall can exceed the end-to-end wall; call the ratio what it
        # is instead of a "share" that can read > 1.
        r["sum_over_e2e"] = r.pop("share")
        rows[name] = {**r, "nested": True}
    other = max(e2e_s - leaf_total, 0.0)
    rows["other"] = {
        "total_ms": round(other * 1e3, 1),
        "share": round(other / e2e_s, 3) if e2e_s else 0.0,
    }
    return {
        "ops": n,
        "end_to_end_ms": round(e2e_s * 1e3, 1),
        "end_to_end_cpu_ms": round(root.get("cpu", 0.0) * 1e3, 1) if root else 0.0,
        "stages": rows,
    }


def object_layer_metrics() -> dict:
    """PutObject / heal / concurrent-PUT throughput through ErasureObjects
    over 16 local drives (runPutObjectBenchmark + verify-healing roles,
    /root/reference/cmd/benchmark-utils_test.go:33,
    buildscripts/verify-healing.sh:16)."""
    import shutil
    import statistics
    import tempfile

    from minio_tpu.control import tracing
    from minio_tpu.control.perf import GLOBAL_PERF
    from minio_tpu.control.profiler import GLOBAL_PROFILER
    from minio_tpu.object.erasure import ErasureObjects
    from minio_tpu.storage import format as fmt
    from minio_tpu.storage import local as local_mod
    from minio_tpu.storage.local import LocalDrive
    from minio_tpu.storage.metered import MeteredDrive

    # Arm the continuous profiling plane for the bench run: the BENCH JSON
    # carries its summary (gil_load, top role stacks, copy ledger) so a
    # number regression comes with its own attribution.
    GLOBAL_PROFILER.ensure_started()

    from minio_tpu.parallel.batching import BatchingDeviceCodec

    codec = BatchingDeviceCodec(max_batch=64)

    root = tempfile.mkdtemp(prefix="bench-objs-", dir=os.path.dirname(os.path.abspath(__file__)))
    out: dict = {}
    try:
        dirs = [os.path.join(root, f"disk{i}") for i in range(16)]
        formats = fmt.init_format(1, 16)
        drives = []
        # Metered, as production stacks them (dist/node.py): the per-call
        # storage ledger is what backs the breakdown's drive-read row.
        for d, f in zip(dirs, formats):
            os.makedirs(d)
            f.save(d)
            drives.append(MeteredDrive(LocalDrive(d)))
        layer = ErasureObjects(drives, codec=codec)  # parity 4 -> 12+4
        layer.make_bucket("bench")

        rng = np.random.default_rng(3)
        body = rng.integers(0, 256, PUT_SIZE, dtype=np.uint8).tobytes()
        # Warm the jit/codec paths off the clock: a 17 MiB put covers the
        # full GROUP_BLOCKS bucket and the tail path, a 1 MiB put covers the
        # single-block bucket used by the latency probe.
        layer.put_object("bench", "warm", body[: 17 << 20])
        layer.put_object("bench", "warm1", body[: 1 << 20])
        layer.delete_object("bench", "warm")
        layer.delete_object("bench", "warm1")

        # --- BASELINE #4: serial PutObject (GiB/s + p50 latency) -----------
        # Each op runs under a bench root span so the always-on stage ledger
        # (control/perf.py) attributes where the wall clock went; the ledger
        # is reset per phase so the breakdown covers exactly these ops.
        GLOBAL_PERF.ledger.reset()
        lat = []
        for i in range(PUT_OBJECTS):
            t0 = time.perf_counter()
            with tracing.root_span("bench.put", "bench", f"bench-put-{i}"):
                layer.put_object("bench", f"o-{i}", body)
            lat.append(time.perf_counter() - t0)
            layer.delete_object("bench", f"o-{i}")  # bound disk use, off-clock
        total = sum(lat)
        put_snap = GLOBAL_PERF.ledger.snapshot()
        out["putobject_gibs"] = round(PUT_OBJECTS * PUT_SIZE / total / (1 << 30), 3)
        out["putobject_p50_ms"] = round(statistics.median(lat) * 1000, 1)
        # Requests/second as a first-class axis (the live cluster reports the
        # same unit via /mtpu/admin/v1/timeseries and the object speedtest).
        out["puts_per_s"] = round(PUT_OBJECTS / total, 2) if total else 0.0
        out["fsync_mode"] = local_mod.fsync_mode()

        # --- durability-knob overhead: same single-stream PUT, barriers off -
        # The crash-consistency plane put fdatasync barriers on the commit
        # path (MTPU_FSYNC, default `commit`); this phase prices them by
        # re-running a shorter single-stream PUT with MTPU_FSYNC=never. The
        # gap between putobject_nosync_gibs and putobject_gibs is exactly
        # what the barriers cost on this disk.
        n_nosync = max(4, PUT_OBJECTS // 4)
        prev_fsync = os.environ.get("MTPU_FSYNC")
        os.environ["MTPU_FSYNC"] = local_mod.FSYNC_NEVER
        try:
            lat_ns = []
            for i in range(n_nosync):
                t0 = time.perf_counter()
                layer.put_object("bench", f"ns-{i}", body)
                lat_ns.append(time.perf_counter() - t0)
                layer.delete_object("bench", f"ns-{i}")
        finally:
            if prev_fsync is None:
                os.environ.pop("MTPU_FSYNC", None)
            else:
                os.environ["MTPU_FSYNC"] = prev_fsync
        out["putobject_nosync_gibs"] = round(
            n_nosync * PUT_SIZE / sum(lat_ns) / (1 << 30), 3
        )

        # BASELINE primary metric geometry: PutObject p50 at 1 MiB objects
        # (12+4 @ 1 MiB block -- one block per object, latency-bound).
        small = body[: 1 << 20]
        lat1 = []
        for i in range(50):
            t0 = time.perf_counter()
            layer.put_object("bench", f"s-{i}", small)
            lat1.append(time.perf_counter() - t0)
        out["putobject_1mib_p50_ms"] = round(statistics.median(lat1) * 1000, 2)
        for i in range(50):
            layer.delete_object("bench", f"s-{i}")

        # --- GetObject throughput (the speedtest GET side, cmd/utils.go:976) -
        # Chunks land in a reusable sink via memoryview assignment -- the
        # bench's stand-in for the server's socket writev -- so the GET
        # breakdown carries an honest response-write row instead of folding
        # the consumer into "other".
        sink = bytearray(4 << 20)

        def read_once(lyr, key: str) -> int:
            _, it = lyr.get_object_stream("bench", key)
            n = 0
            wr_w = wr_c = 0.0
            for c in it:
                lc = len(c)
                if lc > len(sink):
                    sink.extend(bytes(lc - len(sink)))
                t0 = time.perf_counter()
                c0 = time.thread_time()
                sink[:lc] = c
                wr_w += time.perf_counter() - t0
                wr_c += time.thread_time() - c0
                n += lc
            GLOBAL_PERF.ledger.record("api", "response-write", wr_w, wr_c)
            return n

        layer.put_object("bench", "getobj", body)
        assert read_once(layer, "getobj") == PUT_SIZE
        GLOBAL_PERF.ledger.reset()
        copy0 = GLOBAL_PROFILER.copy.snapshot()["hops"]
        t0 = time.perf_counter()
        get_iters = 4
        for gi in range(get_iters):
            with tracing.root_span("bench.get", "bench", f"bench-get-{gi}"):
                read_once(layer, "getobj")
        get_dt = time.perf_counter() - t0
        out["getobject_gibs"] = round(get_iters * PUT_SIZE / get_dt / (1 << 30), 3)
        out["gets_per_s"] = round(get_iters / get_dt, 2) if get_dt else 0.0
        out["total_ops_per_s"] = round(
            (PUT_OBJECTS + get_iters) / (total + get_dt), 2
        ) if (total + get_dt) else 0.0
        # Zero-copy scorecard for the healthy cold loop just timed: readinto
        # drive reads and memoryview frame-parse are MOVED hops; a single
        # COPIED byte here is a read-pipeline regression (the ISSUE 13
        # acceptance line, twin of the conservation test).
        copy1 = GLOBAL_PROFILER.copy.snapshot()["hops"]

        def _copy_delta(kind: str) -> int:
            after = sum(h[kind] for h in copy1.values())
            return after - sum(h[kind] for h in copy0.values())

        out["get_copied_bytes"] = _copy_delta("copied_bytes")
        out["get_moved_bytes"] = _copy_delta("moved_bytes")
        layer.delete_object("bench", "getobj")

        # --- hot-read tier: memcache cold/hot split ------------------------
        # The same GET geometry through the coherent memory cache
        # (object/memcache.py): the first read misses and fills (the cold
        # half of the split -- full shard IO plus the fill admit), the rest
        # serve from process memory. getobject_hot_gibs is the acceptance
        # headline: >= 2x the cold streaming number above. Validation off:
        # a single-process bench has no peers to stay coherent with.
        from minio_tpu.object.memcache import (
            MemCacheConfig,
            MemCacheObjectLayer,
            MemObjectCache,
        )

        hot_size = min(PUT_SIZE, 32 << 20)
        mc = MemObjectCache(MemCacheConfig(limit_bytes=256 << 20, validate=False))
        mc_layer = MemCacheObjectLayer(layer, mc)
        layer.put_object("bench", "hotobj", body[:hot_size])
        t0 = time.perf_counter()
        with tracing.root_span("bench.get", "bench", "bench-hotget-fill"):
            assert read_once(mc_layer, "hotobj") == hot_size  # miss + fill
        out["getobject_fill_gibs"] = round(
            hot_size / (time.perf_counter() - t0) / (1 << 30), 3
        )
        hot_iters = 8
        t0 = time.perf_counter()
        for gi in range(hot_iters):
            with tracing.root_span("bench.get", "bench", f"bench-hotget-{gi}"):
                assert read_once(mc_layer, "hotobj") == hot_size
        out["getobject_hot_gibs"] = round(
            hot_iters * hot_size / (time.perf_counter() - t0) / (1 << 30), 3
        )
        out["memcache"] = mc.stats()  # incl. hit_ratio of this split
        layer.delete_object("bench", "hotobj")

        # One GET row set spanning both halves of the split (cold loop +
        # fill + hot serves ran in the same ledger window under bench.get
        # roots). drive-read/frame-parse run on fan-out pool threads inside
        # the shard-read gather, and the fill's backend read re-enters
        # shard-read -- nested, not leaves, or the same seconds would count
        # twice.
        get_snap = GLOBAL_PERF.ledger.snapshot()
        out["stage_breakdown"] = {
            "put": _stage_breakdown(
                put_snap, "bench.put", ("encode", "shard-fanout", "commit"),
                nested=("drive-sync",),
            ),
            "get": _stage_breakdown(
                get_snap, "bench.get",
                ("shard-read", "decode", "cache-hit", "response-write"),
                nested=("drive-read", "frame-parse", "cache-fill"),
                aliases={"drive-read": "read_file_into"},
            ),
        }
        out["profile"] = GLOBAL_PROFILER.summary()

        # --- 8-concurrent-PUT aggregate (batching fan-in under load) -------
        cbody = body[:CONCURRENT_SIZE]
        rounds = 4

        def cput(i):
            for r in range(rounds):
                layer.put_object("bench", f"c-{i}-{r}", cbody)

        pool = ThreadPoolExecutor(max_workers=CONCURRENT_PUTS)
        t0 = time.perf_counter()
        list(pool.map(cput, range(CONCURRENT_PUTS)))
        dt = time.perf_counter() - t0
        out["concurrent_put_gibs"] = round(
            CONCURRENT_PUTS * rounds * CONCURRENT_SIZE / dt / (1 << 30), 3
        )
        for i in range(CONCURRENT_PUTS):
            for r in range(rounds):
                layer.delete_object("bench", f"c-{i}-{r}")

        # --- BASELINE #5: heal with 3 shards lost (GiB/s of object data) ---
        part_body = body  # PUT_SIZE-sized parts (128 MiB by default)
        n_parts = int(max(1, HEAL_BYTES // len(part_body)))
        up = layer.multipart.new_multipart_upload("bench", "healobj")
        parts = []
        for p in range(1, n_parts + 1):
            pi = layer.multipart.put_object_part("bench", "healobj", up, p, part_body)
            parts.append((p, pi.etag))
        layer.multipart.complete_multipart_upload("bench", "healobj", up, parts)
        # Lose 3 data-row shard files.
        fi, _, _ = layer._read_quorum_fi("bench", "healobj", "")
        lost = 0
        for i, rot in enumerate(fi.erasure.distribution):
            if rot - 1 < 12:  # data row
                obj_dir = os.path.join(dirs[i], "bench", "healobj")
                if os.path.isdir(obj_dir):
                    shutil.rmtree(obj_dir)
                    lost += 1
            if lost == 3:
                break
        t0 = time.perf_counter()
        res = layer.heal_object("bench", "healobj")
        dt = time.perf_counter() - t0
        out["heal_disks_healed"] = res.disks_healed
        out["heal_gibs"] = round(n_parts * len(part_body) / dt / (1 << 30), 3)

        # --- transparent-compression codec (S2 role, object-api-utils.go:907)
        from minio_tpu.control import compress as compress_mod

        with open(os.path.abspath(__file__), "rb") as f:
            src = f.read()
        text = (src * (1 + (64 << 20) // len(src)))[: 64 << 20]
        t0 = time.perf_counter()
        blob, cmeta = compress_mod.compress(text)
        ct = time.perf_counter() - t0
        t0 = time.perf_counter()
        back = compress_mod.decompress(blob, cmeta)
        dt = time.perf_counter() - t0
        assert back == text
        out["compress_algo"] = cmeta[compress_mod.META_COMPRESSION]
        out["compress_gibs"] = round(len(text) / ct / (1 << 30), 3)
        out["decompress_gibs"] = round(len(text) / dt / (1 << 30), 3)
        out["compress_ratio"] = round(len(blob) / len(text), 3)
    finally:
        codec.close()
        shutil.rmtree(root, ignore_errors=True)
    return out


def device_metrics() -> dict:
    """Encode / hash / fused / reconstruct GiB/s on the live device. Any
    kernel that fails to lower or run raises and fails the run."""
    import jax
    import jax.numpy as jnp

    from minio_tpu.ops import fused as fused_ops
    from minio_tpu.ops import highwayhash_jax as hhj
    from minio_tpu.ops import highwayhash_pallas as hhp
    from minio_tpu.models.pipeline import hash_batch_fn
    from minio_tpu.ops import rs

    d0 = jax.devices()[0]
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (BATCH, K, SHARD), dtype=np.uint8)
    dev = jax.device_put(jnp.asarray(data))

    def gibs(fn, arg, nbytes: int, iters: int) -> float:
        jax.block_until_ready(fn(arg))  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(arg)
        jax.block_until_ready(out)
        return nbytes * iters / (time.perf_counter() - t0) / (1 << 30)

    codec = rs.RSCodec(K, M)
    enc_gibs = gibs(jax.jit(codec.encode), dev, BATCH * BLOCK, ITERS)

    # Hash-only throughput of both device implementations over the fused
    # batch's stream shape; the fused number below uses the one that serves.
    hdata = jax.device_put(
        jnp.asarray(
            rng.integers(0, 256, (FUSED_BATCH * (K + M), SHARD), dtype=np.uint8)
        )
    )
    hash_impls = {"xla": hhj.hash256_batch, "pallas": hhp.hash256_batch}
    hiters = max(4, ITERS // 2)
    hash_gibs = {
        name: gibs(jax.jit(fn), hdata, hdata.size, hiters)
        for name, fn in hash_impls.items()
    }

    # Reconstruct 4 missing data shards from the 12 surviving rows.
    w = codec.reconstruct_weights(PRESENT, MISSING)
    full = np.asarray(codec.encode_all(dev))
    surv = jnp.asarray(full[:, [j for j in range(K + M) if PRESENT[j]][:K], :])
    dec_gibs = gibs(jax.jit(lambda s: codec.apply(s, w)), surv, BATCH * BLOCK, ITERS)

    # The fused program hashes all K+M rows and returns parity + digests.
    fdev = jax.device_put(jnp.asarray(data[:FUSED_BATCH]))
    fused_gibs = gibs(
        jax.jit(fused_ops.make_step(codec.encode, hash_batch_fn())),
        fdev, FUSED_BATCH * BLOCK, hiters,
    )

    # Multi-chip fan-out: data-parallel encode over every local device via
    # shard_map ((n,1,1) mesh). Scaling efficiency is vs n * the single-chip
    # encode number.
    multichip_gibs = 0.0
    multichip_eff = 0.0
    n_dev = len(jax.devices())
    if n_dev > 1:
        from jax.sharding import PartitionSpec as P

        from minio_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.make_mesh(n_dev, (n_dev, 1, 1))
        menc = jax.jit(
            jax.shard_map(
                codec.encode,
                mesh=mesh,
                in_specs=P("dp", None, None),
                out_specs=P("dp", None, None),
                check_vma=False,
            )
        )
        mb = -(-BATCH // n_dev) * n_dev
        mdata = jax.device_put(
            jnp.asarray(rng.integers(0, 256, (mb, K, SHARD), dtype=np.uint8)),
            mesh_lib.data_sharding(mesh),
        )
        multichip_gibs = gibs(menc, mdata, mb * BLOCK, ITERS)
        multichip_eff = multichip_gibs / (enc_gibs * n_dev)
    return {
        "platform": d0.platform,
        "device_kind": d0.device_kind,
        "device_count": n_dev,
        "encode_gibs": enc_gibs,
        "decode_recon4_gibs": dec_gibs,
        "fused_encode_hash_gibs": fused_gibs,
        "hash_xla_gibs": round(hash_gibs["xla"], 3),
        "hash_pallas_gibs": round(hash_gibs["pallas"], 3),
        "multichip_encode_gibs": multichip_gibs,
        "multichip_devices": n_dev,
        "multichip_scaling_eff": round(multichip_eff, 3),
    }


def main() -> int:
    from minio_tpu.runtime import probe_device

    probe = probe_device(PROBE_TIMEOUT_S)
    if not probe.ok:
        print(
            "bench: no accelerator -- "
            + (probe.error or f"jax reports platform {probe.platform!r}"),
            file=sys.stderr,
        )
        print(probe.detail, file=sys.stderr)
        return 1

    from minio_tpu import jaxenv
    from minio_tpu.control.flight import GLOBAL_FLIGHT
    from minio_tpu.models.pipeline import kernel_status

    jaxenv.enable_compile_cache()
    rng = np.random.default_rng(1)
    blocks = rng.integers(0, 256, (BATCH, K, SHARD), dtype=np.uint8)
    cpu_enc = cpu_encode_gibs(blocks)
    cpu_dec = cpu_decode_gibs(blocks[: max(32, BATCH // 8)])
    dm = device_metrics()
    if dm["platform"] != probe.platform:
        print(
            f"bench: probe child saw {probe.platform!r} but this process "
            f"opened {dm['platform']!r}",
            file=sys.stderr,
        )
        return 1
    obj = object_layer_metrics()
    enc = dm["encode_gibs"]
    line = {
        "metric": f"erasure-encode GiB/s (12+4 @ 1MiB, batch {BATCH}, {dm['platform']})",
        "value": round(enc, 3),
        "unit": "GiB/s",
        "vs_baseline": round(enc / cpu_enc, 3) if cpu_enc else 0.0,
        "device": True,
        "platform": dm["platform"],
        "device_kind": dm["device_kind"],
        "device_count": dm["device_count"],
        "cpu_avx2_gibs": round(cpu_enc, 3),
        "fused_encode_hash_gibs": round(dm["fused_encode_hash_gibs"], 3),
        "hash_xla_gibs": dm["hash_xla_gibs"],
        "hash_pallas_gibs": dm["hash_pallas_gibs"],
        "multichip_encode_gibs": round(dm["multichip_encode_gibs"], 3),
        "multichip_devices": dm["multichip_devices"],
        "multichip_scaling_eff": dm["multichip_scaling_eff"],
        "kernel_status": kernel_status(),
        "decode_recon4_gibs": round(dm["decode_recon4_gibs"], 3),
        "cpu_decode_recon4_gibs": round(cpu_dec, 3),
        "decode_vs_baseline": (
            round(dm["decode_recon4_gibs"] / cpu_dec, 3) if cpu_dec else 0.0
        ),
        # Flight triggers fired mid-round taint the numbers: a bench second
        # that also dumped a diagnostic bundle measured the incident.
        "flight_triggers_fired": sum(GLOBAL_FLIGHT.stats()["triggers"].values()),
        **obj,
    }
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.exit(main())
