"""The system under test: one node, in this process, which holds the chip.

The node is built and served as ``minio_tpu.cli.serve`` does it (self-test,
``Node(...)``, ``make_app`` on a loopback port through ``_run_app_until``, then
``node.build()``, then the scanner), with ``MINIO_TPU_CODEC=device`` so that the
install raises unless jax opened the TPU and no probe child opens the chip a
second time. Its drives are directories of a memory-backed file system: the
program issues every barrier it issues in a deployment (``MTPU_FSYNC`` stays at
its default), the medium answers at once.

The boot, the wait for the takeover and the shard-losing helpers follow
``chip_smoke.py`` (copied, not imported).
"""

from __future__ import annotations

import os
import shutil
import socket
import threading
import time
import zlib

ACCESS, SECRET, REGION = "benchadmin", "bench-secret-key-0001", "us-east-1"
BUCKET = "bench"
SHM = "/dev/shm"
PREFIX = "mtpu-bench-"
# Counters of the S3 front (minio_tpu.control.metrics.MetricsSys) a snapshot carries.
FRONT_COUNTERS = ("get_stream_hops", "get_stream_chunks")
# Env that would move the server off the deployment a configuration pins
# (chip_smoke.py's list); a configuration's own `env` is applied after.
PINNED_ENV = (
    "MINIO_TPU_CODEC", "MINIO_TPU_RS", "MINIO_TPU_HASH", "MTPU_WORKERS",
    "MTPU_MESH_SHAPE", "MTPU_BATCH_WAIT_US", "MTPU_FSYNC", "MTPU_PROBE_CACHE",
    "MTPU_MEMCACHE_MB", "MTPU_FAST_ETAG", "MINIO_STORAGE_CLASS_STANDARD",
    "MINIO_STORAGE_CLASS_RRS", "MINIO_ERASURE_SET_DRIVE_COUNT",
)


# glibc's mallopt(3) parameters a traffic file may pin (name -> number in <malloc.h>)
MALLOPT_PARAMS = {"M_TRIM_THRESHOLD": -1, "M_TOP_PAD": -2, "M_MMAP_THRESHOLD": -3}


class SetupError(Exception):
    """The run cannot be made: no chip, no room, a server that did not start."""


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        pass  # it lives, and is not ours to signal
    return True


def clean_stale(root: str = SHM) -> list[str]:
    """Remove drive directories that dead runs left behind."""
    gone = []
    for name in os.listdir(root):
        if not name.startswith(PREFIX):
            continue
        pid = name[len(PREFIX):]
        if pid.isdigit() and _pid_alive(int(pid)) and int(pid) != os.getpid():
            continue
        shutil.rmtree(os.path.join(root, name), ignore_errors=True)
        gone.append(name)
    return gone


def dir_bytes(path: str) -> int:
    total = 0
    for base, _, files in os.walk(path):
        for f in files:
            try:
                total += os.lstat(os.path.join(base, f)).st_size
            except OSError:
                pass
    return total


def hash_order(key: str, cardinality: int) -> list[int]:
    """1-based shard row of each drive for an object: the reference's
    hashOrder (cmd/erasure-metadata-utils.go), as minio_tpu/utils/hashes.py
    has it. Drive i holds row hash_order(bucket/key)[i] - 1."""
    start = (zlib.crc32(key.encode()) & 0xFFFFFFFF) % cardinality
    return [1 + ((start + i) % cardinality) for i in range(1, cardinality + 1)]


class Deployment:
    """One configuration, running. `rehearse` serves the same programs on
    jax's CPU backend (MINIO_TPU_CODEC=xla-cpu) for the sandbox."""

    def __init__(self, config: dict, rehearse: bool = False, parity: int | None = None):
        self.config = config
        self.rehearse = rehearse
        self.drives = int(config["drives"])
        self.parity = int(config["parity"]) if parity is None else parity
        self.root = os.path.join(SHM, f"{PREFIX}{os.getpid()}")
        self.drive_dirs = [os.path.join(self.root, f"d{i + 1}") for i in range(self.drives)]
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.endpoint = f"http://127.0.0.1:{self.port}"
        self.node = None
        self._stop = threading.Event()
        self._http: threading.Thread | None = None
        self.install: dict = {}
        self.compile_events: list[float] = []

    # -- start -------------------------------------------------------------------

    def start(self, need_bytes: int, mallopt: dict[str, int] | None = None) -> None:
        if mallopt:
            self.pin_allocator(mallopt)
        if not os.path.isdir(SHM):
            raise SetupError(f"{SHM} is not there: the drives are memory-backed or nothing")
        clean_stale()
        free = shutil.disk_usage(SHM).free
        if free < need_bytes:
            raise SetupError(f"{SHM} has {free} bytes free, the cell needs {need_bytes}")
        for d in self.drive_dirs:
            os.makedirs(d)
        for name in PINNED_ENV:
            os.environ.pop(name, None)
        os.environ.update(self.config.get("env", {}))
        if self.rehearse:
            os.environ["MINIO_TPU_CODEC"] = "xla-cpu"
            os.environ["JAX_PLATFORMS"] = "cpu"

        import jax

        # Every backend compile from here on is stamped: the window must hold none.
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

        from minio_tpu.cli import _run_app_until, boot_self_test
        from minio_tpu.dist.node import Node
        from minio_tpu.runtime import install_status

        from minio_tpu.ops import native

        # The host kernels (-march=native, git-ignored) are built where they
        # run, on first use, as in a deployment; a numpy fallback would be
        # measured as a slow server.
        if not native.available():
            raise SetupError("the native host kernels did not build or load (see the log)")
        boot_self_test()
        self.node = Node(
            self.drive_dirs, root_user=ACCESS, root_password=SECRET,
            parity=self.parity if self.parity != self.config["parity"] else None,
            region=REGION,
        )
        app = self.node.make_app()
        self._http, errors = _run_app_until(app, "127.0.0.1", self.port, self._stop)
        if errors:
            raise SetupError(f"HTTP server failed to start: {errors[0]}")
        try:
            self.node.build()  # blocks until the device codec is warmed and serving
        except Exception as e:  # noqa: BLE001 - boundary: any install failure ends the run
            raise SetupError(f"node bootstrap failed: {type(e).__name__}: {e}") from e
        self.install = install_status()
        if self.install.get("state") != "serving":
            raise SetupError(f"the device codec does not serve: {self.install}")
        want = "cpu" if self.rehearse else "tpu"
        if self.install.get("platform") != want:
            raise SetupError(f"the codec serves on {self.install.get('platform')!r}, not {want!r}")
        k, m = self.drives - self.parity, self.parity
        if self.install.get("geometry") != [k, m]:
            raise SetupError(f"warmed geometry {self.install.get('geometry')}, cell wants {[k, m]}")
        self.node.scanner.start()

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if "backend_compile" in event:
            self.compile_events.append(time.monotonic())

    @staticmethod
    def pin_allocator(pins: dict[str, int]) -> None:
        """Fix glibc malloc's policy for this process, the server's:
        ``mallopt(3)`` for each of `pins` (MALLOPT_PARAMS names them), which is what ``MALLOC_TOP_PAD_`` and its kin in the server's
        environment do at its start; this process has started already when a
        cell's files are read. Left alone the policy is a matter of history: the
        mmap and trim thresholds rise for good when a large chunk happens to be
        freed, and an arena's 64 MiB heaps are unmapped and mapped again, or
        kept, by what else lies in them. A batch's arrays (16 to 64 MiB each) are
        then faulted in page by page, or not, from some moment of chance on, and
        a run of a minute reads when that moment came (PERF.md section 6, PR 34)."""
        import ctypes

        try:
            mallopt = ctypes.CDLL(None).mallopt
        except (OSError, AttributeError) as e:
            raise SetupError(f"no mallopt to pin the allocator with: {e}") from e
        mallopt.argtypes, mallopt.restype = (ctypes.c_int, ctypes.c_int), ctypes.c_int
        for name, value in pins.items():
            if mallopt(MALLOPT_PARAMS[name], value) != 1:
                raise SetupError(f"mallopt({name}, {value}) was refused")

    # -- sources the per-layer readers take ----------------------------------------

    def snapshot(self) -> dict:
        """Ledger, codec counters, the S3 front's counters, compile-cache
        entries and the clock, at one moment. Two of these are differenced
        into a window's per-layer numbers."""
        from minio_tpu import jaxenv
        from minio_tpu.control.perf import GLOBAL_PERF
        from minio_tpu.object import codec as codec_mod

        stats_fn = getattr(codec_mod._default, "stats", None)
        try:
            cache = len(os.listdir(jaxenv.compile_cache_dir()))
        except OSError:
            cache = 0
        ledger = {}
        for layer, stages in GLOBAL_PERF.ledger.snapshot()["stages"].items():
            for stage, h in stages.items():
                ledger[f"{layer}/{stage}"] = {
                    "count": sum(h["counts"]), "wall_s": h["sum"], "cpu_s": h["cpu"]}
        return {
            "t": time.monotonic(),
            "ledger": ledger,
            "codec": dict(stats_fn()) if stats_fn else {},
            "front": {name: getattr(self.node.metrics, name) for name in FRONT_COUNTERS
                      if hasattr(self.node.metrics, name)},
            "cache_entries": cache,
            "compiles": len(self.compile_events),
        }

    def device(self) -> dict:
        import jax

        devs = jax.devices()
        peak = 0
        for d in devs:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs), "memory_peak_bytes": peak}

    # -- losing shards (chip_smoke.py's shard_dirs / lose) -----------------------------

    def shard_dirs(self, key: str) -> list[tuple[int, str]]:
        """(shard row, object directory) per drive, in drive order."""
        order = hash_order(f"{BUCKET}/{key}", self.drives)
        return [(order[i] - 1, os.path.join(self.drive_dirs[i], BUCKET, key))
                for i in range(self.drives)]

    def lose_shards(self, key: str, data: int) -> list[str]:
        """Bring an object to `data` missing data shards: the victims are the
        first `data` drives, in drive order, that hold a data row, so a second
        call finds what the first removed and removes nothing more. Any other
        directory of the object that is not there is an error: the object was
        not stored where the placement says. Returns the victims."""
        k = self.drives - self.parity
        dirs = self.shard_dirs(key)
        victims = [d for row, d in dirs if row < k][:data]
        for _, d in dirs:
            if d not in victims and not os.path.isdir(d):
                raise FileNotFoundError(f"expected shard directory {d}")
        for d in victims:
            if os.path.isdir(d):
                shutil.rmtree(d)
        return victims

    def stored_bytes(self) -> int:
        return sum(dir_bytes(os.path.join(d, BUCKET)) for d in self.drive_dirs)

    def wipe_objects(self) -> None:
        """Empty the bucket's directories on every drive, between the
        independent windows of benchmark/tests/sweep.py (never inside a run)."""
        for d in self.drive_dirs:
            top = os.path.join(d, BUCKET)
            for name in os.listdir(top) if os.path.isdir(top) else ():
                shutil.rmtree(os.path.join(top, name), ignore_errors=True)

    # -- stop --------------------------------------------------------------------

    def close(self) -> None:
        node = self.node
        if node is not None:
            from minio_tpu.runtime import shutdown_data_plane

            try:
                node.close()
                shutdown_data_plane(node.codec)
            except Exception as e:  # noqa: BLE001 - teardown goes on to free the memory
                print(f"[bench] teardown: {type(e).__name__}: {e}", flush=True)
        self._stop.set()
        if self._http is not None:
            self._http.join(5)
        shutil.rmtree(self.root, ignore_errors=True)
