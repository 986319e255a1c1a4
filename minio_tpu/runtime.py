"""Data-plane runtime: install the block codec the server actually serves with.

The reference's hot path always runs its fast codec (AVX2 reedsolomon,
cmd/erasure-coding.go:63). Here the equivalent decision happens once at boot:
if an accelerator is reachable, every PutObject/heal block goes through the
cross-request batching device pipeline (parallel/batching.py); otherwise the
host C++/numpy codec serves (object/codec.py HostCodec).

One process per chip. A chip belongs to the process that opened it, and a
second opener fails or hangs. So device init is probed in a bounded child
process first, and server boot must never wedge on it. The probe
 * runs exactly once per process (cached -- repeated Node builds / tests
   must not fork probe swarms),
 * is strictly sequential with this process's own use of the chip: the
   child opens the chip, proves it executes and EXITS (or is killed as a
   process group on timeout) before probe_device returns, and this process
   first touches jax only after that,
 * keeps the child's stdout/stderr tail -- including an in-child
   faulthandler dump of the wedged stack -- so a timeout carries evidence.
Pre-fork accept workers (MTPU_WORKERS) are N processes on one chip, so they
install the host codec and never probe.

Nothing here hides the device: the device codec is built only on the backend
the mode names, is warmed and oracle-checked before it takes over
(BatchingDeviceCodec.warm), and its takeover -- or the reason it did not
happen -- is logged once and exported by probe_summary().

Env:
    MINIO_TPU_CODEC = auto | device | host | xla-cpu   (default auto)
        auto     probe; device codec on an accelerator, host codec otherwise
        device   device codec on the TPU; raises at install without one
        host     host codec, no probe
        xla-cpu  the device pipeline on jax's CPU backend: debugs the device
                 path in a sandbox without a chip (chip_smoke.py --allow-cpu,
                 tests). Never a serving or measurement mode.
    MINIO_TPU_DEVICE_PROBE_S                 probe timeout, default 60
    MTPU_PROBE_CACHE                         path of a cross-process verdict
                                             cache file ("" / unset = off)
    MTPU_PROBE_CACHE_TTL_S                   verdict freshness, default 3600
                                             (failed verdicts: capped at 900)
"""

from __future__ import annotations

import atexit
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field


from .object import codec as codec_mod
from .control import tracing
from .control.logging import GLOBAL_LOGGER
from .control.sanitizer import san_lock, san_rlock


@dataclass
class ProbeResult:
    """Outcome of one bounded device-init probe."""

    platform: str | None  # "tpu" / "cpu" as the child's jax reports; None on failure
    device_kind: str | None = None
    device_count: int = 0
    error: str | None = None  # short reason on failure
    detail: str = ""  # stdout+stderr tail (faulthandler dump of a wedged init)
    cached: bool = False  # True when served from the cross-process file cache

    @property
    def ok(self) -> bool:
        return self.platform not in (None, "cpu")


_live_probe_pgids: set[int] = set()
_probe_lock = san_lock("runtime._probe_lock")
_probe_once_lock = san_lock("runtime._probe_once_lock")  # single-flight: at most one child at a time
_probe_cache: ProbeResult | None = None
_atexit_registered = False


def _reap_live_probes() -> None:
    """Kill any probe process groups still alive at interpreter exit."""
    with _probe_lock:
        pgids = list(_live_probe_pgids)
        _live_probe_pgids.clear()
    for pgid in pgids:
        try:
            os.killpg(pgid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError, OSError):
            pass


def _tail(text: str, limit: int = 4000) -> str:
    return text[-limit:] if len(text) > limit else text


# -- cross-process probe verdict cache ----------------------------------------
#
# The in-memory cache above is per-process. When MTPU_PROBE_CACHE names a
# file, completed verdicts are stored there with a timestamp and honored
# within MTPU_PROBE_CACHE_TTL_S (default 3600 s). Failed verdicts are honored
# for at most 900 s -- a recovered device must not stay masked for an hour.
# The cache is OPT-IN and nothing in the repo sets it by default: a stale "no
# device" verdict from another machine or run must never answer for the chip.

_PROBE_FAIL_TTL_CAP_S = 900.0

# Verdict *transitions* are first-class: a device falling over (ok -> fail,
# "fallback") and coming back (fail -> ok, "recovery") are the two events an
# operator actually pages on, and a cache that silently flips between them
# hides both. Every stored verdict is diffed against the previous one (file
# or in-memory); the last _TRANSITIONS_KEPT flips ride along in the cache
# file and the latest is exposed via probe_transition() for bench JSON.
_TRANSITIONS_KEPT = 8
_last_transition: dict | None = None
# In-process fallback/recovery tallies (metrics + perf endpoint): how many
# times this process saw the verdict flip each way.
_transition_counts = {"fallback": 0, "recovery": 0}


def _transition_between(prev_platform, result: "ProbeResult") -> dict | None:
    """A fallback/recovery record when the ok-ness flipped, else None."""
    prev_ok = prev_platform not in (None, "cpu")
    if prev_ok == result.ok:
        return None
    return {
        "time": time.time(),
        "kind": "recovery" if result.ok else "fallback",
        "from": prev_platform,
        "to": result.platform,
    }


def _note_transition(t: dict | None) -> None:
    global _last_transition
    if t is not None:
        with _probe_lock:
            _last_transition = t
            if t.get("kind") in _transition_counts:
                _transition_counts[t["kind"]] += 1


def probe_transition_counts() -> dict:
    """{"fallback": n, "recovery": n} verdict flips seen by this process."""
    with _probe_lock:
        return dict(_transition_counts)


def probe_transition() -> dict | None:
    """The most recent ok<->fail probe transition, or None if the verdict
    has never flipped. In-process memory first, then the cross-process
    cache file -- so a fresh bench process still reports the fallback (or
    recovery) that the verdict it inherited went through."""
    with _probe_lock:
        if _last_transition is not None:
            return dict(_last_transition)
    path = _probe_cache_file()
    if not path:
        return None
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    t = doc.get("transition")
    return t if isinstance(t, dict) else None


def _probe_cache_file() -> str:
    return os.environ.get("MTPU_PROBE_CACHE", "")


def _probe_cache_ttl() -> float:
    try:
        return float(os.environ.get("MTPU_PROBE_CACHE_TTL_S", "") or 3600.0)
    except ValueError:
        return 3600.0


def _load_probe_file() -> ProbeResult | None:
    """Fresh cached verdict from MTPU_PROBE_CACHE, or None (disabled /
    missing / stale / unreadable -- every miss means 'probe for real')."""
    path = _probe_cache_file()
    if not path:
        return None
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(doc, dict) or "time" not in doc:
        return None
    try:
        age = time.time() - float(doc["time"])
    except (TypeError, ValueError):
        return None
    ttl = _probe_cache_ttl()
    platform = doc.get("platform") or None
    if platform in (None, "cpu"):
        ttl = min(ttl, _PROBE_FAIL_TTL_CAP_S)
    if age < 0 or age >= ttl:
        return None
    return ProbeResult(
        platform,
        doc.get("device_kind") or None,
        error=doc.get("error") or None,
        detail=str(doc.get("detail", "")),
        cached=True,
    )


def _store_probe_file(result: ProbeResult) -> None:
    """Best-effort atomic write of the verdict (tmp + rename); a cache that
    cannot be written must never fail the probe that produced the result."""
    path = _probe_cache_file()
    if not path:
        return
    # Diff against whatever verdict the file held -- even a stale one: a
    # flip across a TTL expiry is still a flip worth surfacing.
    transitions: list = []
    try:
        with open(path) as f:
            old = json.load(f)
        if isinstance(old, dict):
            prior = old.get("transitions")
            if isinstance(prior, list):
                transitions = [t for t in prior if isinstance(t, dict)]
            t = _transition_between(old.get("platform") or None, result)
            if t is not None:
                transitions.append(t)
                _note_transition(t)
    except (OSError, ValueError):
        pass
    transitions = transitions[-_TRANSITIONS_KEPT:]
    doc = {
        "time": time.time(),
        "platform": result.platform,
        "device_kind": result.device_kind,
        "error": result.error,
        "detail": _tail(result.detail, 2000),
        "transitions": transitions,
        "transition": transitions[-1] if transitions else None,
    }
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def probe_device(timeout_s: float, use_cache: bool = True) -> ProbeResult:
    """Bounded, evidence-preserving, non-leaking probe of jax device init.

    The child (``minio_tpu._probe_child``) arms a faulthandler dump before
    importing jax, so on timeout the captured tail pinpoints the wedge. The
    child runs in its own session; on timeout its whole process group is
    SIGKILLed, and an atexit hook reaps any probe that outlives us (e.g. a
    daemon-thread caller exiting mid-probe). The child has exited (or been
    killed) when this returns -- the caller may open the chip itself.
    """
    global _probe_cache, _atexit_registered
    # Single-flight: concurrent callers (e.g. several in-process nodes booting
    # with background installs) must not fork a probe swarm — the second
    # caller waits and gets the first's cached result.
    with _probe_once_lock:
        with _probe_lock:
            if use_cache and _probe_cache is not None:
                return _probe_cache
            if not _atexit_registered:
                atexit.register(_reap_live_probes)
                _atexit_registered = True
        if use_cache:
            filed = _load_probe_file()
            if filed is not None:
                with _probe_lock:
                    _probe_cache = filed
                return filed
        return _probe_uncached(timeout_s)


def probe_status() -> ProbeResult | None:
    """The cached probe outcome WITHOUT triggering a probe (metrics reads
    this: a scrape must never fork a device-init subprocess). None until a
    probe has run."""
    with _probe_lock:
        return _probe_cache


def _probe_uncached(timeout_s: float) -> ProbeResult:
    global _probe_cache
    out_f = tempfile.TemporaryFile(mode="w+b")
    err_f = tempfile.TemporaryFile(mode="w+b")
    try:
        proc = subprocess.Popen(
            [sys.executable, "-m", "minio_tpu._probe_child", str(timeout_s)],
            stdout=out_f,
            stderr=err_f,
            start_new_session=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
    except OSError as e:
        out_f.close()
        err_f.close()
        result = ProbeResult(None, error=f"spawn failed: {e}")
        with _probe_lock:
            _probe_cache = result
        return result

    pgid = proc.pid  # start_new_session=True -> child leads its own pgrp
    with _probe_lock:
        _live_probe_pgids.add(pgid)
    try:
        try:
            proc.wait(timeout=timeout_s)
            timed_out = False
        except subprocess.TimeoutExpired:
            timed_out = True
            try:
                os.killpg(pgid, signal.SIGKILL)
            except (ProcessLookupError, OSError):
                pass
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
    finally:
        with _probe_lock:
            _live_probe_pgids.discard(pgid)

    out_f.seek(0)
    err_f.seek(0)
    # SIGKILL can truncate mid-multibyte-sequence, and native PJRT/absl logs
    # aren't guaranteed UTF-8 — never let decoding errors mask the evidence.
    stdout = out_f.read().decode("utf-8", errors="replace")
    stderr = err_f.read().decode("utf-8", errors="replace")
    out_f.close()
    err_f.close()
    detail = _tail(stdout + ("\n--- stderr ---\n" + stderr if stderr else ""))

    if timed_out:
        result = ProbeResult(
            None, error=f"device init wedged past {timeout_s:.0f}s (killed pg)", detail=detail
        )
    else:
        ok_line = next(
            (ln for ln in reversed(stdout.splitlines()) if ln.startswith("PROBE_OK ")), None
        )
        if proc.returncode == 0 and ok_line:
            parts = ok_line.split()
            result = ProbeResult(
                parts[1],
                parts[2] if len(parts) > 2 else None,
                int(parts[3]) if len(parts) > 3 else 0,
                detail=detail,
            )
        else:
            result = ProbeResult(
                None, error=f"probe exit={proc.returncode}", detail=detail
            )
    with _probe_lock:
        prev = _probe_cache
        _probe_cache = result
    if prev is not None:
        _note_transition(_transition_between(prev.platform, result))
    _store_probe_file(result)
    return result


# install/shutdown share one lock so a background probe can't install a fresh
# device codec (spawning worker threads) after shutdown already closed the
# data plane (TOCTOU the advisor flagged).
_state_lock = san_lock("runtime._state_lock")
_closed = False

# What the last install did, for probe_summary(): {"state": "none" | "host" |
# "pending" | "serving" | "failed", ...}. "serving" carries the takeover
# report (_describe); "host"/"failed" carry the reason. Replaced whole, never
# mutated, so readers need no lock.
_install: dict = {"state": "none"}

# The geometry warmed when the caller names none: BASELINE.md's 16-drive set.
_DEFAULT_GEOMETRY = (12, 4)


def _open_backend() -> str:
    """The platform jax serves this process with. The first call initialises
    the backend, which on a TPU host opens the chip for this process."""
    import jax

    return jax.default_backend()


def _libtpu_version() -> str | None:
    from importlib import metadata

    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        return None


def _describe(codec, geometry, warm: dict | None, cache_added: int) -> dict:
    """The takeover report: where the device codec runs and with what."""
    import jax
    import jaxlib

    from . import jaxenv
    from .models import pipeline

    devs = jax.devices()
    mesh = codec.mesh if codec.mesh not in (None, "auto") else None
    return {
        "codec": type(codec).__name__,
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": _libtpu_version(),
        "geometry": list(geometry) if geometry else None,
        "mesh": {a: int(n) for a, n in mesh.shape.items()} if mesh is not None else None,
        "kernels": pipeline.kernel_status(),
        "warm": warm,
        "compile_cache": {"dir": jaxenv.compile_cache_dir(), "entries_added": cache_added},
    }


def _cache_entries() -> set[str]:
    from . import jaxenv

    try:
        return set(os.listdir(jaxenv.compile_cache_dir()))
    except OSError:
        return set()


def _make_batching(expect: str, geometry: tuple[int, int] | None):
    """Build the batching device codec on the `expect` backend, warm it and
    describe it: (codec, report). Raises -- never degrades -- when this
    process's backend is not `expect` (libtpu without a chip falls back to
    the CPU with only a warning; a probe child may have seen a chip this
    process then fails to open) or when warm-up fails or disagrees with the
    host codec."""
    from . import jaxenv

    jaxenv.enable_compile_cache()
    t0 = time.perf_counter()
    platform = _open_backend()
    if platform != expect:
        raise RuntimeError(
            f"device codec wants the {expect!r} backend but jax opened "
            f"{platform!r} in this process"
        )
    from .parallel.batching import BatchingDeviceCodec

    before = _cache_entries()
    codec = BatchingDeviceCodec()
    try:
        warm = codec.warm(*geometry) if geometry else None
        report = _describe(codec, geometry, warm, len(_cache_entries() - before))
    except BaseException:
        codec.close()
        raise
    report["setup_seconds"] = round(time.perf_counter() - t0, 3)
    return codec, report


def _announce(report: dict) -> None:
    global _install
    _install = {"state": "serving", **report}
    # The device codec serves, so jax is open in this process: from here on
    # every span and stage is also a host event of any profiler trace, on
    # the clock of the device's ops (control/tracing.py set_annotator).
    import jax

    tracing.set_annotator(jax.profiler.TraceAnnotation)
    kern = report["kernels"]
    warm = report["warm"] or {"programs": 0, "seconds": 0.0}
    GLOBAL_LOGGER.info(
        "device codec serving: platform={platform} kind={device_kind!r} "
        "devices={device_count} mesh={mesh} jax={jax} libtpu={libtpu}; "
        "rs={rs} ({rs_detail}); hash={hash} ({hash_detail}); warmed "
        "{programs} programs in {seconds}s, {added} compile-cache entries "
        "added".format(
            rs=kern["rs"]["serving"], rs_detail=kern["rs"]["detail"],
            hash=kern["hash"]["serving"], hash_detail=kern["hash"]["detail"],
            programs=warm["programs"], seconds=warm["seconds"],
            added=report["compile_cache"]["entries_added"], **report,
        ),
        install=report,
    )


def _stay_on_host(reason: str, state: str = "host", exc: BaseException | None = None) -> None:
    global _install
    _install = {"state": state, "codec": "HostCodec", "reason": reason}
    if exc is not None:
        GLOBAL_LOGGER.error(f"host codec serves: {reason}", exc=exc)
    else:
        GLOBAL_LOGGER.info(f"host codec serves: {reason}")


def _takeover(expect: str, geometry) -> None:
    """Background upgrade: build + warm off the lock (minutes on a cold
    cache; shutdown must not wait on it), then swap the process default.
    A failure is logged and exported, and the host codec keeps serving."""
    try:
        codec, report = _make_batching(expect, geometry)
    except Exception as e:  # noqa: BLE001 - boundary: the server keeps running on the host codec
        _stay_on_host(f"device codec install failed: {type(e).__name__}: {e}", "failed", e)
        return
    with _state_lock:
        if _closed:
            codec.close()
            return
        codec_mod.set_default_codec(codec)
    _announce(report)


# -- periodic recovery re-probe -----------------------------------------------
#
# A device that was unreachable at boot must not park the node on the CPU
# codec for its whole life. When an auto-mode install lands on the host
# codec, a single daemon re-probes on a cadence (MTPU_PROBE_RECOVERY_S,
# default 300 s; <= 0 disables) and swaps in the batching device codec on the
# first good verdict -- no restart. Each tick is the same bounded supervised
# child as boot, and the cross-process file cache (when one is configured)
# still amortizes verdicts, so the cadence bounds *wait*, not child spawns.

_reprobe_stop = threading.Event()
_reprobe_thread: threading.Thread | None = None
_recovery_probes = 0


def _recovery_interval_s() -> float:
    try:
        return float(os.environ.get("MTPU_PROBE_RECOVERY_S", "") or 300.0)
    except ValueError:
        return 300.0


def _recovery_loop(probe_timeout_s: float, geometry) -> None:
    global _probe_cache, _recovery_probes
    while True:
        interval = _recovery_interval_s()  # re-read: can be flipped live
        if interval <= 0:
            return
        if _reprobe_stop.wait(interval):
            return
        with _state_lock:
            if _closed:
                return
        with _probe_lock:
            # Drop only the in-memory verdict: probe_device would otherwise
            # return the boot-time failure forever. The file cache (if
            # configured) still answers within its failed-verdict TTL, so a
            # fleet of nodes doesn't re-probe in lockstep.
            prev = _probe_cache
            _probe_cache = None
            _recovery_probes += 1
        result = probe_device(probe_timeout_s)
        if prev is not None and (result.cached or not _probe_cache_file()):
            # _probe_uncached saw prev=None (we cleared it) and the file-cache
            # diff in _store_probe_file only runs on real probes with a cache
            # file configured -- cover the remaining paths here.
            _note_transition(_transition_between(prev.platform, result))
        if not result.ok:
            continue
        _takeover(result.platform, geometry)
        return


def _start_recovery_reprobe(probe_timeout_s: float, geometry) -> None:
    global _reprobe_thread
    if _recovery_interval_s() <= 0:
        return
    with _state_lock:
        if _closed:
            return
        if _reprobe_thread is not None and _reprobe_thread.is_alive():
            return
        _reprobe_stop.clear()
        # mtpulint: disable=unjoined-thread -- lifecycle bounded by the
        # _reprobe_stop event (shutdown_data_plane sets it) and the
        # _state_lock/_closed fence; exits on first good verdict.
        t = threading.Thread(
            target=_recovery_loop, args=(probe_timeout_s, geometry), daemon=True,
            name="codec-reprobe",
        )
        _reprobe_thread = t
        t.start()


def install_status() -> dict:
    """What the last install did: {"state": none | host | pending | serving
    | failed, ...} with the takeover report or the reason (see _install)."""
    return _install


def probe_summary() -> dict:
    """Probe and install state for the admin perf endpoint and metrics:
    verdict (with the child's evidence when it failed), what the install did
    with it (the takeover report or the reason the host codec serves),
    transition history, and recovery-reprobe posture. Never probes."""
    st = probe_status()
    with _probe_lock:
        reprobes = _recovery_probes
    armed = _reprobe_thread is not None and _reprobe_thread.is_alive()
    return {
        "done": st is not None,
        "ok": bool(st.ok) if st is not None else False,
        "platform": st.platform if st is not None else None,
        "device_kind": st.device_kind if st is not None else None,
        "device_count": st.device_count if st is not None else 0,
        "error": st.error if st is not None else None,
        "detail": _tail(st.detail, 2000) if st is not None and not st.ok else "",
        "cached": bool(st.cached) if st is not None else False,
        "install": install_status(),
        "transition": probe_transition(),
        "transition_counts": probe_transition_counts(),
        "recovery": {
            "interval_s": _recovery_interval_s(),
            "armed": armed,
            "reprobes": reprobes,
        },
    }


def install_data_plane_codec(
    mode: str | None = None,
    probe_timeout_s: float | None = None,
    background: bool = False,
    geometry: tuple[int, int] | None = _DEFAULT_GEOMETRY,
) -> codec_mod.BlockCodec:
    """Pick + install the process-wide codec; returns it.

    `geometry` is the (data, parity) shape the deployment's sets encode with:
    the device codec warms exactly the programs it needs before taking over
    (None warms nothing: a parity-less set never encodes).

    With background=True (server boot), auto mode installs the host codec
    immediately and upgrades the process default to the batching device
    codec from a daemon thread once the probe lands and warm-up has passed
    -- boot never blocks on device init or on a cold compile, and the object
    layer's lazy default-codec resolution makes the swap take effect on live
    traffic. The synchronous modes raise when the device codec cannot be
    built as asked; nothing falls back silently."""
    global _closed, _install
    with _state_lock:
        _closed = False
    mode = (mode or os.environ.get("MINIO_TPU_CODEC", "auto")).lower()
    if mode not in ("auto", "device", "host", "xla-cpu"):
        raise ValueError(f"MINIO_TPU_CODEC={mode!r}: want auto | device | host | xla-cpu")
    if probe_timeout_s is None:
        probe_timeout_s = float(os.environ.get("MINIO_TPU_DEVICE_PROBE_S", "60"))
    from .api.prefork import WORKER_ENV

    report = None
    if os.environ.get(WORKER_ENV) and mode != "host":
        # N accept workers are N processes; a chip takes one. Workers never
        # probe and never open jax.
        if mode == "device":
            raise RuntimeError(
                "MINIO_TPU_CODEC=device with MTPU_WORKERS>1: pre-fork workers "
                "are separate processes and one chip belongs to one process"
            )
        _stay_on_host(
            f"pre-fork worker {os.environ.get('MTPU_WORKER_ID', '?')}: one chip "
            "belongs to one process, so accept workers never open the device"
        )
        codec: codec_mod.BlockCodec = codec_mod.HostCodec()
    elif mode == "host":
        _install = {"state": "host", "codec": "HostCodec", "reason": "MINIO_TPU_CODEC=host"}
        codec = codec_mod.HostCodec()
    elif mode in ("device", "xla-cpu"):
        codec, report = _make_batching("tpu" if mode == "device" else "cpu", geometry)
    elif background:
        codec = codec_mod.HostCodec()
        codec_mod.set_default_codec(codec)
        _install = {"state": "pending", "codec": "HostCodec", "reason": "device probe running"}

        def _bg(timeout=probe_timeout_s):
            probe = probe_device(timeout)
            if not probe.ok:
                _stay_on_host(_no_device_reason(probe))
                _start_recovery_reprobe(timeout, geometry)
                return
            _takeover(probe.platform, geometry)

        # mtpulint: disable=unjoined-thread -- one-shot: the probe's timeout
        # and the finite warm-up plan cap its life; _takeover's
        # _state_lock/_closed handshake fences it against shutdown, which
        # must not block on it.
        threading.Thread(target=_bg, daemon=True, name="codec-probe").start()
        return codec
    else:  # auto, synchronous: only pay device round trips for an accelerator
        probe = probe_device(probe_timeout_s)
        if probe.ok:
            codec, report = _make_batching(probe.platform, geometry)
        else:
            _stay_on_host(_no_device_reason(probe))
            codec = codec_mod.HostCodec()
            _start_recovery_reprobe(probe_timeout_s, geometry)
    with _state_lock:
        if _closed:
            # shutdown_data_plane raced us: don't install after shutdown.
            close = getattr(codec, "close", None)
            if close is not None:
                close()
            return codec
        codec_mod.set_default_codec(codec)
    if report is not None:
        _announce(report)
    return codec


def _no_device_reason(probe: ProbeResult) -> str:
    if probe.platform is not None:
        return f"device probe found no accelerator (platform {probe.platform})"
    return f"device probe failed: {probe.error}"


def device_trace_start(log_dir: str) -> None:
    """Start a profiler trace of this process (the admin profile's
    `device=1`): device ops plus the program's own annotations; the Python
    call tracer stays off (it would log every call of a busy server)."""
    if _install.get("state") != "serving":
        raise RuntimeError("no device codec serves on this node: nothing to trace")
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def device_trace_stop(log_dir: str) -> tuple[str, dict]:
    """Stop the trace: (path of the .xplane.pb, its control/devtrace.py
    reduction -- busy/idle, seconds per program, the longest idle gaps with
    the host stages that overlap each)."""
    import glob

    import jax

    from .control import devtrace

    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under {log_dir}")
    return found[-1], devtrace.summarize(devtrace.load(found[-1]))


def shutdown_data_plane(codec: codec_mod.BlockCodec | None = None) -> None:
    """Close the batching codec (if installed); safe to call many times."""
    global _closed
    _reprobe_stop.set()
    tracing.set_annotator(None)
    with _state_lock:
        _closed = True
        targets = {id(codec): codec, id(codec_mod._default): codec_mod._default}
    for c in targets.values():
        close = getattr(c, "close", None)
        if close is not None:
            close()
