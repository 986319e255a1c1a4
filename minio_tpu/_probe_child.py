"""Device-probe child: bounded jax init with self-diagnosis.

Run as ``python -m minio_tpu._probe_child [timeout_s]`` in a fresh process.
Prints a machine-readable transcript on stdout/stderr that the parent
(runtime.probe_device) keeps even on timeout, so a wedged device init leaves
evidence instead of a bare "timeout" (the reference hard-fails boot self-tests
loudly, cmd/server-main.go:434-436; a silent wedge is the worst outcome).

Before touching jax it prints the env vars that steer backend selection and
arms ``faulthandler.dump_traceback_later`` so that if jax wedges, the exact
blocked frame is dumped to stderr ~85% into the parent's timeout budget,
while the parent is still capturing output.

A chip belongs to one process at a time: this child opens it, proves it
executes, and EXITS before the parent opens it. The parent never touches jax
while the child runs (runtime.probe_device waits for the exit).

On success prints ``PROBE_OK <platform> <device_kind> <device_count>`` as the
last stdout line and exits 0.
"""

from __future__ import annotations

import faulthandler
import os
import sys
import time


def main() -> int:
    t0 = time.time()
    timeout_s = float(sys.argv[1]) if len(sys.argv) > 1 else 60.0
    env_keys = sorted(
        k for k in os.environ if k.startswith(("JAX_", "TPU_", "XLA_", "LIBTPU"))
    )
    print(
        "[probe] env: " + " ".join(f"{k}={os.environ[k]}" for k in env_keys),
        flush=True,
    )

    # Dump the wedged stack while the parent is still listening.
    dump_at = max(5.0, timeout_s * 0.85)
    faulthandler.dump_traceback_later(dump_at, repeat=False, file=sys.stderr)

    import jax  # noqa: PLC0415 - after diagnostics on purpose

    from . import jaxenv  # noqa: PLC0415

    jaxenv.enable_compile_cache()
    print(f"[probe] import jax ok {time.time() - t0:.1f}s v{jax.__version__}", flush=True)
    devs = jax.devices()
    d = devs[0]
    print(f"[probe] devices ok {time.time() - t0:.1f}s n={len(devs)}", flush=True)
    # Prove the chip executes, not just enumerates: tiny u8 op round-trip.
    x = jax.numpy.ones((128, 128), dtype=jax.numpy.uint8)
    y = jax.jit(lambda a: a @ a)(x)
    y.block_until_ready()
    print(f"[probe] exec ok {time.time() - t0:.1f}s", flush=True)
    faulthandler.cancel_dump_traceback_later()
    kind = getattr(d, "device_kind", "?").replace(" ", "_")
    print(f"PROBE_OK {d.platform} {kind} {len(devs)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
