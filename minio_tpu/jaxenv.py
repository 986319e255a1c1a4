"""The jax set-up every compiling entry point shares.

One installation is supported: jax/jaxlib 0.9 with libtpu, backend factories
``cpu`` and ``tpu``. The chip is the serving platform; the CPU backend runs
the same programs for tests and sandbox dry runs (Pallas kernels in interpret
mode) and is never a measurement platform.

jax is imported inside the functions: chip_smoke.py reads the cache location
from a process that must stay off jax, because the chip belongs to the server
it starts.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def on_tpu() -> bool:
    """True when jax's default backend is the chip. Initialises the backend,
    which on a TPU host opens the chip for this process."""
    import jax

    return jax.default_backend() == "tpu"


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lives: the directory the
    environment names, else ``<checkout>/.jax_cache``. The path is part of the
    cache key, so it never carries a temp name, a pid or a time."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CHECKOUT, ".jax_cache"
    )


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on before the first compile.

    Called by everything that compiles (codec install, the probe child,
    bench.py, __graft_entry__, tests/conftest.py). When
    JAX_COMPILATION_CACHE_DIR is set jax reads it itself and no directory is
    set here. Every program is cached, however fast it compiled, so a second
    start of the same code adds no entry."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return compile_cache_dir()
