"""Where this process keeps XLA's persistent compile cache.

The one place that places the cache. ``JAX_COMPILATION_CACHE_DIR`` from the
environment wins: JAX reads it itself and nothing is set here. Otherwise the
cache lives at one fixed directory inside the checkout — the directory is part
of what a later process must repeat to hit, so it is never derived from a pid,
a clock, ``/tmp`` or ``~``. A process held to the CPU (``JAX_PLATFORMS=cpu``:
the tests, the CPU-forced cluster children) gets none: what the cache saves is
the compile a chip call waits for, and XLA:CPU logs an error line of several
KB for every cached executable it reloads (a machine-feature comparison that
always differs), enough to fill a child's stderr pipe.
"""

from __future__ import annotations

import os

#: the JAX option this module owns
DIR_OPTION = "jax_compilation_cache_dir"

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_applied = False


def default_dir(env_dir: str | None, configured: str | None, platforms: str | None) -> str | None:
    """The directory to set, or None to set nothing: one was placed from
    outside (environment, or the option already set), or the process is held
    to the CPU."""
    if env_dir or configured is not None or (platforms or "").strip().lower() == "cpu":
        return None
    return DEFAULT_DIR


def ensure_compile_cache() -> str | None:
    """Place the compile cache; returns the directory in force (None: no
    cache). Must run before the first compile of the process — model
    construction already compiles ``init_params`` — so
    ``observability.device.traced_jit``, the door every device kernel is
    built through, calls it."""
    global _applied
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not _applied:
        _applied = True
        chosen = default_dir(env_dir, getattr(jax.config, DIR_OPTION), jax.config.jax_platforms)
        if chosen is not None:
            jax.config.update(DIR_OPTION, chosen)
        # JAX skips entries that compiled in under a second; the scatter,
        # search and probe kernels do, once per shape bucket per process
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return env_dir or getattr(jax.config, DIR_OPTION)
