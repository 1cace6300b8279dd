"""Host/device platform helpers shared by benchmarks and tools."""

from __future__ import annotations

import os


#: the in-checkout persistent compile cache (git-ignored). The path is
#: part of every cache key's lookup, so it must be the same for every
#: process and every run of one checkout — never ``PIO_HOME``, ``~``, a
#: temporary name, a pid or the time.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_cache_enabled = False


def enable_compilation_cache() -> None:
    """Turn on JAX's persistent compilation cache for accelerator
    backends, so every CLI stage (train, eval, deploy) and every
    repeated run reuses compiled programs across *processes* — the
    analogue of the reference paying JVM/Spark startup once per ``pio``
    command.

    One rule for where it lives: where ``JAX_COMPILATION_CACHE_DIR`` is
    set, JAX reads it itself and no directory is set in code; where it
    is not, the cache is :data:`COMPILE_CACHE_DIR`. Every compile is
    cached (no size or compile-time floor), so a second identical run
    adds no entry. On a CPU backend this is a no-op: CPU compiles are
    fast and XLA:CPU executables embed host machine features (a cached
    binary from another host risks SIGILL). On any other backend a
    failure to enable the cache raises.

    Safe to call many times; the first call wins. Forces backend
    initialization, which callers were about to pay anyway.
    """
    global _cache_enabled
    if _cache_enabled:
        return
    # the env check comes first so a CPU-pinned process never
    # initializes its backend here (multi-process CPU runs must call
    # jax.distributed.initialize before any backend exists); the
    # resolved-backend check covers a host that auto-selects CPU
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return
    import jax

    if jax.default_backend() == "cpu":
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(COMPILE_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _cache_enabled = True


def force_cpu_if_requested() -> None:
    """Make ``JAX_PLATFORMS=cpu`` authoritative: the config update wins
    over any installed accelerator plugin. Call after importing jax and
    before the first device use."""
    if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")
