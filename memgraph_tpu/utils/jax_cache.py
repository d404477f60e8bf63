"""Persistent XLA compilation cache setup.

A process-cold call deserializes a cached executable instead of
compiling it again. The reference keeps exactly this kind of
prepared-state cache native-side (mg_utils.hpp snapshot build); here
the compiler artifact itself is the prepared state.

Where the cache lives is decided outside the program: when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no directory; otherwise the cache is the fixed
``<checkout>/.jax_cache`` (the directory is part of the cache key, so
it must not move between runs).

Called lazily from every kernel entry point (GraphCache, module
procedures). Safe to call multiple times; must run before the
first jit compile to be effective for it.
"""

from __future__ import annotations

import logging
import os

log = logging.getLogger(__name__)

_done = False
_compile_listener = False


#: jax.monitoring's names (jax 0.9: _src/compiler.py, compilation_cache.py)
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def install_compile_counter() -> bool:
    """Runtime witness for the mgxla static compile budget, riding
    ``jax.monitoring`` (zero-cost when nothing compiles). Every XLA
    backend compile in this process — an executable load served from
    the persistent cache too — bumps ``jit.compile_total`` and adds its
    duration to ``jit.backend_seconds_total``; one the persistent cache
    did not serve (compiled, then written to it) bumps
    ``jit.cache_miss_total``. Exported through SHOW METRICS INFO /
    ``GET /stats``, so a silent recompile storm — the exact hazard
    mglint MG008 and the lane-bucket contract check guard statically —
    shows up as moving counters in production, and a 0.5 s cache load
    can be told from a 3 s compile. Idempotent."""
    global _compile_listener
    if _compile_listener:
        return True
    try:
        from jax import monitoring
    except Exception as e:  # noqa: BLE001 — the witness is optional
        log.info("jax.monitoring unavailable; jit.* counters "
                 "disabled: %s", e)
        return False
    from ..observability.metrics import global_metrics

    def _on_duration(event: str, duration: float = 0.0, **kw) -> None:
        if event == _BACKEND_COMPILE_EVENT:
            global_metrics.increment("jit.compile_total")
            global_metrics.increment("jit.backend_seconds_total",
                                     float(duration))

    def _on_event(event: str, **kw) -> None:
        if event == _CACHE_MISS_EVENT:
            global_metrics.increment("jit.cache_miss_total")

    try:
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
    except Exception as e:  # noqa: BLE001 — the witness is optional
        log.info("could not register compile listeners; jit.* "
                 "counters disabled: %s", e)
        return False
    _compile_listener = True
    return True


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — used only when the environment names
    no ``JAX_COMPILATION_CACHE_DIR``."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def ensure_compile_cache() -> bool:
    """Enable jax's persistent compilation cache (idempotent).

    Returns True if the cache is (already) enabled. Disabled by setting
    MEMGRAPH_TPU_COMPILE_CACHE=0.
    """
    global _done
    # the compile-count witness installs even when the persistent cache
    # is opted out — budget observability must not depend on caching
    install_compile_counter()
    if _done:
        return True
    if os.environ.get("MEMGRAPH_TPU_COMPILE_CACHE", "1") == "0":
        return False
    try:
        import jax
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            path = default_cache_dir()
            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
        # cache every program: a threshold on a compile TIME makes the
        # cache's content depend on the host's load (a program that took
        # 0.4 s in one run and 0.6 s in the next is written by the second
        # run only); entries are small
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    except Exception as e:  # noqa: BLE001 — cache is an optimization only
        log.info("persistent compile cache unavailable: %s", e)
        return False
    _done = True
    return True
