"""Persistent XLA compilation cache + warmup helpers.

TPU cold start = pod start + model download + XLA compile.  The reference
leans on the Knative activator for scale-from-zero buffering (reference
test/benchmark/README.md:14-17); the TPU-native mitigation is a persistent
compilation cache on disk so restarts skip recompiles (SURVEY.md §5.3), plus
engine warmup tied into the readiness probe.
"""

import logging
import os

logger = logging.getLogger("kfserving_tpu.compile_cache")

# Where JAX_COMPILATION_CACHE_DIR is unset: a fixed directory inside
# the checkout (git-ignored).  The path is part of the cache's key, so
# it is never built from a temp name, pid or time.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".kfs_cache", "xla")


def note_compilation(source: str, key) -> None:
    """Every engine reports its first-dispatch-per-shape here (the
    JaxEngine bucket grid, the generator's decode/prefill/chunk
    programs).  This module is the funnel because compilation policy
    lives here: today the note feeds the KFS_SANITIZE recompile
    assertion (a compile after `source`'s declared warmup is a
    violation); a disabled sanitizer makes this one env read."""
    from kfserving_tpu.reliability import sanitizer

    sanitizer.note_compilation(source, key)


def declare_warmup_complete(source: str) -> None:
    """Engines call this when their warmup grid is fully compiled;
    from then on a note_compilation() for `source` is a sanitizer
    violation (KFS_SANITIZE=1) instead of expected behavior."""
    from kfserving_tpu.reliability import sanitizer

    sanitizer.declare_warmup_complete(source)


# JAX's monitoring events -> the `event` label of
# kfserving_tpu_jax_compile_events_total.
_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}
_counting = False


def _on_jax_event(event: str, *_, **__) -> None:
    label = _JAX_EVENTS.get(event)
    if label is not None:
        from kfserving_tpu.observability import metrics as obs

        obs.jax_compile_events().labels(event=label).inc()


def count_jax_compile_events() -> None:
    """Register, once per process, `jax.monitoring` listeners that
    count every program JAX traces, lowers and compiles and every
    persistent-cache hit and miss.  JAX itself does the counting, so
    a retrace that `note_compilation`'s key set cannot see (a weak
    type, a new static argument) is counted too."""
    global _counting
    if _counting:
        return
    _counting = True
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
    jax.monitoring.register_event_listener(_on_jax_event)


def enable(min_compile_time_secs: float = 0.5) -> str:
    """Enable the JAX persistent compilation cache; returns its
    directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX's own handling of it
    stands and no directory is set in code (child processes inherit
    the variable, so replicas share one cache).  Where it is not, the
    cache lives at DEFAULT_CACHE_DIR.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    cache_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_time_secs)
    # Marker on the engine event timeline: compile-miss slices after
    # this point are persistent-cache loads, not fresh XLA compiles.
    from kfserving_tpu.observability.profiling import TIMELINE

    TIMELINE.record("host", "compile_cache.enabled",
                    attrs={"dir": cache_dir})
    from kfserving_tpu.observability import REGISTRY

    REGISTRY.gauge(
        "kfserving_tpu_compile_cache_enabled",
        "1 when the persistent XLA compile cache is active").labels(
            dir=cache_dir).set(1)
    logger.info("persistent XLA compile cache at %s", cache_dir)
    return cache_dir
