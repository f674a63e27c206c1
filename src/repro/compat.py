"""The jax runtime hooks several modules share: the Auto-axis mesh
constructor, the compile listener and the persistent compilation cache.

The repo targets jax 0.9; everything else calls the jax API directly.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[2]


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axes (jax 0.9 defaults to Explicit), so
    GSPMD propagates shardings the way the sharding rules expect."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def register_compile_listener(callback):
    """Invoke ``callback(event_name, seconds)`` for every XLA backend
    compilation in this process — the hook ``repro.telemetry`` uses to
    count and time recompiles (first-step warmup, elastic resizes, serving
    promotions of a new ensemble size).

    Rides ``jax.monitoring``'s duration events, filtering to the actual
    backend compile (ignoring the trace/lowering sub-events, which fire
    per jaxpr and would triple-count).  Returns the *unregister* callable.
    """
    def _listener(event, duration, **kwargs):
        if event.endswith("backend_compile_duration"):
            callback(event, duration)

    jax.monitoring.register_event_duration_secs_listener(_listener)
    return lambda: jax.monitoring.unregister_event_duration_listener(
        _listener)


def compilation_cache_dir() -> Path:
    """Where the persistent compilation cache lives.

    ``JAX_COMPILATION_CACHE_DIR``, when set and not empty, is the directory
    and no other is used.  Otherwise the cache lives at
    ``<repo>/.jax_cache`` — a fixed path, because the path is part of the
    cache key and a directory that moves never hits.
    """
    return Path(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                or REPO_ROOT / ".jax_cache")


def setup_compilation_cache() -> Path:
    """Turn on jax's persistent compilation cache at
    :func:`compilation_cache_dir` and return that directory.  Every
    executable is cached, however small or quick to compile.  Call it at
    the start of an entry point's ``main()``, never at import.
    """
    path = str(compilation_cache_dir())
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return Path(path)
