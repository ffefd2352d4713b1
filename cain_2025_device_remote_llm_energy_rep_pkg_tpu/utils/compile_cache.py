"""Persistent XLA compilation cache for serving, studies and benches.

Every (model, bucket) shape pays a jit compile on first use — seconds
each on the chip, minutes summed over a cold server. The compiles happen
outside measurement windows, but they dominate cold start-up and every
restart pays them again. JAX's persistent compilation cache keeps the
compiled executables on disk; a restart against a warm cache loads them
instead.

The directory is placed from OUTSIDE the program: where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets no other directory. Unset, the cache lives at one fixed path inside
the checkout — the path is part of the cache key, so a directory that
moves (a temp name, a pid, ``~`` on another machine) never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

# <repo>/.jax_cache, resolved from this file's own location (listed in
# .gitignore).
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def key_cache_on_metadata() -> None:
    """Make an operation's metadata part of the persistent cache's key.

    By default JAX strips debug info before it hashes a program, so two
    trees whose programs differ only in ``jax.named_scope`` names (or in
    the line an operation was written on) share one cache entry — and
    the executable that comes back carries the names of whichever tree
    compiled it FIRST. A profiler trace then names the device's
    operations by another commit's scopes: measured on the chip (PERF.md
    §6, PR 25), the decode step of a tree with scopes loaded a scope-less
    executable an earlier tree had cached, and every operation read as
    unscoped. With the metadata in the key a tree loads only what a tree
    with the same names (and lines) compiled; the price is one cold
    compile of each program after an edit that moves them, and no
    sharing between callers whose stacks differ (the locations carry
    the traceback). Accelerators only: on the CPU nothing reads a trace
    by scope, and the test suite's many processes and call sites lean
    on one shared cache (keyed this way the suite ran twice as long)."""
    import jax

    if jax.default_backend() != "cpu":
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", True
        )


def enable_compilation_cache() -> Path:
    """Turn the persistent compilation cache on and return the directory
    in use. Safe to call repeatedly. Every compile is cached
    (min-compile-time threshold 0): even small decode loops take seconds
    to build on the chip."""
    import jax

    key_cache_on_metadata()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return Path(env_dir)
    DEFAULT_CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return DEFAULT_CACHE_DIR


# Backend compiles this process has run (persistent-cache loads count:
# they stall the caller too). Process-wide because compilation is.
_compiles = 0
_listening = False


def _on_duration_event(event: str, duration_s: float, **_kwargs) -> None:
    global _compiles
    if event == "/jax/core/compile/backend_compile_duration":
        _compiles += 1


def compile_count() -> int:
    """Running count of backend compiles since the first call. A caller
    that reads it before and after a stretch of work learns whether
    that stretch compiled — the stepped session does so around every
    decode slice, where a compile stalls the resident rows."""
    global _listening
    if not _listening:
        import jax

        _listening = True
        jax.monitoring.register_event_duration_secs_listener(
            _on_duration_event
        )
    return _compiles
