"""Persistent XLA compilation-cache wiring — the ONE place that decides
where compiled executables are kept.

No reference analog (TonY is JVM-side; the user script owns the ML
stack) — this is TPU-native launch-latency plumbing: XLA serializes
compiled executables to a cache dir, so any later process compiling the
same program (a retried attempt, a gateway restart, a bench rerun, the
next ``chip_smoke.py``) loads a file instead of recompiling for
seconds to minutes.

The rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and this
  module sets no directory in code — whoever owns the machine placed
  the cache, and every process on it shares that one.
- unset: ``<checkout>/.jax_compile_cache`` (git-ignored), the same
  path for every entry point. The path is part of the cache key, so it
  is never derived from a temp name, a pid, a job id or the time — a
  directory that moves never hits.

An explicit directory (a CLI's ``--compile-cache DIR``, or
``TONY_COMPILE_CACHE_DIR`` exported through
``tony.application.shell-env``) stands in for the checkout default;
it never overrides ``JAX_COMPILATION_CACHE_DIR``.

The cache key covers the serialized computation, jaxlib/backend
versions, XLA flags, and compile options — a stale dir is never wrong,
only useless, so sharing one dir across processes is safe.
"""

from __future__ import annotations

import logging
import os

from tony_tpu import constants as C

log = logging.getLogger(__name__)

JAX_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")

_enabled: str | None = None


def resolve_dir(cache_dir: str | None = None) -> str:
    """Where the cache is, by the rule above. Touches neither jax nor
    the filesystem, so a process that must stay off the chip (a
    launcher, ``chip_smoke.py``'s parent) can ask too."""
    return (os.environ.get(JAX_ENV, "").strip()
            or (cache_dir or os.environ.get(C.COMPILE_CACHE_DIR)
                or "").strip()
            or DEFAULT_DIR)


def enable(cache_dir: str | None = None) -> str | None:
    """Arm JAX's persistent compilation cache; returns its directory.

    Thresholds are set to cache *everything* (min compile time 0, no
    min entry size): restart latency is dominated by many small
    compiles, not one big one. Safe to call repeatedly — the first
    resolved dir wins for the life of the process (flipping dirs
    mid-process would split the cache for no benefit). Returns None
    only when the directory cannot be created (read-only checkout):
    the process then runs cold rather than not at all.
    """
    global _enabled
    if _enabled is not None:
        return _enabled
    import jax

    resolved = resolve_dir(cache_dir)
    # jax's own reading of the variable stands: no directory set in code
    from_env = bool(os.environ.get(JAX_ENV, "").strip())
    if not from_env:
        try:
            os.makedirs(resolved, exist_ok=True)
        except OSError:
            log.exception("compile cache at %s unavailable; running cold",
                          resolved)
            return None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", resolved)
    _enabled = resolved
    log.info("persistent compilation cache: %s", resolved)
    return resolved


def entries(cache_dir: str) -> list[str]:
    """Names of cached executables (``*-cache`` files) under a cache dir.
    Diagnostic/test helper; empty for a missing dir."""
    try:
        return sorted(n for n in os.listdir(cache_dir) if n.endswith("-cache"))
    except OSError:
        return []
