"""What both children share: the event lines they print for the parent,
the device check, the compile cache's place and jax's own compile
counters. ``CompileMeter`` and the device check are copied from
``chip_smoke.py`` (PR 24), which ran them on the v5e."""

from __future__ import annotations

import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OUT_DIR = os.path.join(CHECKOUT, ".bench_out")


def emit(event: str, **doc) -> None:
    """One JSON line for the parent (it tells them by the ``event``
    key; anything else a child prints is passed on to stderr)."""
    sys.stdout.write(json.dumps({"event": event, **doc}) + "\n")
    sys.stdout.flush()


def enable_compile_cache() -> str:
    """``tony_tpu/utils/compilecache``'s rule, restated: the machine's
    ``JAX_COMPILATION_CACHE_DIR`` if set (jax reads it itself), else a
    fixed directory inside the checkout. Everything is cached."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_compile_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def devices(chips: int, rehearsal: bool) -> dict:
    """Ask jax for its devices — the one question that takes the chip —
    and refuse anything but ``chips`` TPU devices unless rehearsing."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if not rehearsal and (info["platform"] != "tpu" or len(devs) < chips):
        raise SystemExit(f"need {chips} TPU device(s), jax found {info}")
    return info


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device (0 where the
    backend keeps no such count, as the CPU does not)."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def memory_in_use_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in jax.local_devices())


class CompileMeter:
    """What jax compiled or loaded, from its own monitoring events: how
    many executables were asked of the persistent cache, how many it
    held, and the seconds spent compiling."""

    def __init__(self):
        import jax.monitoring as m

        self.requests = self.hits = 0
        self.seconds = 0.0
        m.register_event_listener(self._on_event)
        m.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _on_duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def report(self) -> dict:
        return {"compile_s": round(self.seconds, 2),
                "executables": self.requests, "cache_hits": self.hits,
                "compiled_anew": self.requests - self.hits}


def start_trace(tag: str) -> str:
    import shutil

    import jax

    path = os.path.join(OUT_DIR, "trace-" + tag)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)
    jax.profiler.start_trace(path)
    return path


def stop_trace() -> None:
    import jax

    jax.profiler.stop_trace()


def reduce_trace(path: str, queries: dict, platform: str) -> dict:
    """Reduce the trace under ``path`` and delete it (traces are large
    and the host keeps every block once written)."""
    import shutil

    from . import trace_reduce as T

    prefix = "/device:TPU:" if platform == "tpu" else "/device:"
    try:
        xplane = T.find_xplane(path)
        with open(path + "-summary.txt", "w") as f:   # small; for the eye
            f.write(T.describe(xplane))
        lines = T.load(xplane, prefix)
        if not lines and platform != "tpu":  # the CPU rehearsal
            lines = T.load(xplane, "/host:")
        return T.reduce(lines, queries)
    finally:
        shutil.rmtree(path, ignore_errors=True)
