"""Start-up ledger: where a process's time goes before it serves.

Two process-wide pieces, read together as ``/stats`` ``startup``:

- ``PHASES``, a ``HostPhases`` (``obs/phases.py``) of start-up spans,
  with its leaf rule, its ``rest()`` and its ``tony.`` annotations, so
  that a capture that covers a boot shows ``tony.startup.*`` on the
  same host plane as the hot path. ``cli/gateway`` opens
  ``startup.load`` (the checkpoint), ``startup.build`` (``serve()``
  entered -> the fleet started; its self time is what its leaves do
  not cover) and ``startup.edge`` (scaler, rebalancer, edge -> ready);
  ``serve.Server`` books ``startup.pool``, ``startup.fork_warm`` and
  ``startup.warm_views`` as leaves, and a replica the autoscaler adds
  later books them again (``count`` above 1). Stamps, in seconds since
  the OS started the process: ``imported_s`` (this module),
  ``serve_entered_s`` and ``edge_listening_s``.
- ``COMPILES``, a table of every executable jax traced, lowered and
  compiled (or loaded from the persistent cache) in this process, fed
  by ``jax.monitoring`` listeners registered on import: totals and the
  20 largest rows by ``fun_name``. jax keys its persistent cache by the
  lowered module, so every executable is traced and lowered again on
  every start, cache or no cache: ``trace_s + lower_s`` is the part a
  warm cache does not save. The totals are cumulative; a reader takes
  the difference of two snapshots.

The module is imported at the top of ``cli/gateway.py`` and of
``train/trainer.py``, before either builds anything, so the table sees
the weights' programs too. After start-up it costs nothing: no
listener fires in a window that compiles nothing.
"""

from __future__ import annotations

import contextlib
import os
import re
import threading
import time

import jax.monitoring as monitoring

from tony_tpu.obs.phases import HostPhases

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
KINDS = {TRACE: "trace_s", LOWER: "lower_s", COMPILE: "compile_s"}
TOP = 20


def _process_age_s() -> float:
    """Seconds since the OS started this process: ``/proc/self/stat``
    field 22 (clock ticks after boot) against the boot-time clock; 0
    where that cannot be read (then the clock starts here)."""
    try:
        with open("/proc/self/stat") as f:
            stat = f.read()
        ticks = int(stat[stat.rindex(")") + 2:].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, AttributeError, IndexError):
        return 0.0
    return max(0.0, age)


# the one clock reading: the process's start on perf_counter's clock
_AGE = _process_age_s()
START = time.perf_counter() - _AGE

PHASES = HostPhases()
_stamps = {"imported_s": _AGE}
_owner = threading.RLock()   # held by the thread whose span is open


def _since_start() -> float:
    return time.perf_counter() - START


def stamp(name: str) -> None:
    """Record ``name`` as now, in seconds since process start."""
    _stamps[name] = _since_start()


def serve_entered() -> None:
    """``serve()`` entered: its stamp, and the listening stamp of any
    earlier ``serve()`` in this process cleared."""
    _stamps.pop("edge_listening_s", None)
    stamp("serve_entered_s")


@contextlib.contextmanager
def _booked(span):
    """Run the block inside ``span`` (a leaf or a rest of ``PHASES``)
    unless another thread's span is open, and then unbooked: the ledger
    has one owner at a time (a replica the autoscaler builds while the
    boot's edge starts, or during another's build, is not booked)."""
    if not _owner.acquire(blocking=False):
        yield
        return
    try:
        with span:
            yield
    finally:
        _owner.release()


def leaf(name: str):
    """A start-up leaf ``name`` (``HostPhases.phase``)."""
    return _booked(PHASES.phase(name))


def rest(name: str):
    """An enclosing start-up span (``HostPhases.rest``): its own time
    is what the leaves inside it do not cover."""
    return _booked(PHASES.rest(name))


def _short(fun_name: str) -> str:
    """``jit(step)`` (lowering, compile) and ``step`` (trace) are one
    executable's row."""
    m = re.fullmatch(r"\w+\((.*)\)", fun_name)
    return m.group(1) if m else fun_name


class CompileTable:
    """Per ``fun_name``: executables compiled or loaded, persistent-cache
    hits, and seconds of tracing, lowering and backend compile (or
    cache load). A span inside another of the same kind on its thread
    (a jit traced while its caller is) stays in the outer one; a span
    inside one of another kind is taken out of the outer's time, so
    ``trace_s + lower_s + compile_s`` is each thread's compile time
    once. jax says where a span starts (a scalar event) and ends (a
    time span); the cache's events carry no name and are booked to the
    backend compile open on their thread."""

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._rows: dict[str, dict] = {}
        self._totals = {"executables": 0, "cache_hits": 0,
                        "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0}

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def on_scalar(self, event: str, value, **kw) -> None:
        if event in KINDS:
            # [event, name, nested seconds booked elsewhere, hit]
            self._stack().append([event, kw.get("fun_name", ""), 0.0, False])

    def on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            stack = self._stack()
            if stack and stack[-1][0] == COMPILE:
                stack[-1][3] = True

    def on_span(self, event: str, start: float, end: float, **kw) -> None:
        if event not in KINDS:
            return
        name = kw.get("fun_name", "")
        stack = self._stack()
        frame = stack.pop() if stack and stack[-1][:2] == [event, name] \
            else [event, name, 0.0, False]
        dur = max(0.0, end - start)
        outer = stack[-1] if stack else None
        if outer is not None:
            if outer[0] == event:
                return   # the outer span of its kind holds this one's time
            outer[2] += dur
        self._book(event, _short(name), dur - frame[2], frame[3])

    def _book(self, event: str, name: str, secs: float, hit: bool) -> None:
        with self._lock:
            row = self._rows.get(name)
            if row is None:
                row = self._rows[name] = {"count": 0, "hits": 0,
                                          **dict.fromkeys(KINDS.values(), 0.0)}
            row[KINDS[event]] += secs
            self._totals[KINDS[event]] += secs
            if event == COMPILE:
                row["count"] += 1
                row["hits"] += hit
                self._totals["executables"] += 1
                self._totals["cache_hits"] += hit

    def snapshot(self, top: int = TOP) -> dict:
        with self._lock:
            totals = dict(self._totals)
            rows = [{"name": name, **row} for name, row in self._rows.items()]
        rows.sort(key=lambda r: -sum(r[k] for k in KINDS.values()))
        out = {k: round(v, 4) if isinstance(v, float) else v
               for k, v in totals.items()}
        out["compiled_anew"] = totals["executables"] - totals["cache_hits"]
        out["names"] = len(rows)
        out["top"] = [{k: round(v, 4) if isinstance(v, float) else v
                       for k, v in row.items()} for row in rows[:top]]
        return out


COMPILES = CompileTable()
monitoring.register_scalar_listener(COMPILES.on_scalar)
monitoring.register_event_listener(COMPILES.on_event)
monitoring.register_event_time_span_listener(COMPILES.on_span)


def snapshot() -> dict:
    """``/stats`` ``startup``: the stamps, the spans, the compiles."""
    return {**{k: round(v, 4) for k, v in _stamps.items()},
            "now_s": round(_since_start(), 4),
            "phases": PHASES.snapshot(),
            "compiles": COMPILES.snapshot()}
