"""Host phase ledger: where one scheduler thread's wall clock goes.

``obs/timeline`` records what each device dispatch cost as ONE host-wall
number; the profiler's device plane shows when the chip ran and when it
did not. Neither says what the host did while the chip waited. A
``HostPhases`` is the thread's own answer, kept two ways at once:

- every ``phase(name)`` enters ``jax.profiler.TraceAnnotation("tony." +
  name, **ids)``: while a ``jax.profiler`` capture runs the span lands
  in the xplane's host plane, written by the profiler beside the device
  planes, where ``profiler/xplane.idle_gaps`` joins the two. With no
  capture running an annotation is one inactive C++ check;
- on exit the phase adds to its cumulative ``count``, ``ms`` (wall,
  ``perf_counter_ns``) and ``cpu_ms`` (``thread_time_ns``). Wall minus
  CPU in a phase that does not block is time the thread spent OFF the
  CPU: waiting for the GIL, or descheduled.

The ledger is a PARTITION of the owner thread's wall clock. Phases are
leaves: a leaf never opens inside a leaf (an ``AssertionError`` says so
where the tests run), ``switch`` closes the open leaf and opens the next
on ONE pair of clock reads so consecutive leaves leave no gap, and
``rest(name)`` is the single enclosing level: its SELF time (its span
minus the leaves inside it) is booked under ``name``. ``snapshot()``
therefore satisfies ``sum(ms) + unnamed_ms == wall_ms``.

One thread owns an instance (a replica's scheduler thread, or whoever
drives ``Server.step()`` when there is no gateway), so the hot path
takes no lock. ``snapshot()`` may be called from any thread: it copies
the small table and adds the open leaf's time so far to its row (a
``cv.wait`` or a device wait can be long, and a window's delta must not
book it to ``unnamed``), reading again if a leaf closed meanwhile.
"""

from __future__ import annotations

from time import perf_counter_ns, thread_time_ns

from jax.profiler import TraceAnnotation

ANNOTATION_PREFIX = "tony."


class _Leaf:
    """The context manager ``HostPhases.phase`` returns."""

    __slots__ = ("_owner", "_name", "_ids")

    def __init__(self, owner: "HostPhases", name: str, ids: dict):
        self._owner = owner
        self._name = name
        self._ids = ids

    def __enter__(self) -> "_Leaf":
        owner = self._owner
        assert owner._cur is None, (
            f"host phase {self._name!r} opened inside "
            f"{owner._cur[0]!r}: phases are leaves")
        owner._ids = self._ids
        owner._open(self._name, perf_counter_ns(), thread_time_ns())
        return self

    def __exit__(self, *exc) -> None:
        self._owner._close(perf_counter_ns(), thread_time_ns())


class _Rest:
    """The context manager ``HostPhases.rest`` returns."""

    __slots__ = ("_owner", "_name", "_ann", "_t", "_c", "_leaf_ns",
                 "_leaf_cpu")

    def __init__(self, owner: "HostPhases", name: str):
        self._owner = owner
        self._name = name

    def __enter__(self) -> "_Rest":
        owner = self._owner
        assert owner._cur is None and not owner._in_rest, (
            f"host span {self._name!r} opened inside another")
        owner._in_rest = True
        self._ann = TraceAnnotation(ANNOTATION_PREFIX + self._name)
        self._ann.__enter__()
        self._leaf_ns, self._leaf_cpu = owner._leaf_ns, owner._leaf_cpu
        self._t, self._c = perf_counter_ns(), thread_time_ns()
        return self

    def __exit__(self, *exc) -> None:
        owner = self._owner
        now, cpu = perf_counter_ns(), thread_time_ns()
        self._ann.__exit__(None, None, None)
        owner._add(self._name,
                   (now - self._t) - (owner._leaf_ns - self._leaf_ns),
                   (cpu - self._c) - (owner._leaf_cpu - self._leaf_cpu))
        owner._in_rest = False


class HostPhases:
    """Cumulative per-phase wall and CPU time of one thread; see the
    module docstring for the rules."""

    def __init__(self):
        self._t0 = perf_counter_ns()
        self._acc: dict[str, list] = {}   # name -> [count, ns, cpu ns]
        # the open leaf as ONE tuple (name, wall ns, cpu ns at entry),
        # so that snapshot() reads it whole from another thread
        self._cur: tuple | None = None
        self._closed = 0                  # leaves closed, ever
        self._ids: dict = {}
        self._ann = None
        self._leaf_ns = self._leaf_cpu = 0  # all leaves, for rest()
        self._in_rest = False

    def phase(self, name: str, **ids) -> _Leaf:
        """Open leaf ``name`` for the ``with`` block. ``ids`` ride on
        the trace annotation (``seq=`` a dispatch record's sequence
        number, ``rid=`` an engine request id) and on every leaf this
        one is ``switch``ed to."""
        return _Leaf(self, name, ids)

    def switch(self, name: str) -> None:
        """Close the open leaf and open ``name`` at the same instant,
        with the same ids."""
        assert self._cur is not None, (
            f"switch({name!r}) with no host phase open")
        now, cpu = perf_counter_ns(), thread_time_ns()
        self._close(now, cpu)
        self._open(name, now, cpu)

    def rest(self, name: str) -> _Rest:
        """Enclose leaves; what they do not cover is booked under
        ``name``."""
        return _Rest(self, name)

    def _open(self, name: str, now: int, cpu: int) -> None:
        self._ann = TraceAnnotation(ANNOTATION_PREFIX + name, **self._ids)
        self._ann.__enter__()
        self._cur = (name, now, cpu)

    def _close(self, now: int, cpu: int) -> None:
        self._ann.__exit__(None, None, None)
        name, t, c = self._cur
        ns, cpu_ns = now - t, cpu - c
        self._leaf_ns += ns
        self._leaf_cpu += cpu_ns
        self._add(name, ns, cpu_ns)
        self._cur = None
        self._closed += 1

    def _add(self, name: str, ns: int, cpu_ns: int) -> None:
        row = self._acc.get(name)
        if row is None:
            row = self._acc[name] = [0, 0, 0]
        row[0] += 1
        row[1] += ns
        row[2] += cpu_ns

    def snapshot(self) -> dict:
        """``{"wall_ms", "phases": {name: {count, ms, cpu_ms}},
        "unnamed_ms"}``: ``wall_ms`` since this ledger was made,
        ``unnamed_ms`` what no phase covers. A leaf still open has its
        wall time so far in ``ms``; ``count`` and ``cpu_ms`` (a thread
        reads only its own CPU clock) take it in when it closes."""
        for _ in range(4):   # until no leaf closed while we copied
            closed, cur = self._closed, self._cur
            rows = {name: tuple(row)
                    for name, row in list(self._acc.items())}
            now = perf_counter_ns()
            if self._closed == closed:
                break
        if cur is not None:
            n, ns, cpu = rows.get(cur[0], (0, 0, 0))
            rows[cur[0]] = (n, ns + max(0, now - cur[1]), cpu)
        wall = now - self._t0
        return {
            "wall_ms": round(wall / 1e6, 3),
            "phases": {name: {"count": n, "ms": round(ns / 1e6, 3),
                              "cpu_ms": round(cpu / 1e6, 3)}
                       for name, (n, ns, cpu) in sorted(rows.items())},
            "unnamed_ms": round(
                (wall - sum(ns for _, ns, _ in rows.values())) / 1e6, 3),
        }

    @staticmethod
    def merge(snapshots: list) -> dict:
        """Sum snapshots across replicas (``/stats`` ``engine.host``):
        every thread's wall clock and every phase add, so the merge is
        still a partition, of the fleet's scheduler-thread time."""
        out = {"wall_ms": 0.0, "phases": {}, "unnamed_ms": 0.0}
        for snap in snapshots:
            out["wall_ms"] += snap.get("wall_ms", 0.0)
            out["unnamed_ms"] += snap.get("unnamed_ms", 0.0)
            for name, row in (snap.get("phases") or {}).items():
                m = out["phases"].setdefault(
                    name, {"count": 0, "ms": 0.0, "cpu_ms": 0.0})
                m["count"] += int(row.get("count", 0))
                m["ms"] += row.get("ms", 0.0)
                m["cpu_ms"] += row.get("cpu_ms", 0.0)
        out["wall_ms"] = round(out["wall_ms"], 3)
        out["unnamed_ms"] = round(out["unnamed_ms"], 3)
        for m in out["phases"].values():
            m["ms"] = round(m["ms"], 3)
            m["cpu_ms"] = round(m["cpu_ms"], 3)
        out["phases"] = dict(sorted(out["phases"].items()))
        return out
