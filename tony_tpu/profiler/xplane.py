"""Direct xplane trace parsing — device-busy time without TensorBoard.

The reference has no profiling subsystem at all (SURVEY.md §5.1); this
is the analysis half of tony-tpu's greenfield tracing design
(``profiler.py`` is the capture half). Motivation: wall-clock
microbenches of small kernels are dominated by per-launch dispatch
overhead, and kernel A/B ratios swing between identical runs.
Device-busy time from the profiler's xplane trace has no launch
overhead in it, so ratios derived from it are stable run-to-run.

Parsing is done directly from the ``*.xplane.pb`` protos that
``jax.profiler.start_trace`` writes:

- ``tensorboard_plugin_profile``'s converter is broken in this image
  (protobuf/pywrap mismatch), so we read the proto ourselves via
  ``tensorflow.tsl.profiler.protobuf.xplane_pb2``.
- ``PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python`` must be exported
  before the first ``google.protobuf`` import or the C++ descriptor
  pool rejects the generated code; it is set LAZILY in ``load_xspace``
  (not at module import, so merely importing the profiler never forces
  the slower python protobuf impl on processes that parse no xplanes).
- The device plane is named ``/device:TPU:N``; its ``XLA Ops`` line
  carries one event per executed HLO op with ``duration_ps``. Summing
  durations is safe WITHIN a plane: ops on one TPU core's line are
  serialized. Across planes it is not — ``device_busy_ms`` reports the
  busiest plane (critical-path chip), never the cross-chip sum, which
  would inflate by n_devices on multi-chip traces.

Everything degrades to ``None``/empty off-TPU or when tensorflow is
absent, so callers can fall back to wall-clock.

``idle_gaps`` is the other reader, and goes through
``jax.profiler.ProfileData`` (nothing but jax): it names the device's
idle time by what the host was doing, joining the device planes with
the ``tony.*`` spans the program's ``obs.phases.HostPhases`` writes into
the host plane. ``python -m tony_tpu.profiler.xplane gaps <logdir>``
prints it.
"""

from __future__ import annotations

import glob
import os
import re

_PS_PER_MS = 1e9

_warned_degraded = False


def _warn_degraded(reason: str) -> None:
    """One-time (per process) warning when xplane parsing degrades to
    None: callers fall back to wall-clock ratios, which carry
    per-launch dispatch noise — that silent downgrade must be visible
    in the bench log."""
    global _warned_degraded
    if _warned_degraded:
        return
    _warned_degraded = True
    import warnings

    warnings.warn(
        f"xplane trace parsing degraded to None ({reason}); timing "
        "ratios fall back to wall-clock, which includes dispatch/launch "
        "overhead", RuntimeWarning, stacklevel=3)


def xplane_files(logdir: str) -> list[str]:
    """All xplane dumps under a trace logdir, oldest -> newest."""
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    return sorted(files, key=os.path.getmtime)


def load_xspace(path: str):
    """Parse one ``*.xplane.pb`` into an XSpace proto. None if the
    tensorflow proto stubs are unavailable OR the file is truncated/
    corrupt (e.g. a killed earlier trace session) — degrade, don't
    abort a caller's whole bench run."""
    # the env var must be exported before the FIRST google.protobuf
    # import or the C++ descriptor pool rejects the generated code; set
    # it here (not at module import) so merely importing tony_tpu
    # .profiler does not force the slower python protobuf impl on
    # processes that never parse xplanes
    os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION",
                          "python")
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2

        space = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            space.ParseFromString(f.read())
        return space
    except Exception:
        return None


def device_planes(space) -> list:
    """TPU (or GPU) device planes of an XSpace, excluding host planes."""
    return [p for p in space.planes
            if p.name.startswith("/device:") and "CUSTOM" not in p.name]


_OPKIND_RE = None


def hlo_op_kind(name: str) -> str:
    """HLO op KIND from an xplane op-metadata name. The name is the
    whole HLO statement ('%step.85 = (f32[...]) custom-call(%a, %b)'):
    the op name left of '=' is arbitrary (custom calls inherit jax fn
    names — a function named ``while_scanner`` yields
    '%while_scanner.3'), and the operand list mentions other ops'
    names, so the only reliable token is the kind between the result
    type and '('. Falls back to the name stem when the type expression
    defeats the regex (nested layout parens)."""
    global _OPKIND_RE
    if _OPKIND_RE is None:
        import re

        _OPKIND_RE = re.compile(
            r"=\s*(?:\([^)]*\)|[^\s(]+)\s+([a-z][a-z0-9_-]*)\(")
    m = _OPKIND_RE.search(name)
    if m:
        return m.group(1)
    return name.split("=", 1)[0].strip().lstrip("%").split(".")[0]


def _plane_op_totals(plane, line_name: str,
                     drop_control_flow: bool) -> dict[str, float] | None:
    """Per-op busy ms on ONE device plane's ``line_name`` line. None
    when the plane has no such line (not a device-op plane)."""
    totals: dict[str, float] = {}
    found = False
    meta = {m.id: m.name for m in plane.event_metadata.values()}
    for line in plane.lines:
        if line.name != line_name:
            continue
        found = True
        for ev in line.events:
            name = meta.get(ev.metadata_id, str(ev.metadata_id))
            # ' while(' / ' conditional(' can only be the HLO op
            # kind (op names contain no spaces; operand refs are
            # not followed by '('), so this cannot swallow a
            # custom call from a jax fn NAMED while_*; the
            # prefix check covers dumps whose metadata carries
            # only the op name — 'while.3' never collides with
            # 'while_scanner.3' (dot vs underscore)
            if drop_control_flow and (
                    " while(" in name or " conditional(" in name
                    or name.lstrip("%").startswith(
                        ("while.", "conditional."))):
                continue
            totals[name] = totals.get(name, 0.0) \
                + ev.duration_ps / _PS_PER_MS
    return totals if found else None


def op_totals_ms(logdir: str, line_name: str = "XLA Ops",
                 drop_control_flow: bool = True) \
        -> dict[str, float] | None:
    """Total device-busy ms per op name, summed over every device plane
    and xplane file under ``logdir``. None when nothing parseable.
    NOTE: the per-op SUM spans all chips (the per-op breakdown view);
    for wall-comparable busy time use ``device_busy_ms``, which
    aggregates per plane.

    ``drop_control_flow`` (default): skip while/conditional events —
    their duration INCLUDES the nested body ops, which the XLA Ops line
    logs separately per dynamic execution, so keeping both would count
    every loop body twice (measured: a scan-heavy step summed to ~2x
    its wall time before this filter). Filtering is by parsed HLO op
    KIND, not name prefix — a custom call from a jax fn named
    ``while_*`` must not vanish from the totals."""
    per_plane = per_plane_op_totals_ms(logdir, line_name,
                                       drop_control_flow)
    if per_plane is None:
        return None
    totals: dict[str, float] = {}
    for plane_totals in per_plane.values():
        for name, ms in plane_totals.items():
            totals[name] = totals.get(name, 0.0) + ms
    return totals


def per_plane_op_totals_ms(logdir: str, line_name: str = "XLA Ops",
                           drop_control_flow: bool = True) \
        -> dict[str, dict[str, float]] | None:
    """Per-device-plane per-op busy ms across every xplane file under
    ``logdir`` (plane name -> {op name -> ms}). None when nothing
    parseable — degrade, don't abort the caller's bench run."""
    per_plane: dict[str, dict[str, float]] = {}
    for path in xplane_files(logdir):
        space = load_xspace(path)
        if space is None:
            continue  # unparseable dump: skip it, keep what parses
        for plane in device_planes(space):
            totals = _plane_op_totals(plane, line_name, drop_control_flow)
            if totals is None:
                continue
            agg = per_plane.setdefault(plane.name, {})
            for name, ms in totals.items():
                agg[name] = agg.get(name, 0.0) + ms
    if not per_plane:
        _warn_degraded("no parseable device plane under " + logdir)
        return None
    return per_plane


def device_busy_ms(logdir: str, line_name: str = "XLA Ops") -> float | None:
    """Busy ms of the BUSIEST device across the trace (per-plane sum of
    the per-op line — serialized per core, so a plane's sum IS that
    core's busy time; the max across planes is the critical-path chip,
    the number comparable to wall clock). Summing across planes instead
    would over-report by n_devices on a multi-chip trace — a 4-chip
    data-parallel step would read as 4x "busier" than the wall it fits
    in (ADVICE r5). None when the trace has no device plane (e.g. CPU
    backend) or protos are unavailable."""
    per_plane = per_plane_op_totals_ms(logdir, line_name)
    if per_plane is None:
        return None
    return max(sum(t.values()) for t in per_plane.values())


def trace_device_ms(fn, args=(), steps: int = 10,
                    logdir: str | None = None) -> float | None:
    """Device-busy ms per call of ``fn(*args)`` over ``steps`` traced
    dispatches. The caller must have already compiled/warmed ``fn`` —
    tracing starts immediately. Returns None off-TPU (no device plane).

    The trace closes on ``block_until_ready`` (checked on the v5e in
    PR 24: it waits for every queued dispatch), so nothing but ``fn``
    runs inside the traced window.
    """
    import shutil
    import tempfile

    import jax

    owned = logdir is None
    logdir = logdir or tempfile.mkdtemp(prefix="tony_xplane_")
    try:
        jax.profiler.start_trace(logdir)
        try:
            out = None
            for _ in range(steps):
                out = fn(*args)
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
        busy = device_busy_ms(logdir)
        return busy / steps if busy is not None else None
    finally:
        if owned:
            shutil.rmtree(logdir, ignore_errors=True)


def hbm_estimate_bytes(jitted, *args) -> int:
    """Compile-time HBM footprint of a jitted step: argument + output +
    temp bytes from XLA's memory analysis — known BEFORE the step runs
    (``device.memory_stats()`` reports the peak only afterwards), and
    exact about what the executable will reserve: it correctly
    predicted this repo's OOM boundaries.
    Returns 0 when the backend offers no analysis."""
    try:
        return memory_bytes_of_compiled(jitted.lower(*args).compile())
    except Exception:
        return 0


def memory_bytes_of_compiled(compiled) -> int:
    """HBM bytes from an already-compiled executable's memory analysis
    (callers that also need cost_analysis should lower+compile ONCE and
    feed the result here — a flagship-sized step is slow to trace
    twice). 0 when the backend offers no analysis."""
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return 0
        peak = int(getattr(ma, "peak_memory_in_bytes", 0) or 0)
        if peak > 0:
            # measured >= argument+output+temp-alias on this backend:
            # the compiler's own peak covers live buffers and temps
            return peak
        return int(getattr(ma, "argument_size_in_bytes", 0)
                   + getattr(ma, "output_size_in_bytes", 0)
                   + getattr(ma, "temp_size_in_bytes", 0)
                   - getattr(ma, "alias_size_in_bytes", 0))
    except Exception:
        return 0


# ---------------------------------------------------------------------
# idle gaps, named by the host phase that covers them

_MODULES, _OPS = "XLA Modules", "XLA Ops"
_HOST_PREFIX = "tony."
_RUN_ID_RE = re.compile(r"\(\d+\)$")


def load_planes(path: str) -> list:
    """One ``.xplane.pb`` as ``[(plane, line, [(name, start_ns,
    dur_ns), ...])]``, through ``jax.profiler.ProfileData``: of every
    device plane the program line (``XLA Modules``; ``XLA Ops`` where a
    backend writes no program line), of every host plane each thread's
    ``tony.*`` spans."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            if "CUSTOM" in plane.name:
                continue
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get(_MODULES) or lines.get(_OPS)
            if line is not None:
                out.append((plane.name, line.name, [
                    (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                       for ev in line.events
                       if ev.name.startswith(_HOST_PREFIX)]
                if evs:
                    out.append((plane.name, line.name, evs))
    return out


def _program_name(name: str) -> str:
    """``jit__decode_chunk(123456)`` -> ``jit__decode_chunk``."""
    return _RUN_ID_RE.sub("", name)


def _split(lines: list) -> tuple[dict, list]:
    """(device plane -> its events sorted by start, every host span as
    ``(start, end, phase)`` sorted by start)."""
    device: dict = {}
    spans = []
    for plane, _, evs in lines:
        if plane.startswith("/device:"):
            device.setdefault(plane, []).extend(evs)
        else:
            spans.extend((s, s + d, name[len(_HOST_PREFIX):])
                         for name, s, d in evs
                         if name.startswith(_HOST_PREFIX))
    for evs in device.values():
        evs.sort(key=lambda e: e[1])
    spans.sort()
    return device, spans


def clock_shift_ns(lines: list) -> tuple[int, int]:
    """How far the device planes lie behind the host plane, from
    causality: a ``*.wait`` span returns only after the program it
    waited for has ended, so over every wait and the device program
    that ran through most of it, ``wait end - program end`` is the
    clocks' offset plus that wait's copy back, and its low end (the
    5th percentile, against a stray pairing) estimates the offset.
    Returns ``(shift_ns, pairs)``; ``(0, 0)`` when nothing pairs. The
    profiler converts device timestamps to the host's clock itself, and
    on the v5e left them 1.3 ms early (PERF.md, PR 27)."""
    import bisect

    device, spans = _split(lines)
    events = sorted((s, s + d) for evs in device.values()
                    for _, s, d in evs if d >= 100_000)
    starts = [e[0] for e in events]
    deltas = []
    for ws, we, name in spans:
        if not name.endswith(".wait") or we <= ws:
            continue
        best, best_overlap = None, 0
        for j in range(bisect.bisect_left(starts, we) - 1, -1, -1):
            ds, de = events[j]
            if de <= ws - 50_000_000:   # no program lasts 50 ms more
                break
            overlap = min(de, we) - max(ds, ws)
            if overlap > best_overlap:
                best, best_overlap = (ds, de), overlap
        if best is not None and 2 * best_overlap >= best[1] - best[0]:
            deltas.append(we - best[1])
    if not deltas:
        return 0, 0
    deltas.sort()
    return deltas[len(deltas) // 20], len(deltas)


def split_gaps(lines: list, shift_ns: int | None = None) -> dict:
    """The arithmetic of ``idle_gaps``, over hand-made planes too. For
    each device plane, the idle intervals between consecutive programs
    (shifted by ``shift_ns`` onto the host's clock; ``None`` estimates
    it with ``clock_shift_ns``), each split over the host spans that
    overlap it. Where spans nest or several threads overlap, the span
    that started last owns the instant, so an enclosing ``step.other``
    gets only what its leaves leave. Seconds are averaged over the
    device planes, like the benchmark's idle share."""
    import bisect

    pairs = 0
    if shift_ns is None:
        shift_ns, pairs = clock_shift_ns(lines)
    device, spans = _split(lines)
    if not device:
        return {"planes": 0, "host_spans": len(spans)}
    span_starts = [sp[0] for sp in spans]
    longest = max((e - s for s, e, _ in spans), default=0)
    n = len(device)
    window = busy = 0
    by_gap: dict = {}

    def owners(a: int, b: int):
        """``[(phase or None, ns)]`` partitioning ``[a, b)``."""
        lo = bisect.bisect_left(span_starts, a - longest)
        hi = bisect.bisect_left(span_starts, b)
        live = [sp for sp in spans[lo:hi] if sp[1] > a]
        cuts = sorted({a, b} | {t for s, e, _ in live for t in (s, e)
                               if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            over = [sp for sp in live if sp[0] <= x and sp[1] >= y]
            yield (max(over)[2] if over else None), y - x

    for evs in device.values():
        evs = [(name, s + shift_ns, d) for name, s, d in evs]
        window += max(s + d for _, s, d in evs) - evs[0][1]
        end, prev = None, None
        for name, s, d in evs:
            if end is not None and s > end:
                label = f"{_program_name(prev)} -> {_program_name(name)}"
                rec = by_gap.setdefault(
                    label, {"idle_ns": 0, "uncovered_ns": 0, "phases": {}})
                rec["idle_ns"] += s - end
                for phase, ns in owners(end, s):
                    if phase is None:
                        rec["uncovered_ns"] += ns
                    else:
                        rec["phases"][phase] = \
                            rec["phases"].get(phase, 0) + ns
            if end is None or s + d > end:
                busy += s + d - (s if end is None else max(s, end))
                end, prev = s + d, name

    def secs(ns):
        return ns / n / 1e9

    phases: dict = {}
    for rec in by_gap.values():
        for phase, ns in rec["phases"].items():
            phases[phase] = phases.get(phase, 0) + ns
    idle = sum(r["idle_ns"] for r in by_gap.values())
    uncovered = sum(r["uncovered_ns"] for r in by_gap.values())

    def ranked(d):
        return dict(sorted(((k, secs(v)) for k, v in d.items()),
                           key=lambda kv: -kv[1]))

    return {
        "planes": n, "window_s": secs(window), "busy_s": secs(busy),
        "idle_s": secs(idle), "uncovered_s": secs(uncovered),
        "clock_shift_ms": shift_ns / 1e6, "clock_pairs": pairs,
        "host_spans": len(spans),
        "phases_s": ranked(phases),
        "gaps": {label: {"idle_s": secs(r["idle_ns"]),
                         "uncovered_s": secs(r["uncovered_ns"]),
                         "phases_s": ranked(r["phases"])}
                 for label, r in sorted(by_gap.items(),
                                        key=lambda kv: -kv[1]["idle_ns"])},
    }


def idle_gaps(logdir: str, shift_ns: int | None = None) -> dict:
    """The newest capture under ``logdir`` (``POST /debug/profile``'s
    ``last_logdir``, or any ``jax.profiler`` trace directory): seconds
    the device sat idle between programs, by the ``tony.*`` host phase
    that covered them, ``uncovered_s`` for what no span covers, and the
    same per pair of neighbouring programs."""
    files = xplane_files(logdir)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return {"path": files[-1],
            **split_gaps(load_planes(files[-1]), shift_ns)}


def format_gaps(report: dict, top: int = 6) -> str:
    """``idle_gaps``' report as the table the command prints."""
    out = [f"capture {report.get('path', '')}"]
    if not report.get("planes"):
        return "\n".join(out + [
            f"no device plane; {report.get('host_spans', 0)} tony.* "
            "host spans"]) + "\n"
    idle = report["idle_s"]
    named = idle - report["uncovered_s"]
    out.append(
        f"{report['planes']} device plane(s): window "
        f"{report['window_s']:.3f} s, busy {report['busy_s']:.3f} s, "
        f"idle between programs {idle:.3f} s, of it "
        f"{100 * named / idle if idle else 0:.1f}% under a tony.* span")
    out.append(
        f"device clock moved {report['clock_shift_ms']:+.3f} ms onto "
        f"the host's ({report['clock_pairs']} wait/program pairs)")

    def rows(phases, uncovered, total, indent):
        for name, s in list(phases.items()) + [("(uncovered)", uncovered)]:
            if s > 0:
                out.append(f"{indent}{s:9.4f} s {100 * s / total:5.1f}%"
                           f"  {name}")

    out.append("idle seconds by host phase:")
    rows(report["phases_s"], report["uncovered_s"], idle or 1, "  ")
    for label, g in list(report["gaps"].items())[:top]:
        out.append(f"{label}: {g['idle_s']:.4f} s")
        rows(g["phases_s"], g["uncovered_s"], g["idle_s"] or 1, "    ")
    return "\n".join(out) + "\n"


def main(argv=None) -> int:
    import argparse
    import json

    p = argparse.ArgumentParser(
        prog="python -m tony_tpu.profiler.xplane",
        description="Read a jax.profiler capture of a serving process.")
    sub = p.add_subparsers(dest="cmd", required=True)
    g = sub.add_parser(
        "gaps", help="device idle time by the host phase covering it")
    g.add_argument("logdir")
    g.add_argument("--json", action="store_true",
                   help="print the report as one JSON document")
    args = p.parse_args(argv)
    report = idle_gaps(args.logdir)
    print(json.dumps(report) if args.json else format_gaps(report),
          end="\n" if args.json else "")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
