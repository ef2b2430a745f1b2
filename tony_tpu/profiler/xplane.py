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
"""

from __future__ import annotations

import glob
import os

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
