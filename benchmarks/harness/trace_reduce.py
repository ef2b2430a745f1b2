"""From a profiler trace to numbers. ``load`` needs jax (it reads the
``.xplane.pb`` with ``jax.profiler.ProfileData``); ``reduce`` is plain
Python over ``[(plane, line, [(name, start_ns, dur_ns), ...])]`` so that
it can be checked on a hand-made device plane.

On a TPU v5e each chip is a plane ``/device:TPU:<i>``; its line ``XLA
Ops`` holds one event per executed op (a pallas kernel's name carries
``tpu_custom_call``), ``XLA Modules`` one per executed program
(``jit__decode_chunk(...)``), ``Steps`` one per step.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

OPS, MODULES = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, device_prefix: str = "/device:TPU:") -> list:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(device_prefix):
            continue
        for line in plane.lines:
            if line.name in (OPS, MODULES):
                out.append((plane.name, line.name, [
                    (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events]))
    return out


def describe(path: str, top: int = 25) -> str:
    """Planes, lines and each line's longest-running event names: what to
    look at by hand before trusting a reduction."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            tot: dict = {}
            n = 0
            for ev in line.events:
                n += 1
                rec = tot.setdefault(ev.name[:300], [0, 0])
                rec[0] += 1
                rec[1] += ev.duration_ns
            out.append(f"  line {line.name!r}: {n} events")
            if plane.name.startswith("/device:"):
                for name, (c, ns) in sorted(
                        tot.items(), key=lambda kv: -kv[1][1])[:top]:
                    out.append(f"    {ns / 1e6:10.3f} ms {c:6d} x {name}")
    return "\n".join(out) + "\n"


def union_ns(intervals) -> int:
    """Total length of the union of (start, end) intervals."""
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _program(name: str) -> str:
    """``jit__decode_chunk(123456)`` -> ``jit__decode_chunk``."""
    return re.sub(r"\(\d+\)$", "", name)


def _op(name: str) -> str:
    """A short stable name for an op event: the op's own name, with the
    kernel's name for a pallas call."""
    head = name.split(" = ")[0].lstrip("%").strip()
    if "tpu_custom_call" in name:
        m = re.search(r'kernel_name[^"]*"([^"]+)"', name)
        return f"{head}:{m.group(1) if m else 'tpu_custom_call'}"
    return head[:80]


def reduce(lines: list, queries: dict | None = None, top: int = 10) -> dict:
    """``queries`` is ``{key: {"line": ..., "match": regex}}``; each gives
    ``{"count", "total_s", "union_s"}`` averaged over the device planes. A
    query with a ``"program"`` regex counts only the events that start
    inside an execution of a program of that name.
    Always: ``busy_s`` (union of op intervals), ``window_s`` (first op or
    program start to the last end), per-program and per-op totals, the
    longest idle gaps named by the programs on either side."""
    planes = sorted({p for p, _, _ in lines})
    if not planes:
        return {"planes": 0}
    n = len(planes)
    busy = window = 0.0
    programs: dict = {}
    ops: dict = {}
    gaps: dict = {}
    found = {k: {"count": 0.0, "total_s": 0.0, "union_s": 0.0}
             for k in (queries or {})}
    for plane in planes:
        evs = {ln: e for p, ln, e in lines if p == plane}
        op_evs, mod_evs = evs.get(OPS, []), evs.get(MODULES, [])
        both = op_evs + mod_evs
        if not both:
            continue
        t0 = min(s for _, s, _ in both)
        t1 = max(s + d for _, s, d in both)
        window += (t1 - t0) / 1e9
        busy += union_ns((s, s + d) for _, s, d in op_evs) / 1e9
        for name, _, d in mod_evs:
            rec = programs.setdefault(_program(name), [0, 0.0])
            rec[0] += 1
            rec[1] += d / 1e9
        for name, _, d in op_evs:
            rec = ops.setdefault(_op(name), [0, 0.0])
            rec[0] += 1
            rec[1] += d / 1e9
        for key, q in (queries or {}).items():
            pat = re.compile(q["match"])
            hit = [(s, s + d) for nm, s, d in evs.get(q["line"], [])
                   if pat.search(nm)]
            if "program" in q:
                prog = re.compile(q["program"])
                runs = sorted((s, s + d) for nm, s, d in mod_evs
                              if prog.search(nm))
                starts = [s for s, _ in runs]

                def inside(t):
                    i = bisect.bisect_right(starts, t)
                    return i > 0 and t < runs[i - 1][1]

                hit = [h for h in hit if inside(h[0])]
            found[key]["count"] += len(hit)
            found[key]["total_s"] += sum(e - s for s, e in hit) / 1e9
            found[key]["union_s"] += union_ns(hit) / 1e9
        # idle gaps between consecutive programs (or ops, when the
        # trace has no program line), named by their neighbours
        seq = sorted(mod_evs or op_evs, key=lambda e: e[1])
        namer = _program if mod_evs else _op
        end, prev = None, None
        for name, s, d in seq:
            if end is not None and s > end:
                label = f"{namer(prev)} -> {namer(name)}"
                gaps[label] = gaps.get(label, 0.0) + (s - end) / 1e9
            if end is None or s + d > end:
                end, prev = s + d, name
    rank = lambda d: sorted(  # noqa: E731
        ([k, v[1] / n] for k, v in d.items()), key=lambda kv: -kv[1])[:top]
    return {
        "planes": n, "busy_s": busy / n, "window_s": window / n,
        "programs": {k: {"count": v[0] / n, "total_s": v[1] / n}
                     for k, v in programs.items()},
        "top_ops": rank(ops),
        "top_programs": rank(programs),
        "idle_gaps": sorted(([k, v / n] for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
        "queries": {k: {f: v[f] / n for f in v} for k, v in found.items()},
    }
