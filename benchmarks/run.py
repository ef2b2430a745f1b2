#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell names a
configuration (``configs/<name>.json``) and a traffic mix or job
(``traffic/<name>.json``, whose ``kind`` picks the child); each per-layer
metric of the cell is ``layer_metrics/<metric>.json``, which names a
reader module (``readers/<reader>.py``) and its arguments; the limits of
the comparison that decides ``correct`` are ``limits/<cell>.json``; the
configuration's ``model_type`` is its family, ``families/<model_type>.py``
(sizes, leaves, plain reference, the program's tree, the counts). A name
that resolves to no file is refused. Adding a cell, a mix, a
configuration, a family or a metric is adding files and ``BENCHMARK.json``
entries.

This parent NEVER imports jax (one process per chip): it starts the child
that holds the chip, takes ``setup_s`` from its own start to the child's
``ready``, is the open-loop load generator of a serving window, tells the
child when to start and stop ``jax.profiler`` in a traced run, ends a
gateway with a real SIGTERM drain, and prints the result as the last line
of stdout. Without a TPU (or with fewer chips than the cell asks) the
child refuses and this exits non-zero with no result, unless
``--rehearsal``: toy sizes on whatever device jax has, and a last line
that says ``"correct": false`` — a rehearsal is never a result.
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import importlib
import json
import math
import os
import queue
import random
import signal
import subprocess
import sys
import threading
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, CHECKOUT)

from benchmarks.harness import loadgen  # noqa: E402
from benchmarks.harness import weights as W  # noqa: E402
from benchmarks.harness.peaks import peaks  # noqa: E402

CHILD_LIMIT_S = 1150.0   # a cold first run compiles; the driver allows 1200


def note(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def load_json(*parts: str) -> dict:
    path = os.path.join(*parts)
    if not os.path.isfile(path):
        raise SystemExit(f"{os.path.relpath(path, CHECKOUT)}: no such file "
                         "(every name in BENCHMARK.json resolves to a file)")
    with open(path) as f:
        return json.load(f)


def resolve(workload: str) -> dict:
    """The cell with everything it names, loaded."""
    bench = load_json(CHECKOUT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{[w['name'] for w in bench['workloads']]}")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    per_layer = []
    for m in bench["per_layer"]:
        if applies(m):
            spec = load_json(HERE, "layer_metrics", m["name"] + ".json")
            importlib.import_module("benchmarks.readers." + spec["reader"])
            per_layer.append({**m, **spec})
    return {"bench": bench, "cell": cell,
            "config_path": os.path.join(CHECKOUT, entry["file"]),
            "config": load_json(CHECKOUT, entry["file"]),
            "traffic_path": os.path.join(HERE, "traffic",
                                         cell["traffic"] + ".json"),
            "traffic": load_json(HERE, "traffic", cell["traffic"] + ".json"),
            "limits": load_json(HERE, "limits", workload + ".json"),
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": per_layer}


# ------------------------------------------------------------- the child

class Child:
    """The process that holds the chip: its event lines on a queue,
    everything else it prints passed on to stderr."""

    def __init__(self, module: str, argv: list):
        self.proc = subprocess.Popen(
            [sys.executable, "-u", "-m", module] + argv, cwd=CHECKOUT,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            start_new_session=True)
        self.events: queue.Queue = queue.Queue()
        threading.Thread(target=self._pump, daemon=True).start()

    def _pump(self):
        for line in self.proc.stdout:
            try:
                doc = json.loads(line)
            except ValueError:
                doc = None
            if isinstance(doc, dict) and "event" in doc:
                self.events.put(doc)
            else:
                sys.stderr.write(line)
        self.events.put({"event": "eof"})

    def send(self, **cmd) -> None:
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()

    def wait_for(self, event: str, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                raise SystemExit(f"the child did not say {event!r} in "
                                 f"{timeout:.0f} s")
            try:
                doc = self.events.get(timeout=left)
            except queue.Empty:
                continue
            if doc["event"] == event:
                return doc
            if doc["event"] in ("eof", "failed"):
                raise SystemExit(f"the child ended before {event!r}: {doc}")

    def finish(self, timeout: float) -> int:
        try:
            return self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.kill()
            raise SystemExit("the child did not exit") from None

    def kill(self) -> None:
        """Stop the child and whatever it started."""
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass


# --------------------------------------------------------------- serving

def pick_rows(reqs: list, records: list, n: int, seed: int) -> list:
    """The sample the reference goes over: of the requests the window
    finished, the longest, and ``n - 1`` more drawn from the seed."""
    done = [r for r in records if r.ok]
    if not done:
        return []
    longest = max(done, key=lambda r: r.prompt_len + r.max_new)
    rest = [r for r in done if r is not longest]
    random.Random(int(seed) + 1).shuffle(rest)
    return [[reqs[r.index].prompt, r.tokens] for r in [longest] + rest[:n - 1]]


def run_serve(spec: dict, args, child_argv: list, on_token=None) -> dict:
    mix = dict(spec["traffic"])
    if args.rehearsal:
        mix.update(mix.get("rehearsal", {}))
    if args.rate:
        mix["rate_per_s"] = args.rate
    a = W.arch(spec["config"], args.rehearsal)
    reqs = loadgen.schedule(mix, args.seed, args.seconds, a.vocab)
    child = Child("benchmarks.harness.serve_child", child_argv)
    try:
        ready = child.wait_for("ready", CHILD_LIMIT_S)
        setup_s = time.monotonic() - T_START
        note(f"ready after {setup_s:.1f} s: {ready['setup']}")
        timed = []
        if args.trace:
            span = min(mix["trace_seconds"], args.seconds * 0.4)
            lo = (args.seconds - span) / 2

            def start():
                child.send(cmd="trace_start")
                child.wait_for("trace_started", 120)

            def stop():
                child.send(cmd="trace_stop")
                child.wait_for("trace_stopped", 300)

            timed = [(lo, "trace_start", start), (lo + span, "trace_stop",
                                                  stop)]
        child.send(cmd="window_open")
        win = asyncio.run(loadgen.run_window(
            ready["host"], ready["port"], reqs, args.seconds, timed=timed,
            on_token=on_token))
        child.send(cmd="window_close")
        window = child.wait_for("window", 60)
        rows = pick_rows(reqs, win["records"], mix["check_rows"], args.seed)
        os.makedirs(os.path.join(CHECKOUT, ".bench_out"), exist_ok=True)
        with open(os.path.join(CHECKOUT, ".bench_out", "rows-%s-%d.json" % (
                args.workload, args.seed)), "w") as f:
            json.dump(rows, f)     # what control.py teacher-forces
        child.send(cmd="check", rows=rows)
        child.wait_for("check_received", 60)
        os.kill(child.proc.pid, signal.SIGTERM)   # a real drain
        final = child.wait_for("final", 300)
        rc = child.finish(60)
    finally:
        child.kill()
    summary = loadgen.summarise(win, args.seconds)
    note("window: " + json.dumps({k: v for k, v in summary.items()
                                  if k != "failures"}))
    check = final.get("check", {})
    note("dispatches at close: " + json.dumps({
        k: {f: v[f] for f in ("count", "compiles", "steady_mean_ms", "tokens")}
        for k, v in (win["stats_at_close"].get("engine", {})
                     .get("dispatch") or {}).items()}))
    note(f"check: {json.dumps(check)}; child: " + json.dumps(
        {k: v for k, v in final.items() if k not in ("check", "trace", "event")}
        | {"window": window}))
    numbers = {
        "failed_requests": summary["failed"],
        "unfinished_requests": win["unfinished"],
        "compiles_in_window": window["compiles_in_window"],
        "drain_exit_code": abs(final["drain_exit_code"]) + abs(rc),
        "logit_gap_max": check.get("logit_gap_max", math.inf),
        "logit_gap_mean": check.get("logit_gap_mean", math.inf),
    }
    marks = win["marks"]
    ctx = {"kind": "serve", "arch": a, "mix": mix, "summary": summary,
           "records": win["records"], "stats_before": win["stats_before"],
           "stats_at_close": win["stats_at_close"], "polls": win["polls"],
           "serve_batch": ready["setup"]["serve_batch"],
           "trace": final.get("trace"), "window": window,
           "trace_span": (marks.get("trace_start_done_s"),
                          marks.get("trace_stop_s")),
           "check": check, "setup": ready["setup"]}
    return {"setup_s": setup_s, "device": ready["device"], "ctx": ctx,
            "attempted": summary["attempted"], "failed": summary["failed"],
            "numbers": numbers, "values": summary,
            "memory_peak_bytes": window["memory_peak_bytes"]}


# -------------------------------------------------------------- training

def run_train(spec: dict, args, child_argv: list) -> dict:
    job = dict(spec["traffic"])
    if args.rehearsal:
        job.update(job.get("rehearsal", {}))
    a = W.arch(spec["config"], args.rehearsal)
    child = Child("benchmarks.harness.train_child",
                  child_argv + ["--seconds", str(args.seconds)]
                  + (["--fault", args.fault] if args.fault else []))
    try:
        ready = child.wait_for("ready", CHILD_LIMIT_S)
        setup_s = time.monotonic() - T_START
        note(f"ready after {setup_s:.1f} s: {ready['setup']}")
        window = child.wait_for("window", args.seconds + 300)
        final = child.wait_for("final", 600)
        rc = child.finish(60)
    finally:
        child.kill()
    steps = window["step_host_ms"]
    values = {"train_tokens_per_s": window["tokens"] / window["window_s"],
              "step_host_p50_ms": loadgen.percentile(steps, 0.5)
              if steps else math.nan,
              "step_host_max_ms": max(steps, default=math.nan),
              "steps_in_flight": window["steps_in_flight"],
              "steps": window["steps"], "window_s": window["window_s"]}
    note("window: " + json.dumps(values))
    numbers = dict(final["check"]["numbers"])
    note("check: " + json.dumps({k: v for k, v in final["check"].items()
                                 if k != "numbers"}))
    numbers["compiles_in_window"] = window["compiles_in_window"]
    numbers["exit_code"] = abs(rc)
    ctx = {"kind": "train", "arch": a, "job": job, "summary": values,
           "window": window, "trace": final.get("trace"),
           "check": final["check"], "setup": ready["setup"]}
    return {"setup_s": setup_s, "device": ready["device"], "ctx": ctx,
            "attempted": window["steps"], "failed": 0, "numbers": numbers,
            "values": values, "memory_peak_bytes": window["memory_peak_bytes"]}


# ------------------------------------------------------------------ main

def metric_lines(spec: dict, res: dict, trace: bool) -> dict:
    """``--trace 0``: the cell's end-to-end metrics, taken here. ``--trace
    1``: its per-layer metrics, each by its own reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    if not trace:
        for m in spec["end_to_end"]:
            v = res["setup_s"] if m["name"] == "setup_s" \
                else res["values"].get(m["name"])
            if v is None or not math.isfinite(v):
                raise SystemExit(f"no value for end-to-end {m['name']}")
            out[m["name"]] = {"value": v, "unit": m["unit"]}
        return out
    ctx = res["ctx"]
    try:
        ctx["peaks"] = peaks(res["device"]["kind"])
    except KeyError:
        if res["device"]["platform"] == "tpu":
            raise
        ctx["peaks"] = None     # the CPU rehearsal: no share of a peak
    for m in spec["per_layer"]:
        reader = importlib.import_module("benchmarks.readers." + m["reader"])
        v = reader.read(ctx, **m.get("args", {}))
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true",
                   help="toy sizes on any device; never a result")
    p.add_argument("--fault", default="", help=argparse.SUPPRESS)
    p.add_argument("--rate", type=float, default=None,
                   help=argparse.SUPPRESS)   # the knee sweep's, once
    p.add_argument("--dry", action="store_true",
                   help="resolve every name the cell uses, print them, stop")
    args = p.parse_args(argv)
    spec = resolve(args.workload)
    if args.dry:
        a = W.arch(spec["config"])
        print(json.dumps({
            "workload": args.workload, "config": spec["config"]["name"],
            "family": a.family,
            # how many layers of each kind: a depth cut against its period
            "layer_kinds": dict(collections.Counter(
                W.layer_kind(a, i) for i in range(a.layers))),
            "traffic": spec["cell"]["traffic"],
            "kind": spec["traffic"]["kind"],
            "end_to_end": [m["name"] for m in spec["end_to_end"]],
            "per_layer": {m["name"]: m["reader"] for m in spec["per_layer"]},
            "limits": sorted(spec["limits"]["limits"])}))
        return 0
    if args.seconds is None:
        args.seconds = float(spec["bench"]["run_seconds"])
    if args.fault and not args.rehearsal:
        raise SystemExit("--fault is for the rehearsal's tests")
    queries = {m["name"]: m["trace_query"] for m in spec["per_layer"]
               if "trace_query" in m}
    child_argv = ["--config", spec["config_path"], "--traffic",
                  spec["traffic_path"], "--seed", str(args.seed), "--chips",
                  str(spec["cell"]["chips"]), "--trace", str(args.trace),
                  "--queries", json.dumps(queries)] \
        + (["--rehearsal"] if args.rehearsal else [])
    kind = spec["traffic"]["kind"]
    if kind == "serve":
        on_token = None
        if args.fault == "token":   # a token altered where it is produced
            def on_token(rec, new):
                return [t ^ 1 for t in new] if rec.index % 2 else new
        res = run_serve(spec, args, child_argv, on_token)
    elif kind == "train":
        res = run_train(spec, args, child_argv)
    else:
        raise SystemExit(f"traffic kind {kind!r} has no child")
    device = dict(res["device"], memory_peak_bytes=res["memory_peak_bytes"])
    if device["platform"] != "tpu" and not args.rehearsal:
        raise SystemExit(f"ran on {device['platform']!r}, not a TPU")
    line = {"correct": True, "attempted": res["attempted"],
            "failed": res["failed"],
            "metrics": metric_lines(spec, res, bool(args.trace)),
            "device": device}
    trace = res["ctx"].get("trace")
    if args.trace:
        if not trace or not trace.get("busy_s"):
            if not args.rehearsal:
                raise SystemExit("the trace shows no operation on the device")
        else:
            device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            line["breakdown"] = {"device_ops": trace["top_ops"],
                                 "idle_gaps": trace["idle_gaps"],
                                 "device_programs": trace["top_programs"]}
    checks = {}
    for name, limit in spec["limits"]["limits"].items():
        value = res["numbers"].get(name, math.inf)
        checks[name] = {"value": value, "limit": limit}
        if not (isinstance(value, (int, float)) and value <= limit):
            line["correct"] = False
    if args.rehearsal:   # what the checks said, then never a result
        line["rehearsal"] = {"checks_pass": line["correct"]}
        line["correct"] = False
    # read by the same comparison but held to no limit (PERF.md says why)
    line["observed"] = {k: v for k, v in res["numbers"].items()
                        if k not in checks}
    line["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
