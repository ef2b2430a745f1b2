"""``readers/startup.py`` on a hand-made ``/stats startup`` block, None
(never 0) where the program has none, the four metrics declared as
their files say, and a traced rehearsal that prints all four."""

import json
import math
import os
import subprocess
import sys

import pytest

from benchmarks.readers import startup

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NAMES = ("setup_to_serve_s", "setup_build_s", "setup_compile_s",
         "setup_lower_s")


def _spec(name):
    with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
        return json.load(f)


@pytest.fixture()
def ctx():
    block = {"imported_s": 4.0, "serve_entered_s": 21.5,
             "edge_listening_s": 33.25, "now_s": 50.0,
             "phases": {"wall_ms": 46000.0, "phases": {},
                        "unnamed_ms": 46000.0},
             "compiles": {"executables": 68, "cache_hits": 68,
                          "compiled_anew": 0, "trace_s": 3.5,
                          "lower_s": 6.25, "compile_s": 12.0,
                          "names": 30, "top": []}}
    close = json.loads(json.dumps(block))
    close["compiles"]["compile_s"] = 99.0   # the window is not read
    return {"stats_before": {"startup": block},
            "stats_at_close": {"startup": close}}


@pytest.mark.parametrize("name,want", [
    ("setup_to_serve_s", 21.5), ("setup_build_s", 11.75),
    ("setup_compile_s", 12.0), ("setup_lower_s", 9.75)])
def test_each_metric_reads_the_block_before_the_window(ctx, name, want):
    assert startup.read(ctx, **_spec(name)["args"]) == pytest.approx(want)


@pytest.mark.parametrize("name", NAMES)
def test_no_block_reads_none_never_zero(ctx, name):
    args = _spec(name)["args"]
    assert startup.read({"stats_before": {"engine": {}}}, **args) is None
    assert startup.read({"stats_before": None}, **args) is None
    assert startup.read({}, **args) is None          # a training cell
    del ctx["stats_before"]["startup"]["edge_listening_s"]
    if name == "setup_build_s":   # not listening yet: no span to read
        assert startup.read(ctx, **args) is None


def test_every_startup_metric_is_declared_as_its_file_says():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    mine = sorted(n[:-5] for n in os.listdir(os.path.join(HERE,
                                                          "layer_metrics"))
                  if _spec(n[:-5])["reader"] == "startup")
    assert mine == sorted(NAMES)
    serving = []
    for w in bench["workloads"]:
        with open(os.path.join(HERE, "traffic", w["traffic"] + ".json")) as f:
            if json.load(f)["kind"] == "serve":
                serving.append(w["name"])
    for name in NAMES:
        spec, m = _spec(name), declared[name]
        assert (m["layer"], m["moves"], m["unit"]) == \
            (spec["layer"], spec["moves"], spec["unit"]) == \
            ("start-up", "setup_s", "s")
        want = "program_counter" if name in ("setup_compile_s",
                                             "setup_lower_s") \
            else "program_span"
        assert m["source"] == want
        assert m["workloads"] == serving


def test_a_traced_rehearsal_prints_the_four_startup_metrics():
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"),
         "--workload", "mistral-7b.chat-steady", "--seed", str(2**31 + 9),
         "--seconds", "4", "--rehearsal", "--trace", "1"],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    metrics = json.loads(out.stdout.splitlines()[-1])["metrics"]
    for name in NAMES:
        assert name in metrics, sorted(metrics)
        assert math.isfinite(metrics[name]["value"])
        assert metrics[name]["value"] > 0 and metrics[name]["unit"] == "s"
