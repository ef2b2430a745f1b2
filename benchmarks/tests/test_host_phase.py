"""``readers/host_phase.py`` on hand-made ``/stats`` documents: each
``per`` and ``clock`` mode, the two extra rows, and None (never 0) when
the program has no ledger."""

import json
import os

import pytest

from benchmarks.readers import host_phase

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _stats(wall, unnamed, phases, decodes, lag=None):
    doc = {"engine": {
        "dispatch": {"decode": {"count": decodes}},
        "host": {"wall_ms": wall, "unnamed_ms": unnamed,
                 "phases": {k: {"count": c, "ms": ms, "cpu_ms": cpu}
                            for k, (c, ms, cpu) in phases.items()}}}}
    if lag is not None:
        doc["edge"] = {"emit_lag": {"count": lag[0], "ms": lag[1],
                                    "max_ms": 9.0}}
    return doc


@pytest.fixture()
def ctx():
    before = _stats(1000.0, 10.0, {
        "decode.wait": (10, 180.0, 2.0), "decode.enqueue": (10, 40.0, 30.0),
        "decode.prepare": (10, 10.0, 10.0), "admit.wait": (2, 80.0, 1.0),
        "loop.idle_wait": (5, 600.0, 0.5), "loop.stream": (10, 20.0, 10.0),
    }, decodes=10, lag=(20, 10.0))
    # the window: 100 decode dispatches in 3 s of wall clock, 400 ms idle
    close = _stats(4000.0, 60.0, {
        "decode.wait": (110, 1980.0, 22.0),
        "decode.enqueue": (110, 440.0, 330.0),
        "decode.prepare": (110, 110.0, 110.0),
        "admit.wait": (7, 280.0, 3.0),
        "loop.idle_wait": (9, 1000.0, 0.9),
        "loop.stream": (110, 120.0, 60.0),
        "step.other": (100, 30.0, 30.0),          # new in the window
    }, decodes=110, lag=(220, 70.0))
    return {"stats_before": before, "stats_at_close": close}


OVERHEAD = r"^(?!edge\.)(?!loop\.idle_wait$)(?!.*\.wait$)"


@pytest.mark.parametrize("args,want", [
    # ms per decode dispatch: one phase, and a sum of two
    ({"phases": r"^decode\.wait$", "per": "decode"}, 18.0),
    ({"phases": r"^decode\.(prepare|enqueue)$", "per": "decode"}, 5.0),
    # ms per entry into the phase: the edge's row, and a ledger row
    ({"phases": r"^edge\.emit_lag$", "per": "count"}, 0.3),
    ({"phases": r"^admit\.wait$", "per": "count"}, 40.0),
    # unnamed over the wall clock
    ({"phases": r"^unnamed$", "per": "wall"}, 100 * 50.0 / 3000.0),
    # every phase but the waits and the edge, over wall minus idle:
    # enqueue 400 + prepare 100 + stream 100 + step.other 30 + unnamed 50
    ({"phases": OVERHEAD, "per": "busy"}, 100 * 680.0 / 2600.0),
    # off-CPU share of the same rows: enqueue 100 + stream 50 of 680
    ({"phases": OVERHEAD, "per": "ms", "clock": "offcpu"},
     100 * 150.0 / 680.0),
    ({"phases": r"^decode\.wait$", "per": "decode", "clock": "offcpu"},
     17.8),
])
def test_each_mode_reads_the_windows_delta(ctx, args, want):
    assert host_phase.read(ctx, **args) == pytest.approx(want)


def test_no_ledger_reads_none_never_zero(ctx):
    args = {"phases": r"^decode\.wait$", "per": "decode"}
    parent = {"engine": {"dispatch": {"decode": {"count": 5}}}}
    assert host_phase.read({"stats_before": parent,
                            "stats_at_close": parent}, **args) is None
    assert host_phase.read({"stats_before": None}, **args) is None
    assert host_phase.read({}, **args) is None     # a training cell
    # a ledger with nothing in the window divides by nothing
    still = {"stats_before": ctx["stats_at_close"],
             "stats_at_close": ctx["stats_at_close"]}
    assert host_phase.read(still, **args) is None
    assert host_phase.read(still, phases="^unnamed$", per="wall") is None
    # no edge block (the threaded edge): that row alone is missing
    del ctx["stats_at_close"]["edge"]
    assert host_phase.read(ctx, phases=r"^edge\.emit_lag$",
                           per="count") is None
    assert host_phase.read(ctx, **args) == pytest.approx(18.0)


@pytest.mark.parametrize("bad", [{"per": "step"}, {"clock": "gil"}])
def test_an_unknown_mode_raises(ctx, bad):
    with pytest.raises(ValueError, match="unknown"):
        host_phase.read(ctx, **{"phases": "x", "per": "wall", **bad})


def test_every_host_phase_metric_is_declared_and_resolves():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    names = [f[:-5] for f in os.listdir(os.path.join(HERE, "layer_metrics"))]
    mine = []
    for name in names:
        with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        if spec["reader"] == "host_phase":
            mine.append(name)
            assert declared[name]["source"] == "program_span"
            assert declared[name]["layer"] == spec["layer"]
            assert declared[name]["moves"] == spec["moves"]
    assert len(mine) == 9
