"""The start-up ledger (``obs/startup.py``): its spans partition a
gateway's build, its compile table books jax's own events by
``fun_name`` and agrees with a listener registered the harness's way,
and ``/stats startup`` holds still once the gateway is ready."""

import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import urllib.request

import pytest

from tony_tpu.obs import startup
from tony_tpu.obs.startup import COMPILE, LOWER, TRACE, CompileTable

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LEAVES = ("startup.pool", "startup.fork_warm", "startup.warm_views")
BUILD = ("startup.build", "startup.edge") + LEAVES


def _get(url, body=None):
    data = None if body is None else json.dumps(body).encode()
    with urllib.request.urlopen(url, data=data, timeout=120) as r:
        return json.loads(r.read())


@pytest.fixture(scope="module")
def booted():
    """One demo-model gateway served by ``cli.gateway.serve()`` on this
    (the main) thread, with the copy-on-write fork and every view
    warmed at boot; once ready, the same request twice to warm both of
    its programs (a first prefill, then the prefix store's hit), then
    ``/stats``, a third time, ``/stats`` again, and a real SIGTERM
    drain."""
    from tony_tpu.cli.gateway import build_parser, demo_model, serve

    args = build_parser().parse_args(
        ["--port", "0", "--serve-batch", "2", "--warm-views",
         "--compile-cache", "", "--no-alerts"])
    model, params = demo_model()
    before = startup.PHASES.snapshot()["phases"]
    seen: dict = {}

    def drive(http):
        url = f"http://{http.host}:{http.port}"
        try:
            req = {"token_ids": [1, 2, 3, 4, 5], "max_new_tokens": 6}
            seen["first"] = _get(url + "/v1/generate", req)
            _get(url + "/v1/generate", req)   # its prefix hit's program
            seen["warm"] = _get(url + "/stats")
            seen["again"] = _get(url + "/v1/generate", req)
            seen["after"] = _get(url + "/stats")
        finally:
            os.kill(os.getpid(), signal.SIGTERM)

    handlers = {s: signal.getsignal(s) for s in (signal.SIGTERM,
                                                 signal.SIGINT)}
    try:
        rc = serve(args, model, params, [], on_ready=lambda http:
                   threading.Thread(target=drive, args=(http,),
                                    daemon=True).start())
    finally:
        for s, h in handlers.items():
            signal.signal(s, h)
    assert rc == 0 and "after" in seen, seen
    return before, seen


def test_the_leaves_and_the_build_spans_own_time_fill_its_wall(booted):
    before, seen = booted
    block = seen["warm"]["startup"]
    phases = block["phases"]["phases"]
    for name in ("startup.build", "startup.edge") + LEAVES:
        assert phases[name]["count"] >= 1, name
    ms = sum(phases[n]["ms"] - before.get(n, {}).get("ms", 0.0)
             for n in BUILD)
    wall = 1e3 * (block["edge_listening_s"] - block["serve_entered_s"])
    assert ms == pytest.approx(wall, abs=5.0)
    # the ledger is a partition of the thread's wall clock
    snap = block["phases"]
    assert sum(p["ms"] for p in snap["phases"].values()) \
        + snap["unnamed_ms"] == pytest.approx(snap["wall_ms"], abs=0.01)


def test_stats_startup_stamps_are_finite_and_ordered(booted):
    block = booted[1]["warm"]["startup"]
    assert 0 <= block["imported_s"] <= block["serve_entered_s"] \
        <= block["edge_listening_s"] <= block["now_s"]
    totals = block["compiles"]
    assert totals["executables"] > 0
    assert totals["compiled_anew"] == \
        totals["executables"] - totals["cache_hits"]
    assert len(totals["top"]) <= startup.TOP


def test_warm_requests_compile_nothing_after_ready(booted):
    seen = booted[1]
    assert seen["again"]["token_ids"] == seen["first"]["token_ids"]
    assert seen["after"]["startup"]["compiles"] == \
        seen["warm"]["startup"]["compiles"]


CHILD = textwrap.dedent("""
    import json, sys
    import numpy as np
    import jax
    import jax.monitoring as m
    from tony_tpu.obs import startup

    jax.config.update("jax_compilation_cache_dir", sys.argv[1])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    meter = {"requests": 0, "hits": 0, "seconds": 0.0}

    def on_event(name, **_):   # the harness's CompileMeter, restated
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            meter["requests"] += 1
        elif name == "/jax/compilation_cache/cache_hits":
            meter["hits"] += 1

    def on_duration(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            meter["seconds"] += secs

    m.register_event_listener(on_event)
    m.register_event_duration_secs_listener(on_duration)

    @jax.jit
    def inner_scale(x):
        return x * 3.0

    @jax.jit
    def scaled_sum(x):
        return inner_scale(x).sum()

    x = np.arange(8, dtype=np.float32)
    out = []
    for step in ("first", "same_shape", "after_clear"):
        if step == "after_clear":
            jax.clear_caches()   # the in-memory cache: the disk's is asked
        float(scaled_sum(x))
        out.append(startup.COMPILES.snapshot())
    print(json.dumps({"snaps": out, "meter": meter}))
""")


@pytest.fixture(scope="module")
def compiled(tmp_path_factory):
    cache = tmp_path_factory.mktemp("jaxcache")
    res = subprocess.run(
        [sys.executable, "-c", CHILD, str(cache)], capture_output=True,
        text=True, timeout=300, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.splitlines()[-1])


def _row(snap, name):
    return next((r for r in snap["top"] if r["name"] == name), None)


def test_a_jits_first_call_is_booked_by_name_a_miss_then_a_hit(compiled):
    first, _, again = compiled["snaps"]
    row = _row(first, "scaled_sum")
    assert (row["count"], row["hits"]) == (1, 0)
    assert row["trace_s"] > 0 and row["lower_s"] > 0 \
        and row["compile_s"] > 0
    # traced inside its caller: its time is the caller's
    assert _row(first, "inner_scale") is None
    row = _row(again, "scaled_sum")
    assert (row["count"], row["hits"]) == (2, 1)
    assert again["cache_hits"] >= 1


def test_a_second_call_of_the_same_shape_books_nothing(compiled):
    first, same, _ = compiled["snaps"]
    assert same == first


def test_the_totals_equal_a_listener_registered_the_harness_way(compiled):
    last, meter = compiled["snaps"][-1], compiled["meter"]
    assert last["executables"] == meter["requests"]
    assert last["cache_hits"] == meter["hits"]
    assert last["compile_s"] == pytest.approx(meter["seconds"], abs=1e-3)


def _drive(table, events):
    """``("open", event, name, start)`` and ``("close", event, name,
    start, end)`` in the order jax calls the listeners; ``("hit",)`` is
    the persistent cache's event."""
    for ev in events:
        if ev[0] == "hit":
            table.on_event("/jax/compilation_cache/cache_hits")
        elif ev[0] == "open":
            table.on_scalar(ev[1], ev[3], fun_name=ev[2])
        else:
            table.on_span(ev[1], ev[3], ev[4], fun_name=ev[2])


def test_nested_spans_are_counted_once_by_kind():
    t = CompileTable()
    _drive(t, [
        ("open", TRACE, "outer", 0.0),
        ("open", TRACE, "inner", 1.0),
        ("close", TRACE, "inner", 1.0, 2.0),
        # an eager op compiled while ``outer`` traces
        ("open", COMPILE, "jit(fill)", 2.0),
        ("close", COMPILE, "jit(fill)", 2.0, 2.5),
        ("close", TRACE, "outer", 0.0, 4.0),
        ("open", LOWER, "jit(outer)", 4.0),
        ("close", LOWER, "jit(outer)", 4.0, 5.0),
        ("open", COMPILE, "jit(outer)", 5.0),
        ("hit",),
        ("close", COMPILE, "jit(outer)", 5.0, 5.25),
    ])
    snap = t.snapshot()
    assert (snap["trace_s"], snap["lower_s"], snap["compile_s"]) == \
        (3.5, 1.0, 0.75)
    assert (snap["executables"], snap["cache_hits"],
            snap["compiled_anew"]) == (2, 1, 1)
    rows = {r["name"]: r for r in snap["top"]}
    assert set(rows) == {"outer", "fill"}
    assert rows["outer"] == {"name": "outer", "count": 1, "hits": 1,
                             "trace_s": 3.5, "lower_s": 1.0,
                             "compile_s": 0.25}
    assert snap["top"][0]["name"] == "outer"   # the largest first


def test_a_span_asked_for_while_another_threads_is_open_runs_unbooked():
    name = "startup.test_leaf"
    inside, release = threading.Event(), threading.Event()

    def hold():
        with startup.leaf(name):
            inside.set()
            release.wait(30)

    th = threading.Thread(target=hold)
    th.start()
    assert inside.wait(30)
    ran = []
    with startup.rest("startup.test_rest"):   # no assertion: neither is
        with startup.leaf(name):              # booked
            ran.append(name)
    release.set()
    th.join(30)
    assert ran == [name]
    phases = startup.PHASES.snapshot()["phases"]
    assert phases[name]["count"] == 1 and "startup.test_rest" not in phases
