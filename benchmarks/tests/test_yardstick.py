"""The parts of the yardstick that need no jax: the trace reduction on a
hand-made device plane, FLOPs and bytes against hand counts and against
what they were before the families moved into files of their own, the
traffic generator, the peaks table, and BENCHMARK.json's names."""

import glob
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import loadgen, trace_reduce as T, work as K
from benchmarks.harness import weights as W
from benchmarks.harness.peaks import peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


PINS = json.load(open(os.path.join(ROOT, "benchmarks", "tests", "pins.json")))


def cfg(name):
    return json.load(open(os.path.join(ROOT, "benchmarks", "configs",
                                       name + ".json")))


# ------------------------------------------------------------ the trace

PLANE = [
    ("/device:TPU:0", T.MODULES, [("jit__decode_chunk(1)", 0, 400),
                                  ("jit__prefill(7)", 600, 200),
                                  ("jit__decode_chunk(1)", 1000, 400)]),
    ("/device:TPU:0", T.OPS, [
        ("%fusion.1 = bf16[8,4096] fusion(...)", 0, 100),
        ("%fusion.2 = bf16[8,4096] fusion(...)", 50, 150),   # overlaps .1
        ('%custom-call.3 = custom-call(...), custom_call_target="tpu_custom_call"', 300, 100),
        ("%fusion.1 = bf16[8,4096] fusion(...)", 600, 200),
        ("%fusion.1 = bf16[8,4096] fusion(...)", 1000, 400)]),
]


def test_trace_busy_is_the_union_of_op_intervals():
    r = T.reduce(PLANE)
    # [0,200) + [300,400) + [600,800) + [1000,1400) = 900 of 1400 ns
    assert r["busy_s"] == pytest.approx(900e-9)
    assert r["window_s"] == pytest.approx(1400e-9)
    assert T.union_ns([(0, 10), (5, 7), (20, 30)]) == 20


def test_trace_per_program_time_and_idle_gaps():
    r = T.reduce(PLANE, {"kernels": {"line": T.OPS, "match": "tpu_custom_call"}})
    assert r["programs"]["jit__decode_chunk"] == {"count": 2, "total_s": pytest.approx(800e-9)}
    assert r["programs"]["jit__prefill"]["count"] == 1
    assert r["queries"]["kernels"]["count"] == 1
    assert r["queries"]["kernels"]["total_s"] == pytest.approx(100e-9)
    gaps = dict(r["idle_gaps"])
    assert gaps["jit__decode_chunk -> jit__prefill"] == pytest.approx(200e-9)
    assert gaps["jit__prefill -> jit__decode_chunk"] == pytest.approx(200e-9)


def test_kv_view_query_names_the_page_view_by_what_it_reads():
    """``kv_view_device_pct``'s query, on event names as the v5e's trace
    prints them (operands with their shapes) and as ``as_text()`` does
    (without): the per-leaf gathers of pool pages through the page table
    and the multi-output select that ends ``jnp.take``, inside the decode
    program only; not the write-back scatter (a third operand), not a
    matmul, not a whole-leaf copy, not the prefill's own view."""
    from benchmarks.readers import trace_share_pct

    spec = json.load(open(os.path.join(
        ROOT, "benchmarks", "layer_metrics", "kv_view_device_pct.json")))
    lay, pool = "{3,2,1,0:T(8,128)(2,1)}", "bf16[1024,64,8,128]"
    view = "bf16[8,16,64,8,128]{4,3,2,1,0:T(8,128)(2,1)}"
    leaf = "%cache__block_3____attn____cached_key__.1"
    gather = (f"%fusion.89 = bf16[128,64,8,128]{lay} fusion({pool}{lay} "
              f"{leaf}, s32[8,16]{{1,0:T(8,128)}} %pad_clamp_fusion.1)")
    bare = (f"%fusion.90 = bf16[128,64,8,128]{lay} fusion({leaf}, "
            "%pad_clamp_fusion.1), kind=kCustom, calls=%fused_computation.8")
    select = (f"%broadcast_select_fusion = ({view}, {view}, /*index=2*/{view})"
              f" fusion({view} %bitcast.553, pred[8,16] %compare_and_fusion)")
    others = [
        f"%fusion.15 = {pool}{lay} fusion({pool}{lay} {leaf}, s32[8,1]{{1,0}} "
        f"%fusion.230, bf16[8,1,8,128]{lay} %fusion.8)",
        "%fusion.634 = f32[8,32768]{1,0:T(8,128)} fusion(bf16[32768,4096]"
        "{1,0:T(8,128)(2,1)} %params__lm_head__.1, bf16[8,4096]{1,0} %gte.156)",
        f"%copy.786 = {pool}{lay} copy({pool}{lay} {leaf})"]
    ops = [(gather, 10, 40), (bare, 60, 40), (select, 110, 100)] \
        + [(nm, 300 + 100 * i, 50) for i, nm in enumerate(others)] \
        + [(gather, 1010, 40), (select, 1100, 100)]    # inside the prefill
    plane = [("/device:TPU:0", T.MODULES, [("jit__decode_chunk(1)", 0, 800),
                                           ("jit__paged_prefill_admit(7)", 1000, 400)]),
             ("/device:TPU:0", T.OPS, ops)]
    r = T.reduce(plane, {"kv_view_device_pct": spec["trace_query"]})
    assert r["queries"]["kv_view_device_pct"]["count"] == 3
    assert r["queries"]["kv_view_device_pct"]["total_s"] == pytest.approx(180e-9)
    assert trace_share_pct.read({"trace": r}, **spec["args"]) == \
        pytest.approx(100 * 180 / 800)
    # with no such op in the trace the metric is left out, never 0
    r = T.reduce([plane[0], (plane[1][0], T.OPS, ops[3:6])],
                 {"kv_view_device_pct": spec["trace_query"]})
    assert trace_share_pct.read({"trace": r}, **spec["args"]) is None


def test_trace_readers_return_nothing_for_nothing():
    from benchmarks.readers import roofline, trace_idle_pct, trace_program_ms

    ctx = {"trace": None, "peaks": None, "arch": None}
    assert trace_idle_pct.read(ctx) is None
    assert trace_program_ms.read(ctx, program="^jit") is None
    assert roofline.read(ctx, work="train_step") is None
    r = T.reduce(PLANE)
    assert trace_idle_pct.read({"trace": r}) == pytest.approx(100 * 500 / 1400)
    assert trace_program_ms.read({"trace": r}, program="^jit__decode") == \
        pytest.approx(400e-6)


# ------------------------------------------------- operations and bytes

def test_work_mistral_hand_counts():
    a = W.arch(cfg("mistral-7b-v0.3-l10"))
    per_layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert K.layer_matmul_params(a) == per_layer == 218_103_808
    assert K.n_params(a) == 10 * (per_layer + 2 * 4096) + 2 * 32768 * 4096 + 4096
    assert round(K.n_params(a) / 1e9, 2) == 2.45
    assert K.kv_bytes_per_token(a) == 40_960    # 4,096 B a layer
    # one decode step reads every block, the head and the live K/V
    assert K.decode_step_bytes(a, 1000) == pytest.approx(
        2 * (10 * (per_layer + 8192) + 32768 * 4096 + 4096) + 1000 * 40960)


def test_work_pythia_hand_counts():
    a = W.arch(cfg("pythia-1.4b-l6"))
    assert a.rotary_dims == 32 and a.head_dim == 128 and a.parallel_residual
    assert K.layer_matmul_params(a) == 4 * 2048 * 2048 + 2 * 2048 * 8192
    assert round(K.n_params(a) / 1e6) == 508
    # 6 FLOPs a matmul weight + causal attention: 2.58 GFLOP a token
    assert K.train_flops_per_token(a, 2048) == pytest.approx(
        6 * (6 * 50_331_648 + 50304 * 2048) + 6 * 2048 * 2048 * 6)
    assert round(K.train_flops_per_token(a, 2048) / 1e9, 2) == 2.58
    assert K.flash_flops_per_step(a, 4, 2048) == 6 * 2048 * 2048 * 2048 * 4 * 6


def test_serve_flops_sum_over_positions():
    """A request of 10 prompt and 5 output tokens feeds 14 positions:
    2 FLOPs a block weight each, attention over the positions so far, and
    the head for each of the 5 sampled tokens."""
    a = W.arch(cfg("mistral-7b-v0.3-l10"))
    n, per_layer = 14, 218_103_808
    assert K.serve_flops(a, 10, 5) == pytest.approx(
        2 * 10 * per_layer * n + 4 * 32 * 128 * 10 * n * (n + 1) / 2
        + 2 * 32768 * 4096 * 5)


@pytest.mark.parametrize("case", sorted(PINS["counts"]))
def test_counts_are_what_they_were_before_the_families_moved(case):
    """Every count the readers divide by, at the arguments the cells use,
    equal to the last digit to ``harness/work.py`` on PR 29's parent."""
    name, size = case.split("/")
    a = W.arch(cfg(name), rehearsal=size == "rehearsal")
    args = {k: v[size] if isinstance(v, dict) else v
            for k, v in PINS["count_args"].items()}
    now = {"n_params": K.n_params(a),
           "kv_bytes_per_token": K.kv_bytes_per_token(a)}
    for key, argv in args.items():
        fn = getattr(K, key.replace("_prompt", "").replace("_sampled", ""))
        now[key] = fn(a, *argv)
    assert now == PINS["counts"][case]


def test_peaks_raise_on_an_unknown_device():
    assert peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks("cpu")


# ---------------------------------------------------------- the traffic

MIX = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                  "chat-open-steady.json")))


def test_schedule_is_a_pure_function_of_the_seed():
    big = 2**31 + 12345
    one = loadgen.schedule(MIX, big, 45, 32768)
    two = loadgen.schedule(MIX, big, 45, 32768)
    assert [(r.due_s, r.prompt, r.max_new) for r in one] == \
        [(r.due_s, r.prompt, r.max_new) for r in two]
    other = loadgen.schedule(MIX, big + 1, 45, 32768)
    assert [r.prompt for r in one] != [r.prompt for r in other]


def test_every_seed_sends_the_same_schedule_with_other_tokens():
    one = loadgen.schedule(MIX, 1, 45, 32768)
    two = loadgen.schedule(MIX, 2, 45, 32768)
    assert len(one) == len(two) == round(MIX["rate_per_s"] * 45)
    assert [(r.due_s, len(r.prompt), r.max_new) for r in one] == \
        [(r.due_s, len(r.prompt), r.max_new) for r in two]
    assert [r.prompt for r in one] != [r.prompt for r in two]
    lens = sorted(len(r.prompt) for r in one)
    assert [len(r.prompt) for r in one] != lens      # shuffled, not sorted
    assert lens[0] >= 32 and lens[-1] <= 1536
    assert 300 < lens[len(lens) // 2] < 480          # median 384
    assert all(0 < r.due_s < 45 for r in one)
    assert all(1 <= t < 32768 for r in one for t in r.prompt)
    assert max(len(r.prompt) + r.max_new for r in one) <= 2048
    other = loadgen.schedule(dict(MIX, schedule_seed=27), 1, 45, 32768)
    assert [len(r.prompt) for r in other] != [len(r.prompt) for r in one]


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert loadgen.percentile(xs, 0.90) == 90
    assert loadgen.percentile(xs, 0.95) == 95
    assert loadgen.percentile([5.0], 0.9) == 5.0


def test_a_training_job_sends_four_to_eight_seconds_of_steps_ahead():
    """A stall of the shared host must find the chip fed, and the last
    wait must have an end: every ``train`` job states both."""
    jobs = [json.load(open(f)) for f in glob.glob(
        os.path.join(ROOT, "benchmarks", "traffic", "*.json"))]
    jobs = [j for j in jobs if j["kind"] == "train"]
    assert jobs
    for job in jobs:
        assert 4.0 <= job["dispatch_ahead_s"] <= 8.0
        assert job["max_steps_in_flight"] >= 2


# ------------------------------------------------------------ the names

def test_every_name_resolves_and_every_arrow_lands():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    assert "setup_s" in e2e and BENCH["paths"] == ["benchmarks"]
    for w in BENCH["workloads"]:
        out = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
             "--workload", w["name"], "--dry"], capture_output=True, text=True)
        assert out.returncode == 0, out.stderr
        seen = json.loads(out.stdout)
        assert seen["per_layer"], "every cell reports a per-layer metric"
        assert len(seen["end_to_end"]) >= 2
    for m in BENCH["per_layer"]:
        spec = json.load(open(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".json")))
        assert (spec["layer"], spec["unit"], spec["moves"]) == \
            (m["layer"], m["unit"], m["moves"])
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)
        if m["name"].endswith("_roofline_pct") or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["source"] == "device_trace"
    for c in BENCH["configs"]:
        doc = json.load(open(os.path.join(ROOT, c["file"])))
        assert doc["source"] == c["source"] and doc["reduced"] == c["reduced"]
        assert set(doc["published"]) == set(doc["reduced"])


def test_a_cell_and_a_metric_are_added_by_files_alone(tmp_path):
    """A throw-away cell, mix and per-layer metric: new files and new
    BENCHMARK.json entries, no file of the benchmark edited."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({
        "name": "mistral-7b.throwaway", "config": "mistral-7b-v0.3-l10",
        "traffic": "throwaway-mix", "chips": 1, "why": "a test"})
    bench["end_to_end"][0]["workloads"].append("mistral-7b.throwaway")
    bench["per_layer"].append({
        "name": "throwaway_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "load generator",
        "moves": "itl_p95_ms", "workloads": ["mistral-7b.throwaway"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    b = tmp_path / "benchmarks"
    (b / "traffic" / "throwaway-mix.json").write_text(json.dumps(
        dict(MIX, rate_per_s=1.0)))
    (b / "layer_metrics" / "throwaway_ms.json").write_text(json.dumps({
        "name": "throwaway_ms", "layer": "load generator", "unit": "ms",
        "moves": "itl_p95_ms", "reader": "loadgen",
        "args": {"field": "loadgen_late_max_ms"}}))
    shutil.copy(b / "limits" / "mistral-7b.chat-steady.json",
                b / "limits" / "mistral-7b.throwaway.json")
    run = [sys.executable, str(b / "run.py"), "--dry", "--workload"]
    out = subprocess.run(run + ["mistral-7b.throwaway"], capture_output=True,
                         text=True)
    assert out.returncode == 0, out.stderr
    seen = json.loads(out.stdout)
    assert seen["traffic"] == "throwaway-mix"
    assert seen["per_layer"] == {"throwaway_ms": "loadgen"}
    # and a name with no file behind it is refused
    os.remove(b / "layer_metrics" / "throwaway_ms.json")
    out = subprocess.run(run + ["mistral-7b.throwaway"], capture_output=True,
                         text=True)
    assert out.returncode != 0 and "no such file" in out.stderr


def test_a_model_family_is_added_by_files_alone(tmp_path):
    """A throw-away FAMILY: ``families/throwaway.py``, a configuration
    whose ``model_type`` names it, and BENCHMARK.json entries; no file of
    the benchmark edited. Without the family's file the run is refused by
    the path that would make it known."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "benchmarks"
    (b / "families" / "throwaway.py").write_text(
        "from benchmarks.families.mistral import *  # noqa: F401,F403\n")
    (b / "configs" / "throwaway-l2.json").write_text(json.dumps(dict(
        cfg("mistral-7b-v0.3-l10"), name="throwaway-l2",
        model_type="throwaway", num_hidden_layers=2)))
    shutil.copy(b / "limits" / "mistral-7b.chat-steady.json",
                b / "limits" / "throwaway.chat-steady.json")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(
        bench["configs"][0], name="throwaway-l2",
        file="benchmarks/configs/throwaway-l2.json"))
    bench["workloads"].append({
        "name": "throwaway.chat-steady", "config": "throwaway-l2",
        "traffic": "chat-open-steady", "chips": 1, "why": "a test"})
    bench["end_to_end"][0]["workloads"].append("throwaway.chat-steady")
    bench["per_layer"][0]["workloads"].append("throwaway.chat-steady")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    run = [sys.executable, str(b / "run.py"), "--dry", "--workload",
           "throwaway.chat-steady"]
    out = subprocess.run(run, capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    seen = json.loads(out.stdout)
    assert (seen["config"], seen["family"]) == ("throwaway-l2", "throwaway")
    os.remove(b / "families" / "throwaway.py")
    out = subprocess.run(run, capture_output=True, text=True)
    assert out.returncode != 0 and out.stdout == ""
    assert "benchmarks/families/throwaway.py" in out.stderr


def test_the_general_files_name_no_model_family():
    """What the harness knows of a family is in ``families/``: the entry
    point, the harness and the readers name no model type, and read from
    an ``Arch`` only what every family has."""
    b = os.path.join(ROOT, "benchmarks")
    general = [os.path.join(b, "run.py")] + sorted(
        glob.glob(os.path.join(b, "harness", "*.py"))
        + glob.glob(os.path.join(b, "readers", "*.py")))
    assert len(general) > 15
    families = [os.path.basename(f)[:-3] for f in glob.glob(
        os.path.join(b, "families", "*.py")) if "__" not in f]
    assert {"mistral", "gpt_neox"} <= set(families)
    private = re.compile(
        r"\ba\.(?!family\b|d\b|layers\b|vocab\b|max_len\b)[a-z_]+\b")
    for path in general:
        text = open(path).read()
        for fam in families:
            assert fam not in text, (path, fam)
        assert not private.findall(text), (path, private.findall(text))
