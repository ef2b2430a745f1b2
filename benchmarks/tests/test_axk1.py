"""The A.X-K1 family (``families/axk1.py``) and its cell: leaves and counts
pinned at the published sizes, the counts against a hand count at a toy
size, the share's reference against the uncut one, the cell's files
resolved and rehearsed on the CPU, and the int8 control."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.families import axk1 as F
from benchmarks.harness import weights as W, work as K

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PINS = json.load(open(os.path.join(ROOT, "benchmarks", "tests",
                                   "pins_axk1.json")))
CELL = "ax-k1.reason-steady"


def cfg():
    return json.load(open(os.path.join(ROOT, "benchmarks", "configs",
                                       PINS["config"] + ".json")))


def test_leaves_and_counts_are_pinned_at_the_published_sizes():
    a = W.arch(cfg())
    assert [n for n, _, _ in W.layer_leaves(a, 0)] == PINS["leaves"]["dense"]
    for i in range(1, a.layers):
        assert [n for n, _, _ in W.layer_leaves(a, i)] \
            == PINS["leaves"]["routed"]
    assert [n for n, _, _ in W.global_leaves(a)] == PINS["leaves"]["global"]
    shapes = {n: list(s) for i in (0, 1) for n, s, _ in W.layer_leaves(a, i)}
    shapes.update({n: list(s) for n, s, _ in W.global_leaves(a)})
    for name, shape in PINS["shapes"].items():
        assert shapes[name] == shape, name
    c = PINS["counts"]
    assert K.n_params(a) == c["n_params"]
    assert K.layer_params(a, 0) == c["layer_params_dense"]
    assert K.layer_params(a, 3) == c["layer_params_routed"]
    assert K.kv_bytes_per_token(a) == c["kv_bytes_per_token"]
    assert F.absorbed_position_flops(a) == c["absorbed_position_flops"]
    assert F.held_per_token(a) == c["held_per_token"]
    assert K.serve_token_flops(a, 1000, True) \
        == c["serve_token_flops_1000_sampled"]
    assert K.serve_token_flops(a, 1000, False) \
        == c["serve_token_flops_1000_prompt"]
    assert K.decode_step_flops(a, 40, 40000) \
        == c["decode_step_flops_40_40000"]


def test_the_configuration_keeps_every_published_width():
    c = cfg()
    row = {"hidden_size": 7168, "num_attention_heads": 64,
           "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
           "q_lora_rank": 1536, "kv_lora_rank": 512,
           "intermediate_size": 18432, "moe_intermediate_size": 2048,
           "num_experts_per_tok": 8, "routed_scaling_factor": 2.5,
           "n_shared_experts": 1, "first_k_dense_replace": 1}
    assert {k: c[k] for k in row} == row
    assert c["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert sorted(c["reduced"]) == sorted(
        ["num_hidden_layers", "n_routed_experts", "vocab_size",
         "max_position_embeddings"])
    assert c["published"] == {
        "num_hidden_layers": 61, "n_routed_experts": 192,
        "vocab_size": 163840, "max_position_embeddings": 131072}
    assert c["expert_parallel"] == {"chips": 16, "rank": 0,
                                    "router_experts": 192}
    a = W.arch(c)
    assert (a.n_routed, a.held, a.top_k, a.held_first) == (192, 12, 8, 0)
    # the pool holds 48 sequences at the traffic's longest, no more
    mix = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                      "reason-open-steady.json")))
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    flags = c["serve_flags"]
    assert int(flags[flags.index("--kv-pages") + 1]) \
        == 48 * -(-longest // 64) and longest <= a.max_len


def test_a_share_that_is_no_part_of_the_router_is_refused():
    c = cfg()
    c["expert_parallel"] = {"chips": 16, "rank": 0, "router_experts": 160}
    with pytest.raises(ValueError, match="router"):
        F.arch(c)
    c = cfg()
    c["topk_method"] = "noaux_tc"
    with pytest.raises(ValueError, match="top-k"):
        F.arch(c)


def test_counts_against_a_hand_count_at_toy_size():
    a = W.arch(cfg(), rehearsal=True)
    d, h = 64, 4
    attn = d * 24 + 24 + 24 * h * 24 + d * 24 + 16 + 16 * h * 32 \
        + h * 16 * d + 2 * d                      # incl. the three norms
    dense = attn + 3 * d * 160
    expert, shared, router = 3 * d * 32, 3 * d * 32, d * 16
    routed = attn + router + 4 * expert + shared
    assert K.layer_params(a, 0) == dense
    assert K.layer_params(a, 1) == K.layer_params(a, 2) == routed
    assert K.n_params(a) == dense + 2 * routed + 2 * 512 * d + d
    assert K.kv_bytes_per_token(a) == (16 + 8) * 3 * 2
    assert F.held_per_token(a) == 4 * 4 / 16 == 1.0
    # a token meets every matmul weight but 3 of the 4 held experts a
    # routed layer
    norms = (2 * d + 24 + 16) * 3
    met = dense + 2 * routed - norms - 2 * 3 * expert
    assert K.serve_token_flops(a, 9, False) \
        == 2 * met + 2 * h * (16 + 8 + 16) * 3 * 10
    assert K.serve_token_flops(a, 9, True) \
        == 2 * met + 2 * h * (2 * 16 + 8) * 3 * 10 + 2 * 512 * d
    assert K.decode_step_flops(a, 3, 50) \
        == 3 * (2 * met + 2 * 512 * d) + 2 * h * 40 * 3 * 50
    # bytes: non-expert weights once, experts only as far as rows hit them
    experts = 2 * expert
    none_hit = (dense + 2 * routed - 4 * experts + 512 * d + d) * 2
    assert K.decode_step_bytes(a, 0) == none_hit
    many = K.decode_step_bytes(a, 256 * 64) - 256 * 64 * 144
    assert none_hit < many < none_hit + 4 * experts * 2
    assert abs(many - none_hit - 4 * experts * 2) < 1.0   # 64 rows hit all
    flops, nbytes = F.moe_experts_step(a, 3, 50, {"pairs_per_step": 5,
                                                  "hit_per_step": 4})
    assert (flops, nbytes) == (2 * expert * 5, 2 * expert * 4)
    flops, nbytes = F.mla_absorb_step(a, 3, 50)
    assert nbytes == 50 * 144 + 3 * 16 * h * 32 * 2
    assert flops == 3 * (3 * 2 * h * 16 * 32 + 2 * h * 40 * 50)


def test_the_cells_files_resolve_and_the_depth_cut_keeps_the_kinds():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--dry"], capture_output=True, text=True,
        timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout.splitlines()[-1])
    assert doc["family"] == "axk1"
    assert doc["layer_kinds"] == {"dense": 1, "routed": 5}
    assert "serve_step_mfu_pct.steady" in doc["per_layer"]
    assert "kv_view_device_pct" not in doc["per_layer"]
    for name in ("mla_absorb_roofline_pct", "moe_experts_roofline_pct",
                 "moe_device_pct", "mla_device_pct", "moe_held_share_pct",
                 "moe_expert_load_max_over_mean", "latent_view_device_pct"):
        assert name in doc["per_layer"], name
    mix = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                      "reason-open-steady.json")))
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.8, "min": 32, "max": 1024}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 1152,
                                    "sigma": 0.3, "min": 512, "max": 1792}
    assert (mix["arrivals"], mix["check_rows"], mix["trace_seconds"]) \
        == ("poisson", 6, 5.0)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    seeds = {json.load(open(os.path.join(
        ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))).get(
            "schedule_seed") for w in bench["workloads"]
        if w["traffic"] != mix and w["name"] != CELL}
    assert mix["schedule_seed"] not in seeds


def test_the_queries_name_the_ops_the_v5e_compiler_made():
    """Event names as the v5e's compiler wrote them for this cell's decode
    step (compiled for a described chip while the PR was built)."""
    import re

    q = {n: json.load(open(os.path.join(
        ROOT, "benchmarks", "layer_metrics", n + ".json")))["trace_query"]
        for n in ("mla_device_pct", "mla_absorb_roofline_pct",
                  "moe_device_pct", "moe_experts_roofline_pct",
                  "latent_view_device_pct")}
    lay3, lay4 = "{2,1,0:T(8,128)(2,1)}", "{3,2,1,0:T(8,128)(2,1)}"
    view, rview = f"bf16[48,2048,512]{lay3}", f"bf16[48,2048,64]{lay3}"
    score = (f"%fusion.255 = f32[48,2048,64]{lay3} fusion({view} %fusion.50,"
             f" bf16[48,64,512]{lay3} %fusion.236), kind=kOutput")
    rope = (f"%bitcast_reduce_fusion.2 = (f32[48,64]{{1,0}}, "
            f"f32[48,2048,1,64]{lay4}) fusion(f32[48,2048,64]{lay3} "
            f"%fusion.255, {rview} %fusion.52, pred[48,2048] %x), kind=kOutput")
    summed = (f"%fusion.275 = bf16[48,64,512]{lay3} fusion({view} %fusion.50,"
              " f32[48,2048,1,64] %gte.293), kind=kOutput")
    write = (f"%fusion.24 = {view} fusion({view} %bitcast.12, "
             f"bf16[48,512]{{1,0}} %fusion.607, s32[48] %m), kind=kCustom")
    q_absorb = (f"%fusion.236 = bf16[48,64,512]{lay3} fusion(bf16[512,64,128]"
                f"{lay3} %gte.205, bf16[48,64,128]{lay3} %c), kind=kOutput")
    gather = (f"%fusion.42 = bf16[1536,64,512]{lay3} fusion(bf16[2112,64,512]"
              f"{lay3} %cache__block_0____attn____cached_latent__.1, "
              "s32[1536]{0} %pad_clamp_fusion), kind=kCustom")
    relayout = ("%copy.158 = bf16[2112,64,64]{2,1,0:T(8,128)(2,1)} copy("
                "bf16[2112,64,64]{0,2,1:T(8,128)(2,1)} "
                "%cache__block_0____attn____cached_rope_key__.1)")
    select = (f"%broadcast_select_fusion = (bf16[48,32,64,512]{lay4}, "
              f"/*index=1*/bf16[48,32,64,64]{lay4}) fusion(bf16[48,32,64,512]"
              f"{lay4} %bitcast.123, pred[48,32] %copy.155), kind=kLoop")
    ragged = ("%ragged-dot-none.13 = f32[384,2048]{1,0:T(8,128)S(1)} "
              "custom-call(s32[1]{0} %gte.93, bf16[384,7168]{1,0} %x, "
              'bf16[12,7168,2048]{2,1,0} %w), custom_call_target='
              '"tpu_custom_call"')
    meta = ("%ragged-dot-metadata.4 = (s32[13]{0}, s32[14]{0}) custom-call("
            's32[12]{0} %gte.238), custom_call_target="tpu_custom_call"')
    dense_mlp = (f"%fusion.9 = bf16[48,18432]{{1,0}} fusion(bf16[48,7168]"
                 "{1,0} %h, bf16[7168,18432]{1,0} %w), kind=kOutput")

    def hits(name, event):
        return re.search(q[name]["match"], event) is not None

    for name in ("mla_device_pct", "mla_absorb_roofline_pct"):
        assert q[name]["program"] == "^jit__decode_chunk"
        for ev in (score, rope, summed):
            assert hits(name, ev), (name, ev)
        for ev in (write, q_absorb, gather, select, ragged, dense_mlp):
            assert not hits(name, ev), (name, ev)
    assert hits("moe_device_pct", ragged) and hits("moe_device_pct", meta)
    assert hits("moe_experts_roofline_pct", ragged)
    assert not hits("moe_experts_roofline_pct", meta)
    for ev in (score, gather, dense_mlp):
        assert not hits("moe_device_pct", ev)
    for ev in (gather, relayout, select):
        assert hits("latent_view_device_pct", ev), ev
    for ev in (score, summed, write, ragged, dense_mlp, q_absorb):
        assert not hits("latent_view_device_pct", ev), ev


def test_the_new_readers_return_nothing_for_nothing():
    from benchmarks.readers import counter_ratio, step_roofline

    a = W.arch(cfg(), rehearsal=True)
    empty = {"trace": None, "peaks": None, "arch": a, "stats_before": None,
             "stats_at_close": None, "trace_span": (None, None)}
    assert step_roofline.read(empty, "q", "^jit", "mla_absorb_step") is None
    assert counter_ratio.read(empty, "engine.a", "engine.b") is None
    # a program without the counters (the parent) reads None, never 0
    ctx = dict(empty, stats_before={"engine": {"decode_steps": 1}},
               stats_at_close={"engine": {"decode_steps": 9}})
    assert counter_ratio.read(ctx, "engine.moe_tokens_held",
                              "engine.moe_tokens_routed", 100.0) is None
    assert step_roofline.window_counters(ctx) is None
    ctx["stats_at_close"]["engine"].update(
        moe_tokens_held=40, moe_tokens_routed=640, moe_expert_load_max=20,
        moe_experts_hit=16, moe_experts_held=12)
    assert counter_ratio.read(ctx, "engine.moe_tokens_held",
                              "engine.moe_tokens_routed", 100.0) == 6.25
    assert counter_ratio.read(ctx, "engine.moe_expert_load_max",
                              "engine.moe_tokens_held",
                              times="engine.moe_experts_held") == 6.0
    assert step_roofline.window_counters(ctx) == {
        "pairs_per_step": 5.0, "hit_per_step": 2.0}


def rehearse(*extra):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 35), "--seconds", "6",
         "--rehearsal", *extra], capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_on_the_cpu_to_a_line_with_checks(trace):
    line = rehearse("--trace", str(trace))
    assert list(line)[-1] == "checks" and line["correct"] is False
    assert line["rehearsal"]["checks_pass"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    if trace:
        assert 20 < line["metrics"]["moe_held_share_pct"]["value"] < 30
        assert line["metrics"]["moe_expert_load_max_over_mean"]["value"] >= 1
        # no device plane on the CPU: shares of a peak are left out
        assert "mla_absorb_roofline_pct" not in line["metrics"]
    else:
        assert {"itl_p95_ms", "setup_s"} <= set(line["metrics"])


def test_the_int8_control_reads_well_above_bf16_arithmetic_at_toy_size():
    """The cell's limits are set on the chip at the published widths
    (PERF.md), where logits are some ten times a toy model's; what a test
    can hold is the ORDER the limits rest on: the reference in int8 in
    the program's place reads at least three times what bf16 operands
    alone read, by both of the cell's numbers."""
    from benchmarks.harness.control import serve_control

    a, seed = W.arch(cfg(), rehearsal=True), 7
    rng = np.random.default_rng(0)
    rows = [[p[:9], p[9:]] for p in
            (rng.integers(1, a.vocab, 60).tolist() for _ in range(6))]
    low = serve_control(a, seed, rows)
    bf16 = serve_control(a, seed, rows, quant="bf16")
    assert low["logit_gap_max"] > 3 * bf16["logit_gap_max"] > 0, (low, bf16)
    assert low["logit_gap_mean"] > 3 * bf16["logit_gap_mean"], (low, bf16)
    assert low["argmax_is_float32_argmax"] < 1
