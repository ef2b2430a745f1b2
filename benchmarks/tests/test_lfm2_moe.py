"""The LFM2 family (``families/lfm2_moe.py``) and its cell: leaves and counts
pinned at the published sizes, the counts against a hand count at a toy size,
the family's ``block``/``logits`` against the program (a full forward pass, and
prefill then decode through the engine), the cell's files resolved and
rehearsed on the CPU, and the int8 control.

Tolerances: the program in float32 over the bf16 VALUES the seed gives,
against the float32 reference over the same values, differ by the ORDER of the
same sums (grouped products against a loop over experts, a conv state carried
against shifted adds, a cache against a full pass): 1e-4 on logits of
magnitude about 1."""

import ast
import functools
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.families import lfm2_moe as F
from benchmarks.harness import weights as W, work as K

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PINS = json.load(open(os.path.join(ROOT, "benchmarks", "tests",
                                   "pins_lfm2_moe.json")))
CELL = "lfm2-8b-a1b.assist-steady"
SEED = 2**31 + 37


def cfg():
    return json.load(open(os.path.join(ROOT, "benchmarks", "configs",
                                       PINS["config"] + ".json")))


# --------------------------------------------------- sizes, leaves, counts

def test_leaves_and_counts_are_pinned_at_the_published_sizes():
    a = W.arch(cfg())
    assert [W.layer_kind(a, i) for i in range(a.layers)] == PINS["kinds"]
    for i, kind in enumerate(PINS["kinds"]):
        leaves = W.layer_leaves(a, i)
        assert [n for n, _, _ in leaves] == PINS["leaves"][kind]
        for n, shape, init in leaves:
            assert list(shape) == PINS["shapes"][n], n
            assert init == PINS["init"][n], n
    assert [n for n, _, _ in W.global_leaves(a)] == PINS["leaves"]["global"]
    c = PINS["counts"]
    assert K.n_params(a) == c["n_params"] == 4_740_467_456
    assert K.params(a) == c["block_params"] == 4_472_029_952
    first = {k: PINS["kinds"].index(k) for k in c["layer_params"]}
    assert {k: K.layer_params(a, i) for k, i in first.items()} \
        == c["layer_params"]
    assert K.kv_bytes_per_token(a) == c["kv_bytes_per_token"] == 6144
    assert F.state_bytes_per_sequence(a) == 81920
    assert K.serve_token_flops(a, 1000, True) \
        == c["serve_token_flops_1000_sampled"]
    assert K.serve_token_flops(a, 1000, False) \
        == c["serve_token_flops_1000_prompt"]
    assert K.decode_step_flops(a, 32, 22400) \
        == c["decode_step_flops_32_22400"]
    assert K.decode_step_bytes(a, 22400) == c["decode_step_bytes_22400"]
    assert list(F.conv_mix_step(a, 32, 0)) == c["conv_mix_step_32"]
    assert list(F.moe_experts_step(a, 32, 0)) == c["moe_experts_step_32"]


def test_the_configuration_keeps_every_published_key():
    """Every key of the source row as published, but the four cuts."""
    c = cfg()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    row = next(json.loads(ln) for ln in open(catalog)
               if '"LFM2-8B-A1B"' in ln) if os.path.exists(catalog) else None
    reduced = ["num_hidden_layers", "num_dense_layers", "layer_types",
               "max_position_embeddings"]
    assert c["reduced"] == reduced
    if row is not None:
        assert c["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in reduced:
                assert c["published"][key] == value, key
            else:
                assert c[key] == value, key
    assert (c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_experts"],
            c["num_experts_per_tok"], c["conv_L_cache"]) \
        == (2048, 7168, 1792, 32, 4, 3)
    # the 13 kept are published layers 1 to 13: three whole periods
    assert c["layer_types"] == c["published"]["layer_types"][1:14]
    assert c["layer_types"][1:] == ["full_attention", "conv", "conv",
                                    "conv"] * 3
    a = W.arch(c)
    assert (F.conv_layers(a), F.attn_layers(a), F.moe_layers(a)) == (10, 3, 12)
    # the pool holds 32 sequences at the traffic's longest, no more
    mix = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                      "assist-open-steady.json")))
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    flags = c["serve_flags"]
    assert longest == a.max_len
    assert int(flags[flags.index("--kv-pages") + 1]) == 32 * longest // 64
    assert flags[flags.index("--prefix-cache-mb") + 1] == "0"


def test_what_the_reference_lacks_is_refused():
    for key, value, word in (("conv_bias", True, "conv_bias"),
                             ("tie_word_embeddings", True, "_serve_head"),
                             ("layer_types", ["conv"] * 3, "layer_types")):
        c = cfg()
        c[key] = value
        with pytest.raises(ValueError, match=word):
            F.arch(c)


def test_counts_against_a_hand_count_at_toy_size():
    a = W.arch(cfg(), rehearsal=True)
    d, hd = 64, 16
    assert PINS["kinds"][:2] == [W.layer_kind(a, 0), W.layer_kind(a, 1)]
    conv = d * 3 * d + d * 3 + d * d
    attn = d * 4 * hd + 2 * d * 2 * hd + 4 * hd * d + 2 * hd
    dense = 3 * d * 160
    expert = 3 * d * 32
    moe = d * 8 + 8 + 8 * expert
    assert K.layer_params(a, 0) == 2 * d + conv + dense
    assert K.layer_params(a, 1) == 2 * d + attn + moe
    assert K.layer_params(a, 2) == K.layer_params(a, 4) == 2 * d + conv + moe
    blocks = 5 * 2 * d + 4 * conv + attn + dense + 4 * moe
    assert K.n_params(a) == blocks + 2 * 512 * d + d
    assert K.kv_bytes_per_token(a) == 2 * 2 * hd * 1 * 2
    assert F.state_bytes_per_sequence(a) == 4 * 2 * d * 2
    # a token meets every matmul weight but 6 of the 8 experts a routed
    # layer; norms, q/k scales, taps and the bias are no matmul
    met = 4 * (conv - 3 * d) + (attn - 2 * hd) + dense \
        + 4 * (moe - 8) - 4 * 6 * expert
    mix = 4 * d * (2 * 3 + 2)
    assert K.serve_token_flops(a, 9, False) \
        == 2 * met + mix + 4 * 4 * hd * 1 * 10
    assert K.serve_token_flops(a, 9, True) \
        == K.serve_token_flops(a, 9, False) + 2 * 512 * d
    assert K.decode_step_flops(a, 3, 50) \
        == 3 * (2 * met + mix + 2 * 512 * d) + 4 * 4 * hd * 50
    # bytes: non-expert weights once, experts only as far as rows hit them
    none_hit = (blocks - 4 * 8 * expert + 512 * d + d) * 2
    assert K.decode_step_bytes(a, 0) == none_hit
    rows = 100
    many = K.decode_step_bytes(a, rows * 256) - rows * 256 * 128 \
        - 2 * rows * 1024
    assert abs(many - none_hit - 4 * 8 * expert * 2) < 1.0  # all are hit
    assert F.moe_experts_step(a, 3, 50, {"pairs_per_step": 24,
                                         "hit_per_step": 17}) \
        == (2 * expert * 24, 2 * expert * 17)
    flops, nbytes = F.conv_mix_step(a, 3, 50)
    assert flops == 4 * 3 * (2 * 4 * d * d + d * 8)
    # the projections' weights once a step, and the rows' state
    assert nbytes == 4 * 4 * d * d * 2 + 2 * 3 * 1024


def test_the_family_file_imports_no_jax_and_no_program_at_module_level():
    src = open(os.path.join(ROOT, "benchmarks", "families",
                            "lfm2_moe.py")).read()
    tree = ast.parse(src)
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    names = {(n.module if isinstance(n, ast.ImportFrom) else n.names[0].name)
             for n in top}
    assert not any(m.split(".")[0] in ("jax", "tony_tpu", "flax", "numpy")
                   for m in names), names
    users = {f.name for f in ast.walk(tree) if isinstance(f, ast.FunctionDef)
             and "tony_tpu" in ast.get_source_segment(src, f)
             and any(isinstance(n, (ast.Import, ast.ImportFrom))
                     for n in ast.walk(f))}
    assert users == {"program_config"}, users


# ------------------------------------------ the reference and the program

@functools.lru_cache(maxsize=None)
def toy():
    """(arch, model, params): float32 arithmetic over the bf16 VALUES the
    reference makes from the seed."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import adapter as A
    from tony_tpu.models import Transformer

    a = W.arch(cfg(), rehearsal=True)
    model = Transformer(A.program_config(a, jnp.float32))
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          A.seeded_params(a, SEED, jnp.bfloat16))
    return a, model, params


def test_the_program_tree_is_the_models_own():
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import adapter as A

    a, model, params = toy()
    want = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"])
    assert jax.tree.map(lambda x: x.shape, params) \
        == jax.tree.map(lambda x: x.shape, want)
    names = A.leaf_names(a)
    assert names["block_0"]["conv"]["kernel"] == "0/taps"
    assert names["block_1"]["moe"]["expert_bias"] == "1/expert_bias"
    assert names["block_1"]["attn"]["k_norm"]["scale"] == "1/k_norm.scale"
    cfg_ = model.cfg
    assert cfg_.layer_types == a.layer_types and cfg_.qk_norm
    assert cfg_.routed.selection_bias and cfg_.routed.renorm_eps == 1e-6
    assert cfg_.routed.held == (0, 8) and not cfg_.tied_embeddings
    assert not cfg_.kv_pack_lanes      # derived: 2 heads of 16 fill no row


def test_the_programs_full_forward_is_the_references():
    import jax.numpy as jnp

    from benchmarks.harness import reference as R

    a, model, params = toy()
    rng = np.random.default_rng(0)
    toks = rng.integers(1, a.vocab, (2, 40))
    logits = np.asarray(model.apply({"params": params}, jnp.asarray(toks)))
    pos = np.tile(np.arange(40, dtype=np.int32)[None], (2, 1))
    served = logits.argmax(-1).astype(np.int32)
    best, at, _ = R.serve_logits(a, SEED, toks, pos, served)
    assert np.abs(np.asarray(best) - logits.max(-1)).max() < 1e-4
    assert float(jnp.max(best - at)) < 1e-4


@pytest.mark.parametrize("prefill_chunk", [0, 16])
def test_prefill_then_decode_through_the_engine_agrees_on_logits(
        prefill_chunk):
    """Paged prefill (whole, or in chunks that carry the conv state),
    then decode out of the page pool and the slot rows, two rounds in
    flight and slots reused: each served token's logit lies within 1e-4
    of the reference's best at its position (the reference's full pass
    keeps no cache and no state)."""
    from benchmarks.harness.serve_child import check_rows
    from tony_tpu.serve import Server
    from tony_tpu.serve.engine import Request

    a, model, params = toy()
    rng = np.random.default_rng(1)
    reqs = [rng.integers(1, a.vocab, n).tolist() for n in (9, 33, 17, 64, 5)]
    srv = Server(model, params, batch_size=2, kv_page_size=16,
                 prefill_chunk_tokens=prefill_chunk)
    for i, p in enumerate(reqs):
        srv.submit(Request(prompt=p, max_new_tokens=12 + i, id=i))
    out = {r.id: r.tokens for r in srv.run()}
    res = check_rows(a, SEED, [[reqs[i], out[i]] for i in sorted(out)])
    assert res["served_tokens"] == sum(12 + i for i in range(5))
    assert res["logit_gap_max"] < 1e-4, res["logit_gap_max"]
    c = srv.counters()
    assert c["decode_rounds_overlapped"] > 0 and c["kv_tree_kept"] == 0
    assert c["state_resets"] == 5
    assert c["state_carried_chunks"] == (6 if prefill_chunk else 0)
    assert c["moe_experts_hit"] > 0 and c["moe_experts_held"] == 8


def test_a_dropped_tap_or_bias_shows_in_the_comparison():
    """The seeded taps and bias are random on purpose: a program that
    ignores the conv state's older position, or the selection bias, reads
    well outside the tolerance above."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import reference as R

    a, model, params = toy()
    rng = np.random.default_rng(0)
    toks = rng.integers(1, a.vocab, (2, 40))
    pos = np.tile(np.arange(40, dtype=np.int32)[None], (2, 1))

    def gap(p):
        logits = np.asarray(model.apply({"params": p}, jnp.asarray(toks)))
        best, _, _ = R.serve_logits(a, SEED, toks, pos,
                                    logits.argmax(-1).astype(np.int32))
        return np.abs(np.asarray(best) - logits.max(-1)).max()

    no_tap = jax.tree_util.tree_map_with_path(
        lambda path, x: x.at[:, 0].set(0.0)
        if "conv']['kernel" in jax.tree_util.keystr(path) else x, params)
    assert gap(no_tap) > 1e-2
    big = jax.tree_util.tree_map_with_path(
        lambda path, x: x * 20 if "expert_bias" in jax.tree_util.keystr(path)
        else x, params)
    assert gap(big) > 1e-3


# ------------------------------------------------------- the cell's files

def test_the_cells_files_resolve_and_the_depth_cut_keeps_the_kinds():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--dry"], capture_output=True, text=True,
        timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout.splitlines()[-1])
    assert doc["family"] == "lfm2_moe"
    assert doc["layer_kinds"] == {"conv_dense": 1, "attn_moe": 3,
                                  "conv_moe": 9}
    assert doc["end_to_end"] == ["itl_p95_ms", "setup_s"]
    for name in ("conv_device_pct", "moe_device_pct", "moe_experts_roofline_pct",
                 "moe_expert_load_max_over_mean", "kv_view_device_pct",
                 "decode_hbm_roofline_pct", "serve_step_mfu_pct.steady",
                 "decode_step_device_ms", "device_idle_pct.steady"):
        assert name in doc["per_layer"], name
    for name in ("moe_held_share_pct", "latent_view_device_pct",
                 "mla_device_pct", "conv_mix_roofline_pct"):
        assert name not in doc["per_layer"], name
    mix = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                      "assist-open-steady.json")))
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 512,
                                    "sigma": 0.9, "min": 64, "max": 3072}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.6, "min": 32, "max": 1024}
    assert (mix["arrivals"], mix["check_rows"], mix["trace_seconds"]) \
        == ("poisson", 6, 5.0)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    others = {json.load(open(os.path.join(
        ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))).get(
            "schedule_seed") for w in bench["workloads"] if w["name"] != CELL}
    assert mix["schedule_seed"] not in others
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert f"{mix['rate_per_s']} req/s" in cell["why"]


def test_the_queries_name_the_ops_the_v5e_compiler_made():
    """Event names as the v5e's compiler wrote them for this cell's decode
    step (compiled for a described chip while the PR was built; operands
    carry their shapes in a trace's ``XLA Ops`` line)."""
    q = {n: json.load(open(os.path.join(
        ROOT, "benchmarks", "layer_metrics", n + ".json")))["trace_query"]
        for n in ("conv_device_pct", "kv_view_device_pct",
                  "moe_device_pct")}
    lay = "{2,0,1:T(8,128)(2,1)S(1)}"
    in_proj = (f"%convolution_bitcast_fusion.8 = bf16[32,1,6144]{lay} fusion("
               "bf16[2048,6144]{1,0:T(8,128)(2,1)S(1)} %custom-call.211, "
               "bf16[32,1,2048]{2,0,1} %get-tuple-element.411, f32[32]{0} "
               "%add_rsqrt_fusion.28), kind=kOutput")
    gates = (f"%slice_multiply_fusion.18 = bf16[32,1,2048]{lay} fusion("
             f"bf16[32,1,6144]{lay} %convolution_bitcast_fusion.8), kind=kLoop")
    mix = ("%fusion.196 = f32[32,3,2048]{2,0,1:T(8,128)S(1)} fusion("
           f"bf16[32,1,2048]{lay} %slice_multiply_fusion.18, "
           "bf16[32,2,2048]{2,0,1:T(8,128)(2,1)S(1)} %copy.457), kind=kLoop")
    taps = ("%slice_convert_fusion.26 = (f32[2048,1]{0,1:T(1,128)S(1)}, "
            "f32[2048,1]{0,1:T(1,128)S(1)}, f32[2048,1]{0,1:T(1,128)S(1)}) "
            "fusion(bf16[2048,3]{1,0:T(4,128)(2,1)S(1)} %copy-done.92), "
            "kind=kLoop")
    out_proj = ("%fusion.215 = (f32[32]{0:T(128)S(1)}, bf16[32,2048]{1,0}) "
                "fusion(bf16[32,1,2048]{2,0,1} %get-tuple-element.411, "
                "bf16[2048,2048]{1,0} %custom-call.222, f32[32,3,2048]"
                "{2,0,1:T(8,128)S(1)} %get-tuple-element.441), kind=kOutput")
    relayout = ("%copy.457 = bf16[32,2,2048]{2,0,1:T(8,128)(2,1)S(1)} copy("
                "bf16[32,2,2048]{2,1,0:T(2,128)(2,1)S(1)} %copy-done.67)")
    gather = ("%fusion.14 = bf16[512,64,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} "
              "fusion(bf16[2048,64,4,128]{3,2,1,0:T(4,128)(2,1)} "
              "%cache__block_1____attn____cached_key__.1, s32[512]{0} "
              "%pad_clamp_fusion.24), kind=kCustom")
    write = ("%fusion.51 = bf16[2048,64,4,128]{3,2,1,0:T(4,128)(2,1)} fusion("
             "bf16[2048,64,4,128]{3,2,1,0} "
             "%cache__block_1____attn____cached_key__.1, s32[32,1]{1,0} "
             "%fusion.720, bf16[32,4,128]{2,1,0} %fusion.15), kind=kCustom")
    ragged = ("%ragged-dot-none.13 = f32[128,1792]{1,0:T(8,128)S(1)} "
              "custom-call(s32[1]{0} %gte.93, bf16[128,2048]{1,0} %x, "
              'bf16[32,2048,1792]{2,1,0} %w), custom_call_target='
              '"tpu_custom_call"')
    dense_mlp = ("%fusion.9 = bf16[32,7168]{1,0} fusion(bf16[32,2048]{1,0} "
                 "%h, bf16[2048,7168]{1,0} %w), kind=kOutput")
    q_proj = ("%fusion.77 = bf16[32,1,32,64]{3,2,0,1} fusion(bf16[32,1,2048]"
              "{2,0,1} %h, bf16[2048,32,64]{2,1,0} %w), kind=kOutput")
    combine = ("%fusion.301 = f32[32,2048]{1,0} fusion(f32[32,4,2048]{2,1,0} "
               "%ys, f32[32,4]{1,0} %w), kind=kLoop")

    def hits(name, event):
        return re.search(q[name]["match"], event) is not None

    assert q["conv_device_pct"]["program"] == "^jit__decode_chunk"
    for ev in (in_proj, gates, mix, taps, out_proj, relayout):
        assert hits("conv_device_pct", ev), ev
    for ev in (gather, write, ragged, dense_mlp, q_proj, combine):
        assert not hits("conv_device_pct", ev), ev
    # the page view of a pool that packs two heads of 64 a 128-lane row
    assert hits("kv_view_device_pct", gather)
    for ev in (write, in_proj, mix, ragged):
        assert not hits("kv_view_device_pct", ev), ev
    assert hits("moe_device_pct", ragged) and not hits("moe_device_pct", mix)


def test_the_conv_reader_returns_nothing_for_nothing():
    from benchmarks.readers import trace_share_pct

    a = W.arch(cfg(), rehearsal=True)
    empty = {"trace": None, "peaks": None, "arch": a, "stats_before": None,
             "stats_at_close": None, "trace_span": (None, None)}
    assert trace_share_pct.read(empty, "conv_device_pct", "^jit") is None
    # a program without the ops (the parent, another family): None, never 0
    trace = {"queries": {"conv_device_pct": {"count": 0, "total_s": 0.0,
                                             "union_s": 0.0}},
             "programs": {"jit__decode_chunk": {"count": 9, "total_s": 0.1}}}
    assert trace_share_pct.read(dict(empty, trace=trace), "conv_device_pct",
                                "^jit__decode_chunk") is None


def rehearse(*extra):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", str(SEED), "--seconds", "6",
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
        assert line["metrics"]["moe_expert_load_max_over_mean"]["value"] >= 1
        # no device plane on the CPU: shares of a peak are left out
        assert "moe_experts_roofline_pct" not in line["metrics"]
        assert "decode_dispatch_host_ms" in line["metrics"]
    else:
        assert {"itl_p95_ms", "setup_s"} <= set(line["metrics"])


def test_the_int8_control_reads_well_above_bf16_arithmetic_at_toy_size():
    """The cell's limit is set on the chip at the published widths
    (PERF.md); what a test can hold is the ORDER it rests on: the reference
    in int8 in the program's place reads at least three times what bf16
    operands alone read, by the cell's number."""
    from benchmarks.harness.control import serve_control

    a, seed = W.arch(cfg(), rehearsal=True), 7
    rng = np.random.default_rng(0)
    rows = [[p[:9], p[9:]] for p in     # 888 tokens: a mean over fewer
            (rng.integers(1, a.vocab, 120).tolist() for _ in range(8))]
    low = serve_control(a, seed, rows)  # is a handful of flipped argmaxes
    bf16 = serve_control(a, seed, rows, quant="bf16")
    assert low["logit_gap_mean"] > 3 * bf16["logit_gap_mean"] > 0, (low, bf16)
    assert low["argmax_is_float32_argmax"] < 1
