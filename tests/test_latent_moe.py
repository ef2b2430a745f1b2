"""Latent attention and one chip's share of a routed expert layer, on the
CPU at a toy size (hidden 64, 4 heads, kv rank 16, rope 8, a router of 16
with top-4 of which 4 are held, one dense layer then two routed ones: the
``rehearsal`` block of ``benchmarks/configs/ax-k1-l6-ep16.json``), with
seeded weights, against the benchmark's plain float32 reference
(``benchmarks/families/axk1.py``, which imports nothing of ``tony_tpu``).

Every comparison is float32 against float32 over the same weights, so a
tolerance here is the room for another ORDER of the same sums (a grouped
product against a loop, an absorbed contraction against a materialised
one): 1e-4 on logits of magnitude about 1, 1e-5 on sublayer outputs.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import axk1 as F
from benchmarks.harness import adapter as A, reference as R, weights as W
from tony_tpu.models import Transformer
from tony_tpu.models.transformer import (RopeScaling, latent_attend,
                                         latent_attend_absorbed)
from tony_tpu.parallel.moe import (RoutedConfig, dense_experts,
                                   grouped_experts, routed_share,
                                   sigmoid_top_k)
from tony_tpu.serve import Server
from tony_tpu.serve.engine import Request
from tony_tpu.serve.slots import (PagePool, SlotCache, kv_page_nbytes,
                                  page_nbytes)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 35


def config(**over) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ax-k1-l6-ep16.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearsal"])
    cfg.update(over)
    return cfg


@functools.lru_cache(maxsize=None)
def toy():
    """(arch, model, params): float32 arithmetic over the bf16 VALUES the
    reference makes from the seed."""
    a = W.arch(config())
    model = Transformer(A.program_config(a, jnp.float32))
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          A.seeded_params(a, SEED, jnp.bfloat16))
    return a, model, params


def prompts(a, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, a.vocab, n).tolist() for n in lengths]


def serve(server, reqs, new=12):
    for i, p in enumerate(reqs):
        server.submit(Request(prompt=p, max_new_tokens=new + i, id=i))
    return {r.id: r.tokens for r in server.run()}


# ------------------------------------------- (a) through the paged cache

def test_paged_prefill_then_decode_agrees_with_the_reference_on_logits():
    """Prefill, then absorbed decode out of the paged latent cache with
    two rounds in flight: each served token's logit lies within 1e-4 of
    the reference's best at its position (the reference's full forward
    pass materialises every key; the program never does after prefill)."""
    from benchmarks.harness.serve_child import check_rows

    a, model, params = toy()
    srv = Server(model, params, batch_size=3, kv_page_size=16)
    reqs = prompts(a, (9, 33, 17, 64, 5))
    out = serve(srv, reqs)
    assert sorted(out) == list(range(5))
    res = check_rows(a, SEED, [[reqs[i], out[i]] for i in out])
    assert res["served_tokens"] == sum(12 + i for i in range(5))
    assert res["logit_gap_max"] < 1e-4, res["logit_gap_max"]
    c = srv.counters()
    assert c["decode_rounds_overlapped"] > 0 and c["kv_tree_kept"] == 0
    assert c["decode_rng_pulls"] == 0 and c["freeze_faults"] == 0
    assert c["latent_bytes_per_token"] == (a.kv_rank + a.rope) * a.layers * 4


def test_the_programs_full_forward_is_the_references():
    a, model, params = toy()
    toks = np.asarray(prompts(a, (40, 40)))
    logits = np.asarray(model.apply({"params": params}, jnp.asarray(toks)))
    pos = np.tile(np.arange(40, dtype=np.int32)[None], (2, 1))
    served = logits.argmax(-1).astype(np.int32)
    best, at, _ = R.serve_logits(a, SEED, toks, pos, served)
    assert np.abs(np.asarray(best) - logits.max(-1)).max() < 1e-4
    assert float(jnp.max(best - at)) < 1e-4


@pytest.mark.parametrize("variant", ["prefix_store", "shared_pool",
                                     "unpaged", "unpaged_prefix",
                                     "chunk_steps_4", "chunked_prefill"])
def test_every_served_path_carries_the_latent_layout(variant):
    """Exact hit, suffix prefill over a forked page, a pool lent by the
    gateway, fixed-shape rows, deeper chunks, chunked prefill: the same
    tokens as the plain paged engine (float32, greedy)."""
    a, model, params = toy()
    base = prompts(a, (40,))[0]
    reqs = [base[:21], base[:21], base[:21] + [5, 6, 7], base[:9], base]
    plain = Server(model, params, batch_size=2, kv_page_size=16)
    want = {}
    for r in reqs:  # one at a time: a later one may hit an earlier one's
        want[len(want)] = serve(plain, [r])[0]
    kw = {"prefix_store": dict(prefix_cache_mb=4.0, kv_page_size=16),
          "shared_pool": dict(prefix_cache_mb=4.0, kv_page_size=16,
                              page_pool=PagePool(model, params, 64, 16,
                                                 shared=True)),
          "unpaged": dict(paged=False),
          "unpaged_prefix": dict(paged=False, prefix_cache_mb=4.0),
          "chunk_steps_4": dict(chunk_steps=4, kv_page_size=16),
          "chunked_prefill": dict(prefill_chunk_tokens=16,
                                  kv_page_size=16)}[variant]
    srv = Server(model, params, batch_size=2, **kw)
    got = {i: serve(srv, [r])[0] for i, r in enumerate(reqs)}
    assert got == want
    if "prefix" in variant or variant == "shared_pool":
        assert srv.counters()["prefix_hits"] >= 3
    assert srv.counters()["kv_tree_kept"] == 0


# --------------------------------------- (b) absorbed == materialised

@pytest.mark.parametrize("l", [1, 3])
def test_absorbed_attention_equals_materialised_over_the_same_latent(l):
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    b, m, h, nope, rope, rank, v = 3, 24, 4, 16, 8, 16, 12
    q_n = jax.random.normal(k[0], (b, l, h, nope))
    q_r = jax.random.normal(k[1], (b, l, h, rope))
    c_kv = jax.random.normal(k[2], (b, m, rank))
    k_r = jax.random.normal(k[3], (b, m, rope))
    w = jax.random.normal(k[4], (rank, h, nope + v)) * 0.3
    q_pos = jnp.asarray([[5], [23], [0]]) + jnp.arange(l)[None]
    visible = jnp.arange(m)[None, None] <= q_pos[:, :, None]
    want = latent_attend(q_n, q_r, c_kv, k_r, w, visible, 0.2)
    got = latent_attend_absorbed(q_n, q_r, c_kv, k_r, w, visible, 0.2)
    assert want.shape == (b, l, h, v)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def _avals(fn, *args):
    seen = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            seen.extend(v.aval.shape for v in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return seen


def test_the_decode_step_forms_no_per_head_key_or_value():
    """No value of the single-token step is laid out [rows, cache
    positions, heads, ...]; the multi-token window has one (the
    detector sees what it is meant to see)."""
    a, model, params = toy()
    span, b = 48, 3
    cache = SlotCache(model, params, b).cache
    cache = jax.tree.map(
        lambda x: x[:, :span] if x.ndim == 3 else x, cache)

    def step(l):
        def fn(p, c):
            pos = jnp.full((b, l), 7) + jnp.arange(l)[None]
            return model.apply({"params": p, "cache": c},
                               jnp.ones((b, l), jnp.int32), decode=True,
                               positions=pos[:, 0] if l == 1 else pos,
                               mutable=["cache"])
        return [s for s in _avals(fn, params, cache)
                if len(s) == 4 and s[1] == span and s[2] == a.heads]

    assert step(1) == []
    assert step(2) != []
    leaves = {p[-1].key: x.shape for p, x in
              jax.tree_util.tree_flatten_with_path(cache)[0]}
    assert leaves["cached_latent"] == (b, span, a.kv_rank)
    assert leaves["cached_rope_key"] == (b, span, a.rope)


def test_yarn_frequencies_are_the_references_at_the_published_sizes():
    cfg = config()
    cfg.update(qk_rope_head_dim=64, rope_scaling=dict(
        cfg["rope_scaling"], original_max_position_embeddings=4096))
    a = F.arch(cfg)
    half = a.rope // 2
    plain = 1.0 / (a.theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    got = RopeScaling(kind="yarn", factor=32.0, original_max_len=4096,
                      beta_fast=32, beta_slow=1).apply(plain, a.theta)
    want = F.yarn_inv_freq(a)
    assert np.allclose(got, want, rtol=1e-6, atol=0)
    # the fastest pairs keep their frequency, the slowest lose a factor 32
    assert np.isclose(got[0], plain[0]) and np.isclose(got[-1] * 32,
                                                       plain[-1])
    assert abs(a.softmax_mult - 1.3466 ** 2) < 1e-3


# ------------------------------------------------- (c) the shares add up

def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of all 4 shares, the shared expert counted once,
    are the uncut reference's whole expert layer: program (grouped,
    one share at a time) against reference (every expert, one model)."""
    a = W.arch(config())
    whole = W.arch(config(n_routed_experts=16, expert_parallel={
        "chips": 1, "rank": 0, "router_experts": 16}))
    assert (whole.held, whole.n_routed) == (16, 16)
    p = {n: v.astype(jnp.float32) for n, v in
         W.layer_weights(whole, SEED, 1, jnp.bfloat16).items()}
    h = jax.random.normal(jax.random.PRNGKey(5), (37, a.d))
    dense = functools.partial(R.dense, quant="")
    weight = F.route(whole, p, h, dense)                       # [T, 16]
    assert int(jnp.sum(weight > 0)) == 37 * a.top_k
    shared = F.swiglu(dense, h, p["sg"], p["si"], p["so"])
    want = shared + sum(
        weight[:, e:e + 1] * F.swiglu(dense, h, p["eg"][e], p["ei"][e],
                                      p["eo"][e]) for e in range(16))
    got, counts = shared, np.zeros(4, np.int64)
    for rank in range(4):
        rc = RoutedConfig(16, a.top_k, a.expert_ff, (4 * rank, 4),
                          a.scaling)
        sl = slice(4 * rank, 4 * rank + 4)
        y, c = routed_share(h, p["router"], p["eg"][sl], p["ei"][sl],
                            p["eo"][sl], rc)
        got, counts = got + y, counts + np.asarray(c)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    # every pair is held by exactly one share
    assert counts[0] == 4 * 37 * a.top_k and counts[1] == 37 * a.top_k
    # and the reference's own share is the program's
    share = F.route(a, p, h, dense)
    assert np.array_equal(np.asarray(share), np.asarray(weight[:, :4]))


# -------------------------------------------- (d) grouped == dense

def _experts(t=29, d=64, f=32, held=4, k=4, n_routed=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (t, d))
    wg, wi = (jax.random.normal(ks[i], (held, d, f)) * 0.1 for i in (1, 2))
    wo = jax.random.normal(ks[3], (held, f, d)) * 0.1
    w, idx = sigmoid_top_k(jax.random.normal(ks[4], (t, n_routed)), k, 2.5)
    return x, idx, w, wg, wi, wo


@pytest.mark.parametrize("case", ["random", "one_expert_takes_all",
                                  "a_held_expert_takes_none",
                                  "nothing_is_held", "masked_rows",
                                  "held_in_the_middle"])
def test_the_grouped_path_is_the_dense_evaluation(case):
    x, idx, w, wg, wi, wo = _experts()
    first, live = 0, None
    if case == "one_expert_takes_all":      # expert 2, first choice of all
        idx = idx.at[:, 0].set(2).at[:, 1:].set(jnp.arange(8, 11)[None])
    elif case == "a_held_expert_takes_none":
        idx = jnp.where(idx == 1, 15, idx)
    elif case == "nothing_is_held":
        idx = jnp.clip(idx, 4, 15)
    elif case == "masked_rows":
        live = jnp.arange(x.shape[0]) % 3 != 0
    elif case == "held_in_the_middle":
        first = 6
    want, n_want = dense_experts(x, idx, w, wg, wi, wo, first, live)
    got, n_got = jax.jit(grouped_experts, static_argnums=6)(
        x, idx, w, wg, wi, wo, first, live)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert np.array_equal(np.asarray(n_got), np.asarray(n_want))
    if case == "one_expert_takes_all":
        assert n_got.tolist() == [0, 0, x.shape[0], 0]
    if case == "a_held_expert_takes_none":
        assert int(n_got[1]) == 0 and int(jnp.sum(n_got)) > 0
    if case == "nothing_is_held":
        assert float(jnp.max(jnp.abs(got))) == 0.0
    if case == "masked_rows":
        assert float(jnp.max(jnp.abs(got[::3]))) == 0.0


def test_sigmoid_top_k_renormalises_and_scales():
    logits = jnp.asarray([[2.0, -1.0, 0.5, 3.0], [0.0, 0.0, 0.0, 1.0]])
    w, idx = sigmoid_top_k(logits, 2, 2.5)
    assert idx.tolist() == [[3, 0], [3, 0]]
    p = jax.nn.sigmoid(logits)
    assert np.allclose(w[0], 2.5 * p[0, [3, 0]] / (p[0, 3] + p[0, 0]))
    assert np.allclose(jnp.sum(w, -1), 2.5)


def test_the_expert_layers_work_follows_the_routing():
    """No [T, E, C] one-hot and no product of every token with every
    held expert: the largest value the grouped path forms is a row a
    (token, choice) pair."""
    x, idx, w, wg, wi, wo = _experts(t=64)
    t, k, held, f = 64, 4, 4, 32
    shapes = _avals(lambda *a: grouped_experts(*a, 0), x, idx, w, wg, wi, wo)
    assert (held, t, f) not in shapes and (t, held, f) not in shapes
    assert max(int(np.prod(s)) for s in shapes) <= t * k * 64
    dense_shapes = _avals(lambda *a: dense_experts(*a, 0), x, idx, w, wg,
                          wi, wo)
    assert (held, t, f) in dense_shapes


def test_counters_count_live_pairs_and_ride_the_token_copy():
    a, model, params = toy()
    srv = Server(model, params, batch_size=3, kv_page_size=16)
    serve(srv, prompts(a, (9, 17)), new=6)
    c = srv.counters()
    steps = (6 - 1) + (7 - 1)               # the first token is prefill's
    routed_layers = a.layers - a.first_dense
    assert c["moe_tokens_routed"] == steps * a.top_k * routed_layers
    assert 0 < c["moe_tokens_held"] <= c["moe_tokens_routed"]
    assert c["moe_expert_load_max"] <= c["moe_tokens_held"]
    assert 0 < c["moe_experts_hit"] <= c["decode_steps"] * routed_layers \
        * a.held
    assert c["moe_experts_held"] == a.held
    assert c["decode_settles"] == 0         # no sync beyond the rounds'


# ------------------------------- (e) one page, three ways of counting it

@pytest.mark.parametrize("page_size", [16, 64])
def test_the_latent_page_is_one_size_everywhere(page_size):
    a, model, params = toy()
    pool = PagePool(model, params, 8, page_size)
    analytic = kv_page_nbytes(model.cfg, page_size)
    assert analytic == page_nbytes(pool.cache) == pool.page_nbytes
    assert analytic == page_size * F.kv_bytes_per_token(a, itemsize=4)
    srv = Server(model, params, batch_size=2, kv_page_size=page_size)
    assert srv.cost.kv_token_bytes * page_size == analytic
    # the absorbed step's FLOPs a cached position, as the family counts
    assert srv.cost._attn_flops(1) * a.layers \
        == F.absorbed_position_flops(a) * a.layers


# ------------------------------------- (f) no float32 model at start-up

@pytest.mark.parametrize("build", ["page_pool", "rows", "prefill"])
def test_building_the_cache_runs_no_eager_model_init(build, monkeypatch):
    """``model.init`` is only ever traced for its shapes: every call
    sees abstract tokens, so no parameter is materialised beside the
    served ones."""
    a, model, params = toy()
    calls = []
    real = Transformer.init

    def spy(self, rng, tokens, *args, **kw):
        calls.append(isinstance(tokens, jax.core.Tracer))
        return real(self, rng, tokens, *args, **kw)

    monkeypatch.setattr(Transformer, "init", spy)
    if build == "page_pool":
        tree = PagePool(model, params, 4, 16).cache
    elif build == "rows":
        tree = SlotCache(model, params, 2).cache
    else:
        from tony_tpu.serve.engine import _prefill

        tree, _ = _prefill(model, params, jnp.ones((1, 16), jnp.int32),
                           jnp.int32(9))
    assert calls and all(calls)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    assert all(x.size < n_params / 4 for x in jax.tree.leaves(tree))


# ----------------------------------------------------- (g) the refusals

@pytest.mark.parametrize("option, kw", [
    ("speculate_k", dict(speculate_k=2)),
    ("kv_host_mb", dict(kv_host_mb=1.0, prefix_cache_mb=1.0)),
    ("mesh", dict(mesh="one")),
])
def test_the_engine_refuses_by_the_options_name(option, kw):
    a, model, params = toy()
    if "mesh" in kw:
        kw = dict(mesh=jax.sharding.Mesh(np.array(jax.devices()[:2]),
                                         ("tensor",)))
    with pytest.raises(NotImplementedError, match=option):
        Server(model, params, batch_size=2, **kw)


@pytest.mark.parametrize("knob, value", [
    ("decode_attention", "flash"), ("kv_cache_quant", True),
    ("sliding_window", 8), ("scan_layers", True), ("quantized", True)])
def test_the_config_refuses_what_the_latent_module_lacks(knob, value):
    a, model, _ = toy()
    with pytest.raises(ValueError, match=knob):
        dataclasses.replace(model.cfg, **{knob: value})


@pytest.mark.parametrize("field", ["prefill_only", "handoff", "migrate"])
def test_handoff_and_migration_are_refused_at_submit(field):
    a, model, params = toy()
    srv = Server(model, params, batch_size=2)
    value = True if field == "prefill_only" else {"n_tokens": 3}
    with pytest.raises(NotImplementedError, match="prefill_only/handoff/"
                                                  "migrate"):
        srv.submit(Request(prompt=[1, 2, 3], max_new_tokens=2,
                           **{field: value}))
    with pytest.raises(NotImplementedError, match="extract_session"):
        srv.extract_session("x", wire=True)


def test_routed_config_refuses_a_share_outside_the_router():
    with pytest.raises(ValueError, match="held"):
        RoutedConfig(16, 4, 32, held=(14, 4))
    with pytest.raises(ValueError, match="top_k"):
        RoutedConfig(16, 17, 32, held=(0, 4))


# ------------------------------------------------------------ warm views

def test_warm_views_leaves_no_view_bucket_to_compile_under_traffic():
    a, model, params = toy()
    srv = Server(model, params, batch_size=2, kv_page_size=16,
                 warm_views=True)
    warmed = {k for k in srv._compiled if k[0] == "decode"}
    assert {v for _, _, v in warmed} == {16, 32, 64, 128, 256}
    serve(srv, prompts(a, (40, 70)), new=30)   # grows through 3 buckets
    decode = srv.timeline.summary()["decode"]
    assert decode["count"] > 0 and decode["compiles"] == 0
    assert srv.counters()["kv_tree_kept"] == 0
