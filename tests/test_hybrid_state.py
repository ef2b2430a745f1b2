"""A model whose layers differ in their MIXER: gated short convolutions
(``ShortConv``), whose state is a row a SLOT, beside attention layers that
keep pages; and a routing whose choice of experts sees a selection bias. On
the CPU at toy sizes, seeded.

Float32 comparisons are of the same sums in another order (shifted adds over
a row against a window that continues a state; a grouped product against a
loop): 1e-5 on sublayer outputs, 1e-4 on logits of magnitude about 1. In
bfloat16 every product rounds to 8 bits, and what is compared went through
three of them and a sum over 16: 6e-2 of outputs of magnitude about 1-3 (a
predecessor taken wrongly moves them by about 1).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import (Transformer, TransformerConfig, generate,
                             lfm2_moe_config)
from tony_tpu.models.generate import init_cache
from tony_tpu.models.transformer import LatentConfig, ShortConv
from tony_tpu.parallel.moe import RoutedConfig, routed_share, sigmoid_top_k
from tony_tpu.serve import Server
from tony_tpu.serve.engine import Request
from tony_tpu.serve.slots import (PagePool, copy_page, gather_pages,
                                  kv_page_nbytes, page_nbytes, paged_cache,
                                  paged_view, paged_write_back,
                                  read_slot_row, scatter_pages, slot_rows,
                                  store_slot_rows, write_slot_row)

KINDS = ("conv", "full_attention", "conv", "conv", "full_attention")
HF = dict(vocab_size=97, hidden_size=32, num_attention_heads=4,
          num_key_value_heads=2, num_hidden_layers=5, intermediate_size=64,
          max_position_embeddings=128, norm_eps=1e-5, rope_theta=1e6,
          layer_types=list(KINDS), conv_L_cache=3, num_experts=8,
          num_experts_per_tok=2, moe_intermediate_size=16,
          num_dense_layers=1, use_expert_bias=True,
          routed_scaling_factor=1.0, norm_topk_prob=True, conv_bias=False)


@functools.lru_cache(maxsize=None)
def toy():
    """A 5-layer model of all three kinds of layer (conv + dense, attention
    + routed, conv + routed), float32, its selection bias drawn."""
    model = Transformer(lfm2_moe_config(HF))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    key = jax.random.PRNGKey(3)
    params = jax.tree_util.tree_map_with_path(
        lambda p, x: 0.1 * jax.random.normal(key, x.shape)
        if "expert_bias" in str(p) else x, params)
    return model, params


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, HF["vocab_size"], n).tolist() for n in lengths]


def serve(server, reqs, new=12):
    ids = [server.submit(Request(prompt=p, max_new_tokens=new))
           for p in reqs]
    out = {r.id: r.tokens for r in server.run()}
    return [out[i] for i in ids]


def wanted(reqs, new=12):
    model, params = toy()
    return [np.asarray(generate(model, params, jnp.asarray([p]),
                                max_new_tokens=new))[0].tolist()
            for p in reqs]


# ------------------------------------------ (a) ShortConv: one arithmetic

def conv_module(dtype, taps=3, d=16):
    cfg = TransformerConfig(d_model=d, n_heads=2, n_layers=1, dtype=dtype,
                            max_seq_len=64, layer_types=("conv",),
                            conv_kernel=taps)
    conv = ShortConv(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 40, d)).astype(dtype)
    params = conv.init(jax.random.PRNGKey(2), x)["params"]
    # weights that give outputs of magnitude 1 from inputs of magnitude
    # 1, so that a wrong predecessor shows against the tolerance
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 3))
    params = jax.tree.map(
        lambda p: jax.random.normal(next(keys), p.shape)
        * (1.0 if p.shape == (d, taps) else d ** -0.5), params)
    return conv, params, x


def fresh_state(conv, params, x, rows, fill=0.0):
    shapes = jax.eval_shape(
        lambda: conv.init(jax.random.PRNGKey(0), x[:rows], decode=True))
    return jax.tree.map(lambda s: jnp.full(s.shape, fill, s.dtype),
                        shapes["cache"])


TOL = {jnp.float32: 1e-5, jnp.bfloat16: 6e-2}


@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_row_in_uneven_chunks_is_the_whole_row(dtype, taps):
    conv, params, x = conv_module(dtype, taps)
    whole = conv.apply({"params": params}, x)
    cache = fresh_state(conv, params, x, 3)
    assert cache["conv_state"].shape == (3, taps - 1, 16)
    assert cache["conv_state"].dtype == dtype
    got, at = [], 0
    for n in (5, 1, 1, 7, 2, 24):
        y, mut = conv.apply({"params": params, "cache": cache},
                            x[:, at:at + n], decode=True, mutable=["cache"])
        cache, at = mut["cache"], at + n
        got.append(y)
    got = jnp.concatenate(got, axis=1).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(got - whole.astype(jnp.float32)))) \
        < TOL[dtype]


@pytest.mark.parametrize("stale", [0.0, 7.0])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_prompts_padded_to_one_bucket_leave_their_true_tails_state(
        dtype, stale):
    """Three rows of 9, 16 and 2 real tokens in a window of 16 (padding:
    position -1), then one token at a time by per-slot positions: every
    row's outputs are the whole row's, so the state the window left is ``z``
    at each row's TRUE last positions, and a slot whose last tenant left
    7.0 everywhere reads none of it (a predecessor is taken by position)."""
    conv, params, x = conv_module(dtype)
    whole = conv.apply({"params": params}, x).astype(jnp.float32)
    lengths = np.array([9, 16, 2])
    pos = np.where(np.arange(16)[None] < lengths[:, None],
                   np.arange(16)[None], -1).astype(np.int32)
    cache = fresh_state(conv, params, x, 3, stale)
    y, mut = conv.apply({"params": params, "cache": cache}, x[:, :16],
                        decode=True, positions=jnp.asarray(pos),
                        mutable=["cache"])
    cache = mut["cache"]
    tol = TOL[dtype]
    for r, n in enumerate(lengths):
        assert float(jnp.max(jnp.abs(
            y[r, :n].astype(jnp.float32) - whole[r, :n]))) < tol
    at = lengths.copy()
    for _ in range(6):
        tok = x[np.arange(3), at][:, None]
        # row 1 stands still (an empty or frozen slot): its state stays
        step_pos = np.where(np.arange(3) == 1, -1, at).astype(np.int32)
        y, mut = conv.apply({"params": params, "cache": cache}, tok,
                            decode=True, positions=jnp.asarray(step_pos),
                            mutable=["cache"])
        assert np.array_equal(np.asarray(mut["cache"]["conv_state"][1]),
                              np.asarray(cache["conv_state"][1]))
        cache = mut["cache"]
        for r in (0, 2):
            assert float(jnp.max(jnp.abs(
                y[r, 0].astype(jnp.float32) - whole[r, at[r]]))) < tol
        at[[0, 2]] += 1


def test_a_window_of_one_real_token_keeps_the_older_position():
    """n_real = 1: the state moves by one, the older position stays."""
    conv, params, x = conv_module(jnp.float32)
    whole = conv.apply({"params": params}, x)
    cache = fresh_state(conv, params, x, 1)
    at = 0
    for n_real in (3, 1, 1, 2):
        pos = np.full((1, 4), -1, np.int32)
        pos[0, :n_real] = at + np.arange(n_real)
        y, mut = conv.apply({"params": params, "cache": cache},
                            x[:1, at:at + 4], decode=True,
                            positions=jnp.asarray(pos), mutable=["cache"])
        cache = mut["cache"]
        assert float(jnp.max(jnp.abs(
            y[0, :n_real] - whole[0, at:at + n_real]))) < 1e-5
        at += n_real


def test_packed_rows_take_zeros_across_a_segment_boundary():
    conv, params, x = conv_module(jnp.float32)
    alone = [conv.apply({"params": params}, x[:1, a:b])
             for a, b in ((0, 11), (11, 12), (12, 40))]
    seg = np.zeros((1, 40), np.int32)
    seg[0, 11:12], seg[0, 12:] = 1, 2
    packed = conv.apply({"params": params}, x[:1],
                        segment_ids=jnp.asarray(seg))
    assert float(jnp.max(jnp.abs(
        packed - jnp.concatenate(alone, axis=1)))) < 1e-5
    plain = conv.apply({"params": params}, x[:1])
    assert float(jnp.max(jnp.abs(packed - plain))) \
        > 0.1 * float(jnp.max(jnp.abs(plain)))


def test_a_window_must_be_given_its_slots_row():
    conv, params, x = conv_module(jnp.float32)
    cache = fresh_state(conv, params, x, 3)
    with pytest.raises(ValueError, match="slot_rows"):
        conv.apply({"params": params, "cache": cache}, x[:1, :4],
                   decode=True, mutable=["cache"],
                   positions=jnp.arange(4, dtype=jnp.int32)[None])


# ------------------------------------------ (b) the model and the engine

def test_prefill_through_the_cache_gives_the_full_forwards_logits():
    """qk-norm, the conv mixers and the biased routing, both ways in."""
    model, params = toy()
    toks = jnp.asarray(prompts((40, 40)))
    full = model.apply({"params": params}, toks)
    cache = init_cache(model, params, 2)
    pre, mut = model.apply({"params": params, "cache": cache}, toks[:, :33],
                           decode=True, mutable=["cache"])
    assert float(jnp.max(jnp.abs(pre - full[:, :33]))) < 1e-4
    cache = mut["cache"]
    for t in range(33, 40):
        one, mut = model.apply({"params": params, "cache": cache},
                               toks[:, t:t + 1], decode=True,
                               mutable=["cache"])
        cache = mut["cache"]
        assert float(jnp.max(jnp.abs(one[:, 0] - full[:, t]))) < 1e-4


def test_the_parameter_tree_names_each_layers_mixer():
    _, params = toy()
    for i, kind in enumerate(KINDS):
        blk = params[f"block_{i}"]
        assert ("conv" in blk, "attn" in blk) == (kind == "conv",
                                                  kind != "conv")
        assert ("mlp" in blk) == (i == 0) and ("moe" in blk) == (i > 0)
    attn = params["block_1"]["attn"]
    assert attn["q_norm"]["scale"].shape == (8,)
    assert attn["k_norm"]["scale"].shape == (8,)
    assert params["block_1"]["moe"]["expert_bias"].dtype == jnp.float32
    assert set(params["block_0"]["conv"]) == {"in_proj", "kernel",
                                              "out_proj"}


@pytest.mark.parametrize("chunk_steps", [1, 4])
@pytest.mark.parametrize("prefill_chunk", [0, 16])
def test_the_engine_streams_what_unpaged_generate_gives(chunk_steps,
                                                        prefill_chunk):
    """Six requests over two slots (every slot is reused), greedy,
    float32: token for token ``generate``'s, through the paged prefill
    (whole or in chunks of 16), the resident carry and two rounds in
    flight."""
    model, params = toy()
    reqs = prompts((5, 17, 33, 9, 21, 40))
    srv = Server(model, params, batch_size=2, chunk_steps=chunk_steps,
                 kv_page_size=16, prefill_chunk_tokens=prefill_chunk)
    assert serve(srv, reqs) == wanted(reqs)
    c = srv.counters()
    assert c["kv_tree_kept"] == 0 and c["freeze_faults"] == 0
    assert c["decode_rounds_overlapped"] > 0 and c["decode_settles"] == 0
    assert (c["conv_layers"], c["attn_layers"]) == (3, 2)
    assert c["state_bytes_per_slot"] == 3 * 2 * 32 * 4
    assert c["kv_bytes_per_token"] == 2 * 2 * 2 * 8 * 4
    assert c["state_resets"] == 6
    # prompts of 17, 33, 21 and 40 take 1, 2, 1 and 2 chunks after
    # their first
    assert c["state_carried_chunks"] == (6 if prefill_chunk else 0)


def test_a_slots_second_tenant_reads_nothing_of_the_first():
    """One slot, two requests one after the other: the second's tokens
    are a fresh engine's, also when it stops mid-chunk on a budget."""
    model, params = toy()
    first, second = prompts((33, 9))
    srv = Server(model, params, batch_size=1, chunk_steps=4,
                 kv_page_size=16)
    assert serve(srv, [first], new=7) == wanted([first], new=7)
    assert serve(srv, [second]) == wanted([second])
    fresh = Server(model, params, batch_size=1, chunk_steps=4,
                   kv_page_size=16)
    assert serve(fresh, [second]) == wanted([second])


def test_the_gateway_streams_the_same_and_shows_the_counters():
    """Through ``gateway.Gateway`` (what ``cli.gateway`` serves over):
    the engine's tokens, and the new counters under ``/stats`` engine."""
    from tony_tpu.gateway import Gateway, GenRequest

    model, params = toy()
    reqs = prompts((5, 17, 33))
    gw = Gateway([Server(model, params, batch_size=2, kv_page_size=16)],
                 max_queue=8).start()
    tickets = [gw.submit(GenRequest(p, max_new_tokens=12, id=i))
               for i, p in enumerate(reqs)]
    got = [t.result(timeout=300).tokens for t in tickets]
    engine = gw.snapshot()["engine"]
    assert gw.drain(timeout=60)
    assert got == wanted(reqs)
    assert (engine["conv_layers"], engine["attn_layers"]) == (3, 2)
    assert engine["state_bytes_per_slot"] == 768
    assert engine["kv_bytes_per_token"] == 256
    assert (engine["state_resets"], engine["state_carried_chunks"]) == (3, 0)
    assert engine["kv_tree"]["kept"] == 0


def test_warm_views_runs_empty_rows_and_leaves_nothing_to_compile():
    model, params = toy()
    srv = Server(model, params, batch_size=2, kv_page_size=16,
                 warm_views=True)
    state = [np.array(x) for x in jax.tree_util.tree_leaves(srv.slots.cache)]
    assert all(not s.any() for s in state)  # an empty row writes nothing
    reqs = prompts((40, 50))
    assert serve(srv, reqs, new=30) == wanted(reqs, new=30)
    decode = srv.timeline.summary()["decode"]
    assert decode["count"] > 0 and decode["compiles"] == 0


@pytest.mark.parametrize("family", ["hybrid", "gqa"])
def test_warm_views_reaches_every_program_of_a_chunked_prefill(family):
    """A chunk's program is keyed by the view span its end reaches and
    the final chunk's by suffix bucket x the whole prompt's span: with
    ``prefill_chunk_tokens`` the warm-up walks that product, over
    windows of padding that write nothing, so prompts of any length
    find nothing left to compile (on the v5e each compiled under
    traffic, 15 s a program: PERF.md, Findings PR 37)."""
    model, params = toy() if family == "hybrid" else _gqa()
    top = model.cfg.max_seq_len
    srv = Server(model, params, batch_size=2, kv_page_size=16,
                 prefill_chunk_tokens=16, warm_views=True)
    state = [np.array(x) for x in jax.tree_util.tree_leaves(srv.slots.cache)]
    assert all(not s.any() for s in state)
    rng = np.random.default_rng(1)
    lengths = [17, 23, 32, 33, 40, 47, 48, 49, 57, top - 8]
    reqs = [rng.integers(1, 64, n).tolist() for n in lengths]
    got = serve(srv, reqs, new=6)
    assert got == [np.asarray(generate(
        model, params, jnp.asarray([p]), max_new_tokens=6))[0].tolist()
        for p in reqs]
    seen = srv.timeline.summary()
    assert seen["prefill_chunk"]["count"] >= len(reqs)
    assert seen["prefill_chunk"]["compiles"] == 0
    assert seen["prefill"]["compiles"] == 0 and seen["decode"]["compiles"] == 0
    if family == "hybrid":
        assert srv.counters()["state_carried_chunks"] >= len(reqs)


# ------------------------------------------------------- (c) the refusals

@pytest.mark.parametrize("option, kw", [
    ("prefix_cache_mb", dict(prefix_cache_mb=1.0)),
    ("kv_host_mb", dict(kv_host_mb=1.0)),
    ("speculate_k", dict(speculate_k=2)),
    ("mesh", dict(mesh="one")),
    ("page_pool", dict(page_pool="shared")),
    ("paged=False", dict(paged=False)),
])
def test_the_engine_refuses_by_the_options_name(option, kw):
    model, params = toy()
    if "mesh" in kw:
        kw = dict(mesh=jax.sharding.Mesh(np.array(jax.devices()[:2]),
                                         ("tensor",)))
    if "page_pool" in kw:
        kw = dict(page_pool=PagePool(model, params, 16, 16, shared=True,
                                     slots=2))
    with pytest.raises(NotImplementedError, match=option):
        Server(model, params, batch_size=2, **kw)


@pytest.mark.parametrize("field", ["prefill_only", "handoff", "migrate"])
def test_handoff_and_migration_are_refused_at_submit(field):
    model, params = toy()
    srv = Server(model, params, batch_size=2)
    value = True if field == "prefill_only" else {"n_tokens": 3}
    with pytest.raises(NotImplementedError,
                       match="prefill_only/handoff/migrate"):
        srv.submit(Request(prompt=[1, 2, 3], max_new_tokens=2,
                           **{field: value}))


def test_extract_session_is_refused_by_name():
    model, params = toy()
    with pytest.raises(NotImplementedError, match="extract_session"):
        Server(model, params, batch_size=2).extract_session("x", wire=True)


@pytest.mark.parametrize("knob, value", [
    ("scan_layers", True), ("sliding_window", 8), ("kv_cache_quant", True),
    ("quantized", True), ("decode_attention", "flash"),
    ("latent", LatentConfig(8, 8, 4, 4, 4))])
def test_the_config_refuses_what_mixed_layers_lack(knob, value):
    model, _ = toy()
    with pytest.raises(ValueError, match=knob):
        dataclasses.replace(model.cfg, routed=None, **{knob: value})


@pytest.mark.parametrize("kw", [
    dict(layer_types=("conv",) * 4), dict(layer_types=("window",) * 5),
    dict(conv_kernel=1)])
def test_the_config_refuses_a_list_that_does_not_name_every_layer(kw):
    model, _ = toy()
    with pytest.raises(ValueError, match="layer_types|conv_kernel"):
        dataclasses.replace(model.cfg, **kw)


# ------------------------- (d) two kinds of leaf in one cache tree (slots)

def test_the_paged_tree_holds_pools_and_slot_rows():
    model, params = toy()
    pool = paged_cache(model, params, 24, 16, slots=3)
    for i, kind in enumerate(KINDS):
        leaves = pool[f"block_{i}"]
        if kind == "conv":
            assert set(leaves["conv"]) == {"conv_state"}
            assert leaves["conv"]["conv_state"].shape == (3, 2, 32)
        else:
            assert leaves["attn"]["cached_key"].shape == (24, 16, 2, 8)
    # a page is the attention layers' alone, by either count
    assert page_nbytes(pool) == kv_page_nbytes(model.cfg, 16) \
        == 2 * 16 * 2 * 2 * 8 * 4


def _marked(model, params):
    pool = paged_cache(model, params, 8, 16, slots=3)
    return jax.tree.map(
        lambda x: jnp.arange(x.size, dtype=x.dtype).reshape(x.shape), pool)


def test_what_moves_pages_passes_the_slot_rows_by():
    model, params = toy()
    pool = _marked(model, params)
    state = pool["block_0"]["conv"]["conv_state"]
    forked = copy_page(pool, 1, 5)
    assert np.array_equal(forked["block_0"]["conv"]["conv_state"], state)
    assert np.array_equal(forked["block_1"]["attn"]["cached_key"][5],
                          pool["block_1"]["attn"]["cached_key"][1])
    got = gather_pages(pool, jnp.asarray([2, 3]))
    assert got["block_0"]["conv"]["conv_state"].shape == state.shape
    assert got["block_1"]["attn"]["cached_key"].shape == (2, 16, 2, 8)
    back = scatter_pages(pool, got, jnp.asarray([6, 7]))
    assert np.array_equal(back["block_0"]["conv"]["conv_state"], state)
    assert np.array_equal(back["block_1"]["attn"]["cached_value"][7],
                          pool["block_1"]["attn"]["cached_value"][3])


def test_a_round_takes_the_slot_rows_whole_and_stores_them_by_slot():
    model, params = toy()
    pool = _marked(model, params)
    table = jnp.asarray([[0, 1], [2, 8], [8, 8]], jnp.int32)
    view = paged_view(pool, table, 128)
    assert np.array_equal(view["block_2"]["conv"]["conv_state"],
                          pool["block_2"]["conv"]["conv_state"])
    assert view["block_1"]["attn"]["cached_key"].shape == (3, 32, 2, 8)
    moved = jax.tree_util.tree_map_with_path(
        lambda p, x: x + 1 if "conv_state" in str(p) else x, view)
    out = paged_write_back(pool, moved, table, jnp.asarray([3, 17, -1]), 2,
                           128)
    assert np.array_equal(out["block_2"]["conv"]["conv_state"],
                          pool["block_2"]["conv"]["conv_state"] + 1)


def test_a_one_row_window_takes_and_returns_its_slots_row():
    model, params = toy()
    pool = _marked(model, params)
    rows = slot_rows(pool, 2)
    assert rows["block_0"]["conv"]["conv_state"].shape == (1, 2, 32)
    assert np.array_equal(rows["block_0"]["conv"]["conv_state"][0],
                          pool["block_0"]["conv"]["conv_state"][2])
    assert rows["block_1"]["attn"]["cached_key"].shape == (8, 16, 2, 8)
    rows = jax.tree.map(lambda x: x * 0, rows)
    out = store_slot_rows(pool, rows, 2)
    state = np.asarray(out["block_0"]["conv"]["conv_state"])
    assert not state[2].any() and np.array_equal(
        state[:2], pool["block_0"]["conv"]["conv_state"][:2])
    assert not np.asarray(out["block_1"]["attn"]["cached_key"]).any()


def test_the_state_moves_with_an_unpaged_row():
    model, params = toy()
    cache = jax.tree.map(
        lambda x: jnp.arange(x.size, dtype=x.dtype).reshape(x.shape),
        init_cache(model, params, 3))
    row = read_slot_row(cache, 1)
    assert row["block_0"]["conv"]["conv_state"].shape == (1, 2, 32)
    out = write_slot_row(cache, row, 2)
    assert np.array_equal(out["block_0"]["conv"]["conv_state"][2],
                          cache["block_0"]["conv"]["conv_state"][1])
    assert np.array_equal(out["block_1"]["attn"]["cached_key"][2],
                          cache["block_1"]["attn"]["cached_key"][1])


@pytest.mark.parametrize("family", ["gqa", "latent"])
def test_a_model_of_one_kind_builds_the_cache_tree_it_built(family):
    """``layer_types=()``: the same leaves, shapes and dtypes as before
    this field existed, in rows and in pages, and a page of every
    layer."""
    kw = dict(vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2,
              n_layers=2, d_ff=64, max_seq_len=64, dtype=jnp.bfloat16,
              gated_mlp=True, activation="silu", tied_embeddings=False)
    if family == "latent":
        kw.update(n_kv_heads=None, latent=LatentConfig(8, 16, 4, 4, 4))
        leaf = {"cached_latent": ((3, 64, 16), (8, 16, 16)),
                "cached_rope_key": ((3, 64, 4), (8, 16, 4))}
    else:
        leaf = {"cached_key": ((3, 64, 2, 8), (8, 16, 2, 8)),
                "cached_value": ((3, 64, 2, 8), (8, 16, 2, 8))}
    model = Transformer(TransformerConfig(**kw))
    assert model.cfg.layer_types == () and model.cfg.conv_layers == 0
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))["params"])
    rows = init_cache(model, params, 3)
    pool = paged_cache(model, params, 8, 16, slots=3)
    for tree, which in ((rows, 0), (pool, 1)):
        assert set(tree) == {"block_0", "block_1"}
        for blk in tree.values():
            assert set(blk) == {"attn"}
            assert set(blk["attn"]) == set(leaf) | {"cache_index"}
            for name, shapes in leaf.items():
                assert blk["attn"][name].shape == shapes[which]
                assert blk["attn"][name].dtype == jnp.bfloat16
            assert blk["attn"]["cache_index"].shape == ()
    assert page_nbytes(pool) == kv_page_nbytes(model.cfg, 16)
    paths = [jax.tree_util.keystr(p) for p, _
             in jax.tree_util.tree_flatten_with_path(params)[0]]
    if family == "gqa":  # qk_norm=False declares no scale under attention
        assert not any("attn" in p and "norm" in p for p in paths)


# ------------------------------- (d2) heads narrower than a row of 128 lanes

@dataclasses.dataclass(frozen=True)
class _StoredAsHeads(TransformerConfig):
    """The control: the same widths with the layout not derived (another
    class, so no jit cache takes it for the packed configuration)."""
    kv_pack_lanes = property(lambda self: False)


def _gqa(cls=TransformerConfig, **kw):
    model = Transformer(cls(**{**dict(
        vocab_size=64, d_model=128, n_heads=8, n_kv_heads=8, n_layers=2,
        d_ff=64, max_seq_len=64, dtype=jnp.float32, gated_mlp=True,
        activation="silu", qk_norm=True), **kw}))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def test_heads_packed_into_rows_of_128_serve_the_same_tokens():
    """8 heads of 16 a position are ONE row of 128: the same values in
    the same order, so the same tokens by every way in. The layout is
    derived from the widths (``TransformerConfig.kv_pack_lanes``); the
    control that stores heads is a subclass."""
    packed, params = _gqa()
    assert packed.cfg.kv_pack_lanes
    assert init_cache(packed, params, 3)["block_0"]["attn"][
        "cached_key"].shape == (3, 64, 1, 128)
    pool = paged_cache(packed, params, 8, 16)
    assert pool["block_1"]["attn"]["cached_value"].shape == (8, 16, 1, 128)
    rng = np.random.default_rng(0)
    reqs = [rng.integers(1, 64, n).tolist() for n in (5, 17, 30, 9)]

    def gen(model):
        return [np.asarray(generate(model, params, jnp.asarray([p]),
                                    max_new_tokens=10))[0].tolist()
                for p in reqs]

    got = gen(packed)
    for kw in (dict(kv_page_size=16), dict(kv_page_size=16, chunk_steps=4,
                                           prefill_chunk_tokens=16),
               dict(kv_page_size=16, prefix_cache_mb=1),
               dict(kv_page_size=16, speculate_k=2), dict(paged=False)):
        srv = Server(packed, params, batch_size=2, **kw)
        assert serve(srv, reqs, new=10) == got, kw
        assert srv.counters()["kv_tree_kept"] == 0
    plain, _ = _gqa(_StoredAsHeads)
    assert init_cache(plain, params, 3)["block_0"]["attn"][
        "cached_key"].shape == (3, 64, 8, 16)
    assert page_nbytes(pool) == kv_page_nbytes(packed.cfg, 16) \
        == page_nbytes(paged_cache(plain, params, 8, 16))
    assert gen(plain) == got


@pytest.mark.parametrize("kw", [
    dict(n_kv_heads=2), dict(kv_cache_quant=True),
    dict(decode_attention="flash"), dict(n_heads=1, n_kv_heads=1)],
    ids=["half_a_row", "int8_cache", "flash_decode", "a_head_of_128"])
def test_lane_packing_leaves_what_it_cannot_pack_as_heads(kw):
    """2 heads of 16 fill no row of 128, an int8 cache keeps a scale a
    head, the flash-decode kernel reads heads, and a head of 128 is a
    row already: each keeps ``[.., kv_heads, head_dim]``."""
    model, params = _gqa(**kw)
    cfg = model.cfg
    assert not cfg.kv_pack_lanes
    assert init_cache(model, params, 2)["block_0"]["attn"][
        "cached_key"].shape == (2, 64, cfg.kv_heads, cfg.head_dim)


# ------------------------------------------------------- (e) the routing

def test_a_zero_bias_routes_as_no_bias():
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 2
    w0, i0 = sigmoid_top_k(logits, 4, 2.5)
    w1, i1 = sigmoid_top_k(logits, 4, 2.5, jnp.zeros((32,)))
    assert np.array_equal(i0, i1) and np.array_equal(w0, w1)
    # the published epsilon is the caller's: the default stays 1e-20
    assert RoutedConfig(32, 4, 8, (0, 32)).renorm_eps == 1e-20
    w2, _ = sigmoid_top_k(logits, 4, 1.0, eps=1e-6)
    p = np.sort(np.asarray(jax.nn.sigmoid(logits)), -1)[:, :-5:-1]
    assert np.allclose(w2, p / (p.sum(-1, keepdims=True) + 1e-6), atol=1e-6)


def test_a_bias_moves_the_choice_and_not_the_weight():
    logits = jax.random.normal(jax.random.PRNGKey(1), (50, 32))
    logits = logits.at[:, 9].set(-6.0)   # the router's last choice
    bias = jnp.zeros((32,)).at[9].set(10.0)
    w, idx = sigmoid_top_k(logits, 4, 1.0, bias, 1e-6)
    assert np.all(np.asarray(idx)[:, 0] == 9)
    p = np.asarray(jax.nn.sigmoid(logits))
    chosen = np.take_along_axis(p, np.asarray(idx), -1)
    assert np.allclose(w, chosen / (chosen.sum(-1, keepdims=True) + 1e-6),
                       atol=1e-6)
    assert np.all(np.asarray(w)[:, 0] < 0.01)  # its own sigmoid's share
    plain, plain_idx = sigmoid_top_k(logits, 4, 1.0)
    assert not np.any(np.asarray(plain_idx) == 9)


def _swiglu(h, wg, wi, wo):
    hp = jax.lax.Precision.HIGHEST
    dot = functools.partial(jnp.matmul, precision=hp)
    return dot(jax.nn.silu(dot(h, wg)) * dot(h, wi), wo)


def test_four_shares_of_eight_add_up_to_the_whole_layer():
    """``held`` (0, 8) .. (24, 8) summed, ``held = (0, 32)`` in one go,
    and the uncut float32 reference: every expert on every token, weighed
    by the biased top-4's renormalised sigmoid."""
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    t, d, f, e, k = 37, 24, 16, 32, 4
    h = jax.random.normal(ks[0], (t, d))
    router = jax.random.normal(ks[1], (d, e)) * 0.3
    bias = jax.random.normal(ks[2], (e,)) * 0.3
    wg, wi = (jax.random.normal(ks[i], (e, d, f)) * 0.2 for i in (3, 4))
    wo = jax.random.normal(ks[5], (e, f, d)) * 0.2
    p = jax.nn.sigmoid(jnp.matmul(h, router,
                                  precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(p + bias, k)
    top = jnp.take_along_axis(p, idx, -1)
    weight = jnp.sum(jax.nn.one_hot(idx, e) * (
        top / (jnp.sum(top, -1, keepdims=True) + 1e-6))[..., None], axis=1)
    want = sum(weight[:, j:j + 1] * _swiglu(h, wg[j], wi[j], wo[j])
               for j in range(e))
    rc = lambda held: RoutedConfig(  # noqa: E731
        e, k, f, held, 1.0, selection_bias=True, renorm_eps=1e-6)
    whole, c_whole = routed_share(h, router, wg, wi, wo, rc((0, 32)),
                                  bias=bias)
    parts, counts = 0.0, np.zeros(4, np.int64)
    for first in (0, 8, 16, 24):
        sl = slice(first, first + 8)
        y, c = routed_share(h, router, wg[sl], wi[sl], wo[sl],
                            rc((first, 8)), bias=bias)
        parts, counts = parts + y, counts + np.asarray(c)
    assert float(jnp.max(jnp.abs(whole - want))) < 1e-5
    assert float(jnp.max(jnp.abs(parts - want))) < 1e-5
    # every pair is held by exactly one share, and by the whole
    assert counts[1] == t * k == int(c_whole[1]) == int(c_whole[0])
    # and the bias did move choices: without it another layer comes out
    plain, _ = routed_share(h, router, wg, wi, wo, RoutedConfig(
        e, k, f, (0, 32), 1.0, renorm_eps=1e-6))
    assert float(jnp.max(jnp.abs(plain - want))) > 1e-3
