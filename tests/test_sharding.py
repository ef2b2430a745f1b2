"""parallel/sharding.py preset units (ISSUE-14 satellite): spec_for
rule matching, tree_shardings over a realistic transformer param tree,
shard_params_by_size's non-divisible fallback, and the serving preset
(row-parallel flip, validation, KV-cache shardings, per-chip bytes)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tony_tpu.models import Transformer, TransformerConfig
from tony_tpu.models.generate import init_cache
from tony_tpu.models.transformer import logical_axis_rules_tree
from tony_tpu.parallel.mesh import EXPERT, MeshSpec, TENSOR, make_mesh
from tony_tpu.parallel.sharding import (RULES, kv_cache_shardings,
                                        kv_shard_count,
                                        serve_spec_for,
                                        serving_shardings,
                                        shard_params_by_size, spec_for,
                                        tree_shard_bytes,
                                        tree_shard_count,
                                        tree_shardings, validated_spec)


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh(MeshSpec(data=1, tensor=4),
                     devices=jax.devices()[:4])


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32,
                            attention_backend="reference")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _by_path(tree):
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(getattr(p, "key", str(p)) for p in path)] = leaf
    return out


# ------------------------------------------------------ spec_for rules


def test_spec_for_rule_matching():
    rules = RULES["tp"]
    # q kernel (embed, heads, kv): heads -> tensor under tp
    assert spec_for(("embed", "heads", "kv"), rules) \
        == P(None, TENSOR, None)
    # mlp wi (embed, mlp)
    assert spec_for(("embed", "mlp"), rules) == P(None, TENSOR)
    # unknown logical names and Nones replicate
    assert spec_for((None, "nonexistent"), rules) == P(None, None)
    # dp: batch spans (data, fsdp)
    assert spec_for(("batch", "embed"), RULES["dp"]) \
        == P(("data", "fsdp"), None)


def test_tree_shardings_transformer_tree(mesh4, tiny):
    """tree_shardings over a realistic param tree: every leaf gets a
    NamedSharding whose spec follows its path-derived logical axes."""
    _, params = tiny
    logical = logical_axis_rules_tree(params)
    sh = tree_shardings(mesh4, logical, "tp")
    by = _by_path(sh)
    assert by["block_0/attn/q/kernel"].spec == P(None, TENSOR, None)
    assert by["block_0/mlp/wi/kernel"].spec == P(None, TENSOR)
    # tp shards vocab on the embedding
    assert by["embedding"].spec == P(TENSOR, None)
    # norm scales replicate
    assert by["ln_f/scale"].spec == P(None)
    # every leaf is a NamedSharding on the same mesh
    for leaf in jax.tree_util.tree_leaves(
            sh, is_leaf=lambda x: isinstance(x, NamedSharding)):
        assert isinstance(leaf, NamedSharding)


def test_shard_params_by_size_non_divisible_falls_back_replicated():
    mesh = make_mesh(MeshSpec(data=2, fsdp=4),
                     devices=jax.devices()[:8])
    params = {
        "big_divisible": jnp.zeros((256, 128)),
        # both dims indivisible by fsdp=4 -> replicated, not an error
        "big_odd": jnp.zeros((255, 129)),
        "small": jnp.zeros((4, 4)),
    }
    sh = shard_params_by_size(mesh, params)
    assert sh["big_divisible"].spec == P("fsdp", None)
    assert sh["big_odd"].spec == P()
    assert sh["small"].spec == P()


# ------------------------------------------------------- serve preset


def test_serve_spec_flips_row_parallel_kernels():
    rules = RULES["serve"]
    # column-parallel kernels shard their output dim
    assert serve_spec_for(("embed", "heads", "kv"), rules) \
        == P(None, TENSOR, None)
    assert serve_spec_for(("embed", "mlp"), rules) == P(None, TENSOR)
    # row-parallel kernels (o, wo) FLIP: the heads/mlp contraction dim
    # replicates and the trailing embed (output) dim shards — no
    # cross-chip partial-sum reduction, ever
    assert serve_spec_for(("heads", "kv", "embed"), rules) \
        == P(None, None, TENSOR)
    assert serve_spec_for(("mlp", "embed"), rules) == P(None, TENSOR)
    # the embedding does NOT flip (vocab is an output dim in the
    # logits projection; the input gather is not a contraction)
    assert serve_spec_for(("vocab", "embed"), rules) == P(TENSOR, None)
    # MoE wo keeps its expert axis, flips mlp -> embed
    assert serve_spec_for(("expert", "mlp", "embed"), rules) \
        == P(EXPERT, None, TENSOR)
    # rank-1 leaves never flip
    assert serve_spec_for(("embed",), rules) == P(None)


def test_validated_spec_drops_non_divisible(mesh4):
    # 4 divides 8 -> kept; 4 does not divide 6 -> dropped
    assert validated_spec(mesh4, P(TENSOR, None), (8, 3)) \
        == P(TENSOR, None)
    assert validated_spec(mesh4, P(TENSOR, None), (6, 3)) == P(None, None)
    # tuple assignments validate against the product
    mesh8 = make_mesh(MeshSpec(data=2, tensor=4),
                      devices=jax.devices()[:8])
    assert validated_spec(mesh8, P(("data", "tensor")), (16,)) \
        == P(("data", "tensor"))
    assert validated_spec(mesh8, P(("data", "tensor")), (12,)) == P(None)


def test_serving_shardings_transformer(mesh4, tiny):
    _, params = tiny
    sh = serving_shardings(mesh4, params)
    by = _by_path(sh)
    # q/k/v column-parallel on heads (MHA: kv heads == heads == 4)
    assert by["block_0/attn/q/kernel"].spec == P(None, TENSOR, None)
    assert by["block_0/attn/k/kernel"].spec == P(None, TENSOR, None)
    # o and wo flipped to output-dim (embed) sharding
    assert by["block_0/attn/o/kernel"].spec == P(None, None, TENSOR)
    assert by["block_0/mlp/wo/kernel"].spec == P(None, TENSOR)
    assert by["block_0/mlp/wi/kernel"].spec == P(None, TENSOR)
    assert by["embedding"].spec == P(TENSOR, None)
    assert by["ln_f/scale"].spec == P(None)


def test_serving_shardings_gqa_small_heads_replicate(mesh4):
    """GQA with kv_heads=2 on a tensor=4 mesh: K/V kernels (and the
    pools, below) replicate via validation; q (4 heads... also
    indivisible) replicates too — nothing errors."""
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_kv_heads=2, n_layers=1, d_ff=64,
                            max_seq_len=64, dtype=jnp.float32,
                            attention_backend="reference")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    sh = serving_shardings(mesh4, params)
    by = _by_path(sh)
    # kv_heads=2 not divisible by 4 -> replicated
    assert by["block_0/attn/k/kernel"].spec == P(None, None, None)
    # n_heads=4 IS divisible -> q still shards
    assert by["block_0/attn/q/kernel"].spec == P(None, TENSOR, None)
    cache = init_cache(model, params, 2)
    assert kv_shard_count(mesh4, cache) == 1
    for leaf in jax.tree_util.tree_leaves(
            kv_cache_shardings(mesh4, cache),
            is_leaf=lambda x: isinstance(x, NamedSharding)):
        assert leaf.spec == P()


def test_serving_shardings_q8_leaves(mesh4):
    """int8 serving weights (models/quantize.py): kernel_q8/scale
    leaves shard alongside their bf16 twins — o/wo q8 kernels flip to
    embed like the float kernels."""
    from tony_tpu.models.quantize import quantize_for_serving

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=1, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32,
                            attention_backend="reference")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    _, qparams = quantize_for_serving(model, params)
    sh = serving_shardings(mesh4, qparams)
    by = _by_path(sh)
    # q: column-parallel on the flattened heads output dim
    assert by["block_0/attn/q/kernel_q8"].spec == P(None, TENSOR)
    assert by["block_0/attn/q/scale"].spec == P(TENSOR)
    # o: row-parallel -> flipped to the embed output dim; its rank-1
    # scale ("embed",) has no flip trigger and replicates — tiny, and
    # GSPMD slices it against the sharded output where needed
    assert by["block_0/attn/o/kernel_q8"].spec == P(None, TENSOR)
    assert by["block_0/attn/o/scale"].spec == P(None)
    # wi: column-parallel — its scale shards with the mlp output dim
    assert by["block_0/mlp/wi/kernel_q8"].spec == P(None, TENSOR)
    assert by["block_0/mlp/wi/scale"].spec == P(TENSOR)


# --------------------------------------------------- KV cache shardings


def test_kv_cache_shardings_paged_and_unpaged(mesh4, tiny):
    from tony_tpu.serve.slots import paged_cache

    model, params = tiny
    # unpaged rows [b, max_len, kvh, dh]: kvh (dim 2) shards
    cache = init_cache(model, params, 2)
    by = _by_path(kv_cache_shardings(mesh4, cache))
    key = next(k for k in by if k.endswith("cached_key"))
    assert by[key].spec == P(None, None, TENSOR, None)
    assert kv_shard_count(mesh4, cache) == 4
    # paged pools [n_pages, page_size, kvh, dh]: same rule, page axis
    # whole (the host allocator's page ids mean the same everywhere)
    pool = paged_cache(model, params, 8, 16)
    byp = _by_path(kv_cache_shardings(mesh4, pool))
    keyp = next(k for k in byp if k.endswith("cached_key"))
    assert byp[keyp].spec == P(None, None, TENSOR, None)
    # shared counters replicate
    idx = next(k for k in byp if k.endswith("cache_index"))
    assert byp[idx].spec == P()


def test_tree_shard_bytes_counts_per_chip(mesh4):
    params = {"sharded": jnp.zeros((8, 16), jnp.float32),
              "replicated": jnp.zeros((6, 2), jnp.float32)}
    sh = {"sharded": NamedSharding(mesh4, P(TENSOR, None)),
          "replicated": NamedSharding(mesh4, P())}
    # sharded leaf contributes 1/4, replicated leaf its whole size
    assert tree_shard_bytes(params, sh) == (8 * 16 // 4 + 6 * 2) * 4
    assert tree_shard_count(params, sh) == 8 * 16 // 4 + 6 * 2
