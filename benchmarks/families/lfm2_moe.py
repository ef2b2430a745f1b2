"""LFM2-8B-A1B (``model_type: lfm2_moe``, LiquidAI/LFM2-8B-A1B
``config.json``; ``transformers``' ``lfm2_moe`` is the published
description): a decoder whose layers differ in their MIXER and in their
feed-forward. The contract of a family file is in ``benchmarks/README.md``;
what a reader of THIS family needs is here.

**The layer**, pre-norm with two residual adds, ``h = RMSNorm(x)`` (eps
``norm_eps``), ``K = conv_L_cache``:

    conv mixer (layer_types[i] == "conv"):
        [B; C; u] = h W_in                      three slices of width d, no bias
        z = B * u                               elementwise
        y_t = sum_j w[:, j] * z_{t-(K-1)+j}     a channel, causal, zeros before t = 0
        x += (C * y) W_out
    attention mixer ("full_attention"):
        q, k, v = h W_q, h W_k, h W_v           heads / kv heads of head_dim
        q, k = RMSNorm_head(q), RMSNorm_head(k) over the head's dims, one
                                                scale of head_dim each, BEFORE
        q, k = RoPE(q), RoPE(k)                 all dims, rope_theta
        x += (causal softmax(q k^T / sqrt(head_dim)) v) W_o
    h = RMSNorm(x)
    dense feed-forward (layer < num_dense_layers):
        x += W_o'(silu(h W_g) * (h W_i))        width intermediate_size
    routed feed-forward (the others):
        s = sigmoid(h W_r)                      num_experts scores, float32
        the num_experts_per_tok experts with the largest s + b    (b: the
            selection bias, use_expert_bias; the CHOICE sees it, no weight does)
        w = s_chosen / (sum s_chosen + 1e-6) * routed_scaling_factor
        x += sum over the chosen experts of w_e * expert_e(h)     SwiGLU,
            width moe_intermediate_size; no shared expert

A layer's KIND is its mixer and its feed-forward (``conv_dense``,
``attn_moe``, ``conv_moe``, and ``attn_dense`` where a configuration has
one): what the general code keys leaves and mathematics on.

**Assumed** (the configuration file lists them under ``assumed``): the
head's size is ``hidden_size / num_attention_heads`` (the row's
``head_dim`` is null); the renormalising 1e-6; half-split rotary pairing
in program and reference alike; an UNTIED head: the serving reference
(``harness/reference.py`` ``_serve_head``) hands ``logits`` the global
leaves WITHOUT ``embed``, so a head tied to the embedding cannot be
compared until that function keeps it (``arch`` refuses
``tie_word_embeddings: true`` and says so).

**Seeded weights**: matrices N(0, 0.02) and norm scales 1 + 0.1 N as
everywhere; the convolution's taps are drawn as a SCALE leaf (1 + 0.1 N:
a filter of magnitude 1, so that a conv mixer adds to the residual what
an attention mixer adds, and a predecessor taken wrongly shows); the
selection bias as a bias leaf (0.02 N).

**The counts** are of what the algorithm needs. A cached position costs K
and V of the ATTENTION layers only; a sequence costs ``(K - 1) x d``
values of state a conv layer, whatever its length. A token meets every
mixer's weights, the router, and ``num_experts_per_tok`` experts a routed
layer (not all of them). One decode step reads the non-expert weights
once, each expert at most once and only if a live row chose it, the live
positions' K/V, and the rows' state in and out.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

from benchmarks.families import mistral as M
from benchmarks.harness import work as K


@dataclass(frozen=True)
class Arch:
    family: str
    d: int                # hidden_size
    layers: int
    vocab: int
    max_len: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int               # intermediate_size (the dense layers)
    dense_layers: int     # num_dense_layers
    expert_ff: int        # moe_intermediate_size
    n_experts: int
    top_k: int
    scaling: float        # routed_scaling_factor
    expert_bias: bool     # use_expert_bias
    eps: float
    theta: float
    taps: int             # conv_L_cache
    layer_types: tuple    # "conv" | "full_attention", one a layer


def arch(cfg: dict) -> Arch:
    if cfg["conv_bias"] or not cfg["norm_topk_prob"]:
        raise ValueError("not in the reference: conv_bias, or chosen "
                         "weights that are not renormalised")
    if cfg.get("tie_word_embeddings", True):
        raise ValueError(
            "tie_word_embeddings: the serving reference "
            "(benchmarks/harness/reference.py _serve_head) drops `embed` "
            "from the leaves it hands `logits`, so a tied head cannot be "
            "compared; the configuration assumes an untied head")
    kinds = tuple(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] \
            or set(kinds) - {"conv", "full_attention"}:
        raise ValueError(f"layer_types {kinds} does not name conv or "
                         f"full_attention for each of the "
                         f"{cfg['num_hidden_layers']} layers")
    heads = cfg["num_attention_heads"]
    return Arch(
        cfg["model_type"], cfg["hidden_size"], cfg["num_hidden_layers"],
        cfg["vocab_size"], cfg["max_position_embeddings"], heads,
        cfg["num_key_value_heads"],
        cfg.get("head_dim") or cfg["hidden_size"] // heads,
        cfg["intermediate_size"], cfg["num_dense_layers"],
        cfg["moe_intermediate_size"], cfg["num_experts"],
        cfg["num_experts_per_tok"], float(cfg["routed_scaling_factor"]),
        bool(cfg["use_expert_bias"]), cfg["norm_eps"],
        float(cfg["rope_theta"]), cfg["conv_L_cache"], kinds)


# ------------------------------------------------------------ the leaves
# kind: "w" matrix, "s" scale (norms, and the convolution's taps), "b" bias

def _is_conv(a: Arch, layer: int) -> bool:
    return a.layer_types[layer] == "conv"


def _is_dense(a: Arch, layer: int) -> bool:
    return layer < a.dense_layers


def layer_kind(a: Arch, layer: int) -> str:
    return ("conv" if _is_conv(a, layer) else "attn") + "_" \
        + ("dense" if _is_dense(a, layer) else "moe")


def _mixer_leaves(a: Arch, conv: bool) -> list[tuple[str, tuple, str]]:
    if conv:
        return [("in_proj", (a.d, 3 * a.d), "w"),
                ("taps", (a.d, a.taps), "s"),
                ("out_proj", (a.d, a.d), "w")]
    return [("q", (a.d, a.heads, a.head_dim), "w"),
            ("k", (a.d, a.kv_heads, a.head_dim), "w"),
            ("v", (a.d, a.kv_heads, a.head_dim), "w"),
            ("o", (a.heads, a.head_dim, a.d), "w"),
            ("q_norm.scale", (a.head_dim,), "s"),
            ("k_norm.scale", (a.head_dim,), "s")]


def _ffn_leaves(a: Arch, dense: bool) -> list[tuple[str, tuple, str]]:
    if dense:
        return [("wg", (a.d, a.ff), "w"), ("wi", (a.d, a.ff), "w"),
                ("wo", (a.ff, a.d), "w")]
    bias = [("expert_bias", (a.n_experts,), "b")] if a.expert_bias else []
    return [("router", (a.d, a.n_experts), "w")] + bias + [
        ("eg", (a.n_experts, a.d, a.expert_ff), "w"),
        ("ei", (a.n_experts, a.d, a.expert_ff), "w"),
        ("eo", (a.n_experts, a.expert_ff, a.d), "w")]


def layer_leaves(a: Arch, layer) -> list[tuple[str, tuple, str]]:
    return [("ln1.scale", (a.d,), "s"), ("ln2.scale", (a.d,), "s")] \
        + _mixer_leaves(a, _is_conv(a, layer)) \
        + _ffn_leaves(a, _is_dense(a, layer))


def global_leaves(a: Arch) -> list[tuple[str, tuple, str]]:
    return [("embed", (a.vocab, a.d), "w"), ("head", (a.vocab, a.d), "w"),
            ("ln_f.scale", (a.d,), "s")]


# --------------------------------------------------- the plain reference

def rms(a: Arch, x, scale):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + a.eps) * scale


def short_conv(a: Arch, p: dict, h, d):
    """The conv mixer of one row h [L, D]: ``K - 1`` shifted adds."""
    import jax.numpy as jnp

    gate_b, gate_c, u = jnp.split(d(h, p["in_proj"]), 3, axis=-1)
    z = gate_b * u
    y = z * p["taps"][:, a.taps - 1]
    for back in range(1, a.taps):       # z_{t - back}, zeros before t = 0
        shifted = jnp.concatenate(
            [jnp.zeros((back, a.d), z.dtype), z[:-back]], axis=0)
        y = y + shifted * p["taps"][:, a.taps - 1 - back]
    return d(gate_c * y, p["out_proj"])


def attention(a: Arch, p: dict, h, d):
    q = rms(a, d(h, p["q"]), p["q_norm.scale"])
    k = rms(a, d(h, p["k"]), p["k_norm.scale"])
    out = M.attention(a, M.rope(a, q), M.rope(a, k), d(h, p["v"]))
    return d(out, p["o"], 2)


def swiglu(d, h, wg, wi, wo):
    import jax

    return d(jax.nn.silu(d(h, wg)) * d(h, wi), wo)


def route(a: Arch, p: dict, h, d):
    """[L, num_experts]: the weight each token gives each expert (0 where
    it is not among the token's chosen ``top_k``)."""
    import jax
    import jax.numpy as jnp

    score = jax.nn.sigmoid(d(h, p["router"]))
    choice = score + p["expert_bias"] if a.expert_bias else score
    _, idx = jax.lax.top_k(choice, a.top_k)
    top = jnp.take_along_axis(score, idx, axis=-1)
    w = top / (jnp.sum(top, -1, keepdims=True) + 1e-6) * a.scaling
    hot = idx[..., None] == jnp.arange(a.n_experts)       # [L, k, E]
    return jnp.sum(jnp.where(hot, w[..., None], 0.0), axis=1)


def block(a: Arch, p: dict, x, quant: str = "", kind: str = "conv_moe"):
    """One layer of ``kind`` on one row x [L, D]."""
    from benchmarks.harness.reference import dense

    d = functools.partial(dense, quant=quant)
    mixer, ffn = kind.split("_")
    h = rms(a, x, p["ln1.scale"])
    x = x + (short_conv if mixer == "conv" else attention)(a, p, h, d)
    h = rms(a, x, p["ln2.scale"])
    if ffn == "dense":
        return x + swiglu(d, h, p["wg"], p["wi"], p["wo"])
    weight = route(a, p, h, d)
    for e in range(a.n_experts):        # every expert on every token
        x = x + weight[:, e:e + 1] * swiglu(d, h, p["eg"][e], p["ei"][e],
                                            p["eo"][e])
    return x


def logits(a: Arch, g: dict, h, quant: str = ""):
    """Final norm and head over hidden rows h [..., D]."""
    from benchmarks.harness.reference import dense

    return dense(rms(a, h, g["ln_f.scale"]), g["head"].T, quant=quant)


# ------------------------------------------------ the system under test

def program_config(a: Arch, dtype, **extra):
    """Through ``models/hf.py`` ``lfm2_moe_config``, from the published
    keys the ``Arch`` was made of."""
    from tony_tpu.models.hf import lfm2_moe_config

    keys = dict(
        vocab_size=a.vocab, hidden_size=a.d, num_attention_heads=a.heads,
        num_key_value_heads=a.kv_heads, num_hidden_layers=a.layers,
        intermediate_size=a.ff, max_position_embeddings=a.max_len,
        norm_eps=a.eps, rope_theta=a.theta,
        layer_types=list(a.layer_types), conv_L_cache=a.taps,
        conv_bias=False, num_experts=a.n_experts,
        num_experts_per_tok=a.top_k, moe_intermediate_size=a.expert_ff,
        num_dense_layers=a.dense_layers, use_expert_bias=a.expert_bias,
        routed_scaling_factor=a.scaling, norm_topk_prob=True,
        tie_word_embeddings=False)
    kw = dict(dtype=dtype, explicit_head_dim=0
              if a.head_dim * a.heads == a.d else a.head_dim)
    kw.update(extra)
    return lfm2_moe_config(keys, **kw)


def program_tree(a: Arch, w: dict) -> dict:
    """``weights.all_weights`` (or leaf NAMES in their place) laid out as
    ``Transformer``'s ``params``. The selection bias is a float32 leaf of
    the program: the bf16 VALUE the seed gives goes over unchanged."""
    g = w["g"]
    tree = {"embedding": g["embed"], "lm_head": g["head"],
            "ln_f": {"scale": g["ln_f.scale"]}}
    kernel = lambda lw, names: {n: {"kernel": lw[n]}  # noqa: E731
                                for n in names}

    def f32(x):     # a leaf NAME goes through as it is
        return x.astype("float32") if hasattr(x, "astype") else x

    for i, lw in enumerate(w["layers"]):
        blk = {"ln1": {"scale": lw["ln1.scale"]},
               "ln2": {"scale": lw["ln2.scale"]}}
        if _is_conv(a, i):
            blk["conv"] = {**kernel(lw, ("in_proj", "out_proj")),
                           "kernel": lw["taps"]}
        else:
            blk["attn"] = {**kernel(lw, "qkvo"),
                           "q_norm": {"scale": lw["q_norm.scale"]},
                           "k_norm": {"scale": lw["k_norm.scale"]}}
        if _is_dense(a, i):
            blk["mlp"] = kernel(lw, ("wg", "wi", "wo"))
        else:
            blk["moe"] = {"router": lw["router"], "wg": lw["eg"],
                          "wi": lw["ei"], "wo": lw["eo"]}
            if a.expert_bias:
                blk["moe"]["expert_bias"] = f32(lw["expert_bias"])
        tree[f"block_{i}"] = blk
    return tree


# ------------------------------------------------------------ the counts
# of what the algorithm needs (the docstring above says which)

def _n(a: Arch, what) -> int:
    return sum(1 for i in range(a.layers) if what(a, i))


def conv_layers(a: Arch) -> int:
    return _n(a, _is_conv)


def attn_layers(a: Arch) -> int:
    return a.layers - conv_layers(a)


def moe_layers(a: Arch) -> int:
    return a.layers - _n(a, _is_dense)


def _expert_params(a: Arch) -> int:
    return 3 * a.d * a.expert_ff


def _token_matmul_params(a: Arch) -> float:
    """Matmul weights one token meets across the blocks: all but the
    experts it is not sent to."""
    return K.matmul_params(a) - moe_layers(a) * _expert_params(a) * (
        a.n_experts - a.top_k)


def _token_flops(a: Arch) -> float:
    """One token through every block but its attention over the cache:
    2 a matmul weight it meets, and a conv layer's gates and taps."""
    return 2 * _token_matmul_params(a) \
        + conv_layers(a) * a.d * (2 * a.taps + 2)


def kv_bytes_per_token(a: Arch, itemsize: int = 2) -> int:
    """K and V of every ATTENTION layer for one position."""
    return 2 * a.kv_heads * a.head_dim * attn_layers(a) * itemsize


def state_bytes_per_sequence(a: Arch, itemsize: int = 2) -> int:
    """``z`` at the last ``K - 1`` positions, every conv layer."""
    return conv_layers(a) * (a.taps - 1) * a.d * itemsize


def serve_token_flops(a: Arch, position: int, sampled: bool) -> float:
    """Model FLOPs of one position of a served request: the blocks (4
    experts a token, not 32), attention over the positions so far in the
    attention layers, and the head where a token is sampled."""
    return _token_flops(a) \
        + 4 * a.heads * a.head_dim * attn_layers(a) * (position + 1) \
        + (2 * a.vocab * a.d if sampled else 0)


def experts_hit(a: Arch, batch: float) -> float:
    """Experts of ONE routed layer that at least one of ``batch`` live
    rows chose, in expectation (even routing)."""
    return a.n_experts * (1.0 - (1.0 - a.top_k / a.n_experts)
                          ** max(batch, 0.0))


def decode_step_bytes(a: Arch, live_tokens: float, itemsize: int = 2) -> float:
    """Least bytes one decode step reads: the non-expert weights, the
    final norm and the head once, each expert once IF a live row chose
    it, the K/V of the ``live_tokens`` positions, and the rows' state in
    and out. The signature has no batch, and a count errs low: the rows
    are taken to be as few as ``live_tokens`` allow (each as long as
    ``max_len``)."""
    rows = live_tokens / a.max_len
    experts = moe_layers(a) * _expert_params(a)
    weights = K.params(a) - experts * a.n_experts + a.vocab * a.d + a.d \
        + experts * experts_hit(a, rows)
    return weights * itemsize + live_tokens * kv_bytes_per_token(a, itemsize) \
        + 2 * rows * state_bytes_per_sequence(a, itemsize)


def decode_step_flops(a: Arch, batch: float, live_tokens: float) -> float:
    return batch * (_token_flops(a) + 2 * a.vocab * a.d) \
        + 4 * a.heads * a.head_dim * attn_layers(a) * live_tokens


def moe_experts_step(a: Arch, batch: float, live_tokens: float,
                     counters: dict | None = None) -> tuple[float, float]:
    """(FLOPs, bytes) of the experts' grouped products in one decode
    step, every routed layer: 2 FLOPs a weight for each token-expert
    pair, and each expert's weights once if it was hit. From the
    program's counters where the run has them (``counters``: the
    window's ``moe_tokens_held`` and ``moe_experts_hit`` a decode step),
    else the expectation under even routing."""
    if counters:
        pairs, hit = counters["pairs_per_step"], counters["hit_per_step"]
    else:
        pairs = moe_layers(a) * batch * a.top_k
        hit = moe_layers(a) * experts_hit(a, batch)
    return 2 * _expert_params(a) * pairs, 2 * _expert_params(a) * hit


def conv_mix_step(a: Arch, batch: float, live_tokens: float,
                  counters: dict | None = None) -> tuple[float, float]:
    """(FLOPs, bytes) of the conv mixers in one decode step, every conv
    layer: the in- and out-projection of each live row (2 FLOPs a
    weight), the gates and the taps; the projections' weights once a
    step, and each row's state read and written. NO metric reads this
    count yet: on the v5e the weights' load is no op with a time of its
    own (the compiler prefetches each in four async slices while the
    expert kernels run; a trace holds only the 9 us a step the products
    wait at ``slice-done``), so a share of the roofline over the
    mixers' own ops read 217% (PERF.md, Findings PR 37 and section 7
    row 17). It waits for a reader that can time a start-to-done span."""
    flops = conv_layers(a) * batch * (2 * 4 * a.d * a.d
                                      + a.d * (2 * a.taps + 2))
    return flops, conv_layers(a) * 4 * a.d * a.d * 2 \
        + 2 * batch * state_bytes_per_sequence(a)
