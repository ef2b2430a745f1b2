"""Hugging Face checkpoint import for the flagship transformer.

No reference analog (TonY has no models). GPT-2-family weights map onto
``TransformerConfig(norm="layer", positional="learned", use_bias=True,
activation="gelu_tanh")``; the converter is pure tensor reshuffling
(torch state_dict -> jax pytree), so it works on any GPT-2-sized
checkpoint already on disk — no network needed.

HF GPT-2 layout notes: ``Conv1D`` stores weights as [in, out] (already
the jax kernel orientation); ``c_attn`` packs Q,K,V as one [d, 3d]
matrix split here into per-head kernels; ``wte`` is tied to the LM head
(our model ties through the same ``embedding`` param).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from tony_tpu.models.transformer import (
    RopeScaling,
    Transformer,
    TransformerConfig,
)


_HF_ACTIVATIONS = {"gelu_new": "gelu_tanh", "gelu_pytorch_tanh": "gelu_tanh",
                   "gelu": "gelu", "silu": "silu", "swish": "silu"}


def gpt2_config(hf_config, **overrides) -> TransformerConfig:
    """TransformerConfig matching a transformers GPT2Config."""
    act = getattr(hf_config, "activation_function", "gelu_new")
    if act not in _HF_ACTIVATIONS:
        raise ValueError(f"unsupported GPT-2 activation_function {act!r}; "
                         f"supported: {sorted(_HF_ACTIVATIONS)}")
    n_inner = getattr(hf_config, "n_inner", None)
    kw = dict(
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.n_embd,
        n_heads=hf_config.n_head,
        n_layers=hf_config.n_layer,
        d_ff=n_inner if n_inner else 4 * hf_config.n_embd,
        max_seq_len=hf_config.n_positions,
        dtype=jnp.float32,
        attention_backend="reference",
        norm="layer",
        positional="learned",
        use_bias=True,
        activation=_HF_ACTIVATIONS[act],
        norm_eps=hf_config.layer_norm_epsilon,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def _np(t) -> np.ndarray:
    return t.detach().cpu().numpy()


def convert_gpt2_state_dict(state_dict: dict, cfg: TransformerConfig) -> Any:
    """torch GPT-2 state_dict -> tony-tpu Transformer params pytree."""
    d, h, dh = cfg.d_model, cfg.n_heads, cfg.head_dim
    sd = {k.removeprefix("transformer."): v for k, v in state_dict.items()}
    params: dict[str, Any] = {
        "embedding": _np(sd["wte.weight"]),
        "pos_embedding": _np(sd["wpe.weight"]),
        "ln_f": {"scale": _np(sd["ln_f.weight"]),
                 "bias": _np(sd["ln_f.bias"])},
    }
    for i in range(cfg.n_layers):
        pre = f"h.{i}."
        qkv_w = _np(sd[pre + "attn.c_attn.weight"])  # [d, 3d] (Conv1D)
        qkv_b = _np(sd[pre + "attn.c_attn.bias"])  # [3d]
        qw, kw, vw = np.split(qkv_w, 3, axis=1)
        qb, kb, vb = np.split(qkv_b, 3, axis=0)
        block = {
            "ln1": {"scale": _np(sd[pre + "ln_1.weight"]),
                    "bias": _np(sd[pre + "ln_1.bias"])},
            "ln2": {"scale": _np(sd[pre + "ln_2.weight"]),
                    "bias": _np(sd[pre + "ln_2.bias"])},
            "attn": {
                "q": {"kernel": qw.reshape(d, h, dh),
                      "bias": qb.reshape(h, dh)},
                "k": {"kernel": kw.reshape(d, h, dh),
                      "bias": kb.reshape(h, dh)},
                "v": {"kernel": vw.reshape(d, h, dh),
                      "bias": vb.reshape(h, dh)},
                "o": {"kernel": _np(
                          sd[pre + "attn.c_proj.weight"]).reshape(h, dh, d),
                      "bias": _np(sd[pre + "attn.c_proj.bias"])},
            },
            "mlp": {
                "wi": {"kernel": _np(sd[pre + "mlp.c_fc.weight"]),
                       "bias": _np(sd[pre + "mlp.c_fc.bias"])},
                "wo": {"kernel": _np(sd[pre + "mlp.c_proj.weight"]),
                       "bias": _np(sd[pre + "mlp.c_proj.bias"])},
            },
        }
        params[f"block_{i}"] = block
    return {"params": jax.tree.map(jnp.asarray, params)}


def from_hf_gpt2(model) -> tuple[Transformer, Any]:
    """(Transformer, params) from a transformers GPT2LMHeadModel (or its
    GPT2Model trunk) instance — local weights, no network."""
    cfg = gpt2_config(model.config)
    params = convert_gpt2_state_dict(model.state_dict(), cfg)
    return Transformer(cfg), params


def _effective_sliding_window(hf_config) -> int:
    """Sliding-window size actually in force for this checkpoint.

    Mistral: ``sliding_window`` (None = full attention). Qwen2 ships
    ``sliding_window`` set but gated behind ``use_sliding_window`` (False
    on the released checkpoints), so honor the gate when present.
    """
    win = getattr(hf_config, "sliding_window", None)
    if not win:
        return 0
    if not getattr(hf_config, "use_sliding_window", True):
        return 0
    # Qwen2-style layer gating: HF windows only layers with
    # layer_idx >= max_window_layers. A single global cfg.sliding_window
    # can represent "all layers" (gate at 0) or "no layers" (gate past the
    # stack); anything in between would silently diverge — reject.
    gate = getattr(hf_config, "max_window_layers", 0) or 0
    if gate >= hf_config.num_hidden_layers:
        return 0
    if gate > 0:
        raise ValueError(
            f"per-layer sliding-window gating (max_window_layers={gate} of "
            f"{hf_config.num_hidden_layers}) is not supported; only "
            "all-layers or no-layers windows import exactly")
    return int(win)


def _rope_scaling(hf_config) -> RopeScaling | None:
    """HF rope_scaling dict -> RopeScaling (llama3 / linear), None when
    absent or "default". Unknown kinds (yarn, dynamic, longrope) are
    rejected — importing them as plain RoPE would silently corrupt
    long-position attention."""
    rs = getattr(hf_config, "rope_scaling", None)
    if not rs:
        return None
    kind = rs.get("rope_type", rs.get("type", ""))
    if kind == "default":
        return None
    if kind == "linear":
        return RopeScaling(kind="linear", factor=float(rs["factor"]))
    if kind == "llama3":
        return RopeScaling(
            kind="llama3",
            factor=float(rs["factor"]),
            low_freq_factor=float(rs["low_freq_factor"]),
            high_freq_factor=float(rs["high_freq_factor"]),
            original_max_len=int(rs["original_max_position_embeddings"]))
    raise ValueError(f"unsupported rope_scaling type {kind!r} "
                     "(supported: default, linear, llama3)")


def llama_config(hf_config, **overrides) -> TransformerConfig:
    """TransformerConfig matching a transformers LlamaConfig or close kin:
    any RMSNorm + RoPE + GQA + SwiGLU architecture, including Mistral
    (sliding-window attention -> cfg.sliding_window), Qwen2 (q/k/v
    projection biases -> cfg.qkv_bias), and Llama-3 long-context
    checkpoints (rope_scaling llama3/linear -> cfg.rope_scaling).
    Variants with full attention_bias/mlp_bias or exotic rope scaling are
    rejected rather than silently mis-imported."""
    if getattr(hf_config, "attention_bias", False) or \
            getattr(hf_config, "mlp_bias", False):
        raise ValueError("attention_bias/mlp_bias Llama variants are not "
                         "supported (only Qwen2-style qkv biases are)")
    if "activation" in overrides:
        act_name = overrides["activation"]  # caller (gemma_config) already
        # resolved the family's activation-field semantics
    else:
        act = getattr(hf_config, "hidden_act", "silu")
        if act not in _HF_ACTIVATIONS:
            raise ValueError(f"unsupported hidden_act {act!r}")
        act_name = _HF_ACTIVATIONS[act]
    kw = dict(
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=getattr(hf_config, "num_key_value_heads", None),
        n_layers=hf_config.num_hidden_layers,
        d_ff=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        dtype=jnp.float32,
        attention_backend="reference",
        norm="rms",
        positional="rope",
        use_bias=False,
        qkv_bias=getattr(hf_config, "model_type", "") == "qwen2",
        sliding_window=_effective_sliding_window(hf_config),
        activation=act_name,
        norm_eps=hf_config.rms_norm_eps,
        rope_theta=getattr(hf_config, "rope_theta", 10_000.0),
        rope_scaling=_rope_scaling(hf_config),
        gated_mlp=True,
        tied_embeddings=getattr(hf_config, "tie_word_embeddings", False),
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def lfm2_moe_config(hf_config, **overrides) -> TransformerConfig:
    """TransformerConfig for a ``lfm2_moe`` ``config.json`` (LiquidAI
    LFM2-8B-A1B; ``hf_config`` an object or a mapping of its keys): a
    gated short convolution (``conv_L_cache`` taps, no bias) or GQA
    attention with per-head q/k RMSNorm by ``layer_types``; the first
    ``num_dense_layers`` feed-forwards a SwiGLU of ``intermediate_size``,
    the others ``num_experts`` SwiGLU experts of
    ``moe_intermediate_size`` routed by sigmoid scores, the choice moved
    by ``use_expert_bias``'s leaf, ``num_experts_per_tok`` a token, no
    shared expert; all the experts are held here. The config only: no
    checkpoint importer reads these weights yet."""
    from tony_tpu.parallel.moe import RoutedConfig

    get = hf_config.get if isinstance(hf_config, dict) \
        else lambda k, d=None: getattr(hf_config, k, d)
    if get("conv_bias", False) or not get("norm_topk_prob", True):
        raise ValueError("lfm2_moe with conv_bias, or without "
                         "norm_topk_prob, is not supported")
    n_experts = get("num_experts")
    kw = dict(
        vocab_size=get("vocab_size"),
        d_model=get("hidden_size"),
        n_heads=get("num_attention_heads"),
        n_kv_heads=get("num_key_value_heads"),
        explicit_head_dim=get("head_dim") or 0,
        n_layers=get("num_hidden_layers"),
        d_ff=get("intermediate_size"),
        max_seq_len=get("max_position_embeddings"),
        dtype=jnp.float32,
        attention_backend="reference",
        norm="rms",
        positional="rope",
        use_bias=False,
        activation="silu",
        norm_eps=get("norm_eps"),
        rope_theta=float(get("rope_theta", 1_000_000.0)),
        gated_mlp=True,
        tied_embeddings=bool(get("tie_word_embeddings",
                                 get("tie_embedding", True))),
        layer_types=tuple(get("layer_types")),
        conv_kernel=get("conv_L_cache"),
        qk_norm=True,
        routed=RoutedConfig(
            n_routed=n_experts, top_k=get("num_experts_per_tok"),
            d_ff=get("moe_intermediate_size"), held=(0, n_experts),
            scaling=float(get("routed_scaling_factor", 1.0)),
            first_dense=get("num_dense_layers"),
            selection_bias=bool(get("use_expert_bias", False)),
            renorm_eps=1e-6),
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def _convert_rms_decoder(state_dict: dict, cfg: TransformerConfig, *,
                         family: str, ffn_consumed, ffn_build) -> Any:
    """Shared RMSNorm+RoPE+GQA decoder conversion (Llama-layout state
    dicts): embedding / final norm / lm_head, per-layer norms and
    q/k/v/o, with the strict leftover check. The FFN leaf — dense SwiGLU
    vs sparse MoE — comes from the caller: ``ffn_consumed(i)`` names its
    tensors, ``ffn_build(i, proj)`` returns ``(param_name, leaf_dict)``.

    torch ``nn.Linear`` stores [out, in]; jax kernels are [in, out], so
    every projection transposes. q/k/v rows are head-major, so the
    transposed [d, heads*dh] reshapes straight into [d, heads, dh];
    RoPE conventions already agree (half-split rotate, see
    ``rotary_embedding``).
    """
    d, h, dh, kvh = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.kv_heads
    sd = {k.removeprefix("model."): v for k, v in state_dict.items()}
    # lm_head.weight is consumed untied and a duplicate view when tied
    consumed = {"embed_tokens.weight", "norm.weight", "lm_head.weight"}
    for i in range(cfg.n_layers):
        consumed |= {f"layers.{i}.{s}.weight" for s in (
            "input_layernorm", "post_attention_layernorm",
            "self_attn.q_proj", "self_attn.k_proj", "self_attn.v_proj",
            "self_attn.o_proj")}
        if cfg.qkv_bias:
            consumed |= {f"layers.{i}.self_attn.{p}_proj.bias"
                         for p in "qkv"}
        consumed |= ffn_consumed(i)
    # strictness: an unmapped tensor means this checkpoint is NOT the
    # architecture the config claimed (e.g. stray projection biases when
    # qkv_bias is off) and the import would be silently wrong. inv_freq
    # buffers (old transformers) carry no weights.
    leftover = {k for k in sd
                if k not in consumed and not k.endswith("inv_freq")}
    if leftover:
        raise ValueError(
            f"state_dict has tensors the {family} importer does not map "
            f"(not a plain-{family} architecture?): {sorted(leftover)[:8]}")
    params: dict[str, Any] = {
        "embedding": _np(sd["embed_tokens.weight"]),
        "ln_f": {"scale": _np(sd["norm.weight"])},
    }
    if not cfg.tied_embeddings:
        params["lm_head"] = _np(sd["lm_head.weight"])
    for i in range(cfg.n_layers):
        pre = f"layers.{i}."
        proj = lambda name: _np(sd[pre + name + ".weight"]).T  # noqa: E731

        def head_proj(name, heads):
            leaf = {"kernel": proj(name).reshape(d, heads, dh)}
            if cfg.qkv_bias:
                leaf["bias"] = _np(
                    sd[pre + name + ".bias"]).reshape(heads, dh)
            return leaf

        ffn_name, ffn_leaf = ffn_build(i, proj)
        params[f"block_{i}"] = {
            "ln1": {"scale": _np(sd[pre + "input_layernorm.weight"])},
            "ln2": {"scale": _np(
                sd[pre + "post_attention_layernorm.weight"])},
            "attn": {
                "q": head_proj("self_attn.q_proj", h),
                "k": head_proj("self_attn.k_proj", kvh),
                "v": head_proj("self_attn.v_proj", kvh),
                "o": {"kernel": proj("self_attn.o_proj").reshape(h, dh, d)},
            },
            ffn_name: ffn_leaf,
        }
    return {"params": jax.tree.map(jnp.asarray, params)}


def convert_llama_state_dict(state_dict: dict, cfg: TransformerConfig) -> Any:
    """torch Llama state_dict -> tony-tpu Transformer params pytree."""

    def ffn_consumed(i):
        return {f"layers.{i}.mlp.{p}.weight"
                for p in ("gate_proj", "up_proj", "down_proj")}

    def ffn_build(i, proj):
        return "mlp", {
            "wg": {"kernel": proj("mlp.gate_proj")},
            "wi": {"kernel": proj("mlp.up_proj")},
            "wo": {"kernel": proj("mlp.down_proj")},
        }

    return _convert_rms_decoder(state_dict, cfg, family="Llama",
                                ffn_consumed=ffn_consumed,
                                ffn_build=ffn_build)


def from_hf_llama(model) -> tuple[Transformer, Any]:
    """(Transformer, params) from a transformers LlamaForCausalLM (or
    Mistral/Qwen2-compatible) instance — local weights, no network."""
    cfg = llama_config(model.config)
    params = convert_llama_state_dict(model.state_dict(), cfg)
    return Transformer(cfg), params


def mixtral_config(hf_config, **overrides) -> TransformerConfig:
    """TransformerConfig matching a transformers MixtralConfig.

    Mixtral = Mistral attention (RMSNorm + RoPE + GQA + optional sliding
    window) with EVERY dense MLP replaced by a top-k sparse MoE of SwiGLU
    experts whose gate weights are softmax-then-renormalized over the
    selected k (transformers MixtralSparseMoeBlock). Import maps onto
    ``moe_every=1`` + the Mixtral knobs, with ``moe_dropless=True`` so
    evaluation is EXACT (no capacity dropping) — the capacity-routed
    training path stays available by flipping moe_dropless/capacity."""
    act = getattr(hf_config, "hidden_act", "silu")
    if act not in _HF_ACTIVATIONS:
        raise ValueError(f"unsupported Mixtral hidden_act {act!r}; "
                         f"supported: {sorted(_HF_ACTIVATIONS)}")
    kw = dict(
        gated_mlp=False,  # no dense MLP anywhere; moe_every=1 covers all
        moe_every=1,
        moe_num_experts=hf_config.num_local_experts,
        moe_top_k=hf_config.num_experts_per_tok,
        moe_gated=True,
        moe_renormalize=True,
        moe_dropless=True,
        moe_activation=_HF_ACTIVATIONS[act],
        moe_d_ff=hf_config.intermediate_size,
    )
    kw.update(overrides)
    return llama_config(hf_config, **kw)


def convert_mixtral_state_dict(state_dict: dict,
                               cfg: TransformerConfig) -> Any:
    """torch Mixtral state_dict -> tony-tpu params. The attention/norm
    layout is Llama's (shared converter); each block's MoE maps
    gate.weight [E, D] -> router [D, E] and experts.e.{w1,w3,w2} ->
    stacked wg/wi/wo with the expert-leading orientation of
    parallel/moe.py."""
    e = cfg.moe_num_experts

    def ffn_consumed(i):
        return {f"layers.{i}.block_sparse_moe.gate.weight"} | {
            f"layers.{i}.block_sparse_moe.experts.{x}.{w}.weight"
            for x in range(e) for w in ("w1", "w2", "w3")}

    def ffn_build(i, proj):
        return "moe", {
            "router": proj("block_sparse_moe.gate"),  # [D, E]
            "wg": np.stack([proj(f"block_sparse_moe.experts.{x}.w1")
                            for x in range(e)]),  # [E, D, FF]
            "wi": np.stack([proj(f"block_sparse_moe.experts.{x}.w3")
                            for x in range(e)]),  # [E, D, FF]
            "wo": np.stack([proj(f"block_sparse_moe.experts.{x}.w2")
                            for x in range(e)]),  # [E, FF, D]
        }

    return _convert_rms_decoder(state_dict, cfg, family="Mixtral",
                                ffn_consumed=ffn_consumed,
                                ffn_build=ffn_build)


def from_hf_mixtral(model) -> tuple[Transformer, Any]:
    """(Transformer, params) from a transformers MixtralForCausalLM —
    local weights, no network. Evaluation is exact (dropless dense MoE)."""
    if getattr(model.config, "model_type", "") != "mixtral":
        raise ValueError(
            f"from_hf_mixtral got model_type "
            f"{getattr(model.config, 'model_type', None)!r}")
    cfg = mixtral_config(model.config)
    params = convert_mixtral_state_dict(model.state_dict(), cfg)
    return Transformer(cfg), params


def neox_config(hf_config, **overrides) -> TransformerConfig:
    """TransformerConfig matching a transformers GPTNeoXConfig (Pythia /
    GPT-NeoX-20B family): LayerNorm (with bias) + PARTIAL rotary
    (rotary_pct of each head) + biased dense everywhere + classic
    2-matmul gelu MLP, and — on every released Pythia checkpoint —
    the parallel residual (x + attn(ln1 x) + mlp(ln2 x))."""
    act = getattr(hf_config, "hidden_act", "gelu")
    if act not in _HF_ACTIVATIONS:
        raise ValueError(f"unsupported GPT-NeoX hidden_act {act!r}; "
                         f"supported: {sorted(_HF_ACTIVATIONS)}")
    if not getattr(hf_config, "attention_bias", True):
        # bias-free NeoX variants lack tensors this importer maps; a
        # silent mis-model is worse than a refusal (strictness convention)
        raise ValueError("attention_bias=False GPT-NeoX variants are not "
                         "supported")
    head_dim = hf_config.hidden_size // hf_config.num_attention_heads
    rotary_dims = int(head_dim * getattr(hf_config, "rotary_pct", 1.0))
    if rotary_dims % 2:
        # the half-split rotation needs an even width (true of every
        # released NeoX/Pythia checkpoint; HF's rotate_half would produce
        # mismatched halves for an odd width too)
        raise ValueError(
            f"rotary_pct x head_dim = {rotary_dims} is odd; partial "
            "rotary needs an even rotary width")
    kw = dict(
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_heads=hf_config.num_attention_heads,
        n_layers=hf_config.num_hidden_layers,
        d_ff=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        dtype=jnp.float32,
        attention_backend="reference",
        norm="layer",
        positional="rope",
        use_bias=True,
        activation=_HF_ACTIVATIONS[act],
        norm_eps=hf_config.layer_norm_eps,
        rope_theta=float(getattr(hf_config, "rotary_emb_base", 10_000.0)),
        rope_scaling=_rope_scaling(hf_config),  # map linear / reject exotic
        rotary_dims=0 if rotary_dims >= head_dim else rotary_dims,
        parallel_residual=getattr(hf_config, "use_parallel_residual", True),
        tied_embeddings=getattr(hf_config, "tie_word_embeddings", False),
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def convert_neox_state_dict(state_dict: dict, cfg: TransformerConfig) -> Any:
    """torch GPT-NeoX state_dict -> tony-tpu params. The fused
    query_key_value projection packs rows head-major as [q_h, k_h, v_h]
    per head: transposed [d, 3hd] reshapes to [d, h, 3, dh] and splits
    on the packed axis."""
    d, h, dh = cfg.d_model, cfg.n_heads, cfg.head_dim
    sd = {k.removeprefix("gpt_neox."): v for k, v in state_dict.items()}
    consumed = {"embed_in.weight", "final_layer_norm.weight",
                "final_layer_norm.bias", "embed_out.weight"}
    for i in range(cfg.n_layers):
        consumed |= {f"layers.{i}.{s}.{wb}" for wb in ("weight", "bias")
                     for s in ("input_layernorm", "post_attention_layernorm",
                               "attention.query_key_value",
                               "attention.dense", "mlp.dense_h_to_4h",
                               "mlp.dense_4h_to_h")}
    buffers = ("inv_freq", "attention.bias", "attention.masked_bias",
               "rotary_emb.inv_freq")
    leftover = {k for k in sd if k not in consumed
                and not k.endswith(buffers)}
    if leftover:
        raise ValueError(
            f"state_dict has tensors the GPT-NeoX importer does not map "
            f"(not a plain-NeoX architecture?): {sorted(leftover)[:8]}")
    params: dict[str, Any] = {
        "embedding": _np(sd["embed_in.weight"]),
        "ln_f": {"scale": _np(sd["final_layer_norm.weight"]),
                 "bias": _np(sd["final_layer_norm.bias"])},
    }
    if not cfg.tied_embeddings:
        params["lm_head"] = _np(sd["embed_out.weight"])
    for i in range(cfg.n_layers):
        pre = f"layers.{i}."
        qkv_w = _np(sd[pre + "attention.query_key_value.weight"]).T \
            .reshape(d, h, 3, dh)
        qkv_b = _np(sd[pre + "attention.query_key_value.bias"]) \
            .reshape(h, 3, dh)

        def lin(name):
            return {"kernel": _np(sd[pre + name + ".weight"]).T,
                    "bias": _np(sd[pre + name + ".bias"])}

        params[f"block_{i}"] = {
            "ln1": {"scale": _np(sd[pre + "input_layernorm.weight"]),
                    "bias": _np(sd[pre + "input_layernorm.bias"])},
            "ln2": {"scale": _np(
                        sd[pre + "post_attention_layernorm.weight"]),
                    "bias": _np(
                        sd[pre + "post_attention_layernorm.bias"])},
            "attn": {
                "q": {"kernel": qkv_w[:, :, 0], "bias": qkv_b[:, 0]},
                "k": {"kernel": qkv_w[:, :, 1], "bias": qkv_b[:, 1]},
                "v": {"kernel": qkv_w[:, :, 2], "bias": qkv_b[:, 2]},
                "o": {"kernel": _np(sd[pre + "attention.dense.weight"])
                      .T.reshape(h, dh, d),
                      "bias": _np(sd[pre + "attention.dense.bias"])},
            },
            "mlp": {
                "wi": lin("mlp.dense_h_to_4h"),
                "wo": lin("mlp.dense_4h_to_h"),
            },
        }
    return {"params": jax.tree.map(jnp.asarray, params)}


def from_hf_neox(model) -> tuple[Transformer, Any]:
    """(Transformer, params) from a transformers GPTNeoXForCausalLM
    (Pythia family) — local weights, no network."""
    if getattr(model.config, "model_type", "") != "gpt_neox":
        raise ValueError(
            f"from_hf_neox got model_type "
            f"{getattr(model.config, 'model_type', None)!r}")
    cfg = neox_config(model.config)
    params = convert_neox_state_dict(model.state_dict(), cfg)
    return Transformer(cfg), params


def phi_config(hf_config, **overrides) -> TransformerConfig:
    """TransformerConfig matching a transformers PhiConfig (Phi-1/1.5/2):
    LayerNorm + partial rotary (``partial_rotary_factor``) + biased dense
    everywhere + parallel residual where BOTH branches read the SAME
    input LayerNorm, + an untied lm_head WITH bias. The shared norm maps
    onto this model's two-norm parallel block by duplicating the weights
    into ln2 (identical input -> identical math)."""
    act = getattr(hf_config, "hidden_act", "gelu_new")
    if act not in _HF_ACTIVATIONS:
        raise ValueError(f"unsupported Phi hidden_act {act!r}; "
                         f"supported: {sorted(_HF_ACTIVATIONS)}")
    head_dim = hf_config.hidden_size // hf_config.num_attention_heads
    rotary_dims = int(head_dim * getattr(hf_config, "partial_rotary_factor",
                                         0.5))
    if rotary_dims % 2:
        raise ValueError(
            f"partial_rotary_factor x head_dim = {rotary_dims} is odd; "
            "partial rotary needs an even rotary width")
    # No released Phi ties embeddings; a tied variant would silently drop
    # the converted biased lm_head (tied logits read the embedding), so
    # refuse rather than mismodel — same convention as the NeoX
    # attention_bias=False refusal above.
    if getattr(hf_config, "tie_word_embeddings", False):
        raise ValueError("tie_word_embeddings=True Phi variants are not "
                         "supported (the importer emits an untied biased "
                         "lm_head; a tied model would silently ignore it)")
    kw = dict(
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=getattr(hf_config, "num_key_value_heads", None),
        n_layers=hf_config.num_hidden_layers,
        d_ff=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        dtype=jnp.float32,
        attention_backend="reference",
        norm="layer",
        positional="rope",
        use_bias=True,
        activation=_HF_ACTIVATIONS[act],
        norm_eps=hf_config.layer_norm_eps,
        rope_theta=float(getattr(hf_config, "rope_theta", 10_000.0)),
        rope_scaling=_rope_scaling(hf_config),
        rotary_dims=0 if rotary_dims >= head_dim else rotary_dims,
        parallel_residual=True,
        tied_embeddings=getattr(hf_config, "tie_word_embeddings", False),
        lm_head_bias=True,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def convert_phi_state_dict(state_dict: dict, cfg: TransformerConfig) -> Any:
    """torch Phi state_dict -> tony-tpu params. Llama-style per-layer
    names but LayerNorm (weight+bias), biased q/k/v/dense/fc1/fc2, a
    single input_layernorm duplicated into ln1+ln2 (shared-norm parallel
    residual), and a biased untied lm_head."""
    d, h, dh, kvh = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.kv_heads
    sd = {k.removeprefix("model."): v for k, v in state_dict.items()}
    consumed = {"embed_tokens.weight", "final_layernorm.weight",
                "final_layernorm.bias", "lm_head.weight", "lm_head.bias"}
    for i in range(cfg.n_layers):
        consumed |= {f"layers.{i}.{s}.{wb}" for wb in ("weight", "bias")
                     for s in ("input_layernorm", "self_attn.q_proj",
                               "self_attn.k_proj", "self_attn.v_proj",
                               "self_attn.dense", "mlp.fc1", "mlp.fc2")}
    leftover = {k for k in sd if k not in consumed
                and not k.endswith("inv_freq")}
    if leftover:
        raise ValueError(
            f"state_dict has tensors the Phi importer does not map "
            f"(not a plain-Phi architecture?): {sorted(leftover)[:8]}")
    params: dict[str, Any] = {
        "embedding": _np(sd["embed_tokens.weight"]),
        "ln_f": {"scale": _np(sd["final_layernorm.weight"]),
                 "bias": _np(sd["final_layernorm.bias"])},
        "lm_head": _np(sd["lm_head.weight"]),
        "lm_head_bias": _np(sd["lm_head.bias"]),
    }
    for i in range(cfg.n_layers):
        pre = f"layers.{i}."
        proj = lambda name: _np(sd[pre + name + ".weight"]).T  # noqa: E731
        bias = lambda name: _np(sd[pre + name + ".bias"])  # noqa: E731
        norm = {"scale": _np(sd[pre + "input_layernorm.weight"]),
                "bias": _np(sd[pre + "input_layernorm.bias"])}

        def head_proj(name, heads):
            return {"kernel": proj(name).reshape(d, heads, dh),
                    "bias": bias(name).reshape(heads, dh)}

        params[f"block_{i}"] = {
            "ln1": dict(norm),
            "ln2": dict(norm),  # shared input norm -> both branches
            "attn": {
                "q": head_proj("self_attn.q_proj", h),
                "k": head_proj("self_attn.k_proj", kvh),
                "v": head_proj("self_attn.v_proj", kvh),
                "o": {"kernel": proj("self_attn.dense").reshape(h, dh, d),
                      "bias": bias("self_attn.dense")},
            },
            "mlp": {
                "wi": {"kernel": proj("mlp.fc1"), "bias": bias("mlp.fc1")},
                "wo": {"kernel": proj("mlp.fc2"), "bias": bias("mlp.fc2")},
            },
        }
    return {"params": jax.tree.map(jnp.asarray, params)}


def from_hf_phi(model) -> tuple[Transformer, Any]:
    """(Transformer, params) from a transformers PhiForCausalLM — local
    weights, no network."""
    if getattr(model.config, "model_type", "") != "phi":
        raise ValueError(
            f"from_hf_phi got model_type "
            f"{getattr(model.config, 'model_type', None)!r}")
    cfg = phi_config(model.config)
    params = convert_phi_state_dict(model.state_dict(), cfg)
    return Transformer(cfg), params


def gemma_config(hf_config, **overrides) -> TransformerConfig:
    """TransformerConfig matching a transformers GemmaConfig (Gemma-1).

    Gemma's distinctives vs Llama: explicit per-head width (7B: 16 heads
    x 256 > hidden 3072), embeddings scaled by sqrt(hidden) in activation
    dtype, RMSNorm applied as (1 + weight) with zero-init weight, tied
    embeddings, and gelu-tanh gated MLP. Gemma-2 (attn/final logit
    softcapping, alternating local attention) is NOT this architecture
    and is rejected by the model_type check in from_hf_gemma."""
    # transformers' GemmaMLP runs ACT2FN[config.hidden_act] (verified on
    # 4.57) even though hub configs ALSO carry hidden_activation — parity
    # is against the installed torch reference, so mirror its resolution
    # exactly: hidden_act first, hidden_activation as the fallback
    act = getattr(hf_config, "hidden_act", None) or \
        getattr(hf_config, "hidden_activation", None) or "gelu_pytorch_tanh"
    if act not in _HF_ACTIVATIONS:
        raise ValueError(f"unsupported Gemma activation {act!r}")
    # the shared RMSNorm+RoPE+GQA+gated-MLP mapping (and its strictness:
    # attention/mlp-bias rejection, rope_scaling map-or-reject) lives in
    # llama_config; only Gemma's distinctives are overridden here
    kw = dict(
        activation=_HF_ACTIVATIONS[act],
        qkv_bias=False,
        tied_embeddings=getattr(hf_config, "tie_word_embeddings", True),
        explicit_head_dim=getattr(hf_config, "head_dim", 0) or 0,
        embed_scale=True,
        norm_unit_offset=True,
    )
    kw.update(overrides)
    return llama_config(hf_config, **kw)


def from_hf_gemma(model) -> tuple[Transformer, Any]:
    """(Transformer, params) from a transformers GemmaForCausalLM.
    The state-dict layout is Llama's (same projection/norm names), so the
    conversion is shared; only the config semantics differ."""
    if getattr(model.config, "model_type", "") != "gemma":
        raise ValueError(
            f"from_hf_gemma got model_type "
            f"{getattr(model.config, 'model_type', None)!r} (gemma2's "
            "softcapping/local-attention architecture is not this model)")
    cfg = gemma_config(model.config)
    params = convert_llama_state_dict(model.state_dict(), cfg)
    return Transformer(cfg), params
