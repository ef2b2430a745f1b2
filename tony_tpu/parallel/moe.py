"""Mixture-of-Experts with expert parallelism.

Absent from the reference (SURVEY.md section 2.4: EP "NO"). Implementation
is the pjit idiom: expert weights carry a leading expert dim annotated with
the ``expert`` mesh axis; dispatch/combine are einsums against a capacity-
limited one-hot dispatch tensor, so under pjit XLA lowers the token
exchange to all-to-all over ICI — no hand-written comms.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp


@dataclass
class MoEConfig:
    num_experts: int = 8
    capacity_factor: float = 1.25
    top_k: int = 2
    d_model: int = 512
    d_ff: int = 2048
    # Mixtral-family experts: SwiGLU, wo(act(wg x) * (wi x)) per expert,
    # instead of the 2-matmul wo(act(wi x)) expert
    gated: bool = False
    activation: str = "gelu"  # gelu | silu
    # HF Mixtral renormalizes the selected top-k gate weights to sum to 1
    renormalize_top_k: bool = False
    # dropless=True computes EVERY expert on every token and combines by
    # gate weight — exact (no capacity dropping), memory O(E*T*ff), the
    # eval/checkpoint-parity path. False = capacity-limited dispatch
    # einsums (all-to-all under pjit), the training path.
    dropless: bool = False
    # int8 expert serving over expert parallelism: a vmapped pallas call
    # is opaque to GSPMD, so expert-sharded q8 weights fed to the vmapped
    # dequant matmul under bare pjit would be ALL-GATHERED (defeating the
    # only way a 47B Mixtral fits a slice). With ``mesh`` set and the
    # ``expert_axis`` present, the q8 expert FFN runs under shard_map over
    # that axis: each device dequant-matmuls its LOCAL experts only.
    mesh: Any = None
    expert_axis: str = "expert"


def _act(name: str):
    """Same semantics as models/transformer._activation: 'gelu' is the
    erf form, 'gelu_tanh' the approximation (HF gelu_new/pytorch_tanh)."""
    table = {
        "gelu": lambda x: jax.nn.gelu(x, approximate=False),
        "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
        "silu": jax.nn.silu,
    }
    if name not in table:
        raise ValueError(f"unsupported MoE activation {name!r} "
                         f"(supported: {sorted(table)})")
    return table[name]


def _gates(logits: jnp.ndarray, k: int, renormalize: bool):
    """Shared routing math for the routed and dropless paths: softmax
    probs, top-k gate (values, indices) — optionally renormalized to sum
    to 1 per token (Mixtral) — and the load-balancing aux loss."""
    e = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # [T, k]
    if renormalize:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    return probs, gate_vals, gate_idx, _aux_loss(probs, gate_idx, e, k)


def _expert_ffn(params: dict, x: jnp.ndarray, cfg: "MoEConfig",
                up_spec: str, down_spec: str) -> jnp.ndarray:
    """Per-expert FFN shared by both paths: 2-matmul act(wi) or SwiGLU
    act(wg)*wi (``cfg.gated``), then wo. The einsum specs carry the
    layout difference (routed [E,C,D] vs dropless [T,D]-broadcast).

    int8 serving (Mixtral --int8): ``wi_q8 [E, D, F] + wi_scale [E, F]``
    (per-expert, per-output-channel — models/quantize.py) run through the
    pallas dequant matmul vmapped over the expert dim: expert weights
    cross HBM as int8, dequantized in VMEM, matching the q8 dense path.
    The vmapped outputs are exactly the einsums' expert-major layouts
    ([E, T, F] dropless / [E, C, F] routed)."""
    act = _act(cfg.activation)
    if "wi_q8" in params:
        x_axis = None if x.ndim == 2 else 0  # dropless broadcasts tokens
        ep = _expert_shards(cfg)
        if ep > 1:
            # expert-sharded int8 serving: shard_map over the expert axis
            # so each device's pallas dequant matmul sees only its local
            # expert shard (vmapped pallas is opaque to GSPMD — bare pjit
            # would all-gather the very weights EP exists to split)
            from jax.sharding import PartitionSpec as P

            from jax import shard_map

            ax = cfg.expert_axis
            w3, w2 = P(ax, None, None), P(ax, None)
            xspec = P(None, None) if x_axis is None else P(ax, None, None)
            names = [nm for nm in ("wi", "wg", "wo")
                     if nm + "_q8" in params]
            weights = [params[nm + sfx] for nm in names
                       for sfx in ("_q8", "_scale")]
            w_specs = [sp for _ in names for sp in (w3, w2)]

            def local_ffn(x_l, *flat):
                local = {nm + sfx: flat[2 * i + j]
                         for i, nm in enumerate(names)
                         for j, sfx in enumerate(("_q8", "_scale"))}
                return _q8_expert_ffn(local, x_l, x_axis, act, cfg.gated)

            return shard_map(
                local_ffn, mesh=cfg.mesh,
                in_specs=(xspec, *w_specs),
                out_specs=P(ax, None, None),
                check_vma=False,
            )(x, *weights)
        return _q8_expert_ffn(params, x, x_axis, act, cfg.gated)
    up = jnp.einsum(up_spec, x, params["wi"])
    if cfg.gated:
        h = act(jnp.einsum(up_spec, x, params["wg"])) * up
    else:
        h = act(up)
    return jnp.einsum(down_spec, h, params["wo"])


def _expert_shards(cfg: MoEConfig) -> int:
    """Way size of the expert axis when the q8 shard_map path applies
    (mesh set, axis present, experts divisible); 1 = run unsharded."""
    if cfg.mesh is None or cfg.expert_axis not in cfg.mesh.shape:
        return 1
    ways = cfg.mesh.shape[cfg.expert_axis]
    return ways if ways > 1 and cfg.num_experts % ways == 0 else 1


def _q8_expert_ffn(params: dict, x, x_axis, act, gated: bool):
    """The vmapped int8 expert FFN body (shard-local or global): expert
    weights cross HBM as int8 tiles and dequantize in VMEM (ops/quant)."""
    from tony_tpu.ops.quant import q8_matmul

    up_mm = jax.vmap(q8_matmul, in_axes=(x_axis, 0, 0))
    up = up_mm(x, params["wi_q8"], params["wi_scale"])
    if gated:
        h = act(up_mm(x, params["wg_q8"], params["wg_scale"])) * up
    else:
        h = act(up)
    return jax.vmap(q8_matmul)(h, params["wo_q8"], params["wo_scale"])


def init_moe_params(key, cfg: MoEConfig, dtype=jnp.float32) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale_in = cfg.d_model ** -0.5
    params = {
        "router": jax.random.normal(k1, (cfg.d_model, cfg.num_experts),
                                    dtype) * scale_in,
        # leading expert dim -> sharded on the "expert" mesh axis
        "wi": jax.random.normal(k2, (cfg.num_experts, cfg.d_model, cfg.d_ff),
                                dtype) * scale_in,
        "wo": jax.random.normal(k3, (cfg.num_experts, cfg.d_ff, cfg.d_model),
                                dtype) * (cfg.d_ff ** -0.5),
    }
    if cfg.gated:
        params["wg"] = jax.random.normal(
            k4, (cfg.num_experts, cfg.d_model, cfg.d_ff), dtype) * scale_in
    return params


def moe_logical_axes() -> dict:
    """Logical sharding annotations (see parallel.sharding RULES['ep'])."""
    return {
        "router": (None, None),
        "wi": ("expert", None, "mlp"),
        "wg": ("expert", None, "mlp"),
        "wo": ("expert", "mlp", None),
    }


def _aux_loss(probs, gate_idx, e, k):
    """Switch/GShard load-balancing loss from routing decisions."""
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(gate_idx, e), axis=1), axis=0)
    return e * jnp.sum(me * ce) / k


def top_k_gating(logits: jnp.ndarray, k: int, capacity: int,
                 renormalize: bool = False):
    """Top-k token->expert routing with per-expert capacity.

    logits: [T, E]. Returns (dispatch [T, E, C] one-hot, combine [T, E, C]
    weights, aux_loss scalar). ``renormalize`` rescales the k selected
    gate weights to sum to 1 per token (Mixtral's convention).
    """
    t, e = logits.shape
    probs, gate_vals, gate_idx, aux_loss = _gates(logits, k, renormalize)

    dispatch = jnp.zeros((t, e, capacity), dtype=logits.dtype)
    combine = jnp.zeros((t, e, capacity), dtype=logits.dtype)
    # position of each token within its expert's buffer, per top-k choice
    taken = jnp.zeros((e,), dtype=jnp.int32)
    for choice in range(k):
        idx = gate_idx[:, choice]  # [T]
        one_hot = jax.nn.one_hot(idx, e, dtype=jnp.int32)  # [T, E]
        pos_within = jnp.cumsum(one_hot, axis=0) - 1 + taken[None, :]
        taken = taken + jnp.sum(one_hot, axis=0)
        pos = jnp.sum(pos_within * one_hot, axis=1)  # [T]
        keep = pos < capacity
        w = gate_vals[:, choice] * keep
        dispatch = dispatch + (
            jax.nn.one_hot(idx, e, dtype=logits.dtype)[:, :, None]
            * jax.nn.one_hot(jnp.where(keep, pos, 0), capacity,
                             dtype=logits.dtype)[:, None, :]
            * keep[:, None, None]
        )
        combine = combine + (
            jax.nn.one_hot(idx, e, dtype=logits.dtype)[:, :, None]
            * jax.nn.one_hot(jnp.where(keep, pos, 0), capacity,
                             dtype=logits.dtype)[:, None, :]
            * w[:, None, None]
        )
    return dispatch, combine, aux_loss


def _dropless_moe(params: dict, tokens: jnp.ndarray, logits: jnp.ndarray,
                  cfg: MoEConfig):
    """Exact dense evaluation: every expert runs on every token; outputs
    combine by (optionally renormalized) top-k gate weight. No capacity,
    no dropping — the checkpoint-parity/eval path (compute O(E) of the
    routed path, memory O(E*T*ff))."""
    t, e = logits.shape
    probs, gate_vals, gate_idx, aux = _gates(logits, cfg.top_k,
                                             cfg.renormalize_top_k)
    # [T, E] combine weights: selected experts carry their gate weight
    weights = jnp.sum(
        jax.nn.one_hot(gate_idx, e, dtype=gate_vals.dtype)
        * gate_vals[..., None], axis=1)
    expert_out = _expert_ffn(params, tokens, cfg,
                             "td,edf->etf", "etf,efd->etd")
    out = jnp.einsum("etd,te->td", expert_out, weights)
    return out, aux


def moe_layer(params: dict, x: jnp.ndarray, cfg: MoEConfig):
    """x: [B, L, D] -> ([B, L, D], aux_loss).

    Token exchange happens in the two einsums against dispatch/combine;
    with wi/wo sharded on the expert axis XLA emits all-to-all.
    """
    b, l, d = x.shape
    tokens = x.reshape(b * l, d)
    logits = tokens @ params["router"]
    if cfg.dropless:
        out, aux = _dropless_moe(params, tokens, logits, cfg)
        return out.reshape(b, l, d), aux
    capacity = max(1, int(cfg.capacity_factor * (b * l) / cfg.num_experts))
    dispatch, combine, aux = top_k_gating(logits, cfg.top_k, capacity,
                                          renormalize=cfg.renormalize_top_k)
    # [E, C, D]: gather each expert's tokens (all-to-all under pjit)
    expert_in = jnp.einsum("td,tec->ecd", tokens, dispatch)
    expert_out = _expert_ffn(params, expert_in, cfg,
                             "ecd,edf->ecf", "ecf,efd->ecd")
    out = jnp.einsum("ecd,tec->td", expert_out, combine)
    return out.reshape(b, l, d), aux
