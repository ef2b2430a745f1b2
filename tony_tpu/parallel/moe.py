"""Mixture-of-Experts with expert parallelism.

Two layers live here. ``moe_layer`` (below) is the Switch/Mixtral-style
one: softmax gates, every expert on the chip or sharded by GSPMD,
capacity-limited dispatch einsums (training) or a dense evaluation of
every expert on every token (``dropless``, checkpoint parity).
``routed_share`` (at the end) is the SERVED one: a chip is told which
experts of a wider router it holds, routes over all of them, and
computes its own experts' part of the result with work that follows the
routing (tokens sorted by expert, a grouped product) — one chip's share
of wide expert parallelism, run without its exchange.

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


@dataclass(frozen=True)
class RoutedConfig:
    """A routed expert layer as ONE chip of a wider deployment sees it
    (``routed_share``): the router scores all ``n_routed`` experts and
    picks ``top_k`` a token; this chip holds the ``held[1]`` experts
    from index ``held[0]`` on and adds what they give, times
    ``scaling``. ``shared_d_ff`` > 0 adds a shared SwiGLU expert of that
    width, computed whole on every chip; the first ``first_dense``
    layers of the model keep a dense MLP. ``selection_bias`` adds a
    float32 leaf ``expert_bias`` [n_routed] that only the CHOICE of
    experts sees (``sigmoid_top_k``); ``renorm_eps`` is what the chosen
    weights' sum is guarded with. Hashable: it rides
    ``TransformerConfig``, a static argument of jitted code."""

    n_routed: int
    top_k: int
    d_ff: int
    held: tuple = (0, 0)
    scaling: float = 1.0
    shared_d_ff: int = 0
    first_dense: int = 0
    selection_bias: bool = False
    renorm_eps: float = 1e-20

    def __post_init__(self):
        first, count = self.held
        if not (0 <= first and count > 0
                and first + count <= self.n_routed):
            raise ValueError(f"held={self.held} is no range of the "
                             f"{self.n_routed} routed experts")
        if not 0 < self.top_k <= self.n_routed:
            raise ValueError(f"top_k={self.top_k} of {self.n_routed}")


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


# ------------------------------------------------ one chip's routed share

def sigmoid_top_k(logits, k: int, scaling: float = 1.0, bias=None,
                  eps: float = 1e-20):
    """DeepSeek-V3-style routing without groups: ``p =
    sigmoid(logits)`` (float32), the ``k`` experts with the largest ``p
    + bias`` (``bias`` [E] float32 or None: it moves the CHOICE only),
    their weights ``p`` renormalised to sum to 1 (the sum guarded by
    ``eps``) and multiplied by ``scaling``. Returns (weights [T, k]
    float32, expert indices [T, k])."""
    p = jax.nn.sigmoid(logits.astype(jnp.float32))
    if bias is None:
        top, idx = jax.lax.top_k(p, k)
    else:
        _, idx = jax.lax.top_k(p + bias.astype(jnp.float32), k)
        top = jnp.take_along_axis(p, idx, axis=-1)
    return top / (jnp.sum(top, -1, keepdims=True) + eps) * scaling, idx


N_COUNTS = 4  # what ``routed_share`` counts beside its result


def _held_here(idx, first: int, n_held: int, live):
    """(the held experts' own index of each chosen one, whether that
    (token, choice) pair is computed here): its expert is one of the
    ``n_held`` from ``first`` on and its token is ``live``."""
    local = idx - first
    here = (local >= 0) & (local < n_held)
    if live is not None:
        here = here & live[:, None]
    return local, here


def grouped_experts(x, idx, w, wg, wi, wo, first: int, live=None):
    """What the experts held here add: ``sum_j w[t, j] *
    expert_{idx[t, j]}(x[t])`` over the pairs whose expert is one of the
    ``wg.shape[0]`` held from index ``first`` on (and whose token is
    ``live``). The (token, choice) pairs are sorted by expert, held
    ones first, and each expert's run of rows goes through its own
    weights in ONE grouped product (``jax.lax.ragged_dot``; a Mosaic
    kernel on the TPU that visits only the row tiles a group covers):
    FLOPs and weight bytes follow the routing — an expert no pair chose
    is not read, a pair sent elsewhere costs a gathered row and no
    product. ``x`` [T, d]; ``idx``/``w`` [T, k]; ``wg``/``wi`` [H, d,
    f], ``wo`` [H, f, d]. Returns (y [T, d] float32, group sizes [H])."""
    t, k = idx.shape
    n_held = wg.shape[0]
    local, here = _held_here(idx, first, n_held, live)
    group = jnp.where(here, local, n_held).reshape(-1)     # absent: last
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((n_held + 1,), jnp.int32).at[group].add(1)[:n_held]
    rows = x[order // k]                                   # [T k, d]
    dot = lambda a, b: jax.lax.ragged_dot(  # noqa: E731
        a, b, sizes, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(dot(rows, wg)) * dot(rows, wi)).astype(x.dtype)
    ys = dot(h, wo)
    # rows past the held pairs belong to no group: whatever the kernel
    # left there must not reach the sum (0 * NaN)
    ys = jnp.where((jnp.arange(t * k) < jnp.sum(sizes))[:, None], ys, 0.0)
    back = jnp.argsort(order)                              # undo the sort
    ys = ys[back].reshape(t, k, -1)
    return jnp.sum(ys * jnp.where(here, w, 0.0)[..., None], axis=1), sizes


def dense_experts(x, idx, w, wg, wi, wo, first: int, live=None):
    """The same sum by evaluating EVERY held expert on every token and
    weighting: what ``grouped_experts`` is tested against, and nothing a
    served path calls. Returns (y [T, d] float32, group sizes [H])."""
    n_held = wg.shape[0]
    local, here = _held_here(idx, first, n_held, live)
    hot = jax.nn.one_hot(jnp.where(here, local, n_held), n_held + 1,
                         dtype=jnp.float32)[..., :n_held]   # [T, k, H]
    up = lambda w_: jnp.einsum(  # noqa: E731
        "td,edf->etf", x, w_, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(up(wg)) * up(wi)).astype(x.dtype)
    per = jnp.einsum("etf,efd->etd", h, wo,
                     preferred_element_type=jnp.float32)
    weight = jnp.einsum("tkh,tk->th", hot, w)
    return jnp.einsum("etd,te->td", per, weight), \
        jnp.sum(hot, axis=(0, 1)).astype(jnp.int32)


def routed_share(x, router, wg, wi, wo, cfg: RoutedConfig, live=None,
                 experts=grouped_experts, bias=None):
    """One chip's share of a routed expert layer on tokens ``x`` [T, d]:
    route over ALL ``cfg.n_routed`` experts (``router`` [d, n_routed],
    scores in float32), add what the experts held here give, leave out
    what the absent ones would (no code stands in for them or for the
    exchange). ``live`` [T] masks padding and empty slots out of the
    routing; ``bias`` [n_routed] is the selection bias
    (``cfg.selection_bias``). Returns ``(y [T, d] float32, counts
    [N_COUNTS] int32)``: the
    token-expert pairs chosen by live tokens, those of them whose
    expert is held here, the most pairs one held expert took, and how
    many held experts took at least one (whose weights were read)."""
    with jax.named_scope("moe.route"):
        logits = jnp.einsum("td,de->te", x, router,
                            preferred_element_type=jnp.float32)
        w, idx = sigmoid_top_k(logits, cfg.top_k, cfg.scaling, bias,
                               cfg.renorm_eps)
    with jax.named_scope("moe.experts"):
        y, sizes = experts(x, idx, w, wg, wi, wo, cfg.held[0], live)
    n_live = x.shape[0] if live is None else jnp.sum(live)
    counts = jnp.stack([n_live * cfg.top_k, jnp.sum(sizes), jnp.max(sizes),
                        jnp.sum(sizes > 0)]).astype(jnp.int32)
    return y, counts
