"""A.X-K1 (``model_type: axk1``, skt/A.X-K1 ``config.json``): DeepSeek-V3's
block. Multi-head latent attention in every layer; the first
``first_k_dense_replace`` layers keep a dense SwiGLU, every later one
routes each token to ``num_experts_per_tok`` of ``n_routed_experts``
experts and adds one shared expert. The contract of a family file is in
``benchmarks/README.md``; what a reader of THIS family needs is here.

**The layer**, with ``h = RMSNorm(x)`` (eps ``rms_norm_eps``):

    c_q = RMSNorm(h W_qa)                      q_lora_rank
    [q_n; q_r] = c_q W_qb                      per head: nope + rope
    [c_kv; k_r] = h W_kva                      kv_lora_rank + rope
    c_kv = RMSNorm(c_kv)
    [k_n; v] = c_kv W_kvb                      per head: nope + v_head_dim
    score = (q_n . k_n + RoPE(q_r) . RoPE(k_r)) * s     ONE k_r for all heads
    s = (nope + rope)^-0.5 * m^2,  m = 0.1 * mscale_all_dim * ln(factor) + 1
    x += (causal softmax(score) . v) W_o
    h = RMSNorm(x)
    dense layer:   x += W_o'(silu(W_g h) * W_i h)               width intermediate_size
    routed layer:  p = sigmoid(h W_r)                           all n_routed experts, float32
                   the top-k of p;  w = p_top / (sum p_top + 1e-20) * routed_scaling_factor
                   x += sum over the chosen experts HELD HERE of w_e * expert_e(h)
                        + shared(h)                both SwiGLU, width moe_intermediate_size

RoPE is YaRN as ``transformers`` computes it (``_compute_yarn_parameters``):
pair ``i`` of ``rope/2`` keeps its frequency ``theta^(-2i/rope)`` where its
wave turns more than ``beta_fast`` times over
``original_max_position_embeddings``, is divided by ``factor`` where it
turns less than ``beta_slow`` times, with a linear ramp over ``i`` between;
the cos/sin factor ``mscale(factor, mscale) / mscale(factor,
mscale_all_dim)`` is 1 for the published values and ``arch`` refuses a
configuration where it is not.

**Assumed** (the configuration file lists them under ``assumed``):
``topk_method: "none"`` is read as plain top-k over all sigmoid scores
(no group limit, ``n_group``/``topk_group`` unused, no selection bias);
the rotary pairing is the half-split one (dimension ``i`` pairs with ``i
+ rope/2``), the same in program and reference, where the published
checkpoint interleaves (a fixed permutation of ``W_qb``'s and ``W_kva``'s
rotary columns, which seeded weights do not see).

**One chip's share** (README, "A family that holds one chip's SHARE"):
``n_routed_experts`` in the configuration is what is HELD here;
``expert_parallel`` states over how many chips a layer's experts are
divided, which of them this is, and the router's width (``chips *
n_routed_experts``, the published count). The router leaf keeps that
width, the expert leaves hold only the share, and ``block`` adds what the
held experts give: a token whose chosen experts all live elsewhere gets
the shared expert alone. ``vocab`` is the slice.

**The counts** are of what the algorithm needs. A cached position costs
``kv_lora_rank + rope`` values a layer (the program stores exactly that).
A token meets every attention and shared weight, the router, and IN
EXPECTATION ``top_k * held / n_routed`` held experts a routed layer (0.5
at the published sizes; even routing is the expectation under seeded
weights, and the program's counters say what a run really sent). One
decode step reads the non-expert weights once, each held expert at most
once and only if a live row chose it, and the live positions' latents;
its attention is counted in the ABSORBED form (``q_n W_kvb[K]`` scored
against the latent, the softmax summed over the latent): ``2 * heads *
(2 kv_lora_rank + rope)`` FLOPs a cached position a layer, the cheapest
way to attend from a latent cache without re-materialising every cached
key. A prompt position is counted in the materialised form (``2 * heads *
(nope + rope + v)``), which is cheaper where keys and values are made
once for the whole window.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

from benchmarks.harness import work as K


@dataclass(frozen=True)
class Arch:
    family: str
    d: int                # hidden_size
    layers: int
    vocab: int            # the slice held here
    max_len: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    ff: int               # intermediate_size (the dense layers)
    first_dense: int      # first_k_dense_replace
    expert_ff: int        # moe_intermediate_size
    n_routed: int         # the router's width (all chips' experts)
    top_k: int
    held: int             # experts held here
    held_first: int       # index of the first of them
    shared_ff: int        # n_shared_experts * moe_intermediate_size
    scaling: float        # routed_scaling_factor
    eps: float
    theta: float
    yarn_factor: float
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_original: int
    softmax_mult: float   # m^2


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def arch(cfg: dict) -> Arch:
    if cfg["topk_method"] != "none" or cfg["scoring_func"] != "sigmoid" \
            or not cfg["norm_topk_prob"]:
        raise ValueError("the reference routes by plain top-k over sigmoid "
                         "scores with renormalised weights")
    if cfg["moe_layer_freq"] != 1 or cfg["attention_bias"] \
            or cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]:
        raise ValueError("not in the reference: moe_layer_freq != 1, "
                         "attention_bias, another activation, a tied head")
    rs = cfg["rope_scaling"]
    if rs["type"] != "yarn" or abs(_mscale(rs["factor"], rs["mscale"])
                                   / _mscale(rs["factor"],
                                             rs["mscale_all_dim"]) - 1) > 1e-9:
        raise ValueError("the reference has YaRN with a cos/sin factor of 1")
    ep = cfg["expert_parallel"]
    held = cfg["n_routed_experts"]
    if ep["chips"] * held != ep["router_experts"] \
            or not 0 <= ep["rank"] < ep["chips"]:
        raise ValueError(
            f"expert_parallel {ep}: chips x n_routed_experts ({held} held "
            "here) must be the router's width, rank one of the chips")
    return Arch(
        cfg["model_type"], cfg["hidden_size"], cfg["num_hidden_layers"],
        cfg["vocab_size"], cfg["max_position_embeddings"],
        cfg["num_attention_heads"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
        cfg["intermediate_size"], cfg["first_k_dense_replace"],
        cfg["moe_intermediate_size"], ep["router_experts"],
        cfg["num_experts_per_tok"], held, ep["rank"] * held,
        cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        float(cfg["routed_scaling_factor"]), cfg["rms_norm_eps"],
        float(cfg["rope_theta"]), float(rs["factor"]),
        float(rs["beta_fast"]), float(rs["beta_slow"]),
        rs["original_max_position_embeddings"],
        _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2)


# ------------------------------------------------------------ the leaves
# kind: "w" matrix, "s" norm scale. Expert leaves are named e*, the
# shared expert's s*, so that no name is shared between the two kinds'
# MLPs with another shape.

def layer_kind(a: Arch, layer: int) -> str:
    return "dense" if layer < a.first_dense else "routed"


def _attention_leaves(a: Arch) -> list[tuple[str, tuple, str]]:
    return [("ln1.scale", (a.d,), "s"), ("ln2.scale", (a.d,), "s"),
            ("q_a", (a.d, a.q_rank), "w"), ("q_norm.scale", (a.q_rank,), "s"),
            ("q_b", (a.q_rank, a.heads, a.nope + a.rope), "w"),
            ("kv_a", (a.d, a.kv_rank + a.rope), "w"),
            ("kv_norm.scale", (a.kv_rank,), "s"),
            ("kv_b", (a.kv_rank, a.heads, a.nope + a.v_dim), "w"),
            ("o", (a.heads, a.v_dim, a.d), "w")]


def layer_leaves(a: Arch, layer) -> list[tuple[str, tuple, str]]:
    if layer_kind(a, layer) == "dense":
        return _attention_leaves(a) + [
            ("wg", (a.d, a.ff), "w"), ("wi", (a.d, a.ff), "w"),
            ("wo", (a.ff, a.d), "w")]
    return _attention_leaves(a) + [
        ("router", (a.d, a.n_routed), "w"),
        ("eg", (a.held, a.d, a.expert_ff), "w"),
        ("ei", (a.held, a.d, a.expert_ff), "w"),
        ("eo", (a.held, a.expert_ff, a.d), "w"),
        ("sg", (a.d, a.shared_ff), "w"), ("si", (a.d, a.shared_ff), "w"),
        ("so", (a.shared_ff, a.d), "w")]


def global_leaves(a: Arch) -> list[tuple[str, tuple, str]]:
    return [("embed", (a.vocab, a.d), "w"), ("head", (a.vocab, a.d), "w"),
            ("ln_f.scale", (a.d,), "s")]


# --------------------------------------------------- the plain reference

def rms(a: Arch, x, scale):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + a.eps) * scale


def yarn_inv_freq(a: Arch):
    """The ``rope/2`` rotary frequencies (docstring above)."""
    import jax.numpy as jnp

    half = a.rope // 2
    base = a.theta ** (jnp.arange(half, dtype=jnp.float32) / half)

    def pair(turns):     # the pair index whose wave turns ``turns`` times
        return a.rope * math.log(a.yarn_original / (turns * 2 * math.pi)) \
            / (2 * math.log(a.theta))

    low = max(math.floor(pair(a.yarn_beta_fast)), 0)
    high = min(math.ceil(pair(a.yarn_beta_slow)), a.rope - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return 1.0 / (a.yarn_factor * base) * (1.0 - keep) + 1.0 / base * keep


def rope(a: Arch, x):
    """x [L, heads, rope]: (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin)
    with x1, x2 the two halves."""
    import jax.numpy as jnp

    half = a.rope // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * yarn_inv_freq(a)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(a: Arch, p: dict, h, d):
    """The latent attention sublayer of one row h [L, D], keys and
    values materialised for every position."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    n = h.shape[0]
    q = d(rms(a, d(h, p["q_a"]), p["q_norm.scale"]), p["q_b"])
    q_n, q_r = q[..., :a.nope], rope(a, q[..., a.nope:])
    kv = d(h, p["kv_a"])
    c_kv = rms(a, kv[:, :a.kv_rank], p["kv_norm.scale"])
    k_r = rope(a, kv[:, None, a.kv_rank:])[:, 0]
    kv = d(c_kv, p["kv_b"])
    k_n, v = kv[..., :a.nope], kv[..., a.nope:]
    s = (jnp.einsum("qhd,khd->hqk", q_n, k_n, precision=hi)
         + jnp.einsum("qhd,kd->hqk", q_r, k_r, precision=hi)) \
        * ((a.nope + a.rope) ** -0.5 * a.softmax_mult)
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                     precision=hi)
    return d(out, p["o"], 2)


def swiglu(d, h, wg, wi, wo):
    import jax

    return d(jax.nn.silu(d(h, wg)) * d(h, wi), wo)


def route(a: Arch, p: dict, h, d):
    """[L, held]: the weight each token gives each expert HELD HERE (0
    where it is not among the token's top-k of all ``n_routed``)."""
    import jax
    import jax.numpy as jnp

    prob = jax.nn.sigmoid(d(h, p["router"]))
    top, idx = jax.lax.top_k(prob, a.top_k)
    w = top / (jnp.sum(top, -1, keepdims=True) + 1e-20) * a.scaling
    here = idx[..., None] == a.held_first + jnp.arange(a.held)  # [L, k, held]
    return jnp.sum(jnp.where(here, w[..., None], 0.0), axis=1)


def block(a: Arch, p: dict, x, quant: str = "", kind: str = "routed"):
    """One layer of ``kind`` on one row x [L, D]."""
    from benchmarks.harness.reference import dense

    d = functools.partial(dense, quant=quant)
    x = x + attention(a, p, rms(a, x, p["ln1.scale"]), d)
    h = rms(a, x, p["ln2.scale"])
    if kind == "dense":
        return x + swiglu(d, h, p["wg"], p["wi"], p["wo"])
    weight = route(a, p, h, d)
    for e in range(a.held):     # every held expert on every token
        x = x + weight[:, e:e + 1] * swiglu(d, h, p["eg"][e], p["ei"][e],
                                            p["eo"][e])
    return x + swiglu(d, h, p["sg"], p["si"], p["so"])


def logits(a: Arch, g: dict, h, quant: str = ""):
    """Final norm and head over hidden rows h [..., D]."""
    from benchmarks.harness.reference import dense

    return dense(rms(a, h, g["ln_f.scale"]), g["head"].T, quant=quant)


# ------------------------------------------------ the system under test

def program_config(a: Arch, dtype, **extra):
    from tony_tpu.models import TransformerConfig
    from tony_tpu.models.transformer import LatentConfig, RopeScaling
    from tony_tpu.parallel.moe import RoutedConfig

    kw = dict(vocab_size=a.vocab, d_model=a.d, n_heads=a.heads,
              n_layers=a.layers, d_ff=a.ff, max_seq_len=a.max_len,
              dtype=dtype, positional="rope", norm="rms", use_bias=False,
              activation="silu", norm_eps=a.eps, rope_theta=a.theta,
              rope_scaling=RopeScaling(
                  kind="yarn", factor=a.yarn_factor,
                  original_max_len=a.yarn_original,
                  beta_fast=a.yarn_beta_fast, beta_slow=a.yarn_beta_slow),
              gated_mlp=True, tied_embeddings=False, scan_layers=False,
              latent=LatentConfig(
                  q_rank=a.q_rank, kv_rank=a.kv_rank, nope_dim=a.nope,
                  rope_dim=a.rope, v_dim=a.v_dim, scale_mult=a.softmax_mult),
              routed=RoutedConfig(
                  n_routed=a.n_routed, top_k=a.top_k, d_ff=a.expert_ff,
                  held=(a.held_first, a.held), scaling=a.scaling,
                  shared_d_ff=a.shared_ff, first_dense=a.first_dense))
    kw.update(extra)
    return TransformerConfig(**kw)


def program_tree(a: Arch, w: dict) -> dict:
    """``weights.all_weights`` (or leaf NAMES in their place) laid out as
    ``Transformer``'s ``params``: every leaf goes over as it is."""
    g = w["g"]
    tree = {"embedding": g["embed"], "lm_head": g["head"],
            "ln_f": {"scale": g["ln_f.scale"]}}
    kernel = lambda lw, names: {n: {"kernel": lw[n]}  # noqa: E731
                                for n in names}
    for i, lw in enumerate(w["layers"]):
        blk = {"ln1": {"scale": lw["ln1.scale"]},
               "ln2": {"scale": lw["ln2.scale"]},
               "attn": {**kernel(lw, ("q_a", "q_b", "kv_a", "o")),
                        "q_norm": {"scale": lw["q_norm.scale"]},
                        "kv_norm": {"scale": lw["kv_norm.scale"]},
                        "kv_b": lw["kv_b"]}}
        if layer_kind(a, i) == "dense":
            blk["mlp"] = kernel(lw, ("wg", "wi", "wo"))
        else:
            blk["moe"] = {"router": lw["router"], "wg": lw["eg"],
                          "wi": lw["ei"], "wo": lw["eo"],
                          "shared": {"wg": {"kernel": lw["sg"]},
                                     "wi": {"kernel": lw["si"]},
                                     "wo": {"kernel": lw["so"]}}}
        tree[f"block_{i}"] = blk
    return tree


# ------------------------------------------------------------ the counts
# of what the algorithm needs (the docstring above says which form)

def _routed_layers(a: Arch) -> int:
    return a.layers - a.first_dense


def _expert_params(a: Arch) -> int:
    return 3 * a.d * a.expert_ff


def held_per_token(a: Arch) -> float:
    """Held experts a token is sent to in one routed layer, in
    expectation (even routing)."""
    return a.top_k * a.held / a.n_routed


def _token_matmul_params(a: Arch) -> float:
    """Matmul weights one token meets across the blocks: all but the
    held experts it is not sent to."""
    return K.matmul_params(a) - _routed_layers(a) * _expert_params(a) * (
        a.held - held_per_token(a))


def absorbed_position_flops(a: Arch) -> int:
    """Score and weighted sum of ONE query over ONE cached position in
    one layer, absorbed: every head over the cache's width, then over
    the latent."""
    return 2 * a.heads * (2 * a.kv_rank + a.rope)


def kv_bytes_per_token(a: Arch, itemsize: int = 2) -> int:
    """The normed latent and the rotated shared key of every layer."""
    return (a.kv_rank + a.rope) * a.layers * itemsize


def serve_token_flops(a: Arch, position: int, sampled: bool) -> float:
    """Model FLOPs of one position of a served request. A decode step
    (``sampled``) attends absorbed, a prompt position materialised; a
    prompt's last position is both and is counted as a step."""
    per_position = absorbed_position_flops(a) if sampled \
        else 2 * a.heads * (a.nope + a.rope + a.v_dim)
    return 2 * _token_matmul_params(a) \
        + per_position * a.layers * (position + 1) \
        + (2 * a.vocab * a.d if sampled else 0)


def experts_hit(a: Arch, batch: float) -> float:
    """Held experts of ONE routed layer that at least one of ``batch``
    live rows chose, in expectation."""
    return a.held * (1.0 - (1.0 - a.top_k / a.n_routed) ** max(batch, 0.0))


def decode_step_bytes(a: Arch, live_tokens: float, itemsize: int = 2) -> float:
    """Least bytes one decode step reads: the non-expert weights, the
    final norm and the head once, each held expert once IF a live row
    chose it, and the latents of the ``live_tokens`` positions. The
    signature has no batch, and a count errs low: the rows are taken to
    be as few as ``live_tokens`` allow (each as long as ``max_len``)."""
    experts = _routed_layers(a) * _expert_params(a)
    weights = K.params(a) - experts * a.held + a.vocab * a.d + a.d \
        + experts * experts_hit(a, live_tokens / a.max_len)
    return weights * itemsize + live_tokens * kv_bytes_per_token(a, itemsize)


def decode_step_flops(a: Arch, batch: float, live_tokens: float) -> float:
    return batch * (2 * _token_matmul_params(a) + 2 * a.vocab * a.d) \
        + absorbed_position_flops(a) * a.layers * live_tokens


def mla_absorb_step(a: Arch, batch: float, live_tokens: float,
                    counters: dict | None = None) -> tuple[float, float]:
    """(FLOPs, bytes) of the absorbed attention of one decode step,
    every layer: the two absorbing products of each live row (``q_n
    W_kvb[K]``, ``o_latent W_kvb[V]``, whose weights are read once), and
    score and sum over each live position's latent, read once."""
    absorb = 2 * a.heads * a.kv_rank * (a.nope + a.v_dim)
    flops = a.layers * (batch * absorb
                        + absorbed_position_flops(a) * live_tokens)
    nbytes = live_tokens * kv_bytes_per_token(a) \
        + a.layers * a.kv_rank * a.heads * (a.nope + a.v_dim) * 2
    return flops, nbytes


def moe_experts_step(a: Arch, batch: float, live_tokens: float,
                     counters: dict | None = None) -> tuple[float, float]:
    """(FLOPs, bytes) of the held experts' grouped products in one
    decode step, every routed layer: 2 FLOPs a weight for each
    token-expert pair, and each expert's weights once if it was hit.
    From the program's counters where the run has them (``counters``:
    the window's ``moe_tokens_held`` and ``moe_experts_hit`` a decode
    step), else the expectation under even routing."""
    if counters:
        pairs, hit = counters["pairs_per_step"], counters["hit_per_step"]
    else:
        pairs = _routed_layers(a) * batch * held_per_token(a)
        hit = _routed_layers(a) * experts_hit(a, batch)
    return 2 * _expert_params(a) * pairs, 2 * _expert_params(a) * hit
