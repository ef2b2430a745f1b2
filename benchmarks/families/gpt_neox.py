"""GPT-NeoX as Pythia publishes it (``model_type: gpt_neox``).

``h += Attn(LN1(h)) + MLP(LN2(h))`` (parallel residual): LayerNorm with
bias, biases on every dense, rotary over the first ``rotary_pct`` of each
head, erf GELU, untied head. Its attention is ``mistral``'s with as many
K/V heads as query heads, so that function and the counts (which read
only ``heads``, ``kv_heads``, ``head_dim`` and the leaf lists) are that
file's. The contract of a family file is in ``benchmarks/README.md``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

from benchmarks.families.mistral import (  # noqa: F401 — the family's own
    attention, decode_step_bytes, decode_step_flops, flash_flops_per_step,
    kv_bytes_per_token, serve_token_flops, train_flops_per_token)


@dataclass(frozen=True)
class Arch:
    family: str
    d: int               # hidden_size
    layers: int
    vocab: int
    max_len: int
    heads: int
    kv_heads: int        # = heads
    head_dim: int
    ff: int              # intermediate_size
    eps: float
    theta: float
    rotary_dims: int     # leading dims of each head that rotate
    parallel_residual: bool
    act: str             # "gelu" (erf) | "silu"


def arch(cfg: dict) -> Arch:
    heads = cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    return Arch(cfg["model_type"], cfg["hidden_size"],
                cfg["num_hidden_layers"], cfg["vocab_size"],
                cfg["max_position_embeddings"], heads, heads, head_dim,
                cfg["intermediate_size"], cfg["layer_norm_eps"],
                float(cfg["rotary_emb_base"]),
                int(head_dim * cfg["rotary_pct"]),
                bool(cfg["use_parallel_residual"]), cfg["hidden_act"])


# ------------------------------------------------------------ the leaves
# kind: "w" matrix, "s" norm scale, "b" bias

def layer_leaves(a: Arch, layer) -> list[tuple[str, tuple, str]]:
    return [("ln1.scale", (a.d,), "s"), ("ln2.scale", (a.d,), "s"),
            ("q", (a.d, a.heads, a.head_dim), "w"),
            ("k", (a.d, a.heads, a.head_dim), "w"),
            ("v", (a.d, a.heads, a.head_dim), "w"),
            ("o", (a.heads, a.head_dim, a.d), "w"),
            ("wi", (a.d, a.ff), "w"), ("wo", (a.ff, a.d), "w"),
            ("ln1.bias", (a.d,), "b"), ("ln2.bias", (a.d,), "b"),
            ("q.bias", (a.heads, a.head_dim), "b"),
            ("k.bias", (a.heads, a.head_dim), "b"),
            ("v.bias", (a.heads, a.head_dim), "b"),
            ("o.bias", (a.d,), "b"), ("wi.bias", (a.ff,), "b"),
            ("wo.bias", (a.d,), "b")]


def global_leaves(a: Arch) -> list[tuple[str, tuple, str]]:
    return [("embed", (a.vocab, a.d), "w"), ("head", (a.vocab, a.d), "w"),
            ("ln_f.scale", (a.d,), "s"), ("ln_f.bias", (a.d,), "b")]


# --------------------------------------------------- the plain reference

def norm(a: Arch, x, p: dict, name: str):
    import jax.numpy as jnp

    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + a.eps) * p[name + ".scale"] \
        + p[name + ".bias"]


def rope(a: Arch, x):
    """x [L, heads, head_dim]; rotate the first ``rotary_dims`` of each
    head: (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin)."""
    import jax.numpy as jnp

    r = a.rotary_dims
    half = r // 2
    inv = 1.0 / (a.theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:r], x[..., r:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def block(a: Arch, p: dict, x, quant: str = ""):
    """One decoder block on one row x [L, D]."""
    import jax

    from benchmarks.harness.reference import dense

    d = functools.partial(dense, quant=quant)
    h = norm(a, x, p, "ln1")
    q = rope(a, d(h, p["q"], bias=p["q.bias"]))
    k = rope(a, d(h, p["k"], bias=p["k.bias"]))
    v = d(h, p["v"], bias=p["v.bias"])
    att = d(attention(a, q, k, v), p["o"], 2, bias=p["o.bias"])
    if not a.parallel_residual:
        x = x + att
    h = norm(a, x, p, "ln2")
    act = {"gelu": functools.partial(jax.nn.gelu, approximate=False),
           "silu": jax.nn.silu}[a.act]
    m = d(act(d(h, p["wi"], bias=p["wi.bias"])), p["wo"], bias=p["wo.bias"])
    return x + att + m if a.parallel_residual else x + m


def logits(a: Arch, g: dict, h, quant: str = ""):
    """Final norm and head over hidden rows h [..., D]."""
    from benchmarks.harness.reference import dense

    return dense(norm(a, h, g, "ln_f"), g["head"].T, quant=quant)


# ------------------------------------------------ the system under test

def program_config(a: Arch, dtype, **extra):
    """``tony_tpu``'s config, as ``models/hf.py`` ``neox_config`` maps
    the family."""
    from tony_tpu.models import TransformerConfig

    kw = dict(vocab_size=a.vocab, d_model=a.d, n_heads=a.heads,
              n_kv_heads=a.heads, n_layers=a.layers, d_ff=a.ff,
              max_seq_len=a.max_len, dtype=dtype, positional="rope",
              norm="layer", use_bias=True, activation=a.act,
              norm_eps=a.eps, rope_theta=a.theta, gated_mlp=False,
              parallel_residual=a.parallel_residual,
              rotary_dims=0 if a.rotary_dims >= a.head_dim
              else a.rotary_dims,
              explicit_head_dim=0 if a.head_dim * a.heads == a.d
              else a.head_dim,
              tied_embeddings=False, scan_layers=False)
    kw.update(extra)
    return TransformerConfig(**kw)


def program_tree(a: Arch, w: dict) -> dict:
    """``weights.all_weights`` (or a tree of the same shape holding leaf
    NAMES) laid out as ``Transformer``'s ``params``."""
    def dense(lw, n):
        return {"kernel": lw[n], "bias": lw[n + ".bias"]}

    def norm(lw, n):
        return {"scale": lw[n + ".scale"], "bias": lw[n + ".bias"]}

    tree = {"embedding": w["g"]["embed"], "lm_head": w["g"]["head"],
            "ln_f": norm(w["g"], "ln_f")}
    for i, lw in enumerate(w["layers"]):
        tree[f"block_{i}"] = {
            "ln1": norm(lw, "ln1"), "ln2": norm(lw, "ln2"),
            "attn": {n: dense(lw, n) for n in "qkvo"},
            "mlp": {n: dense(lw, n) for n in ("wi", "wo")}}
    return tree
