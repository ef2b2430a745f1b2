"""The Llama family as Mistral-7B publishes it (``model_type: mistral``).

``h += Attn(RMSNorm(h)); h += W_o(silu(W_g x) * W_i x)``: grouped-query
attention, rotary embedding over the whole head (half-split
``rotate_half`` convention), RMSNorm, SwiGLU, no biases, untied head.
The contract of a family file is in ``benchmarks/README.md``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

from benchmarks.harness import work as K


@dataclass(frozen=True)
class Arch:
    family: str
    d: int               # hidden_size
    layers: int
    vocab: int
    max_len: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int              # intermediate_size
    eps: float
    theta: float
    act: str


def arch(cfg: dict) -> Arch:
    if cfg.get("sliding_window"):
        raise ValueError("sliding_window is not in the reference")
    heads = cfg["num_attention_heads"]
    return Arch(cfg["model_type"], cfg["hidden_size"],
                cfg["num_hidden_layers"], cfg["vocab_size"],
                cfg["max_position_embeddings"], heads,
                cfg["num_key_value_heads"],
                cfg.get("head_dim") or cfg["hidden_size"] // heads,
                cfg["intermediate_size"], cfg["rms_norm_eps"],
                float(cfg["rope_theta"]), cfg["hidden_act"])


# ------------------------------------------------------------ the leaves
# kind: "w" matrix, "s" norm scale, "b" bias

def layer_leaves(a: Arch, layer) -> list[tuple[str, tuple, str]]:
    return [("ln1.scale", (a.d,), "s"), ("ln2.scale", (a.d,), "s"),
            ("q", (a.d, a.heads, a.head_dim), "w"),
            ("k", (a.d, a.kv_heads, a.head_dim), "w"),
            ("v", (a.d, a.kv_heads, a.head_dim), "w"),
            ("o", (a.heads, a.head_dim, a.d), "w"),
            ("wi", (a.d, a.ff), "w"), ("wo", (a.ff, a.d), "w"),
            ("wg", (a.d, a.ff), "w")]


def global_leaves(a: Arch) -> list[tuple[str, tuple, str]]:
    return [("embed", (a.vocab, a.d), "w"), ("head", (a.vocab, a.d), "w"),
            ("ln_f.scale", (a.d,), "s")]


# --------------------------------------------------- the plain reference

def norm(a: Arch, x, p: dict, name: str):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + a.eps) \
        * p[name + ".scale"]


def rope(a: Arch, x):
    """x [L, heads, head_dim]; rotate each head: (x1, x2) -> (x1 cos -
    x2 sin, x2 cos + x1 sin)."""
    import jax.numpy as jnp

    half = a.head_dim // 2
    inv = 1.0 / (a.theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(a: Arch, q, k, v):
    """Causal softmax attention of one row; q [L, H, hd], k/v [L, KVH,
    hd]; query head h reads kv head h // (H / KVH)."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    n = q.shape[0]
    g = a.heads // a.kv_heads
    q = q.reshape(n, a.kv_heads, g, a.head_dim)
    s = jnp.einsum("qhgd,khd->hgqk", q, k, precision=hi) \
        / jnp.sqrt(jnp.float32(a.head_dim))
    causal = jnp.tril(jnp.ones((n, n), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("hgqk,khd->qhgd", p, v, precision=hi)
    return out.reshape(n, a.heads, a.head_dim)


def block(a: Arch, p: dict, x, quant: str = ""):
    """One decoder block on one row x [L, D]."""
    import jax

    from benchmarks.harness.reference import dense

    d = functools.partial(dense, quant=quant)
    h = norm(a, x, p, "ln1")
    q, k, v = rope(a, d(h, p["q"])), rope(a, d(h, p["k"])), d(h, p["v"])
    x = x + d(attention(a, q, k, v), p["o"], 2)
    h = norm(a, x, p, "ln2")
    return x + d(jax.nn.silu(d(h, p["wg"])) * d(h, p["wi"]), p["wo"])


def logits(a: Arch, g: dict, h, quant: str = ""):
    """Final norm and head over hidden rows h [..., D]."""
    from benchmarks.harness.reference import dense

    return dense(norm(a, h, g, "ln_f"), g["head"].T, quant=quant)


# ------------------------------------------------ the system under test

def program_config(a: Arch, dtype, **extra):
    """``tony_tpu``'s config, as ``models/hf.py`` ``llama_config`` maps
    the family."""
    from tony_tpu.models import TransformerConfig

    kw = dict(vocab_size=a.vocab, d_model=a.d, n_heads=a.heads,
              n_kv_heads=a.kv_heads, n_layers=a.layers, d_ff=a.ff,
              max_seq_len=a.max_len, dtype=dtype, positional="rope",
              norm="rms", use_bias=False, activation=a.act,
              norm_eps=a.eps, rope_theta=a.theta, gated_mlp=True,
              parallel_residual=False, rotary_dims=0,
              explicit_head_dim=0 if a.head_dim * a.heads == a.d
              else a.head_dim,
              tied_embeddings=False, scan_layers=False)
    kw.update(extra)
    return TransformerConfig(**kw)


def program_tree(a: Arch, w: dict) -> dict:
    """``weights.all_weights`` (or a tree of the same shape holding leaf
    NAMES) laid out as ``Transformer``'s ``params``."""
    g = w["g"]
    tree = {"embedding": g["embed"], "lm_head": g["head"],
            "ln_f": {"scale": g["ln_f.scale"]}}
    for i, lw in enumerate(w["layers"]):
        tree[f"block_{i}"] = {
            "ln1": {"scale": lw["ln1.scale"]},
            "ln2": {"scale": lw["ln2.scale"]},
            "attn": {n: {"kernel": lw[n]} for n in "qkvo"},
            "mlp": {n: {"kernel": lw[n]} for n in ("wi", "wo", "wg")}}
    return tree


# ------------------------------------------------------------ the counts
# of what the algorithm needs, whatever implements it (harness/work.py)

def kv_bytes_per_token(a: Arch, itemsize: int = 2) -> int:
    """K and V of every layer for one position."""
    return 2 * a.kv_heads * a.head_dim * a.layers * itemsize


def train_flops_per_token(a: Arch, seq: int) -> float:
    """Forward + backward of one token of a ``seq``-long row: 6 FLOPs a
    matmul weight (every block and the head; the embedding is a
    gather), and causal attention's two matmuls forward and four
    backward over seq/2 keys on average."""
    dense = 6 * (a.layers * K.layer_matmul_params(a) + a.vocab * a.d)
    attn = 6 * 2 * (seq / 2) * a.heads * a.head_dim * a.layers
    return dense + attn


def flash_flops_per_step(a: Arch, rows: int, seq: int) -> float:
    """Causal attention's own work in one training step, forward (QK^T,
    PV) and backward (dV, dP, dQ, dK): six matmuls of seq x seq/2 x
    head_dim per head, per row, per layer. The forward's recomputation
    inside a flash backward is not counted."""
    return 6 * 2 * (seq * seq / 2) * a.head_dim * a.heads * rows * a.layers


def serve_token_flops(a: Arch, position: int, sampled: bool) -> float:
    """Model FLOPs of one position of a served request: every block (2 a
    weight, attention over the positions so far), and the head where a
    token is sampled."""
    return 2 * a.layers * K.layer_matmul_params(a) \
        + 4 * a.heads * a.head_dim * a.layers * (position + 1) \
        + (2 * a.vocab * a.d if sampled else 0)


def decode_step_bytes(a: Arch, live_tokens: float, itemsize: int = 2) -> float:
    """Least bytes one decode step reads: every block's weights, the
    final norm and the head once (the embedding is a gather of a few
    rows), and the K/V of the ``live_tokens`` positions the batch
    attends to."""
    weights = a.layers * K.layer_params(a) + a.vocab * a.d + a.d
    return weights * itemsize + live_tokens * kv_bytes_per_token(a, itemsize)


def decode_step_flops(a: Arch, batch: float, live_tokens: float) -> float:
    return batch * (2 * a.layers * K.layer_matmul_params(a) + 2 * a.vocab * a.d) \
        + 4 * a.heads * a.head_dim * a.layers * live_tokens
