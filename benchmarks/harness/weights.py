"""Sizes and seeded weights: the benchmark's own, shared by the program's
adapter and the plain reference. Nothing here comes from ``tony_tpu``.

A configuration file carries the published ``config.json`` keys of its
model. ``arch`` turns them into the handful of sizes every other module
of the benchmark uses. ``leaf`` makes ONE weight from ``(seed, layer,
name)`` with ``jax.random``: each value is a function of the key and the
element's index alone, so a leaf made alone (the reference, layer by
layer) is bit-identical to the same leaf made inside the one jitted
program that builds the whole model for the system under test.

Matrices are N(0, 0.02); norm scales 1 + 0.1 N; biases 0.02 N — scales
and biases are random on purpose, so a dropped bias or scale shows in
the comparison that decides ``correct``.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Arch:
    family: str          # "mistral" (RMSNorm, SwiGLU, GQA) | "gpt_neox"
    d: int               # hidden_size
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int              # intermediate_size
    vocab: int
    max_len: int
    eps: float
    theta: float
    rotary_dims: int     # leading dims of each head that rotate
    gated: bool          # SwiGLU (wg, wi, wo) against wi, wo
    bias: bool           # biases on every dense and norm (GPT-NeoX)
    layer_norm: bool     # LayerNorm against RMSNorm
    parallel_residual: bool
    act: str             # "silu" | "gelu" (erf)


def arch(cfg: dict, rehearsal: bool = False) -> Arch:
    """The sizes of a configuration file (its ``rehearsal`` block laid
    over them for the CPU rehearsal)."""
    c = dict(cfg)
    if rehearsal:
        c.update(cfg["rehearsal"])
    family = c["model_type"]
    heads = c["num_attention_heads"]
    head_dim = c.get("head_dim") or c["hidden_size"] // heads
    if family == "mistral":
        if c.get("sliding_window"):
            raise ValueError("sliding_window is not in the reference")
        return Arch(family, c["hidden_size"], c["num_hidden_layers"], heads,
                    c["num_key_value_heads"], head_dim,
                    c["intermediate_size"], c["vocab_size"],
                    c["max_position_embeddings"], c["rms_norm_eps"],
                    float(c["rope_theta"]), head_dim, True, False, False,
                    False, c["hidden_act"])
    if family == "gpt_neox":
        return Arch(family, c["hidden_size"], c["num_hidden_layers"], heads,
                    heads, head_dim, c["intermediate_size"], c["vocab_size"],
                    c["max_position_embeddings"], c["layer_norm_eps"],
                    float(c["rotary_emb_base"]),
                    int(head_dim * c["rotary_pct"]), False, True, True,
                    bool(c["use_parallel_residual"]), c["hidden_act"])
    raise ValueError(f"no reference for model_type {family!r}")


# kind: "w" matrix, "s" norm scale, "b" bias
def layer_leaves(a: Arch) -> list[tuple[str, tuple, str]]:
    """(name, shape, kind) of one block's weights, in a fixed order."""
    out = [("ln1.scale", (a.d,), "s"), ("ln2.scale", (a.d,), "s"),
           ("q", (a.d, a.heads, a.head_dim), "w"),
           ("k", (a.d, a.kv_heads, a.head_dim), "w"),
           ("v", (a.d, a.kv_heads, a.head_dim), "w"),
           ("o", (a.heads, a.head_dim, a.d), "w"),
           ("wi", (a.d, a.ff), "w"), ("wo", (a.ff, a.d), "w")]
    if a.gated:
        out.append(("wg", (a.d, a.ff), "w"))
    if a.bias:
        out += [("ln1.bias", (a.d,), "b"), ("ln2.bias", (a.d,), "b"),
                ("q.bias", (a.heads, a.head_dim), "b"),
                ("k.bias", (a.kv_heads, a.head_dim), "b"),
                ("v.bias", (a.kv_heads, a.head_dim), "b"),
                ("o.bias", (a.d,), "b"), ("wi.bias", (a.ff,), "b"),
                ("wo.bias", (a.d,), "b")]
    return out


def global_leaves(a: Arch) -> list[tuple[str, tuple, str]]:
    out = [("embed", (a.vocab, a.d), "w"), ("head", (a.vocab, a.d), "w"),
           ("ln_f.scale", (a.d,), "s")]
    if a.bias:
        out.append(("ln_f.bias", (a.d,), "b"))
    return out


def root_key(seed: int, stream: int = 0):
    """``--seed`` may pass 2**31: split it, fold the halves."""
    import jax

    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                             seed >> 31)
    return jax.random.fold_in(key, stream)


def leaf(a: Arch, seed, layer, name: str, dtype):
    """One weight. ``layer`` is the block's index (may be traced), or -1
    for the embedding, the head and the final norm. ``seed`` is an int
    or a key from ``root_key``."""
    import jax
    import jax.numpy as jnp

    specs = global_leaves(a) if isinstance(layer, int) and layer < 0 \
        else layer_leaves(a)
    idx, (_, shape, kind) = next(
        (i, s) for i, s in enumerate(specs) if s[0] == name)
    key = seed if hasattr(seed, "dtype") else root_key(seed)
    key = jax.random.fold_in(jax.random.fold_in(key, layer + 1), idx)
    x = jax.random.normal(key, shape, jnp.float32)
    x = {"w": 0.02 * x, "s": 1.0 + 0.1 * x, "b": 0.02 * x}[kind]
    return x.astype(dtype)


def layer_weights(a: Arch, seed, layer, dtype) -> dict:
    return {n: leaf(a, seed, layer, n, dtype) for n, _, _ in layer_leaves(a)}


def global_weights(a: Arch, seed, dtype) -> dict:
    return {n: leaf(a, seed, -1, n, dtype) for n, _, _ in global_leaves(a)}


def all_weights(a: Arch, seed, dtype) -> dict:
    """Every weight, ``{"g": {...}, "layers": [{...}, ...]}`` — call it
    under ONE ``jax.jit`` so the model is made on the device in one
    program."""
    key = seed if hasattr(seed, "dtype") else root_key(seed)
    return {"g": global_weights(a, key, dtype),
            "layers": [layer_weights(a, key, i, dtype)
                       for i in range(a.layers)]}


def token_batch(a: Arch, seed, step, rows: int, seq: int):
    """Training batch ``step``: ``rows`` x ``seq`` ids, every row
    different, every step different."""
    import jax
    import jax.numpy as jnp

    key = seed if hasattr(seed, "dtype") else root_key(seed, 1)
    return jax.random.randint(jax.random.fold_in(key, step), (rows, seq), 0,
                              a.vocab, jnp.int32)
