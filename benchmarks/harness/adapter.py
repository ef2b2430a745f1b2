"""The one place that knows how the system under test names things: its
``TransformerConfig`` for an ``Arch``, and the parameter tree its
``Transformer`` expects, filled with the benchmark's own seeded weights.
Only the children that hold the chip import this."""

from __future__ import annotations

from . import weights as W


def program_config(a: W.Arch, dtype, **extra):
    """``tony_tpu``'s config for the architecture, as ``models/hf.py``
    maps the two families (``llama_config``, ``neox_config``)."""
    from tony_tpu.models import TransformerConfig

    kw = dict(vocab_size=a.vocab, d_model=a.d, n_heads=a.heads,
              n_kv_heads=a.kv_heads, n_layers=a.layers, d_ff=a.ff,
              max_seq_len=a.max_len, dtype=dtype, positional="rope",
              norm="layer" if a.layer_norm else "rms", use_bias=a.bias,
              activation=a.act, norm_eps=a.eps, rope_theta=a.theta,
              gated_mlp=a.gated, parallel_residual=a.parallel_residual,
              rotary_dims=0 if a.rotary_dims >= a.head_dim
              else a.rotary_dims,
              explicit_head_dim=0 if a.head_dim * a.heads == a.d
              else a.head_dim,
              tied_embeddings=False, scan_layers=False)
    kw.update(extra)
    return TransformerConfig(**kw)


def _dense(lw: dict, name: str) -> dict:
    out = {"kernel": lw[name]}
    if name + ".bias" in lw:
        out["bias"] = lw[name + ".bias"]
    return out


def _norm(lw: dict, name: str) -> dict:
    out = {"scale": lw[name + ".scale"]}
    if name + ".bias" in lw:
        out["bias"] = lw[name + ".bias"]
    return out


def program_tree(a: W.Arch, w: dict) -> dict:
    """``weights.all_weights`` (or a tree of the same shape holding leaf
    NAMES) laid out as ``Transformer``'s ``params``."""
    g = w["g"]
    tree = {"embedding": g["embed"], "lm_head": g["head"],
            "ln_f": _norm(g, "ln_f")}
    for i, lw in enumerate(w["layers"]):
        mlp = {"wi": _dense(lw, "wi"), "wo": _dense(lw, "wo")}
        if a.gated:
            mlp["wg"] = _dense(lw, "wg")
        tree[f"block_{i}"] = {
            "ln1": _norm(lw, "ln1"), "ln2": _norm(lw, "ln2"),
            "attn": {n: _dense(lw, n) for n in "qkvo"}, "mlp": mlp}
    return tree


def leaf_names(a: W.Arch) -> dict:
    """The program's tree with, at each leaf, the reference's name for
    it (``"embed"``, ``"3/q.bias"``)."""
    return program_tree(a, {
        "g": {n: n for n, _, _ in W.global_leaves(a)},
        "layers": [{n: f"{i}/{n}" for n, _, _ in W.layer_leaves(a)}
                   for i in range(a.layers)]})


def seeded_params(a: W.Arch, seed: int, dtype):
    """The model's parameters, made on the device from the seed by one
    jitted program, in the dtype they are used in."""
    import jax

    key = W.root_key(seed)
    return jax.jit(lambda k: program_tree(a, W.all_weights(a, k, dtype)))(key)
