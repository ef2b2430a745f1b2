"""int8 weight-only serving conversion for the flagship transformer.

``quantize_for_serving(model, params)`` rewrites every dense kernel of a
trained/imported model into the ``{kernel_q8 int8, scale fp32}`` form
that ``TransformerConfig(quantized=True)``'s QuantDense consumes through
the pallas dequant-matmul (ops/quant.py) — HALF the weight bytes per
decode step (docs/PERF.md decode roofline). Embeddings, norms, biases,
and the LM head stay full precision: they are a small fraction of the
bytes and dominate quality.

Scope: the dense transformer family (everything models/hf.py imports —
GPT-2, Llama/Mistral/Qwen2, Gemma, GPT-NeoX, Phi) plus MoE expert
weights (Mixtral: per-expert, per-output-channel scales, served through
a vmapped pallas dequant matmul). scan-stacked layers are rejected
rather than half-converted.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

from tony_tpu.models.transformer import Transformer
from tony_tpu.ops.quant import quantize_q8

# parent module names whose "kernel" leaf becomes int8
_DENSE_NAMES = ("q", "k", "v", "o", "wi", "wg", "wo")


def _quantize_kernel(kernel, is_o: bool, on_device: bool = False):
    """kernel [in, *out] (q/k/v/wi/wg/wo) or [*in, out] (o) -> 2-D
    int8 + per-output-channel scale, matching QuantDense's flatten.

    ``on_device``: keep the leaf a jax array so multi-GB checkpoints
    already living in HBM never round-trip to host (the transfers
    would dominate the conversion)."""
    if on_device:
        import jax.numpy as xp
    else:
        xp = np
    arr = kernel if on_device else np.asarray(kernel)
    if is_o:  # o: [heads, dh, d] — leading axes are the INPUT
        in_flat = arr.shape[0] * arr.shape[1] if arr.ndim == 3 \
            else arr.shape[0]
        w2 = xp.reshape(arr, (in_flat, arr.shape[-1]))
    else:  # [in, *out]
        w2 = xp.reshape(arr, (arr.shape[0], -1))
    w_q, scale = quantize_q8(w2)
    return {"kernel_q8": w_q, "scale": scale}


def quantize_transformer_params(params: Any, on_device: bool = False) -> Any:
    """params pytree (as from model.init / hf import) -> quantized tree.
    Biases ride along unchanged; every other leaf passes through.
    ``on_device``: quantize with jnp, for params already in HBM."""

    xp = np
    if on_device:
        import jax.numpy as xp  # noqa: F811

    def quantize_expert(arr):
        # [E, in, out]: contraction over axis 1, so the per-output-channel
        # scale is per (expert, out) — the 3-D analog of quantize_q8
        a = xp.asarray(arr, xp.float32)
        absmax = xp.max(xp.abs(a), axis=1)
        scale = xp.maximum(absmax, 1e-8) / 127.0
        q = xp.clip(xp.round(a / scale[:, None, :]), -127, 127) \
            .astype(xp.int8)
        return q, scale.astype(xp.float32)

    def walk(node, name=""):
        if not isinstance(node, dict):
            return node
        if "kernel" in node and name in _DENSE_NAMES:
            out = _quantize_kernel(node["kernel"], is_o=(name == "o"),
                                   on_device=on_device)
            if "bias" in node:
                out["bias"] = node["bias"]
            extra = set(node) - {"kernel", "bias"}
            if extra:
                raise ValueError(f"unexpected leaves under {name}: {extra}")
            return out
        if "router" in node and "wi" in node:  # MoE expert block (Mixtral)
            out = {"router": node["router"]}
            for nm in ("wi", "wg", "wo"):
                if nm in node:
                    out[nm + "_q8"], out[nm + "_scale"] = \
                        quantize_expert(node[nm])
            extra = set(node) - {"router", "wi", "wg", "wo"}
            if extra:
                raise ValueError(f"unexpected MoE leaves: {extra}")
            return out
        return {k: walk(v, k) for k, v in node.items()}

    return walk(params)


def quantize_for_serving(model: Transformer, params: Any,
                         on_device: bool = False
                         ) -> tuple[Transformer, Any]:
    """(model, params) -> (quantized model, quantized params): the
    returned pair drops into generate()/score exactly like the original.
    ``on_device``: convert with jnp so a multi-GB tree already in HBM
    never round-trips through host memory.
    """
    cfg = model.cfg
    if cfg.scan_layers:
        raise ValueError("int8 serving conversion expects per-block "
                         "params (scan_layers stacks them)")
    qcfg = dataclasses.replace(cfg, quantized=True)
    return Transformer(qcfg), quantize_transformer_params(
        params, on_device=on_device)


def shard_expert_qparams(mesh, qparams: Any, axis: str = "expert") -> Any:
    """Place a quantized tree's MoE expert weights SHARDED on ``axis``
    (wi/wg/wo_q8 on dim 0, their scales likewise) and leave everything
    else where it is. This is the placement the shard_mapped q8 expert
    FFN consumes (parallel/moe.py): per-device HBM holds only E/ways
    experts — how a 47B-class Mixtral fits a slice. Pair with a
    TransformerConfig whose ``mesh`` carries the same axis."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    def place(node, name=""):
        if isinstance(node, dict):
            return {k: place(v, k) for k, v in node.items()}
        if name in ("wi_q8", "wg_q8", "wo_q8"):
            return jax.device_put(jnp.asarray(node),
                                  NamedSharding(mesh, P(axis, None, None)))
        if name in ("wi_scale", "wg_scale", "wo_scale"):
            return jax.device_put(jnp.asarray(node),
                                  NamedSharding(mesh, P(axis, None)))
        return node

    return place(qparams)


def quantize_cli(model, params):
    """CLI-facing wrapper: unsupported configs exit with a clean message
    instead of a traceback (shared by the generate and score CLIs)."""
    try:
        return quantize_for_serving(model, params)
    except ValueError as e:
        raise SystemExit(f"--int8: {e}")
