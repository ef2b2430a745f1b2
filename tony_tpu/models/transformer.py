"""GPT-style decoder-only transformer — the long-context flagship.

No reference analog (TonY has no model code); built TPU-first:

- logical-axis param annotations ("embed", "heads", "mlp", "vocab") so the
  parallel.sharding presets (dp/fsdp/tp/fsdp_tp) apply unchanged
- attention backend selectable: "reference" (O(L^2)), "blockwise"
  (chunked online-softmax), "ring" (sequence-parallel over the seq mesh
  axis), or "pallas" (fused TPU kernel, tony_tpu.ops.attention)
- bfloat16 activations / float32 params + optimizer, MXU-sized dims
- optional remat (jax.checkpoint) per block to trade FLOPs for HBM
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, replace
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tony_tpu.parallel.moe import N_COUNTS, moe_logical_axes, routed_share
from tony_tpu.parallel.ring_attention import (
    blockwise_attention,
    reference_attention,
    ring_attention,
)


@dataclass(frozen=True)
class LatentConfig:
    """Sizes of multi-head latent attention (DeepSeek-V2's MLA): queries
    through a ``q_rank`` bottleneck, keys and values through a shared
    ``kv_rank`` latent; each head's query and key are ``nope_dim``
    unrotated dims from the latent and ``rope_dim`` rotated ones (ONE
    rotary key for all heads), its value ``v_dim``. ``scale_mult``
    multiplies the ``(nope_dim + rope_dim) ** -0.5`` softmax scale
    (YaRN's squared ``mscale_all_dim`` term)."""

    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    scale_mult: float = 1.0

    @property
    def cache_width(self) -> int:
        """Values cached a token a layer: the normed latent and the
        rotated shared key."""
        return self.kv_rank + self.rope_dim


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    d_model: int = 512
    n_heads: int = 8
    n_kv_heads: int | None = None  # GQA: fewer K/V heads; None = MHA
    n_layers: int = 6
    d_ff: int = 2048
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    attention_backend: str = "blockwise"  # reference|blockwise|ring|ulysses|pallas
    attention_block_size: int = 512
    # pallas backend only: kv-block size when it should differ from the
    # q-block size (0 = same). Measured on v5e at (b4, seq 2048, 8x128):
    # block 512x1024 runs the fwd+bwd kernels 15% faster than 512x512 —
    # half the kv-loop steps means half the per-body fixed VPU work.
    attention_block_k: int = 0
    remat: bool = False
    # what the remat pass may KEEP from the forward instead of
    # recomputing it for backward:
    #   "nothing" — full per-block remat: minimum memory, but the whole
    #     forward (~2N FLOPs) re-executes, capping model-FLOPs MFU at
    #     6/8 of hardware utilization;
    #   "dots" — keep matmul outputs, recompute only elementwise ops:
    #     recompute FLOPs ~0 at O(tokens * (5*d + d_ff)) bytes/layer —
    #     the right trade whenever it fits HBM (docs/PERF.md). The flash
    #     attention call is a pallas custom_vjp, NOT a dot: its forward
    #     still re-executes for backward under this policy;
    #   "attn_saved" — the attention sublayer runs OUTSIDE the remat
    #     region (its residuals, ~8 KB/token/layer in bf16, are saved,
    #     so the flash forward never re-runs) and only the MLP is
    #     rematted with dots kept. Fastest; costs the most HBM.
    remat_policy: str = "nothing"  # nothing | dots | attn_saved
    # required for the ring and ulysses backends, and for the pallas
    # backend on more than one device (its kernel rides a shard_map)
    mesh: Any = None
    # architecture family knobs: the defaults are the Llama-style TPU
    # flagship (RMSNorm + RoPE + no biases + gelu); flipping them to
    # ("layer", "learned", True, "gelu_tanh") gives GPT-2 exactly —
    # models/hf.py imports HF GPT-2 checkpoints into that configuration
    norm: str = "rms"  # rms | layer
    positional: str = "rope"  # rope | learned
    use_bias: bool = False
    # biases on the q/k/v projections ONLY (Qwen2 family: biased qkv, bias-
    # free o/mlp). Independent of use_bias, which biases every dense.
    qkv_bias: bool = False
    # sliding-window attention (Mistral family): each query sees only the
    # last `sliding_window` keys. 0 = full causal. Supported by the
    # reference and blockwise backends, the KV-cache decode path, and the
    # pallas backend (banded kernel: O(L*window) compute and HBM traffic).
    sliding_window: int = 0
    activation: str = "gelu"  # gelu (erf) | gelu_tanh | silu
    norm_eps: float = 1e-6
    rope_theta: float = 10_000.0
    # long-context RoPE rescaling (Llama-3 family); None = plain RoPE
    rope_scaling: RopeScaling | None = None
    # SwiGLU-style gated FFN (Llama family): wo(act(wg(x)) * wi(x));
    # False = classic 2-matmul MLP (GPT-2 family)
    gated_mlp: bool = False
    # per-head width when it differs from d_model // n_heads (Gemma-7B:
    # 16 heads x 256 > d_model 3072); 0 = derived
    explicit_head_dim: int = 0
    # GPT-NeoX/Pythia family: rotate only the first rotary_dims of each
    # head (rotary_pct; 0 = full head_dim), and compute attention + MLP
    # from the SAME block input in parallel (x + attn(ln1 x) + mlp(ln2 x))
    rotary_dims: int = 0
    parallel_residual: bool = False
    # SERVING-ONLY int8 weight-only mode: dense kernels are stored as
    # {kernel_q8, scale} and run through the pallas dequant-matmul
    # (ops/quant.py) — use models.quantize.quantize_for_serving to
    # convert a trained/imported model; training this config is
    # unsupported (int8 weights have no useful gradients)
    quantized: bool = False
    # SERVING int8 KV cache: cache buffers store int8 with per-(position,
    # head) fp32 scales, quantized on write after RoPE — HALF the decode
    # cache HBM traffic (the dominant decode bytes at long context,
    # docs/PERF.md). Read back through the flash-decode kernel (int8
    # tiles dequantized in VMEM) or dequantized for the einsum path.
    kv_cache_quant: bool = False
    # decode-step attention implementation for single-token steps:
    # "einsum" = XLA path (default; exact reference), "flash" = pallas
    # flash-decode kernel (ops/decode.py: fused online-softmax over the
    # cache, int8-aware). Prefill (multi-token decode) always uses the
    # einsum path.
    decode_attention: str = "einsum"
    # multiply token embeddings by sqrt(d_model), in activation dtype
    # (Gemma's normalizer)
    embed_scale: bool = False
    # RMSNorm computes x_norm * (1 + scale) with zero-init scale (Gemma's
    # parameterization; checkpoints store the offset-from-one weight)
    norm_unit_offset: bool = False
    # False adds a separate lm_head param instead of reusing the input
    # embedding for output logits (Llama unties; GPT-2 ties)
    tied_embeddings: bool = True
    # Phi family: the untied output projection carries a bias
    lm_head_bias: bool = False
    # MoE (expert-parallel FFN): 0 = dense MLP everywhere; k > 0 replaces the
    # MLP of every k-th block with a mixture-of-experts layer
    moe_every: int = 0
    moe_num_experts: int = 8
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # Mixtral-family MoE: SwiGLU experts + renormalized top-k gates +
    # dropless (exact dense) evaluation; moe_d_ff sizes the experts when
    # it differs from the dense d_ff (0 = same). moe_activation is
    # separate from the dense-MLP activation knob.
    moe_gated: bool = False
    moe_renormalize: bool = False
    moe_dropless: bool = False
    moe_activation: str = "gelu"
    moe_d_ff: int = 0
    # scan the layer stack with nn.scan: one traced/compiled block instead
    # of n_layers copies — XLA compile time and HBM for code stay O(1) in
    # depth (the standard TPU deep-stack idiom). Params gain a leading
    # stacked "layers" dim (shardable over the pipe axis). Uniform layers
    # only (incompatible with moe_every, which alternates block types).
    scan_layers: bool = False
    # SHARDED SERVING (ISSUE-14; needs cfg.mesh): pin activations
    # replicated at the row-parallel boundaries — the attention output
    # entering the o projection, o's output, the MLP hidden entering
    # wo, and wo's output. Under the parallel.sharding "serve" preset
    # (weights sharded on OUTPUT dims only) these four constraints
    # force GSPMD to all-gather activations BEFORE any matmul whose
    # contraction dim they shard, so every float reduction runs whole
    # on one chip in the single-chip order and all cross-chip ICI
    # traffic is pure data movement — the structural argument behind
    # the serving engine's mesh=1 == mesh=N byte-identical-streams
    # contract (which holds on the CPU backend; TPU chips round the
    # narrower per-chip matmuls differently). Training presets
    # (dp/fsdp/tp) must leave this False:
    # a replicate pin would all-gather batch-sharded activations.
    shard_activations: bool = False
    # multi-head LATENT attention (``LatentAttention``): the cache holds
    # one compressed key/value vector and one shared rotary key a token,
    # not per-head keys and values. None = ``Attention``.
    latent: LatentConfig | None = None
    # ROUTED experts of which this chip holds a share (``RoutedMLP``,
    # parallel/moe.py ``RoutedConfig``): the MLP of every layer from
    # ``routed.first_dense`` on. None = dense everywhere (or moe_every).
    routed: Any = None
    # the MIXER of each layer, by index: "full_attention" (``Attention``)
    # or "conv" (``ShortConv``, a gated causal convolution of
    # ``conv_kernel`` taps whose state is ``conv_kernel - 1`` positions
    # a sequence, not a cached position). () = every layer attends.
    layer_types: tuple = ()
    conv_kernel: int = 0
    # RMSNorm over each query and key head's dims (one learned scale of
    # head_dim each) before the rotary embedding
    qk_norm: bool = False
    def __post_init__(self):
        # invalid knob combinations fail at construction, not first apply
        if self.layer_types:
            kinds = set(self.layer_types) - {"conv", "full_attention"}
            if kinds or len(self.layer_types) != self.n_layers:
                raise ValueError(
                    f"layer_types names one of conv | full_attention for "
                    f"each of the {self.n_layers} layers; got "
                    f"{len(self.layer_types)} entries, unknown {kinds}")
            if "conv" in self.layer_types and self.conv_kernel < 2:
                raise ValueError("a conv layer needs conv_kernel >= 2")
            for knob in ("scan_layers", "latent", "sliding_window",
                         "kv_cache_quant", "quantized"):
                if getattr(self, knob):
                    raise ValueError(
                        f"{knob} is not implemented for a model of mixed "
                        "layer kinds (layer_types)")
            if self.decode_attention != "einsum":
                raise ValueError(
                    "decode_attention='flash' is not implemented for a "
                    "model of mixed layer kinds (layer_types)")
        if self.latent is not None:
            for knob in ("kv_cache_quant", "quantized", "sliding_window",
                         "scan_layers"):
                if getattr(self, knob):
                    raise ValueError(
                        f"{knob} is not implemented for latent attention")
            if self.decode_attention != "einsum":
                raise ValueError(
                    "decode_attention='flash' is not implemented for latent "
                    "attention (the flash-decode kernel reads per-head K/V)")
            if self.positional != "rope" or self.rotary_dims:
                raise ValueError("latent attention rotates its own rope_dim "
                                 "slice: positional='rope', rotary_dims=0")
        if self.routed is not None:
            if self.moe_every or self.scan_layers or self.quantized:
                raise ValueError(
                    "routed experts are exclusive of moe_every, scan_layers "
                    "(layers differ) and quantized")
            if not self.gated_mlp or self.parallel_residual:
                raise ValueError("routed experts are SwiGLU in a serial "
                                 "block: gated_mlp=True, no parallel_residual")
        if self.gated_mlp and self.moe_every:
            raise ValueError("gated_mlp is not implemented for MoE expert "
                             "FFNs; use moe_every with gated_mlp=False")
        if self.scan_layers and self.moe_every:
            raise ValueError("scan_layers needs uniform layers "
                             "(moe_every alternates block types)")
        if self.remat_policy not in ("nothing", "dots", "attn_saved"):
            raise ValueError(
                f"unknown remat_policy {self.remat_policy!r}; "
                "expected one of: nothing, dots, attn_saved")

    @property
    def head_dim(self) -> int:
        return self.explicit_head_dim or self.d_model // self.n_heads

    @property
    def cache_values_per_token(self) -> int:
        """What ONE attention layer caches for one position (a conv
        layer caches none: ``attn_layers``), in values of the cache
        dtype: the cache spec ``serve/slots.kv_page_nbytes`` sizes a page
        from (int8 K/V adds its float32 scales there)."""
        if self.latent is not None:
            return self.latent.cache_width
        return 2 * self.kv_heads * self.head_dim

    @property
    def kv_pack_lanes(self) -> bool:
        """Whether the K/V cache stores a position's heads as rows of 128
        values, ``[b, max_len, kv_heads * head_dim / 128, 128]``: the same
        bytes in the same order. Derived, never set: heads narrower than
        the TPU's 128 lanes that fill whole rows, in a plain K/V cache
        read by the einsum path (the int8 cache keeps a scale a head and
        the flash-decode kernel reads heads, so both keep heads). The
        v5e's compiler lays a pool whose minor dimension is under 128 out
        PAGES-minor and then copies the whole pool to row-major and back
        in every decode step (compiled for a described v5e at 8 heads of
        64: two copies of each 134 MB leaf a step, a byte count, not a
        measured time: ``tests/test_tpu_compile.py``)."""
        return (self.latent is None and self.head_dim < 128
                and (self.kv_heads * self.head_dim) % 128 == 0
                and not self.kv_cache_quant
                and self.decode_attention == "einsum")

    @property
    def attn_layers(self) -> int:
        """Layers that keep keys and values (or a latent) a position."""
        return self.n_layers - self.conv_layers

    @property
    def conv_layers(self) -> int:
        """Layers whose mixer is a ``ShortConv``: state a SEQUENCE."""
        return sum(t == "conv" for t in self.layer_types)

    @property
    def state_values_per_slot(self) -> int:
        """What the conv layers keep for one sequence, in values of the
        model's dtype: ``conv_kernel - 1`` positions of ``d_model``."""
        return self.conv_layers * (self.conv_kernel - 1) * self.d_model

    @property
    def kv_heads(self) -> int:
        kv = self.n_heads if self.n_kv_heads is None else self.n_kv_heads
        if kv <= 0 or self.n_heads % kv:
            raise ValueError(
                f"n_kv_heads={kv} must be positive and divide "
                f"n_heads={self.n_heads}")
        return kv


def _serve_replicate(cfg: TransformerConfig, x):
    """The sharded-serving replicate pin (``cfg.shard_activations``):
    constrain ``x`` fully replicated so the matmul consuming it next
    contracts over whole operands (see the config field comment). A
    no-op without a mesh or with the flag off — training paths never
    pay the gather."""
    if cfg.mesh is None or not cfg.shard_activations:
        return x
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.lax.with_sharding_constraint(
        x, NamedSharding(cfg.mesh, PartitionSpec()))


def _attention(cfg: TransformerConfig, q, k, v, segment_ids=None):
    if cfg.attention_backend == "reference":
        return reference_attention(q, k, v, causal=True,
                                   window=cfg.sliding_window,
                                   segment_ids=segment_ids)
    if cfg.attention_backend == "blockwise":
        return blockwise_attention(q, k, v, block_size=cfg.attention_block_size,
                                   causal=True, window=cfg.sliding_window,
                                   segment_ids=segment_ids)
    if cfg.attention_backend == "ring":
        if cfg.mesh is None:
            raise ValueError("ring attention needs cfg.mesh")
        return ring_attention(q, k, v, cfg.mesh, causal=True,
                              window=cfg.sliding_window,
                              segment_ids=segment_ids)
    if cfg.attention_backend == "ulysses":
        if cfg.mesh is None:
            raise ValueError("ulysses attention needs cfg.mesh")
        from tony_tpu.parallel.ulysses import ulysses_attention

        return ulysses_attention(q, k, v, cfg.mesh, causal=True,
                                 block_size=cfg.attention_block_size,
                                 window=cfg.sliding_window,
                                 segment_ids=segment_ids)
    if cfg.attention_backend == "pallas":
        return _pallas_attention(cfg, q, k, v, segment_ids)
    raise ValueError(f"unknown attention backend {cfg.attention_backend}")


def _pallas_attention(cfg: TransformerConfig, q, k, v, segment_ids):
    """The flash kernel, run per shard under ``cfg.mesh``. A pallas
    call is opaque to GSPMD and the chip's compiler refuses to
    partition one ("Mosaic kernels cannot be automatically
    partitioned"), so on more than one device the model must be given
    its mesh and the call rides a ``shard_map``. Attention is
    independent per batch row and per head: the batch splits over the
    data axes, the heads over the tensor axis (when it divides the kv
    heads — contiguous chunks keep each q head beside its kv head), no
    collective is needed, and the sequence stays whole (the ring and
    ulysses backends are the sequence-parallel ones)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from tony_tpu.ops.attention import flash_attention
    from tony_tpu.parallel.mesh import TENSOR
    from tony_tpu.parallel.sharding import _axis_size, batch_sharding

    flash = functools.partial(
        flash_attention, causal=True, block_q=cfg.attention_block_size,
        block_k=cfg.attention_block_k or cfg.attention_block_size,
        window=cfg.sliding_window)
    mesh = cfg.mesh
    batch = heads = None
    if mesh is not None:
        batch = batch_sharding(mesh).spec[0]
        if q.shape[0] % _axis_size(mesh, batch):
            batch = None  # e.g. init's one-row dummy: nothing to split
        n_tensor = mesh.shape.get(TENSOR, 1)
        if n_tensor > 1 and k.shape[2] % n_tensor == 0:
            heads = TENSOR
    if batch is None and heads is None:
        return flash(q, k, v, segment_ids=segment_ids)
    spec = P(batch, None, heads, None)
    if segment_ids is None:
        return shard_map(lambda q, k, v: flash(q, k, v), mesh=mesh,
                         in_specs=(spec, spec, spec), out_specs=spec,
                         check_vma=False)(q, k, v)
    return shard_map(lambda q, k, v, s: flash(q, k, v, segment_ids=s),
                     mesh=mesh, in_specs=(spec, spec, spec, P(batch, None)),
                     out_specs=spec, check_vma=False)(q, k, v, segment_ids)


class RMSNorm(nn.Module):
    dtype: Any = jnp.bfloat16
    eps: float = 1e-6
    # Gemma parameterization: scale is zero-init and applied as
    # (1 + scale) — checkpoints store the offset-from-one weight
    unit_offset: bool = False

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.zeros_init() if self.unit_offset \
            else nn.initializers.ones_init()
        scale = self.param("scale", init, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                                   + self.eps)
        mult = 1.0 + scale if self.unit_offset else scale
        return (norm * mult).astype(self.dtype)


class LayerNorm(nn.Module):
    """Mean-subtracting norm with bias (GPT-2 family); fp32 math."""

    dtype: Any = jnp.bfloat16
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones_init(), (d,),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(), (d,),
                          jnp.float32)
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
        return (((x32 - mu) * jax.lax.rsqrt(var + self.eps)) * scale
                + bias).astype(self.dtype)


def make_norm(cfg: TransformerConfig, name: str):
    if cfg.norm == "layer":
        if cfg.norm_unit_offset:
            raise ValueError("norm_unit_offset is an RMSNorm (Gemma) knob")
        return LayerNorm(cfg.dtype, cfg.norm_eps, name=name)
    if cfg.norm == "rms":
        return RMSNorm(cfg.dtype, cfg.norm_eps, cfg.norm_unit_offset,
                       name=name)
    raise ValueError(f"unknown norm {cfg.norm}")


def _activation(cfg: TransformerConfig):
    if cfg.activation == "gelu":
        return lambda x: nn.gelu(x, approximate=False)
    if cfg.activation == "gelu_tanh":
        return lambda x: nn.gelu(x, approximate=True)
    if cfg.activation == "silu":
        return nn.silu
    raise ValueError(f"unknown activation {cfg.activation}")


@dataclass(frozen=True)
class RopeScaling:
    """Long-context RoPE frequency rescaling (hashable so configs stay
    valid jit static args).

    kind="linear": every frequency divided by ``factor`` (position
    interpolation). kind="llama3": HF's Llama-3 rule — low-frequency
    (long-wavelength) components are divided by ``factor``, high-frequency
    ones kept, with a smooth ramp between the two wavelength thresholds
    derived from ``low_freq_factor``/``high_freq_factor`` and the
    pre-extension ``original_max_len``. kind="yarn": HF's YaRN rule
    (``_compute_yarn_parameters``) — the pair whose wave turns
    ``beta_fast`` times over ``original_max_len`` and every faster one
    is kept, the one turning ``beta_slow`` times and every slower one
    is divided by ``factor``, a linear ramp over the pair index between
    (the cos/sin factor is the caller's: 1 where ``mscale`` equals
    ``mscale_all_dim``).
    """

    kind: str = "llama3"  # llama3 | linear | yarn
    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_len: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0

    def apply(self, freq, theta: float = 10_000.0):
        if self.kind == "linear":
            return freq / self.factor
        if self.kind == "yarn":
            half = freq.shape[0]

            def pair(turns):  # the pair index that turns ``turns`` times
                return half * math.log(self.original_max_len / (
                    turns * 2 * math.pi)) / math.log(theta)

            low = max(math.floor(pair(self.beta_fast)), 0)
            high = min(math.ceil(pair(self.beta_slow)), 2 * half - 1)
            ramp = jnp.clip(
                (jnp.arange(half, dtype=jnp.float32) - low)
                / max(high - low, 0.001), 0.0, 1.0)
            return freq / self.factor * ramp + freq * (1.0 - ramp)
        if self.kind != "llama3":
            raise ValueError(f"unknown rope scaling kind {self.kind!r}")
        two_pi = 2.0 * jnp.pi
        wavelen = two_pi / freq
        low_wl = self.original_max_len / self.low_freq_factor
        high_wl = self.original_max_len / self.high_freq_factor
        smooth = (self.original_max_len / wavelen - self.low_freq_factor) / (
            self.high_freq_factor - self.low_freq_factor)
        mid = (1.0 - smooth) * freq / self.factor + smooth * freq
        scaled = jnp.where(wavelen > low_wl, freq / self.factor, mid)
        return jnp.where(wavelen < high_wl, freq, scaled)


def rotary_embedding(x, positions, theta: float = 10_000.0,
                     scaling: RopeScaling | None = None,
                     rotary_dims: int = 0):
    """RoPE over head_dim (TPU-friendly: pure elementwise, fuses away).
    Half-split rotation convention (matches HF Llama's rotate_half).
    ``rotary_dims`` < head_dim rotates only the leading slice and passes
    the rest through (GPT-NeoX/Pythia rotary_pct). ``positions`` is [L]
    (shared across the batch) or [B, L] (per-row — the continuous-batching
    decode step, where every cache slot sits at its own position)."""
    d = x.shape[-1]
    if rotary_dims and rotary_dims < d:
        rotated = rotary_embedding(x[..., :rotary_dims], positions, theta,
                                   scaling)
        return jnp.concatenate([rotated, x[..., rotary_dims:]], axis=-1)
    half = d // 2
    freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if scaling is not None:
        freq = scaling.apply(freq, theta)
    angles = positions[..., None].astype(jnp.float32) * freq  # [..., half]
    if angles.ndim == 3:  # per-row positions [B, L, half]
        cos = jnp.cos(angles)[:, :, None, :]
        sin = jnp.sin(angles)[:, :, None, :]
    else:  # shared positions [L, half]
        cos = jnp.cos(angles)[None, :, None, :]
        sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def take_pages(leaf, table, axis: int = 0):
    """Gather pool pages through a page table: ``leaf`` ``[.., n_pages,
    ps, ..]`` (pages on ``axis``) becomes ``[.., *table.shape, ps, ..]``.
    THE page gather: the in-model paged branches, ``serve/slots``'
    ``paged_view`` and ``gather_pages`` all read the pool through here.
    The clamp is the gather's own mode: XLA's gather clamps an
    out-of-range start index natively, so a sentinel entry (``>=
    n_pages``) reads page ``n_pages - 1`` with no mask built and no
    select run over the gathered values (``jnp.take``'s default
    ``fill`` mode pays that second pass over every gathered byte)."""
    return jnp.take(leaf, table, axis=axis, mode="clip")


class Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, decode: bool = False, segment_ids=None,
                 positions=None, page_table=None):
        cfg = self.cfg
        b, l, _ = x.shape
        # logical sharding axes for these kernels come from path-name
        # matching in logical_axis_rules_tree, not from annotations here
        if cfg.quantized:
            dense = lambda name, feats, bias: QuantDense(  # noqa: E731
                feats, in_axes=1, use_bias=bias, dtype=cfg.dtype, name=name,
                mesh=cfg.mesh, shard_axes=_q8_shard_axes(cfg, name))
        else:
            dense = lambda name, feats, bias: nn.DenseGeneral(  # noqa: E731
                feats, axis=-1, use_bias=bias, dtype=cfg.dtype,
                param_dtype=jnp.float32, name=name,
                kernel_init=nn.initializers.normal(0.02))
        qkv_bias = cfg.use_bias or cfg.qkv_bias
        q = dense("q", (cfg.n_heads, cfg.head_dim), qkv_bias)(x)
        k = dense("k", (cfg.kv_heads, cfg.head_dim), qkv_bias)(x)
        v = dense("v", (cfg.kv_heads, cfg.head_dim), qkv_bias)(x)
        if cfg.qk_norm:
            with jax.named_scope("attn.qk_norm"):
                q = RMSNorm(cfg.dtype, cfg.norm_eps, name="q_norm")(q)
                k = RMSNorm(cfg.dtype, cfg.norm_eps, name="k_norm")(k)
        if decode:
            out = self._decode_attention(q, k, v, positions, page_table)
            # serve-shard pin: attn out is kv-head-sharded (it read the
            # sharded KV pools locally); the o projection contracts
            # over heads, so gather it whole first — exact data
            # movement, not a partial-sum psum
            out = _serve_replicate(cfg, out)
        else:
            if cfg.positional == "rope":
                positions = jnp.arange(l)
                q = rotary_embedding(q, positions, cfg.rope_theta,
                                     cfg.rope_scaling, cfg.rotary_dims)
                k = rotary_embedding(k, positions, cfg.rope_theta,
                                     cfg.rope_scaling, cfg.rotary_dims)
            if cfg.kv_heads != cfg.n_heads and \
                    cfg.attention_backend != "pallas":
                # GQA: broadcast K/V head groups up to n_heads for the
                # backend. XLA fuses the repeat into the score einsum, so
                # nothing is materialized; the HBM win (small KV) is kept
                # where it matters — the decode cache below. The pallas
                # kernel takes grouped K/V natively (its kv BlockSpec
                # indexes the group row per q head), so it skips this.
                group = cfg.n_heads // cfg.kv_heads
                k = jnp.repeat(k, group, axis=2)
                v = jnp.repeat(v, group, axis=2)
            out = _attention(cfg, q, k, v, segment_ids)
            # serving never takes this branch (every engine dispatch
            # runs decode=True), but the pin completes the contract
            # for any non-decode apply of a shard_activations model
            out = _serve_replicate(cfg, out)
        if cfg.quantized:
            out = QuantDense((cfg.d_model,), in_axes=2,
                             use_bias=cfg.use_bias, dtype=cfg.dtype,
                             name="o", mesh=cfg.mesh,
                             shard_axes=_q8_shard_axes(cfg, "o"))(out)
        else:
            out = nn.DenseGeneral(
                cfg.d_model, axis=(-2, -1), use_bias=cfg.use_bias,
                dtype=cfg.dtype, param_dtype=jnp.float32, name="o",
                kernel_init=nn.initializers.normal(0.02))(out)
        # serve-shard pin: o's output is embed-sharded (the serve
        # preset's row-parallel flip); the residual add and the next
        # norm's mean/rsqrt must see it whole
        return _serve_replicate(cfg, out)

    def _decode_attention(self, q, k, v, positions=None, page_table=None):
        """Incremental attention over a fixed-size KV cache.

        ``page_table`` [b, max_pages] int32 switches the per-slot modes
        to the PAGED cache layout (serve/slots.PagePool): the cache
        leaves are page POOLS ``[n_pages, page_size, kvh, dh]`` (scales
        ``[n_pages, page_size, kvh]``) with no batch dim — row i's
        token at position p writes pool page ``page_table[i, p //
        page_size]`` at offset ``p % page_size``, and row i attends
        over the GATHER of its own pages, reshaped back to the
        ``[max_pages * page_size]`` position-ordered view the unpaged
        buffer would hold — same values at the same logical positions,
        so the attention reduction (and greedy outputs) are identical
        to the unpaged path. Table entries >= n_pages are UNALLOCATED
        sentinels: writes through them drop (scatter mode="drop"),
        gathers clamp to the last page (the gather's own mode="clip",
        ``take_pages``: no in-bounds mask, no select over the gathered
        view), whose junk the per-row position-visibility mask hides —
        exactly the bucket-padding argument. Positions at or past
        ``max_pages * page_size`` also
        drop (a chunk overshooting a finished slot's budget must not
        wrap into the slot's own live pages). The host allocator
        guarantees every position that must LAND maps to an allocated,
        unshared page (copy-on-write forks happen at admission,
        serve/engine.py).

        Flax "cache" collection, the standard jittable decode shape: the
        cache is a static [b, max_seq_len, kv_heads, dh] buffer (GQA: only
        n_kv_heads are cached — the decode-path HBM bound) updated with
        lax.dynamic_update_slice at the current index, so every decode
        step compiles to the same static-shape program (no growing
        tensors, no recompiles — the XLA-friendly way to autoregress).

        ``positions`` [b] int32 switches to PER-SLOT decode (the
        continuous-batching serving step, serve/): every batch row is an
        independent cache slot sitting at its own position — the new
        token is scatter-written at ``positions[i]`` and row i attends
        over ``[0, positions[i]]`` only. The shared ``cache_index``
        scalar is meaningless across mixed-length slots and is neither
        read nor advanced; a row with ``positions[i] < 0`` is an EMPTY
        slot (no visible keys — its output is garbage by construction
        and the serving scheduler ignores it).

        ``positions`` [b, l] int32 is the MULTI-TOKEN per-slot window
        (speculative verify, serve/engine._verify_chunk): row i's token
        j is written and rotated at ``positions[i, j]`` and attends
        over everything at-or-before it — which includes the window's
        own earlier tokens, so the intra-window mask is causal by
        position arithmetic alone. Entries with ``positions[i, j] < 0``
        are PADDING (a slot drafting fewer tokens than the batch
        window): their cache writes are dropped outright (scatter
        mode="drop" on an out-of-range index) and their logits are
        garbage the scheduler never reads. Draft tokens past the
        accepted prefix DO write their K/V — junk beyond a slot's
        accepted length is invisible under the same per-row visibility
        mask and overwritten as the slot advances (the prefix-store
        exactness argument, serve/prefix.py).
        """
        cfg = self.cfg
        b, l, h, dh = q.shape
        kvh = cfg.kv_heads
        group = h // kvh
        max_len = cfg.max_seq_len
        is_init = self.has_variable("cache", "cached_key")
        quant = cfg.kv_cache_quant
        # cache holds only kv_heads — the GQA HBM saving that makes long
        # batched decode fit (cache is the decode-path memory bound).
        # kv_cache_quant stores int8 + per-(pos, head) scales: half the
        # bytes again (docs/PERF.md decode roofline next lever).
        cache_dtype = jnp.int8 if quant else k.dtype
        # what one position stores: its heads, or (kv_pack_lanes) the
        # same values as rows of 128 lanes; ``unpack`` is the view back
        stored = (kvh * dh // 128, 128) if cfg.kv_pack_lanes else (kvh, dh)
        unpack = lambda t: t.reshape(t.shape[:2] + (kvh, dh))  # noqa: E731
        cached_k = self.variable("cache", "cached_key", jnp.zeros,
                                 (b, max_len) + stored, cache_dtype)
        cached_v = self.variable("cache", "cached_value", jnp.zeros,
                                 (b, max_len) + stored, cache_dtype)
        if quant:
            k_scales = self.variable("cache", "cached_key_scale", jnp.zeros,
                                     (b, max_len, kvh), jnp.float32)
            v_scales = self.variable("cache", "cached_value_scale",
                                     jnp.zeros, (b, max_len, kvh),
                                     jnp.float32)
        cache_index = self.variable("cache", "cache_index",
                                    lambda: jnp.array(0, jnp.int32))
        if not is_init:  # shape-only init pass
            return jnp.zeros((b, l, h, dh), q.dtype)
        per_slot = positions is not None
        paged = page_table is not None
        if paged and not per_slot:
            raise ValueError("page_table requires per-slot positions")
        if per_slot:
            # normalize to the [b, l] window form: [b] is the classic
            # single-token step, [b, l] the speculative verify window
            if positions.ndim == 1:
                if l != 1:
                    raise ValueError(
                        "per-slot decode with positions=[b] is a "
                        "single-token step; got l=%d (pass [b, l] "
                        "positions for a multi-token window)" % l)
                pos2d = positions[:, None]
            elif positions.shape == (b, l):
                pos2d = positions
            else:
                raise ValueError(
                    f"positions shape {positions.shape} does not match "
                    f"the token window ({b}, {l})")
        cur = cache_index.value
        if cfg.positional == "rope":
            # per-slot mode rotates row i's token j at its own position
            # (2-D positions ride a per-row cos/sin in rotary_embedding;
            # padding rows rotate at -1 — junk nothing reads)
            rope_pos = pos2d if per_slot else cur + jnp.arange(l)
            q = rotary_embedding(q, rope_pos, cfg.rope_theta,
                                 cfg.rope_scaling, cfg.rotary_dims)
            k = rotary_embedding(k, rope_pos, cfg.rope_theta,
                                 cfg.rope_scaling, cfg.rotary_dims)
        if quant:
            from tony_tpu.ops.decode import quantize_kv

            k, k_sc = quantize_kv(k)  # quantize-on-write, after RoPE
            v, v_sc = quantize_kv(v)
        k, v = (t.reshape(t.shape[:2] + stored) for t in (k, v))
        if paged:
            # paged scatter: token (i, j) lands in pool page
            # page_table[i, pos // page_size] at offset pos % page_size.
            # Invalid entries — padding (pos < 0), positions past the
            # table's span (budget overshoot), unallocated sentinel
            # table entries (>= n_pages) — are redirected to the
            # explicit out-of-range page index and DROPPED, never
            # clamped: a clamp would overwrite a LIVE page (possibly a
            # copy-on-write page another slot shares).
            pool_k, pool_v = cached_k.value, cached_v.value
            n_pages, ps = pool_k.shape[-4], pool_k.shape[-3]
            span = page_table.shape[1] * ps
            valid = (pos2d >= 0) & (pos2d < span)
            safe = jnp.where(valid, pos2d, 0)
            page = jnp.take_along_axis(page_table, safe // ps, axis=1)
            page = jnp.where(valid, page, n_pages)  # drop via OOB
            off = safe % ps
            if quant:
                k_scales.value = k_scales.value.at[page, off].set(
                    k_sc, mode="drop")
                v_scales.value = v_scales.value.at[page, off].set(
                    v_sc, mode="drop")
            pool_k = pool_k.at[page, off].set(k, mode="drop")
            pool_v = pool_v.at[page, off].set(v, mode="drop")
            cached_k.value = pool_k
            cached_v.value = pool_v
            # gather each row's pages back into the position-ordered
            # [span] view the unpaged buffer would hold (position p =
            # gather index p — identical values, identical reduction).
            # Sentinel entries clamp to page n_pages-1 (the gather's
            # own mode, take_pages): junk the visibility mask hides,
            # same as bucket padding.
            keys = take_pages(pool_k, page_table).reshape(
                b, span, kvh, dh)
            values = take_pages(pool_v, page_table).reshape(
                b, span, kvh, dh)
            if quant:
                ksc = take_pages(k_scales.value, page_table).reshape(
                    b, span, kvh)
                vsc = take_pages(v_scales.value, page_table).reshape(
                    b, span, kvh)
        elif per_slot:
            # scatter each row's tokens at that row's own cache
            # positions (one batched scatter — no per-slot dispatch).
            # Invalid entries (empty slots, window padding: position
            # < 0) are redirected to max_len and DROPPED by the scatter
            # — never clamped: a clamp would overwrite a live position
            # (negative indices wrap in lax scatter, so the redirect
            # must be an explicit positive out-of-range index).
            rows = jnp.arange(b)[:, None]
            write = jnp.where(pos2d >= 0, pos2d, max_len)
            if quant:
                k_scales.value = k_scales.value.at[rows, write].set(
                    k_sc, mode="drop")
                v_scales.value = v_scales.value.at[rows, write].set(
                    v_sc, mode="drop")
            keys = cached_k.value.at[rows, write].set(k, mode="drop")
            values = cached_v.value.at[rows, write].set(v, mode="drop")
            cached_k.value = keys
            cached_v.value = values
            keys, values = unpack(keys), unpack(values)
            # cache_index stays untouched: per-slot lengths live with the
            # caller (serve.SlotCache), not in the shared scalar
        else:
            if quant:
                k_scales.value = jax.lax.dynamic_update_slice(
                    k_scales.value, k_sc, (0, cur, 0))
                v_scales.value = jax.lax.dynamic_update_slice(
                    v_scales.value, v_sc, (0, cur, 0))
            keys = jax.lax.dynamic_update_slice(
                cached_k.value, k, (0, cur, 0, 0))
            values = jax.lax.dynamic_update_slice(
                cached_v.value, v, (0, cur, 0, 0))
            cached_k.value = keys
            cached_v.value = values
            keys, values = unpack(keys), unpack(values)
            cache_index.value = cur + l
        # query positions, [rows, l]: one broadcast row in scalar mode,
        # one row per slot in per-slot mode — the visibility mask below
        # is written once against this shape. In the multi-token window
        # this mask IS the intra-window causal mask: window token j's
        # key sits at pos2d[i, j], visible only to queries at-or-after
        # it; padding queries (pos -1) see nothing.
        q_pos = pos2d if per_slot else (cur + jnp.arange(l))[None, :]
        win = cfg.sliding_window
        if l == 1 and cfg.decode_attention == "flash":
            # the decode hot loop: fused pallas kernel over the (possibly
            # int8) FULL cache buffer — online softmax in VMEM, GQA tiles
            # read once. The kernel masks window/length itself and skips
            # out-of-range blocks' FLOPs via predication, so the einsum
            # path's static window slice (whose odd win+1 span has no
            # legal TPU tile divisor) is neither needed nor wanted here.
            # Per-slot lengths feed straight through: flash_decode takes
            # a [B] length vector and zero-length rows emit exact zeros.
            from tony_tpu.ops.decode import flash_decode

            length = jnp.maximum(pos2d[:, 0] + 1, 0) if per_slot \
                else cur + 1
            out = flash_decode(
                q[:, 0], keys, values, length, window=win,
                k_scale=(ksc if paged else k_scales.value)
                if quant else None,
                v_scale=(vsc if paged else v_scales.value)
                if quant else None)
            return out[:, None].astype(q.dtype)
        if not per_slot and win > 0 and win + l <= max_len:
            # windowed decode: attend over a STATIC (window+l)-sized slice
            # ending at the newest token instead of the whole max_len
            # buffer — per-step attention work drops from O(max_len) to
            # O(window), the same static-shape/no-recompile properties
            # (dynamic_slice start is traced, its size is not)
            span = win + l
            start = jnp.clip(cur + l - span, 0, max_len - span)
            keys_att = jax.lax.dynamic_slice(keys, (0, start, 0, 0),
                                             (b, span, kvh, dh))
            values_att = jax.lax.dynamic_slice(values, (0, start, 0, 0),
                                               (b, span, kvh, dh))
            if quant:
                ks_att = jax.lax.dynamic_slice(k_scales.value, (0, start, 0),
                                               (b, span, kvh))
                vs_att = jax.lax.dynamic_slice(v_scales.value, (0, start, 0),
                                               (b, span, kvh))
            kv_pos = start + jnp.arange(span)
        else:
            keys_att, values_att = keys, values
            if quant:
                ks_att, vs_att = (ksc, vsc) if paged else \
                    (k_scales.value, v_scales.value)
            # size by the BUFFER, not cfg.max_seq_len: the paged
            # engine's bucketed views run this branch with a cache
            # shorter than max_len (every dropped column would have
            # contributed exactly-0.0 softmax weight, so outputs are
            # bit-identical — and the attention read is O(live extent))
            kv_pos = jnp.arange(keys.shape[1])
        # grouped attention: q [b, l, kvh, group, dh] against kv [b, m, kvh, dh]
        qg = q.astype(jnp.float32).reshape(b, l, kvh, group, dh)
        # int8 cache: convert to bf16, not fp32 — int8 magnitudes
        # (<=127) are exact in bf16, the MXU eats bf16 natively, and a
        # convert the scan fails to fuse then materializes HALF the
        # bytes; accumulation stays fp32 via the fp32 q operand
        k_op = keys_att.astype(jnp.bfloat16 if quant else jnp.float32)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_op) / jnp.sqrt(dh)
        if quant:
            # int8 cache: the per-(pos, head) scale distributes over the
            # d-contraction, so apply it to the SMALL score tensor
            # instead of dequantizing the cache — a materialized fp32
            # dequant of the whole cache inside the token scan measured
            # 2.5x per-token slowdown at cache 3584; with this fold the
            # einsum reads the int8 buffer through a FUSED convert
            # (trace-verified: s8 operands feed the score fusion
            # directly). Residual cost at long cache: XLA lowers the
            # single-query contraction as a VPU multiply-reduce (never
            # MXU), and the inline convert slows that VPU loop — see
            # docs/PERF.md's context-dependent --kv-int8 guidance.
            s = s * ks_att.transpose(0, 2, 1)[:, :, None, None, :]
        # [rows, l, span]: rows == 1 (shared positions) broadcasts over
        # the batch; rows == b is the per-slot mask
        visible = kv_pos[None, None, :] <= q_pos[:, :, None]
        if win > 0:
            visible = visible & (q_pos[:, :, None] - kv_pos[None, None, :]
                                 < win)
        s = jnp.where(visible[:, None, None, :, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        if quant:
            # likewise fold the value scale into the probabilities
            p = p * vs_att.transpose(0, 2, 1)[:, :, None, None, :]
        v_op = values_att.astype(jnp.bfloat16 if quant else jnp.float32)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_op)
        return out.reshape(b, l, h, dh).astype(q.dtype)


def latent_attend(q_n, q_r, c_kv, k_r, w_kvb, visible, scale):
    """Latent attention with keys and values MATERIALISED from the cache
    (the prefill form): ``[k_n; v]`` of every head from the latent, then
    plain masked softmax attention. ``q_n`` [b, l, h, nope], ``q_r``
    [b, l, h, rope] (rotated), ``c_kv`` [b, m, kv_rank] (the normed
    latent), ``k_r`` [b, m, rope] (the rotated shared key), ``w_kvb``
    [kv_rank, h, nope + v], ``visible`` [b | 1, l, m]. Returns [b, l, h,
    v]. Scores and softmax are float32; operands stay in the activation
    dtype."""
    nope = q_n.shape[-1]
    kv = jnp.einsum("bmr,rhd->bmhd", c_kv, w_kvb)
    k_n, v = kv[..., :nope], kv[..., nope:]
    s = jnp.einsum("blhd,bmhd->bhlm", q_n, k_n,
                   preferred_element_type=jnp.float32) \
        + jnp.einsum("blhd,bmd->bhlm", q_r, k_r,
                     preferred_element_type=jnp.float32)
    p = jax.nn.softmax(jnp.where(visible[:, None], s * scale, -1e30), axis=-1)
    return jnp.einsum("bhlm,bmhd->blhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q_n.dtype)


def latent_attend_absorbed(q_n, q_r, c_kv, k_r, w_kvb, visible, scale):
    """The same attention with the key and value projections ABSORBED
    (the decode form): the query is folded into the latent space
    (``q_n W_kvb[K]``, per head) and scored against the cached latent
    itself, the rotary part against the shared key; the softmax is
    summed over the latent, and only that [b, l, h, kv_rank] result goes
    through ``W_kvb[V]``. Nothing of shape [b, m, h, ...] is ever
    formed: the step reads the cache's ``kv_rank + rope`` values a
    position and that is all. Same arguments and result as
    ``latent_attend``."""
    nope, f32 = q_n.shape[-1], jnp.float32
    # float32 operands, as Attention's cached path has them: the chip
    # multiplies in bfloat16 at default precision either way and the
    # converts fuse into the products; the CPU has no mixed product
    w_k, w_v = w_kvb[..., :nope].astype(f32), w_kvb[..., nope:].astype(f32)
    c_kv = c_kv.astype(f32)
    q_lat = jnp.einsum("blhd,rhd->blhr", q_n.astype(f32), w_k)
    s = jnp.einsum("blhr,bmr->bhlm", q_lat, c_kv) \
        + jnp.einsum("blhd,bmd->bhlm", q_r.astype(f32), k_r.astype(f32))
    p = jax.nn.softmax(jnp.where(visible[:, None], s * scale, -1e30), axis=-1)
    o_lat = jnp.einsum("bhlm,bmr->blhr", p, c_kv)
    return jnp.einsum("blhr,rhd->blhd", o_lat, w_v).astype(q_n.dtype)


class LatentAttention(nn.Module):
    """Multi-head latent attention (``cfg.latent``). With x the normed
    input: ``c_q = RMSNorm(x W_qa)``, ``[q_n; q_r] = c_q W_qb`` per head;
    ``[c_kv; k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``, ``k_r`` rotated
    (one rotary key for all heads); ``[k_n; v] = c_kv W_kvb`` per head;
    score ``(q_n . k_n + RoPE(q_r) . k_r) * scale``, causal softmax in
    float32, then ``W_o``.

    The cache holds ``kv_rank + rope`` values a token a layer and no
    per-head key or value: ``cached_latent`` [b, max_len, kv_rank]
    (``c_kv`` after its norm) and ``cached_rope_key`` [b, max_len, rope]
    (``k_r`` after RoPE); page pools [n_pages, page_size, ...] when
    paged. Two leaves and not one of their joint width: a minor
    dimension that is no multiple of the TPU's 128 lanes makes the
    compiler store the pool pages-minor, and every step then transposes
    the whole pool in and out (compiled for the v5e at kv_rank 512, rope
    64: one copy of the pool a layer each way). The three write modes
    are ``Attention._decode_attention``'s (shared ``cache_index``;
    per-slot ``positions``; per-slot through a ``page_table``, where
    sentinel pages and padding drop), and so is the visibility mask. A
    window of several tokens (prefill, a chunk of one) materialises
    keys and values from the latent (``latent_attend``); a single-token
    step runs absorbed (``latent_attend_absorbed``)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, decode: bool = False, segment_ids=None,
                 positions=None, page_table=None):
        cfg, la = self.cfg, self.cfg.latent
        if segment_ids is not None:
            raise ValueError("segment_ids are not implemented for latent "
                             "attention")
        b, l, _ = x.shape
        h = cfg.n_heads
        init = nn.initializers.normal(0.02)
        dense = lambda name, feats: nn.DenseGeneral(  # noqa: E731
            feats, axis=-1, use_bias=False, dtype=cfg.dtype,
            param_dtype=jnp.float32, name=name, kernel_init=init)
        norm = lambda name: RMSNorm(cfg.dtype, cfg.norm_eps,  # noqa: E731
                                    name=name)
        q = dense("q_b", (h, la.nope_dim + la.rope_dim))(
            norm("q_norm")(dense("q_a", la.q_rank)(x)))
        q_n, q_r = q[..., :la.nope_dim], q[..., la.nope_dim:]
        kv = dense("kv_a", la.cache_width)(x)
        c_kv, k_r = norm("kv_norm")(kv[..., :la.kv_rank]), kv[..., la.kv_rank:]
        w_kvb = self.param("kv_b", init, (la.kv_rank, h,
                                          la.nope_dim + la.v_dim),
                           jnp.float32).astype(cfg.dtype)
        scale = la.scale_mult * (la.nope_dim + la.rope_dim) ** -0.5
        rope = lambda t, pos: rotary_embedding(  # noqa: E731
            t, pos, cfg.rope_theta, cfg.rope_scaling)
        if decode:
            out = self._cached(q_n, q_r, c_kv, k_r, w_kvb, scale, rope,
                               positions, page_table)
        else:
            pos = jnp.arange(l)
            with jax.named_scope("mla.prefill"):
                out = latent_attend(
                    q_n, rope(q_r, pos), c_kv,
                    rope(k_r[:, :, None], pos)[:, :, 0], w_kvb,
                    (pos[None, :] <= pos[:, None])[None], scale)
        return nn.DenseGeneral(
            cfg.d_model, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
            param_dtype=jnp.float32, name="o", kernel_init=init)(out)

    def _cached(self, q_n, q_r, c_kv, k_r, w_kvb, scale, rope, positions,
                page_table):
        cfg, la = self.cfg, self.cfg.latent
        b, l, h, _ = q_n.shape
        max_len = cfg.max_seq_len
        is_init = self.has_variable("cache", "cached_latent")
        leaves = [self.variable("cache", name, jnp.zeros,
                                (b, max_len, width), c_kv.dtype)
                  for name, width in (("cached_latent", la.kv_rank),
                                      ("cached_rope_key", la.rope_dim))]
        cache_index = self.variable("cache", "cache_index",
                                    lambda: jnp.array(0, jnp.int32))
        if not is_init:  # shape-only init pass
            return jnp.zeros((b, l, h, la.v_dim), q_n.dtype)
        per_slot = positions is not None
        if page_table is not None and not per_slot:
            raise ValueError("page_table requires per-slot positions")
        cur = cache_index.value
        if per_slot:
            pos2d = positions[:, None] if positions.ndim == 1 else positions
            if pos2d.shape != (b, l):
                raise ValueError(
                    f"positions shape {positions.shape} does not match "
                    f"the token window ({b}, {l})")
        q_pos = pos2d if per_slot else (cur + jnp.arange(l))[None, :]
        rope_pos = pos2d if per_slot else cur + jnp.arange(l)
        q_r = rope(q_r, rope_pos)
        new = (c_kv, rope(k_r[:, :, None], rope_pos)[:, :, 0])
        if page_table is not None:
            # the paged scatter and the position-ordered gather of
            # Attention._decode_attention, over these two leaves
            # (sentinel entries clamp by the gather's own mode)
            n_pages, ps = leaves[0].value.shape[-3:-1]
            span = page_table.shape[1] * ps
            valid = (pos2d >= 0) & (pos2d < span)
            safe = jnp.where(valid, pos2d, 0)
            page = jnp.take_along_axis(page_table, safe // ps, axis=1)
            page = jnp.where(valid, page, n_pages)  # drop via OOB
            seen = []
            for leaf, val in zip(leaves, new):
                leaf.value = leaf.value.at[page, safe % ps].set(
                    val, mode="drop")
                seen.append(take_pages(leaf.value, page_table).reshape(
                    b, span, -1))
        elif per_slot:
            rows = jnp.arange(b)[:, None]
            write = jnp.where(pos2d >= 0, pos2d, max_len)  # drop, never clamp
            for leaf, val in zip(leaves, new):
                leaf.value = leaf.value.at[rows, write].set(val, mode="drop")
            seen = [leaf.value for leaf in leaves]
        else:
            for leaf, val in zip(leaves, new):
                leaf.value = jax.lax.dynamic_update_slice(
                    leaf.value, val, (0, cur, 0))
            seen = [leaf.value for leaf in leaves]
            cache_index.value = cur + l
        # sized by the BUFFER: the engine's bucketed views are shorter
        # than max_len, and a masked column weighs exactly 0.0
        kv_pos = jnp.arange(seen[0].shape[1])
        visible = kv_pos[None, None, :] <= q_pos[:, :, None]
        if l == 1:
            with jax.named_scope("mla.absorb"):
                return latent_attend_absorbed(q_n, q_r, *seen, w_kvb,
                                              visible, scale)
        with jax.named_scope("mla.prefill"):
            return latent_attend(q_n, q_r, *seen, w_kvb, visible, scale)


class ShortConv(nn.Module):
    """A gated short causal convolution in the place of attention
    (``cfg.layer_types[i] == "conv"``). With x the normed input:
    ``[B, C, u] = split3(x W_in)``, ``z = B * u``, ``y_t = sum_j w[:, j]
    * z_{t-(K-1)+j}`` a channel (``K = cfg.conv_kernel`` taps, zeros
    before the sequence's start), ``out = (C * y) W_out``. No bias.

    What a sequence carries from one call to the next is ``z`` at its
    last ``K - 1`` positions and nothing that grows with its length:
    the cache variable ``conv_state`` [b, K-1, d] in the model's dtype,
    a row a SLOT (``serve/slots.slot_resident``), never pages. One
    arithmetic, three ways in:

    - ``decode=False``: a whole row, shifted adds; rows packed with
      ``segment_ids`` take zeros across a boundary.
    - ``decode=True``, ``positions`` None (``generate``): the window
      continues the state and leaves its own last ``K - 1`` positions.
    - ``decode=True`` with per-slot ``positions`` [b] or [b, l] (the
      serving engine; a window's padding sits at its END with position
      -1): a predecessor is taken by POSITION, so a token at position
      ``p < k`` reads zero for ``z_{p-k}`` whatever the slot's last
      tenant left, and the state handed on is ``z`` at the row's last
      ``K - 1`` REAL positions, never the window's end. A row with no
      real position (an empty or frozen slot) keeps its state.

    ``page_table`` is accepted and unused: the engine hands a one-row
    window the row of ITS slot (``slots.slot_rows``)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, decode: bool = False, segment_ids=None,
                 positions=None, page_table=None):
        cfg = self.cfg
        b, l, d = x.shape
        taps = cfg.conv_kernel
        dense = lambda name, feats: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=jnp.float32, name=name,
            kernel_init=nn.initializers.normal(0.02))
        with jax.named_scope("conv.in"):
            gate_b, gate_c, u = jnp.split(dense("in_proj", 3 * d)(x), 3,
                                          axis=-1)
            z = gate_b * u
        w = self.param("kernel", nn.initializers.normal(0.02), (d, taps),
                       jnp.float32)
        with jax.named_scope("conv.mix"):
            if decode:
                y = self._continued(z, w, positions)
            else:
                y = _causal_taps(z, w, segment_ids=segment_ids)
        with jax.named_scope("conv.out"):
            return dense("out_proj", d)(gate_c * y.astype(cfg.dtype))

    def _continued(self, z, w, positions):
        b, l, d = z.shape
        keep = self.cfg.conv_kernel - 1
        is_init = self.has_variable("cache", "conv_state")
        state = self.variable("cache", "conv_state", jnp.zeros,
                              (b, keep, d), z.dtype)
        if not is_init:  # shape-only init pass
            return jnp.zeros((b, l, d), jnp.float32)
        if state.value.shape[0] != b:
            raise ValueError(
                f"conv_state holds {state.value.shape[0]} rows, the window "
                f"{b}: a slot-resident leaf goes to a one-row window as its "
                "slot's row (serve/slots.slot_rows)")
        ext = jnp.concatenate([state.value, z], axis=1)  # [b, keep + l, d]
        if positions is None:
            state.value = ext[:, l:]
            return _causal_taps(ext, w)[:, keep:]
        pos2d = positions[:, None] if positions.ndim == 1 else positions
        if pos2d.shape != (b, l):
            raise ValueError(
                f"positions shape {positions.shape} does not match "
                f"the token window ({b}, {l})")
        y = _causal_taps(ext, w, positions=pos2d)[:, keep:]
        n_real = jnp.sum(pos2d >= 0, axis=1)
        if l == 1:  # the decode step: shift one in, or stand still
            state.value = jnp.where((n_real > 0)[:, None, None],
                                    ext[:, 1:], state.value)
        else:
            at = n_real[:, None] + jnp.arange(keep)[None, :]
            state.value = jnp.take_along_axis(ext, at[:, :, None], axis=1)
        return y


def _causal_taps(z, w, *, segment_ids=None, positions=None):
    """``y_t = sum_j w[:, j] * z_{t-(K-1)+j}`` along axis 1 of ``z`` [b,
    n, d] (float32 sums), ``w`` [d, K]: K - 1 shifted adds, zeros before
    index 0. ``segment_ids`` [b, n] zeroes a predecessor of another
    segment. ``positions`` [b, l] are those of the LAST ``l`` rows of
    ``z`` (the rows before them are carried state): a predecessor ``k``
    back counts only where ``positions - k >= 0``."""
    n, taps = z.shape[1], w.shape[1]
    z32 = z.astype(jnp.float32)
    y = z32 * w[:, taps - 1]
    for k in range(1, taps):
        back = jnp.pad(z32, ((0, 0), (k, 0), (0, 0)))[:, :n]
        if segment_ids is not None:
            same = jnp.pad(segment_ids, ((0, 0), (k, 0)),
                           constant_values=-1)[:, :n] == segment_ids
            back = jnp.where(same[..., None], back, 0.0)
        if positions is not None:
            real = jnp.pad(positions >= k,
                           ((0, 0), (n - positions.shape[1], 0)))
            back = jnp.where(real[..., None], back, 0.0)
        y = y + back * w[:, taps - 1 - k]
    return y


def _q8_shard_axes(cfg: TransformerConfig, name: str) -> tuple:
    """(in_axis, out_axis) mesh axes for a QuantDense, mirroring the
    'tp' preset's logical rules in logical_axis_rules_tree: q/wi/wg
    column-parallel on heads/mlp, o/wo row-parallel, GQA k/v replicated
    (kv_heads must never split over a bigger tensor axis). Falls back to
    replication when the dim does not divide the axis."""
    from tony_tpu.parallel.mesh import TENSOR

    mesh = cfg.mesh
    if mesh is None or mesh.shape.get(TENSOR, 1) <= 1:
        return (None, None)
    t = mesh.shape[TENSOR]
    heads_ok = cfg.n_heads % t == 0
    ff_ok = cfg.d_ff % t == 0
    if name == "q":
        return (None, TENSOR) if heads_ok else (None, None)
    if name in ("k", "v"):
        grouped = cfg.kv_heads != cfg.n_heads
        return (None, TENSOR) if (not grouped and heads_ok) \
            else (None, None)
    if name == "o":
        return (TENSOR, None) if heads_ok else (None, None)
    if name in ("wi", "wg"):
        return (None, TENSOR) if ff_ok else (None, None)
    if name == "wo":
        return (TENSOR, None) if ff_ok else (None, None)
    return (None, None)


class QuantDense(nn.Module):
    """int8 weight-only dense for SERVING (``cfg.quantized``): parameters
    are the converter's ``{kernel_q8 int8 [in_flat, out_flat], scale
    [out_flat], bias?}`` (see ``models.quantize``); the matmul runs
    through the pallas dequant kernel, so HBM traffic for weights is
    int8 — the decode-path bandwidth win (docs/PERF.md). Multi-dim
    in/out axes (head projections) flatten around the 2-D kernel.

    Tensor parallelism: GSPMD cannot see inside a pallas call, so a
    tensor-sharded q8 kernel would be silently all-gathered. When
    ``mesh`` is set, ``shard_axes=(in_axis, out_axis)`` runs the kernel
    under shard_map manual ONLY over those mesh axes (everything else —
    data/fsdp batch sharding — stays under automatic propagation):
    column-parallel (out_axis) shards are independent; row-parallel
    (in_axis, the Megatron o/wo layout) psums partial products — the
    per-output-channel scale distributes over the contraction sum."""

    features: tuple
    in_axes: int = 1
    use_bias: bool = False
    dtype: Any = jnp.bfloat16
    mesh: Any = None
    shard_axes: tuple = (None, None)

    @nn.compact
    def __call__(self, x):
        from tony_tpu.ops.quant import q8_matmul

        feats = self.features if isinstance(self.features, tuple) \
            else (self.features,)
        in_flat = 1
        for s in x.shape[-self.in_axes:]:
            in_flat *= s
        out_flat = 1
        for s in feats:
            out_flat *= s
        w_q = self.param("kernel_q8", nn.initializers.zeros,
                         (in_flat, out_flat), jnp.int8)
        scale = self.param("scale", nn.initializers.ones, (out_flat,),
                           jnp.float32)
        lead = x.shape[:-self.in_axes]
        x2 = x.reshape(-1, in_flat).astype(self.dtype)
        in_ax, out_ax = self.shard_axes
        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P

            from tony_tpu.parallel.mesh import DATA, FSDP
            from jax import shard_map

            # manual over the WHOLE mesh (partial-manual shard_map needs
            # explicit-type meshes): batch rows ride the data/fsdp axes
            # when they divide, so dp x tp serving keeps its batch shards
            import math

            baxes = tuple(a for a in (DATA, FSDP)
                          if self.mesh.shape.get(a, 1) > 1)
            bsize = math.prod(self.mesh.shape[a] for a in baxes) \
                if baxes else 1
            bspec = baxes if baxes and x2.shape[0] % bsize == 0 else None

            def local(xl, wl, sl):
                y = q8_matmul(xl, wl, sl)
                return jax.lax.psum(y, in_ax) if in_ax else y

            y = shard_map(
                local, mesh=self.mesh,
                in_specs=(P(bspec, in_ax), P(in_ax, out_ax), P(out_ax)),
                out_specs=P(bspec, out_ax),
                check_vma=False,
            )(x2, w_q, scale)
        else:
            y = q8_matmul(x2, w_q, scale)
        y = y.reshape(*lead, *feats)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros, feats,
                              jnp.float32)
            y = y + bias.astype(self.dtype)
        return y


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        if cfg.quantized:
            dense = lambda name, feats: QuantDense(  # noqa: E731
                (feats,), use_bias=cfg.use_bias, dtype=cfg.dtype, name=name,
                mesh=cfg.mesh, shard_axes=_q8_shard_axes(cfg, name))
        else:
            dense = lambda name, feats: nn.Dense(  # noqa: E731
                feats, use_bias=cfg.use_bias, dtype=cfg.dtype,
                param_dtype=jnp.float32, name=name,
                kernel_init=nn.initializers.normal(0.02))
        h = _activation(cfg)(dense("wi" if not cfg.gated_mlp else "wg",
                                   cfg.d_ff)(x))
        if cfg.gated_mlp:
            # SwiGLU: the gate rides the same [B,L,ff] tile as wi's output,
            # so XLA fuses the elementwise product into the matmul epilogue
            h = h * dense("wi", cfg.d_ff)(x)
        # serve-shard pins: wo contracts over the mlp dim h is sharded
        # on — gather h whole first; wo's output is embed-sharded (the
        # row-parallel flip) — gather it before the residual/norm
        h = _serve_replicate(cfg, h)
        return _serve_replicate(cfg, dense("wo", cfg.d_model)(h))


class MoEMLP(nn.Module):
    """Expert-parallel FFN: router + per-expert wi/wo with a leading expert
    dim (sharded on the ``expert`` mesh axis under pjit — the dispatch and
    combine einsums lower to all-to-all over ICI, see parallel/moe.py).

    The load-balancing auxiliary loss is sown into the ``losses`` collection.
    It is NOT applied automatically: your ``apply_fn`` must run
    ``logits, mut = model.apply(params, tokens, mutable=["losses"])`` and add
    ``moe_aux_loss(mut["losses"])`` to the objective, or the router trains
    unregularized and can collapse onto a few experts. Plain
    ``model.apply(params, tokens)`` still works for inference (sow no-ops).
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from tony_tpu.parallel.moe import MoEConfig, moe_layer

        cfg = self.cfg
        d_ff = cfg.moe_d_ff or cfg.d_ff
        moe_cfg = MoEConfig(
            num_experts=cfg.moe_num_experts,
            capacity_factor=cfg.moe_capacity_factor,
            top_k=cfg.moe_top_k,
            d_model=cfg.d_model,
            d_ff=d_ff,
            gated=cfg.moe_gated,
            activation=cfg.moe_activation,
            renormalize_top_k=cfg.moe_renormalize,
            dropless=cfg.moe_dropless,
            # int8 + EP serving: with cfg.mesh carrying an expert axis,
            # the q8 expert FFN runs shard-mapped over it so quantized
            # expert weights SHARD instead of replicating (the 47B-
            # Mixtral-on-a-slice requirement; see parallel/moe.py)
            mesh=cfg.mesh,
        )
        init = nn.initializers.normal(0.02)
        e = cfg.moe_num_experts
        params = {"router": self.param("router", init,
                                       (cfg.d_model, e), jnp.float32)}
        names = ("wi", "wg", "wo") if cfg.moe_gated else ("wi", "wo")
        for nm in names:
            shp = (e, d_ff, cfg.d_model) if nm == "wo" \
                else (e, cfg.d_model, d_ff)
            if cfg.quantized:
                # int8 expert weights + per-(expert, out-channel) scales
                # (models/quantize.py Mixtral conversion)
                params[nm + "_q8"] = self.param(
                    nm + "_q8", nn.initializers.zeros, shp, jnp.int8)
                params[nm + "_scale"] = self.param(
                    nm + "_scale", nn.initializers.ones, (shp[0], shp[2]),
                    jnp.float32)
            else:
                params[nm] = self.param(nm, init, shp, jnp.float32)
        # experts compute in cfg.dtype (bf16 on TPU); the router stays fp32 —
        # bf16 routing logits quantize near-tied gate probabilities and flip
        # top-k choices step to step, destabilizing load balancing. int8
        # leaves and their fp32 scales pass through untouched (the pallas
        # dequant matmul owns the cast).
        cast = {k: (v if k == "router" or v.dtype == jnp.int8
                    or k.endswith("_scale") else v.astype(cfg.dtype))
                for k, v in params.items()}
        out, aux = moe_layer(cast, x, moe_cfg)
        if not self.is_initializing():
            # sowing during init would put a "losses" collection into the
            # init() output, which callers then pass around as if it were
            # params (and would double-count: apply(mutable=["losses"])
            # seeds the collection from the input before sow appends)
            self.sow("losses", "moe_aux", aux.astype(jnp.float32))
        # serve-shard pin (the dense-MLP wo rule, MoE flavor). NOTE:
        # the expert-parallel combine itself sums expert outputs across
        # the expert axis, so MoE serving under expert>1 is exact-
        # correct but NOT pinned bitwise vs single-chip — the dense
        # transformer is (docs/SERVING.md).
        return _serve_replicate(cfg, out.astype(cfg.dtype))


class RoutedMLP(nn.Module):
    """This chip's share of a routed expert layer (``cfg.routed``,
    parallel/moe.py ``routed_share``) plus the shared expert: the
    router keeps its published width, the expert leaves ``wg``/``wi``
    [held, d, f] and ``wo`` [held, f, d] hold only the experts that live
    here; ``expert_bias`` [n_routed] float32 where the routing has a
    selection bias. ``live`` [b, l] keeps padding and empty slots out of the
    routing (they neither count nor touch an expert). Outside ``init``
    the layer sows its counts (``routed_share``) into the
    ``moe_stats`` collection, summed over the routed layers of the call;
    a caller that does not make the collection mutable pays nothing."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, live=None):
        cfg, rc = self.cfg, self.cfg.routed
        b, l, d = x.shape
        init = nn.initializers.normal(0.02)
        n_held = rc.held[1]
        leaf = lambda name, shape: self.param(  # noqa: E731
            name, init, shape, jnp.float32).astype(cfg.dtype)
        router = leaf("router", (d, rc.n_routed))
        wg, wi = (leaf(n, (n_held, d, rc.d_ff)) for n in ("wg", "wi"))
        wo = leaf("wo", (n_held, rc.d_ff, d))
        bias = self.param("expert_bias", nn.initializers.zeros,
                          (rc.n_routed,), jnp.float32) \
            if rc.selection_bias else None
        y, counts = routed_share(
            x.reshape(b * l, d), router, wg, wi, wo, rc,
            None if live is None else live.reshape(b * l), bias=bias)
        if not self.is_initializing():
            self.sow("moe_stats", "counts", counts,
                     reduce_fn=lambda acc, c: acc + c,
                     init_fn=lambda: jnp.zeros((N_COUNTS,), jnp.int32))
        y = y.reshape(b, l, d)
        if rc.shared_d_ff:
            with jax.named_scope("moe.shared"):
                y = y + MLP(replace(cfg, d_ff=rc.shared_d_ff),
                            name="shared")(x)
        return y.astype(cfg.dtype)


class Block(nn.Module):
    cfg: TransformerConfig
    use_moe: bool = False
    use_routed: bool = False
    layer: int = 0  # picks the mixer where ``cfg.layer_types`` names them

    @nn.compact
    def __call__(self, x, decode: bool = False, segment_ids=None,
                 positions=None, page_table=None):
        attn_cls = Attention if self.cfg.latent is None else LatentAttention
        conv = bool(self.cfg.layer_types) \
            and self.cfg.layer_types[self.layer] == "conv"
        mixer = ShortConv(self.cfg, name="conv") if conv \
            else attn_cls(self.cfg, name="attn")
        attn_out = mixer(
            make_norm(self.cfg, "ln1")(x), decode=decode,
            segment_ids=segment_ids, positions=positions,
            page_table=page_table)
        if self.use_routed:
            live = None if positions is None \
                else (positions >= 0).reshape(x.shape[0], -1)
            x = x + attn_out
            return x + RoutedMLP(self.cfg, name="moe")(
                make_norm(self.cfg, "ln2")(x), live)
        ffn_cls = MoEMLP if self.use_moe else MLP
        if (self.cfg.remat and not decode
                and self.cfg.remat_policy == "attn_saved"):
            # attn_saved: attention (above) stays un-rematted — its
            # custom-vjp residuals are saved, the flash forward never
            # re-runs — and only the FFN pays the remat pass, with its
            # dot outputs kept
            ffn_cls = nn.remat(
                ffn_cls, policy=jax.checkpoint_policies.dots_saveable)
        ffn = ffn_cls(self.cfg, name="moe" if self.use_moe else "mlp")
        if self.cfg.parallel_residual:
            # GPT-NeoX: both sublayers read the block INPUT; one residual
            # add (fuses into a single elementwise epilogue on TPU)
            return x + attn_out + ffn(make_norm(self.cfg, "ln2")(x))
        x = x + attn_out
        return x + ffn(make_norm(self.cfg, "ln2")(x))


_STRUCTURAL = "structural"  # attn_saved: remat applied inside Block


def _remat_policy(cfg: TransformerConfig):
    """Map cfg.remat_policy to a jax.checkpoint policy, or _STRUCTURAL
    for attn_saved (see the TransformerConfig field comment)."""
    try:
        return {
            "nothing": jax.checkpoint_policies.nothing_saveable,
            "dots": jax.checkpoint_policies.dots_saveable,
            "attn_saved": _STRUCTURAL,
        }[cfg.remat_policy]
    except KeyError:
        raise ValueError(
            f"unknown remat_policy {cfg.remat_policy!r}; "
            "expected 'nothing', 'dots' or 'attn_saved'") from None


class _ScanBody(nn.Module):
    """Block adapted to nn.scan's (carry, out) body signature."""

    cfg: TransformerConfig
    decode: bool = False

    @nn.compact
    def __call__(self, x, segment_ids, positions, page_table):
        return Block(self.cfg, name="block")(
            x, self.decode, segment_ids=segment_ids,
            positions=positions, page_table=page_table), None


class Transformer(nn.Module):
    cfg: TransformerConfig

    def _learned_positions(self, l: int, decode: bool, positions=None):
        """GPT-2-style absolute position embeddings. In decode mode a
        top-level cache counter tracks the current offset (the per-layer
        attention cache keeps its own; they advance in lockstep). Per-slot
        decode (``positions`` [b]) reads each row's own offset and leaves
        the shared counter untouched — slot lengths live with the caller."""
        cfg = self.cfg
        pos_emb = self.param("pos_embedding", nn.initializers.normal(0.02),
                             (cfg.max_seq_len, cfg.d_model), jnp.float32)
        if decode:
            is_init = self.has_variable("cache", "pos_index")
            pos_index = self.variable("cache", "pos_index",
                                      lambda: jnp.array(0, jnp.int32))
            if positions is not None:
                # declared-but-unchanged pos_index keeps the mutated cache
                # tree congruent with the carried one across serve steps.
                # [b] = single-token step -> [b, 1, d]; [b, l] = multi-
                # token verify window -> [b, l, d] (clipped padding rows
                # read a junk embedding nothing consumes)
                rows = jnp.clip(positions, 0, cfg.max_seq_len - 1)
                emb = pos_emb[rows]
                if positions.ndim == 1:
                    emb = emb[:, None]
                return emb.astype(cfg.dtype)
            if is_init:
                pos = pos_index.value + jnp.arange(l)
                pos_index.value = pos_index.value + l
            else:
                pos = jnp.arange(l)
        else:
            pos = jnp.arange(l)
        return pos_emb[pos][None].astype(cfg.dtype)

    def _scan_blocks(self, x, decode: bool, segment_ids=None,
                     positions=None, page_table=None):
        cfg = self.cfg
        body = _ScanBody
        if cfg.remat and not decode:
            policy = _remat_policy(cfg)
            if policy is not _STRUCTURAL:  # attn_saved remats inside Block
                body = nn.remat(_ScanBody, policy=policy)
        scanned = nn.scan(
            body,
            variable_axes={"params": 0, "cache": 0},
            split_rngs={"params": True},
            in_axes=nn.broadcast,  # segment_ids/positions/page_table:
            length=cfg.n_layers,   # same every layer
            metadata_params={nn.PARTITION_NAME: "layers"},
        )
        x, _ = scanned(cfg, decode, name="layers")(x, segment_ids,
                                                   positions, page_table)
        return x

    @nn.compact
    def __call__(self, tokens, decode: bool = False,
                 return_hidden: bool = False, segment_ids=None,
                 positions=None, page_table=None):
        """return_hidden=True yields the final [B, L, D] activations
        (post ln_f) instead of logits, for the chunked large-vocab loss
        (ops.xent.chunked_cross_entropy with params["embedding"]) — the
        [B, L, V] logits tensor is never materialized.

        segment_ids [B, L] (packed-document training): attention is
        restricted to same-segment keys, so documents packed into one
        window never leak into each other. Training-path only (decode
        caches have no segment notion); reference/blockwise/pallas
        backends (the pallas kernels stream the ids as blocked operands).

        positions [B] or [B, L] int32 (decode-only): PER-SLOT decode
        for the continuous-batching server (serve/) — each batch row is
        an independent cache slot at its own position; negative = empty
        slot. [B, L] is the multi-token window (speculative verify):
        row i's token j sits at positions[i, j]; negative entries are
        dropped padding. See Attention._decode_attention.

        page_table [B, max_pages] int32 (decode + positions only):
        the PAGED cache layout — cache leaves are page pools
        [n_pages, page_size, kvh, dh] (serve/slots.PagePool) and row
        i's positions map through its page table; see
        Attention._decode_attention."""
        if segment_ids is not None and decode:
            raise ValueError("segment_ids are a training-path feature; "
                             "decode has no segment notion")
        if positions is not None and not decode:
            raise ValueError("positions (per-slot decode) requires "
                             "decode=True")
        if page_table is not None and positions is None:
            raise ValueError("page_table (paged KV cache) requires "
                             "per-slot positions")
        cfg = self.cfg
        embed = self.param("embedding", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.d_model), jnp.float32)
        x = embed[tokens].astype(cfg.dtype)
        if cfg.embed_scale:
            # in activation dtype, matching HF Gemma's normalizer cast
            x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.dtype)
        if cfg.positional == "learned":
            x = x + self._learned_positions(tokens.shape[1], decode,
                                            positions)
        if cfg.scan_layers:
            x = self._scan_blocks(x, decode, segment_ids, positions,
                                  page_table)
        else:
            block = Block
            if cfg.remat and not decode:
                policy = _remat_policy(cfg)
                if policy is not _STRUCTURAL:
                    block = nn.remat(Block, static_argnums=(2,),
                                     policy=policy)
            for i in range(cfg.n_layers):
                use_moe = cfg.moe_every > 0 and (i + 1) % cfg.moe_every == 0
                use_routed = cfg.routed is not None \
                    and i >= cfg.routed.first_dense
                x = block(cfg, use_moe=use_moe, use_routed=use_routed,
                          layer=i, name=f"block_{i}")(
                    x, decode, segment_ids=segment_ids, positions=positions,
                    page_table=page_table)
        x = make_norm(cfg, "ln_f")(x)
        if not cfg.tied_embeddings:
            head = self.param("lm_head", nn.initializers.normal(0.02),
                              (cfg.vocab_size, cfg.d_model), jnp.float32)
        # created BEFORE the return_hidden branch (like lm_head) so init
        # yields the full param set regardless of mode
        head_bias = self.param(
            "lm_head_bias", nn.initializers.zeros, (cfg.vocab_size,),
            jnp.float32) if cfg.lm_head_bias else None
        if return_hidden:
            # chunked large-vocab loss: pair with params["lm_head"] when
            # untied, params["embedding"] when tied (ops.xent) — and pass
            # params["lm_head_bias"] as its bias= when configured.
            return x.astype(jnp.float32)
        head = embed if cfg.tied_embeddings else head
        logits = jnp.einsum("bld,vd->blv", x.astype(jnp.float32), head)
        if head_bias is not None:
            logits = logits + head_bias
        return logits


def logical_axis_rules_tree(params: Any) -> Any:
    """Best-effort logical axes for the transformer param tree, consumed by
    parallel.sharding.tree_shardings. Derived from param path names."""
    # Pre-scan head counts: a GQA K/V kernel has fewer heads (dim 1) than
    # its sibling q kernel and must get the always-replicated "kv_heads"
    # axis (splitting n_kv_heads over a larger tensor axis would fail);
    # full-MHA K/V keeps "heads" and stays tensor-shardable.
    def is_stacked(joined: str) -> bool:
        # scan_layers params live under ".../layers/block/..." with a
        # leading stacked dim (one slice per layer)
        return "/layers/" in joined

    head_counts: dict[str, int] = {}
    q8_out: dict[str, int] = {}  # attn parent -> q kernel_q8 out_flat
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        joined = "/" + "/".join(getattr(p, "key", str(p)) for p in path)
        off = 1 if is_stacked(joined) else 0
        if "/q/" in joined and getattr(leaf, "ndim", 0) == 3 + off:
            head_counts[joined.rsplit("/q/", 1)[0]] = leaf.shape[1 + off]
        if joined.endswith("/q/kernel/b") and \
                getattr(leaf, "ndim", 0) == 3 + off:
            # LoRA trees carry no bare q kernel; B [r, h, dh] has the count
            head_counts[joined.rsplit("/q/", 1)[0]] = leaf.shape[1 + off]
        if joined.endswith("/q/kernel_q8"):
            q8_out[joined.rsplit("/q/", 1)[0]] = leaf.shape[-1]

    def bias_axes(joined: str, x, off: int, leaf_dims: int) -> tuple:
        # use_bias=True (GPT-2 family): biases shard like their kernel's
        # OUTPUT dims — q/k/v [h, dh], o/wo [d_model], wi [d_ff]
        if "/q/" in joined:
            return ("heads", "kv")[:leaf_dims]
        for s in ("/k/", "/v/"):
            if s in joined:
                parent = joined.rsplit(s, 1)[0]
                grouped = (leaf_dims == 2 and x.shape[off] !=
                           head_counts.get(parent, x.shape[off]))
                return ("kv_heads" if grouped else "heads",
                        "kv")[:leaf_dims]
        if "/o/" in joined or "/wo/" in joined:
            return ("embed",)
        if "/wi/" in joined or "/wg/" in joined:
            return ("mlp",)
        return tuple([None] * leaf_dims)  # norm biases etc: replicated

    def axes_for(path: tuple, x) -> tuple:
        joined = "/" + "/".join(getattr(p, "key", str(p)) for p in path)
        off = 1 if is_stacked(joined) else 0
        leaf_dims = x.ndim - off
        base: tuple
        def _q8_dense_name() -> str | None:
            # QuantDense leaves: .../<dense>/kernel_q8 and .../<dense>/scale
            # (norm layers also own a "scale" param — only dense parents
            # count). Returns the dense module name or None.
            parts = joined.rsplit("/", 2)
            if len(parts) == 3 and parts[2] in ("kernel_q8", "scale") \
                    and parts[1] in ("q", "k", "v", "o", "wi", "wg", "wo"):
                return parts[1]
            return None

        q8name = _q8_dense_name()
        if q8name is not None:
            # int8 serving leaves shard on the SAME logical axes as their
            # bf16 kernels, on the flattened dims: out_flat carries the
            # kernel's leading output axis ("heads"/"mlp"/"embed"), which
            # QuantDense's shard_map branch runs as shard-local
            # column-parallel pallas calls; o/wo in_flat carries the
            # row-parallel axis (psum over partial products).
            # GQA k/v (smaller out_flat than q) keep the always-replicated
            # "kv_heads" so a big tensor axis never splits n_kv_heads.
            parent = joined.rsplit("/", 2)[0]
            if q8name in ("k", "v"):
                q_out = q8_out.get(parent)
                grouped = q_out is not None and x.shape[-1] != q_out
                out_ax = "kv_heads" if grouped else "heads"
            else:
                out_ax = {"q": "heads", "o": "embed", "wi": "mlp",
                          "wg": "mlp", "wo": "embed"}[q8name]
            in_ax = {"q": "embed", "k": "embed", "v": "embed",
                     "o": "heads", "wi": "embed", "wg": "embed",
                     "wo": "mlp"}[q8name]
            base = (in_ax, out_ax) if joined.endswith("/kernel_q8") \
                else (out_ax,)
            return ("layers",) + base if off else base
        if joined.endswith(("/kernel/a", "/kernel/b")):
            # LoRA adapters: A [in, r] shards its input dim like the host
            # kernel's input; B [r, *out] carries the kernel's output axes
            # (rank stays replicated — it is tiny)
            kj = joined[: -2]  # .../kernel
            if "/q/" in kj:
                kin, kout = "embed", ("heads", "kv")
            elif "/k/" in kj or "/v/" in kj:
                s2 = "/k/" if "/k/" in kj else "/v/"
                parent = kj.rsplit(s2, 1)[0]
                grouped = (joined.endswith("/b") and x.ndim >= 2 + off
                           and x.shape[1 + off] != head_counts.get(
                               parent, x.shape[1 + off]))
                kin, kout = "embed", ("kv_heads" if grouped else "heads",
                                      "kv")
            elif "/wi/" in kj or "/wg/" in kj:
                kin, kout = "embed", ("mlp",)
            elif "/wo/" in kj:
                kin, kout = "mlp", ("embed",)
            else:  # o (two contracted input dims) and anything exotic
                base = (None,) * leaf_dims
                return ("layers",) + base if off else base
            base = (kin, None) if joined.endswith("/a") \
                else ((None,) + kout)[:leaf_dims]
            return ("layers",) + tuple(base) if off else tuple(base)
        if joined.endswith("/bias"):
            base = bias_axes(joined, x, off, leaf_dims)
        elif "pos_embedding" in joined:
            base = (None, "embed")
        elif "embedding" in joined or "lm_head" in joined:
            # truncation matters: lm_head_bias is rank-1 ("vocab",)
            base = ("vocab", "embed")[:leaf_dims]
        elif "/q/" in joined:
            base = ("embed", "heads", "kv")[:leaf_dims]
        elif any(s in joined for s in ("/k/", "/v/")):
            s = "/k/" if "/k/" in joined else "/v/"
            parent = joined.rsplit(s, 1)[0]
            grouped = (leaf_dims == 3 and x.shape[1 + off] !=
                       head_counts.get(parent, x.shape[1 + off]))
            base = ("embed", "kv_heads" if grouped else "heads",
                    "kv")[:leaf_dims]
        elif "/o/" in joined:
            # note: NOT endswith("o/kernel") — that would also capture
            # the MLP's "wo/kernel"
            base = ("heads", "kv", "embed")[:leaf_dims]
        elif "router" in joined:
            base = (None, None)
        # MoE expert weights: must match parallel.moe.moe_logical_axes()
        # (single source of truth for 3-dim expert params). Dense MLP
        # kernels live at .../wi/kernel; MoE expert arrays are the leaf
        # .../moe/wi itself
        elif "/wi/" in joined or "/wg/" in joined \
                or joined.endswith(("/wi", "/wg")):
            base = moe_logical_axes()["wi"] if leaf_dims == 3 \
                else ("embed", "mlp")
        elif "/wo/" in joined or joined.endswith("/wo"):
            base = moe_logical_axes()["wo"] if leaf_dims == 3 \
                else ("mlp", "embed")
        else:
            base = tuple([None] * leaf_dims)
        return ("layers",) + tuple(base) if off else tuple(base)

    return jax.tree_util.tree_map_with_path(axes_for, params)


def moe_aux_loss(losses: Any, weight: float = 0.01):
    """Sum the sown MoE load-balancing losses from a ``losses`` collection
    (as returned by ``model.apply(..., mutable=["losses"])``)."""
    leaves = jax.tree_util.tree_leaves(losses)
    if not leaves:
        return jnp.float32(0.0)
    return weight * sum(jnp.sum(leaf) for leaf in leaves)
