"""Softmax cross-entropy over a large vocabulary without the [T, V] logits.

No reference analog (TonY has no numerics). Motivation: with logits
[B, L, V] in fp32, a 256k-vocab model at L=8k burns gigabytes of HBM on a
tensor that exists only to be reduced — on TPU the loss becomes the memory
peak of the whole step.

Two passes, chosen by whether the call is differentiated (a custom VJP):

- **The value alone** streams vocab chunks of the embedding through an
  online logsumexp (the flash-attention trick applied to the classifier):
  one logits matmul, memory O(T x chunk). Scope ``xent.lse``.
- **Under differentiation** the forward rule tiles ROWS and computes the
  gradient in the loss's own pass (scope ``xent.fused``). A row's
  ``dlogits = softmax - onehot`` needs its final logsumexp, known only
  after the last vocab tile, and every vocab tile's ``dW`` needs every
  row's: tiled over the vocabulary the backward must recompute each
  logits tile (four vocab-wide matmuls a step). A tile of ``r`` rows over
  the WHOLE vocabulary has its logsumexp at hand, so one logits matmul
  gives its loss and ``dlogits``, a second its complete ``dhidden``, a
  third its share of ``dW``: the three the model needs. It holds one
  [r, V] fp32 tile and the head's fp32 gradient [V, D]; ``r`` is sized so
  the tile is no larger than that gradient. The backward rule only scales
  the stored gradients by the cotangent.

The matmuls are large, static-shaped and MXU-friendly; chunk defaults to
a multiple of 128 lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def chunked_cross_entropy(hidden, embedding, labels, *,
                          chunk_size: int = 8192, z_loss: float = 0.0,
                          mask=None, bias=None, compute_dtype=None):
    """Mean token cross-entropy of ``logits = hidden @ embedding.T`` without
    materializing the logits.

    Args:
      hidden: [B, L, D] (or [T, D]) final-layer activations.
      embedding: [V, D] tied output embedding.
      labels: [B, L] (or [T]) int targets.
      chunk_size: vocab tile width of the value-alone pass (rounded use:
        keep a multiple of 128); it holds one [T, chunk] tile. A
        differentiated call holds one [r, V] tile instead, with
        ``r = min(T, max(D, 384))`` rounded up to a multiple of 8.
      z_loss: optional logsumexp^2 regularizer weight (PaLM-style), keeps
        logits from drifting — free here since lse is already computed.
      mask: optional per-position 0/1 (or bool) weights shaped like
        labels — e.g. packed-document training dropping the
        cross-boundary target after each EOS. Data: no gradient flows
        to it.
      bias: optional [V] output bias (Phi-family ``lm_head_bias``),
        added per vocab tile — the chunked twin of
        ``logits = h @ W.T + b``.
      compute_dtype: dtype for the logit MATMUL inputs (accumulation is
        always fp32 via preferred_element_type, and all softmax math
        stays fp32). Default None keeps the historical fp32 dot; pass
        ``jnp.bfloat16`` on TPU — fp32 matmuls run several times below
        the bf16 MXU rate, and the head is ~9 percent of a small
        model's FLOPs, so an fp32 head dominates the step.

    Returns mean loss (fp32 scalar) over the unmasked positions.
    """
    if hidden.ndim == 3:
        t = hidden.shape[0] * hidden.shape[1]
        hidden = hidden.reshape(t, hidden.shape[2])
        labels = labels.reshape(t)
        if mask is not None:
            mask = mask.reshape(t)
    return _xent(hidden, embedding, bias, labels.astype(jnp.int32), mask,
                 chunk_size, float(z_loss), compute_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _xent(hidden, embedding, bias, labels, mask, chunk_size, z_loss,
          compute_dtype):
    v, d = embedding.shape
    chunk = min(chunk_size, v)
    n_chunks = (v + chunk - 1) // chunk
    pad = n_chunks * chunk - v
    emb = jnp.pad(embedding, ((0, pad), (0, 0))) if pad else embedding
    if bias is not None:
        bias = jnp.pad(bias, (0, pad)) if pad else bias
        bias = bias.astype(jnp.float32)
    h_mm = hidden.astype(compute_dtype or jnp.float32)

    def body(carry, i):
        m, s, lab = carry
        e_chunk = lax.dynamic_slice(emb, (i * chunk, 0), (chunk, d))
        # [T, chunk]; fp32 accumulation regardless of input dtype
        logits = jnp.matmul(h_mm, e_chunk.astype(h_mm.dtype).T,
                            preferred_element_type=jnp.float32)
        if bias is not None:
            logits = logits + lax.dynamic_slice(bias, (i * chunk,),
                                                (chunk,))[None, :]
        pos = i * chunk + jnp.arange(chunk)
        logits = jnp.where(pos[None, :] < v, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=-1)
        idx = labels - i * chunk
        in_chunk = (idx >= 0) & (idx < chunk)
        picked = jnp.take_along_axis(
            logits, jnp.clip(idx, 0, chunk - 1)[:, None], axis=-1)[:, 0]
        lab = jnp.where(in_chunk, picked, lab)
        return (m_new, s, lab), None

    t = h_mm.shape[0]
    init = (jnp.full((t,), NEG_INF, jnp.float32),
            jnp.zeros((t,), jnp.float32),
            jnp.full((t,), NEG_INF, jnp.float32))
    with jax.named_scope("xent.lse"):
        (m, s, lab), _ = lax.scan(body, init, jnp.arange(n_chunks))
    lse = m + jnp.log(s)
    per_tok = lse - lab
    if mask is None:
        loss = jnp.mean(per_tok)
        if z_loss:
            loss = loss + z_loss * jnp.mean(lse * lse)
        return loss
    w = mask.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(w), 1.0)
    loss = jnp.sum(per_tok * w) / denom
    if z_loss:
        loss = loss + z_loss * jnp.sum(lse * lse * w) / denom
    return loss


def _xent_fwd(hidden, embedding, bias, labels, mask, chunk_size, z_loss,
              compute_dtype):
    t, d = hidden.shape
    v = embedding.shape[0]
    r = -(-min(t, max(d, 384)) // 8) * 8
    n = -(-t // r)
    # each row's share of the mean; padding rows weigh nothing
    if mask is None:
        w = jnp.full((t,), 1.0 / t, jnp.float32)
    else:
        w = mask.astype(jnp.float32)
        w = w / jnp.maximum(jnp.sum(w), 1.0)
    mm = compute_dtype or jnp.float32
    h_mm, lab, w = (jnp.pad(x, ((0, n * r - t),) + ((0, 0),) * (x.ndim - 1))
                    for x in (hidden.astype(mm), labels, w))
    # tile i holds rows i, i + n, i + 2n, ...: a batch-sharded row axis
    # stays sharded within every tile
    h_mm, lab, w = (x.reshape(r, n, *x.shape[1:]).swapaxes(0, 1)
                    for x in (h_mm, lab, w))
    e_mm = embedding.astype(mm)
    b32 = None if bias is None else bias.astype(jnp.float32)

    def body(carry, xs):
        loss, d_emb, d_bias = carry
        h, lab, w = xs
        logits = jnp.matmul(h, e_mm.T, preferred_element_type=jnp.float32)
        if b32 is not None:
            logits = logits + b32[None, :]
        m = jnp.max(logits, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
        picked = jnp.take_along_axis(logits, lab[:, None], axis=-1)[:, 0]
        loss = loss + jnp.sum(w * (lse - picked))
        p_w = w
        if z_loss:
            loss = loss + z_loss * jnp.sum(w * lse * lse)
            p_w = w * (1.0 + 2.0 * z_loss * lse)
        d_logits = (jnp.exp(logits - lse[:, None]) * p_w[:, None]
                    - jax.nn.one_hot(lab, v, dtype=jnp.float32)
                    * w[:, None])
        g = d_logits.astype(mm)
        d_h = jnp.matmul(g, e_mm, preferred_element_type=jnp.float32)
        d_emb = d_emb + jnp.matmul(g.T, h, preferred_element_type=jnp.float32)
        if d_bias is not None:
            d_bias = d_bias + jnp.sum(d_logits, axis=0)
        return (loss, d_emb, d_bias), d_h.astype(hidden.dtype)

    init = (jnp.zeros((), jnp.float32), jnp.zeros((v, d), jnp.float32),
            None if bias is None else jnp.zeros((v,), jnp.float32))
    with jax.named_scope("xent.fused"):
        (loss, d_emb, d_bias), d_h = lax.scan(body, init, (h_mm, lab, w))
    d_h = d_h.swapaxes(0, 1).reshape(n * r, d)[:t]
    return loss, (d_h, d_emb.astype(embedding.dtype),
                  None if bias is None else d_bias.astype(bias.dtype))


def _xent_bwd(chunk_size, z_loss, compute_dtype, res, g):
    d_h, d_emb, d_bias = res
    scale = lambda x: None if x is None else (x * g).astype(x.dtype)
    return scale(d_h), scale(d_emb), scale(d_bias), None, None


_xent.defvjp(_xent_fwd, _xent_bwd)


def full_cross_entropy(hidden, embedding, labels):
    """Reference O(T*V)-memory computation (tests / small vocab)."""
    if hidden.ndim == 3:
        t = hidden.shape[0] * hidden.shape[1]
        hidden = hidden.reshape(t, hidden.shape[2])
        labels = labels.reshape(t)
    logits = hidden.astype(jnp.float32) @ embedding.astype(jnp.float32).T
    lse = jax.nn.logsumexp(logits, axis=-1)
    lab = jnp.take_along_axis(logits, labels[:, None].astype(jnp.int32),
                              axis=-1)[:, 0]
    return jnp.mean(lse - lab)
