"""The loss head's custom VJP (ops/xent.py): a differentiated call computes
the gradient in the loss's own pass over row tiles, the value alone keeps
the vocab-chunked online logsumexp."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.ops import chunked_cross_entropy, full_cross_entropy


def _reference(hidden, emb, labels, mask=None, bias=None, z_loss=0.0):
    """Plain float32 loss over the materialized logits."""
    d = hidden.shape[-1]
    h = hidden.reshape(-1, d).astype(jnp.float32)
    labels = labels.reshape(-1)
    logits = jnp.matmul(h, emb.astype(jnp.float32).T, precision="highest")
    if bias is not None:
        logits = logits + bias[None, :]
    lse = jax.nn.logsumexp(logits, axis=-1)
    per_tok = lse - jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    w = jnp.ones_like(lse) if mask is None else mask.reshape(-1).astype(
        jnp.float32)
    denom = jnp.maximum(jnp.sum(w), 1.0)
    return (jnp.sum(per_tok * w) + z_loss * jnp.sum(lse * lse * w)) / denom


# (hidden shape, vocab, chunk, mask: none, 0/1 floats or "bool", z_loss,
# bias); D=32 makes the row tile min(T, 384) rounded up to 8
CASES = {
    "plain": ((96, 32), 256, 64, False, 0.0, False),
    "mask": ((96, 32), 256, 64, True, 0.0, False),
    "z_loss": ((96, 32), 256, 64, False, 1e-3, False),
    "bias": ((96, 32), 256, 64, True, 1e-3, True),
    "rows_not_tile_multiple": ((1000, 32), 256, 128, True, 0.0, True),
    "rows_under_one_tile": ((13, 32), 256, 64, False, 0.0, False),
    "vocab_not_lane_multiple": ((96, 32), 300, 128, False, 0.0, True),
    "hidden_3d": ((3, 41, 32), 200, 64, "bool", 1e-3, False),
}


def _inputs(shape, v, with_mask, with_bias, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    hidden = jax.random.normal(k[0], shape, jnp.float32)
    emb = jax.random.normal(k[1], (v, shape[-1]), jnp.float32) * 0.3
    labels = jax.random.randint(k[2], shape[:-1], 0, v)
    mask = jax.random.uniform(k[3], shape[:-1]) > 0.3
    mask = (mask if with_mask == "bool" else mask.astype(jnp.float32)) \
        if with_mask else None
    bias = jax.random.normal(k[4], (v,), jnp.float32) if with_bias else None
    return hidden, emb, labels, mask, bias


@pytest.mark.parametrize("case", sorted(CASES))
def test_fused_gradient_matches_float32_reference(case):
    shape, v, chunk, with_mask, z, with_bias = CASES[case]
    hidden, emb, labels, mask, bias = _inputs(shape, v, with_mask, with_bias)

    def loss(h, e, b):
        return chunked_cross_entropy(h, e, labels, chunk_size=chunk,
                                     z_loss=z, mask=mask, bias=b)

    def ref(h, e, b):
        return _reference(h, e, labels, mask, b, z)

    argnums = (0, 1, 2) if with_bias else (0, 1)
    value, grads = jax.jit(jax.value_and_grad(loss, argnums))(
        hidden, emb, bias)
    want_value, want = jax.value_and_grad(ref, argnums)(hidden, emb, bias)
    np.testing.assert_allclose(float(value), float(want_value), rtol=1e-5)
    for got, exp in zip(grads, want):
        assert got.shape == exp.shape and got.dtype == exp.dtype
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   rtol=1e-4, atol=1e-6)
    # the differentiated call's value is the value-alone call's
    np.testing.assert_allclose(float(value),
                               float(jax.jit(loss)(hidden, emb, bias)),
                               rtol=1e-6)


def test_fused_gradient_matches_full_cross_entropy():
    hidden, emb, labels, _, _ = _inputs((2, 24, 16), 100, False, False, 3)
    got = jax.grad(lambda h, e: chunked_cross_entropy(
        h, e, labels, chunk_size=32), argnums=(0, 1))(hidden, emb)
    want = jax.grad(full_cross_entropy, argnums=(0, 1))(hidden, emb, labels)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_cotangent_scales_the_stored_gradient():
    hidden, emb, labels, _, _ = _inputs((40, 16), 64, False, False, 4)
    f = lambda h, e: chunked_cross_entropy(h, e, labels, chunk_size=32)
    g1 = jax.grad(f, argnums=(0, 1))(hidden, emb)
    g3 = jax.grad(lambda h, e: -3.0 * f(h, e), argnums=(0, 1))(hidden, emb)
    for a, b in zip(g1, g3):
        np.testing.assert_allclose(np.asarray(b), -3.0 * np.asarray(a),
                                   rtol=1e-6, atol=1e-9)


def _vocab_dots(lowered, dims) -> int:
    """dot_general ops of a lowered program with an operand or result
    dimension among ``dims`` (the vocabulary and its chunk)."""
    n = 0
    for line in lowered.as_text().splitlines():
        if "stablehlo.dot_general" not in line:
            continue
        shapes = re.findall(r"tensor<([0-9x]+)x[a-z]+[0-9]*>", line)
        if any(int(s) in dims for shape in shapes for s in shape.split("x")):
            n += 1
    return n


def test_one_logits_matmul_under_differentiation():
    """Three vocab-wide matmuls a differentiated call (logits, dhidden,
    dW: no recompute of the logits), one for the value alone."""
    t, d, v, chunk = 1000, 32, 500, 128   # row tile 384: three tiles
    hidden, emb, labels, _, _ = _inputs((t, d), v, False, False, 5)
    f = lambda h, e: chunked_cross_entropy(h, e, labels, chunk_size=chunk)
    dims = {v, chunk}
    grad_prog = jax.jit(jax.value_and_grad(f, argnums=(0, 1))).lower(
        hidden, emb)
    assert _vocab_dots(grad_prog, dims) == 3
    assert "xent.fused" in grad_prog.as_text(debug_info=True)
    value_prog = jax.jit(f).lower(hidden, emb)
    assert _vocab_dots(value_prog, dims) == 1
    assert "xent.lse" in value_prog.as_text(debug_info=True)


def test_bf16_head_keeps_the_norm_scale_gradient():
    """compute_dtype=bf16: the gradient reaching a norm scale in front of
    the head is within 1% of the float32 reference's. Summed across 128
    vocab chunks in bf16 it read 7.5% short in norm here."""
    t, d, v = 1024, 64, 16384
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(k[0], (t, d), jnp.float32)
    emb = jax.random.normal(k[1], (v, d), jnp.float32) * 0.02
    scale = 1.0 + 0.1 * jax.random.normal(k[2], (d,), jnp.float32)
    labels = jax.random.randint(k[3], (t,), 0, v)

    def normed(s):
        y = x - x.mean(-1, keepdims=True)
        return y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + 1e-5) * s

    def loss_bf16(s):
        return chunked_cross_entropy(
            normed(s).astype(jnp.bfloat16), emb.astype(jnp.bfloat16),
            labels, chunk_size=128, compute_dtype=jnp.bfloat16)

    got = np.asarray(jax.jit(jax.grad(loss_bf16))(scale))
    want = np.asarray(jax.grad(
        lambda s: _reference(normed(s), emb, labels))(scale))
    norm = np.linalg.norm(want)
    assert abs(np.linalg.norm(got) - norm) / norm < 0.01
    assert np.linalg.norm(got - want) / norm < 0.01
