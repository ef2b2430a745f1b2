"""Pallas kernel tests (interpreter mode on CPU)."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.ops import add_rmsnorm, flash_attention, rmsnorm
from tony_tpu.parallel import reference_attention


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_reference(causal):
    key = jax.random.PRNGKey(0)
    b, l, h, d = 2, 128, 2, 32
    q, k, v = (jax.random.normal(kk, (b, l, h, d), jnp.float32)
               for kk in jax.random.split(key, 3))
    out = flash_attention(q, k, v, causal, 64, 64)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_flash_attention_grad():
    key = jax.random.PRNGKey(1)
    b, l, h, d = 1, 64, 2, 16
    q, k, v = (jax.random.normal(kk, (b, l, h, d), jnp.float32)
               for kk in jax.random.split(key, 3))

    g_flash = jax.grad(lambda q, k, v: flash_attention(q, k, v, True, 32, 32)
                       .sum())(q, k, v)
    g_ref = jax.grad(lambda q, k, v: reference_attention(q, k, v, causal=True)
                     .sum())(q, k, v)
    np.testing.assert_allclose(np.asarray(g_flash), np.asarray(g_ref),
                               atol=5e-5, rtol=5e-5)


def test_flash_attention_block_fallback():
    """Non-divisible seq lens fall back to the largest multiple-of-8
    divisor block and still match the reference; lengths with no usable
    divisor are a clear error (not a silent degenerate kernel)."""
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((1, 160, 2, 16)), jnp.float32)
    out = flash_attention(q, q, q, True, 64, 64)  # falls back to block 40
    s = jnp.einsum("bqhd,bkhd->bhqk", q, q) / 4.0
    s = jnp.where(jnp.tril(jnp.ones((160, 160), bool))[None, None], s, -1e30)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)
    # 100 has no mult-of-8 divisor <= 64: causal pads to 128 and slices
    rng2 = np.random.default_rng(8)
    q = jnp.asarray(rng2.standard_normal((1, 100, 2, 16)), jnp.float32)
    out = flash_attention(q, q, q, True, 64, 64)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, q) / 4.0
    s = jnp.where(jnp.tril(jnp.ones((100, 100), bool))[None, None], s, -1e30)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)
    # ... and its gradient flows through the pad/slice
    grad = jax.grad(lambda q: jnp.sum(flash_attention(q, q, q, True, 64, 64)))(q)
    assert grad.shape == q.shape and bool(jnp.all(jnp.isfinite(grad)))
    # non-causal cannot pad safely: clear error
    with pytest.raises(ValueError, match="non-causal"):
        flash_attention(q, q, q, False, 64, 64)


def test_rmsnorm_matches():
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 32, 64))
    scale = jax.random.normal(jax.random.PRNGKey(3), (64,)) + 1.0
    out = rmsnorm(x, scale)
    x32 = x.astype(jnp.float32)
    ref = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + 1e-6) * scale
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5,
                               rtol=1e-5)


def test_add_rmsnorm():
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 32))
    r = jax.random.normal(jax.random.PRNGKey(5), (8, 32))
    scale = jnp.ones((32,))
    normed, summed = add_rmsnorm(x, r, scale)
    np.testing.assert_allclose(np.asarray(summed), np.asarray(x + r), atol=1e-6)
    s = (x + r).astype(jnp.float32)
    ref = s * jax.lax.rsqrt(jnp.mean(s * s, -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(normed), np.asarray(ref), atol=1e-5,
                               rtol=1e-5)


def test_flash_attention_gqa_matches_repeated_reference():
    """Grouped K/V (2 kv heads, 4 q heads) must equal reference attention
    over explicitly repeated K/V — forward and grads."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(k1, (2, 32, 4, 16))
    k = jax.random.normal(k2, (2, 32, 2, 16))
    v = jax.random.normal(k3, (2, 32, 2, 16))
    kf = jnp.repeat(k, 2, axis=2)
    vf = jnp.repeat(v, 2, axis=2)
    out = flash_attention(q, k, v, True, 16, 16)
    ref = reference_attention(q, kf, vf, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)

    def loss_gqa(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 16, 16) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(
            q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2),
            causal=True) ** 2)

    g_gqa = jax.grad(loss_gqa, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_gqa, g_ref):
        assert a.shape == b.shape  # dk/dv stay at kv-head width
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3)


def test_transformer_pallas_gqa_backend():
    """The pallas backend consumes grouped K/V directly (no repeat) and
    agrees with the reference backend."""
    from tony_tpu.models import Transformer, TransformerConfig

    mk = lambda backend: TransformerConfig(  # noqa: E731
        vocab_size=64, d_model=64, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=64, max_seq_len=64, dtype=jnp.float32,
        attention_backend=backend, attention_block_size=16)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 32), 0, 64)
    model_ref = Transformer(mk("reference"))
    params = model_ref.init(jax.random.PRNGKey(0), tokens)
    out_ref = model_ref.apply(params, tokens)
    out_pl = Transformer(mk("pallas")).apply(params, tokens)
    np.testing.assert_allclose(np.asarray(out_pl), np.asarray(out_ref),
                               atol=1e-3, rtol=1e-3)


def test_chunked_xent_matches_full():
    from tony_tpu.ops import chunked_cross_entropy, full_cross_entropy

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    hidden = jax.random.normal(k1, (2, 8, 16))
    emb = jax.random.normal(k2, (100, 16))  # vocab not a chunk multiple
    labels = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 100)
    ref = full_cross_entropy(hidden, emb, labels)
    for chunk in (16, 32, 100, 4096):
        got = chunked_cross_entropy(hidden, emb, labels, chunk_size=chunk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_chunked_xent_grads_match():
    from tony_tpu.ops import chunked_cross_entropy, full_cross_entropy

    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    hidden = jax.random.normal(k1, (12, 8))
    emb = jax.random.normal(k2, (40, 8))
    labels = jax.random.randint(jax.random.PRNGKey(3), (12,), 0, 40)
    g_ref = jax.grad(full_cross_entropy, argnums=(0, 1))(hidden, emb, labels)
    g_chk = jax.grad(
        lambda h, e: chunked_cross_entropy(h, e, labels, chunk_size=16),
        argnums=(0, 1))(hidden, emb)
    for a, b in zip(g_chk, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_chunked_xent_bf16_compute_dtype():
    """compute_dtype=bf16 (the TPU head path: bf16 dot, fp32 accumulate)
    stays within bf16 rounding of the fp32 loss, values AND grads."""
    from tony_tpu.ops import chunked_cross_entropy, full_cross_entropy

    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    hidden = jax.random.normal(k1, (4, 16, 32))
    emb = jax.random.normal(k2, (96, 32))
    labels = jax.random.randint(jax.random.PRNGKey(8), (4, 16), 0, 96)
    ref = full_cross_entropy(hidden, emb, labels)
    got = chunked_cross_entropy(hidden, emb, labels, chunk_size=32,
                                compute_dtype=jnp.bfloat16)
    assert got.dtype == jnp.float32  # loss math stays fp32
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)
    g_ref = jax.grad(full_cross_entropy, argnums=(0, 1))(
        hidden.reshape(-1, 32), emb, labels.reshape(-1))
    g_bf = jax.grad(
        lambda h, e: chunked_cross_entropy(
            h, e, labels.reshape(-1), chunk_size=32,
            compute_dtype=jnp.bfloat16),
        argnums=(0, 1))(hidden.reshape(-1, 32), emb)
    for a, b in zip(g_bf, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-2, atol=5e-3)


def test_chunked_xent_z_loss_and_jit():
    from tony_tpu.ops import chunked_cross_entropy

    hidden = jax.random.normal(jax.random.PRNGKey(4), (2, 4, 8))
    emb = jax.random.normal(jax.random.PRNGKey(5), (30, 8))
    labels = jnp.zeros((2, 4), jnp.int32)
    base = chunked_cross_entropy(hidden, emb, labels, chunk_size=8)
    with_z = jax.jit(functools.partial(
        chunked_cross_entropy, chunk_size=8, z_loss=1e-3))(hidden, emb, labels)
    assert float(with_z) > float(base)  # lse^2 regularizer is additive


def test_transformer_hidden_plus_chunked_xent():
    """Training path: return_hidden + chunked loss == logits + standard CE."""
    from tony_tpu.models import Transformer, TransformerConfig
    from tony_tpu.ops import chunked_cross_entropy
    from tony_tpu.train import cross_entropy_loss

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                            d_ff=64, max_seq_len=32, dtype=jnp.float32,
                            attention_backend="blockwise",
                            attention_block_size=16)
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 16), 0, 64)
    params = model.init(jax.random.PRNGKey(0), tokens)
    logits = model.apply(params, tokens)
    ref = cross_entropy_loss(logits[:, :-1], tokens[:, 1:])
    hidden = model.apply(params, tokens, return_hidden=True)
    got = chunked_cross_entropy(hidden[:, :-1],
                                params["params"]["embedding"],
                                tokens[:, 1:], chunk_size=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_chunked_xent_with_untied_lm_head():
    """return_hidden + params["lm_head"] must reproduce the full-logits
    loss for an untied (Llama-style) model — the documented pairing."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from tony_tpu.models import Transformer, TransformerConfig
    from tony_tpu.ops import chunked_cross_entropy

    cfg = TransformerConfig(
        vocab_size=300, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32, dtype=jnp.float32, attention_backend="reference",
        gated_mlp=True, tied_embeddings=False)
    model = Transformer(cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 300, (2, 16)))
    params = model.init(jax.random.PRNGKey(0), tokens)
    labels = jnp.asarray(
        np.random.default_rng(1).integers(0, 300, (2, 16)))

    logits = model.apply(params, tokens)
    onehot = jax.nn.one_hot(labels, 300)
    full = -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits), axis=-1))

    hidden = model.apply(params, tokens, return_hidden=True)
    chunked = chunked_cross_entropy(
        hidden, params["params"]["lm_head"], labels, chunk_size=128)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(full),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_sliding_window_matches_reference():
    rng = jax.random.PRNGKey(5)
    q, k, v = (jax.random.normal(key, (2, 64, 2, 8))
               for key in jax.random.split(rng, 3))
    ref = reference_attention(q, k, v, causal=True, window=10)
    out = flash_attention(q, k, v, True, 16, 16, window=10)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # window crossing block boundaries AND window smaller than one block
    for w in (3, 16, 33):
        ref = reference_attention(q, k, v, causal=True, window=w)
        out = flash_attention(q, k, v, True, 16, 16, window=w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5, err_msg=f"w={w}")


def test_flash_attention_sliding_window_grad():
    rng = jax.random.PRNGKey(6)
    q, k, v = (jax.random.normal(key, (1, 32, 2, 8))
               for key in jax.random.split(rng, 3))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    g_flash = jax.grad(
        loss(lambda q, k, v: flash_attention(q, k, v, True, 8, 8, window=5)),
        argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        loss(lambda q, k, v: reference_attention(q, k, v, causal=True,
                                                 window=5)),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5, err_msg=name)


def test_flash_attention_sliding_window_gqa_grad():
    """Window + GQA together: the dkv kernel's group accumulation must
    respect the window's block pruning."""
    rng = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (1, 32, 4, 8))
    k = jax.random.normal(kk, (1, 32, 2, 8))
    v = jax.random.normal(kv, (1, 32, 2, 8))
    group = 2

    def ref_fn(q, k, v):
        kr = jnp.repeat(k, group, axis=2)
        vr = jnp.repeat(v, group, axis=2)
        return reference_attention(q, kr, vr, causal=True, window=9)

    g_flash = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, True, 8, 8, window=9) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(ref_fn(q, k, v) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5, err_msg=name)


def test_flash_attention_window_requires_causal():
    q = jnp.ones((1, 16, 2, 8))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, False, 16, 16, window=4)


def test_flash_attention_window_with_padded_length():
    """Unblockable seq lens go through the zero-pad path; the window mask
    must stay correct on the padded program."""
    rng = jax.random.PRNGKey(9)
    q, k, v = (jax.random.normal(key, (1, 50, 2, 8))  # 50: no divisor of 16
               for key in jax.random.split(rng, 3))
    ref = reference_attention(q, k, v, causal=True, window=12)
    out = flash_attention(q, k, v, True, 16, 16, window=12)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    g = jax.grad(lambda q: jnp.sum(
        flash_attention(q, k, v, True, 16, 16, window=12) ** 2))(q)
    gr = jax.grad(lambda q: jnp.sum(
        reference_attention(q, k, v, causal=True, window=12) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_segment_ids_match_reference():
    rng = jax.random.PRNGKey(11)
    q, k, v = (jax.random.normal(key, (2, 32, 2, 8))
               for key in jax.random.split(rng, 3))
    segs = jnp.asarray([[0] * 10 + [1] * 12 + [2] * 10,
                        [0] * 32], jnp.int32)
    ref = reference_attention(q, k, v, causal=True, segment_ids=segs)
    out = flash_attention(q, k, v, True, 8, 8, segment_ids=segs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # gradients through all three operands
    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)
    gf = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, True, 8, 8, segment_ids=segs)), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(lambda q, k, v: reference_attention(
        q, k, v, causal=True, segment_ids=segs)), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5, err_msg=name)


def test_flash_attention_segments_with_window_and_gqa():
    rng = jax.random.PRNGKey(12)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (1, 32, 4, 8))
    k = jax.random.normal(kk, (1, 32, 2, 8))
    v = jax.random.normal(kv, (1, 32, 2, 8))
    segs = jnp.asarray([[0] * 13 + [1] * 19], jnp.int32)
    kr = jnp.repeat(k, 2, axis=2)
    vr = jnp.repeat(v, 2, axis=2)
    ref = reference_attention(q, kr, vr, causal=True, window=9,
                              segment_ids=segs)
    out = flash_attention(q, k, v, True, 8, 8, window=9, segment_ids=segs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_segments_padded_length():
    rng = jax.random.PRNGKey(13)
    q, k, v = (jax.random.normal(key, (1, 27, 2, 8))  # unblockable
               for key in jax.random.split(rng, 3))
    segs = jnp.asarray([[0] * 11 + [1] * 16], jnp.int32)
    ref = reference_attention(q, k, v, causal=True, segment_ids=segs)
    out = flash_attention(q, k, v, True, 8, 8, segment_ids=segs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_segments_mixed_blocks_padded():
    """block_q != block_k on an unblockable length: both sides must pad to
    one common length (regression: q-side seg blocks ran past the array)."""
    rng = jax.random.PRNGKey(14)
    q, k, v = (jax.random.normal(key, (1, 33, 2, 8))
               for key in jax.random.split(rng, 3))
    segs = jnp.asarray([[0] * 13 + [1] * 20], jnp.int32)
    ref = reference_attention(q, k, v, causal=True, segment_ids=segs)
    out = flash_attention(q, k, v, True, 16, 8, segment_ids=segs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # and without segments the mixed-block padded path stays exact too
    ref2 = reference_attention(q, k, v, causal=True)
    out2 = flash_attention(q, k, v, True, 16, 8)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2),
                               atol=2e-5, rtol=2e-5)


def test_chunked_xent_mask_matches_full():
    from tony_tpu.ops import chunked_cross_entropy, full_cross_entropy

    rng = jax.random.PRNGKey(15)
    hidden = jax.random.normal(rng, (2, 6, 16))
    emb = jax.random.normal(jax.random.fold_in(rng, 1), (40, 16))
    labels = jax.random.randint(jax.random.fold_in(rng, 2), (2, 6), 0, 40)
    mask = jnp.asarray([[1, 1, 0, 1, 1, 1], [1, 0, 0, 1, 1, 1]], jnp.float32)
    got = float(chunked_cross_entropy(hidden, emb, labels, chunk_size=16,
                                      mask=mask))
    logits = jnp.einsum("bld,vd->blv", hidden, emb)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    want = -float((ll * mask).sum() / mask.sum())
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # mask=None unchanged vs full reference
    np.testing.assert_allclose(
        float(chunked_cross_entropy(hidden, emb, labels, chunk_size=16)),
        float(full_cross_entropy(hidden, emb, labels)), rtol=1e-5)


def test_flash_attention_rejects_unequal_unblockable_causal():
    q = jnp.ones((1, 41, 2, 8))
    kv = jnp.ones((1, 24, 2, 8))
    with pytest.raises(ValueError, match="UNEQUAL"):
        flash_attention(q, kv, kv, True, 8, 8)


def test_flash_attention_causal_cross_blockable_lengths():
    """Regression (ADVICE r1): blockable causal cross-attention with
    lq > lk must not let the banded diagonal index run past the kv grid —
    the clamp in _banded_ki restores a full scan + position mask."""
    key = jax.random.PRNGKey(11)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 32, 2, 8), jnp.float32)
    k = jax.random.normal(kk, (1, 8, 2, 8), jnp.float32)
    v = jax.random.normal(kv_, (1, 8, 2, 8), jnp.float32)
    out = flash_attention(q, k, v, True, 8, 8)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)
    # ... and the lq < lk direction (kv-cache-style prefill chunk)
    out2 = flash_attention(k, q, q, True, 8, 8)
    ref2 = reference_attention(k, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2), atol=2e-5,
                               rtol=2e-5)
    # dQ path shares the banded index: gradient must be finite and match
    g = jax.grad(lambda q: flash_attention(q, k, v, True, 8, 8).sum())(q)
    g_ref = jax.grad(
        lambda q: reference_attention(q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=5e-5,
                               rtol=5e-5)



# -- the flash backward: one fused kernel, or dq + dk/dv past the budget -----

def _flash_bwd_kernels(fn, *args):
    """Names of the flash backward kernels that tracing ``fn`` reaches."""
    return set(re.findall(r"flash_bwd_\w+", str(jax.make_jaxpr(fn)(*args))))


# name -> (q shape, kv shape, flash_attention keywords); segments by row
_BWD_CASES = {
    "causal": ((2, 64, 2, 16), (2, 64, 2, 16),
               dict(causal=True, block_q=16, block_k=32)),
    "gqa": ((1, 64, 4, 16), (1, 64, 2, 16),
            dict(causal=True, block_q=16, block_k=16)),
    "window": ((1, 64, 2, 8), (1, 64, 2, 8),
               dict(causal=True, block_q=8, block_k=16, window=20)),
    "segments_gqa_window": ((2, 32, 4, 8), (2, 32, 2, 8),
                            dict(causal=True, block_q=8, block_k=8,
                                 window=9, segment_ids=[[0] * 10 + [1] * 22,
                                                        [0] * 32])),
    "non_causal": ((1, 64, 2, 16), (1, 64, 2, 16),
                   dict(causal=False, block_q=16, block_k=32)),
    "padded_length": ((1, 27, 2, 8), (1, 27, 2, 8),
                      dict(causal=True, block_q=8, block_k=8)),
    "cross_lengths": ((1, 32, 2, 8), (1, 8, 2, 8),
                      dict(causal=True, block_q=8, block_k=8)),
}


@pytest.mark.parametrize("case", sorted(_BWD_CASES))
def test_flash_backward_fused_matches_split(case, monkeypatch):
    """The fused backward (P recomputed once a tile, dQ accumulated in f32
    across kv blocks and rounded once) and the split one (a dq and a dk/dv
    kernel) give the SAME dQ, dK and dV on the same inputs: each tile's
    arithmetic and the order of every f32 sum are the same. The split path
    is reached as in production, by a shape past the VMEM budget: here the
    budget is lowered below what any shape needs."""
    from tony_tpu.ops import attention

    q_shape, kv_shape, kw = _BWD_CASES[case]
    kw = dict(kw)
    if "segment_ids" in kw:
        kw["segment_ids"] = jnp.asarray(kw["segment_ids"], jnp.int32)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(21), 3)
    q = jax.random.normal(kq, q_shape)
    k = jax.random.normal(kk, kv_shape)
    v = jax.random.normal(kv, kv_shape)

    def run(kernels):
        # a new function each time: jax caches a function's trace
        grad = jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(q, k, v, **kw) ** 2),
            argnums=(0, 1, 2))
        assert _flash_bwd_kernels(grad, q, k, v) == kernels
        return grad(q, k, v)

    fused = run({"flash_bwd_fused"})
    monkeypatch.setattr(attention, "_FUSED_BWD_VMEM", 0)
    split = run({"flash_bwd_dq", "flash_bwd_dkv"})
    for a, b, name in zip(fused, split, "qkv"):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"d{name}")


@pytest.mark.parametrize("q_shape,kv_heads", [
    ((1, 131072, 2, 128), 2),
    ((1, 16384, 32, 128), 4),
], ids=["long_sequence", "wide_gqa_group"])
def test_flash_backward_splits_where_dq_outgrows_the_vmem_budget(
        q_shape, kv_heads):
    """Where the q-head group's f32 dQ scratch alone is past the budget
    (64 MiB here: 131072 positions of one head, or 16384 of a group of
    8), the backward takes the split dq and dk/dv kernels; the training
    cell's shapes (4 x 2048, 16 heads x 128, blocks 512 x 1024) take the
    fused one. Traced only: nothing of this size runs."""
    from tony_tpu.ops import attention

    b, length, heads, d = q_shape
    assert (heads // kv_heads) * length * d * 4 > attention._FUSED_BWD_VMEM
    grad = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, True, 512, 1024).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, length, kv_heads, d), jnp.bfloat16)
    assert _flash_bwd_kernels(grad, q, kv, kv) == {"flash_bwd_dq",
                                                   "flash_bwd_dkv"}
    cell = jax.ShapeDtypeStruct((4, 2048, 16, 128), jnp.bfloat16)
    assert _flash_bwd_kernels(grad, cell, cell, cell) == {"flash_bwd_fused"}

# -- int8 weight-only matmul (ops/quant.py) ----------------------------------


def test_quantize_q8_roundtrip_error_bound():
    from tony_tpu.ops import dequantize_q8, quantize_q8

    w = jnp.asarray(np.random.default_rng(0).standard_normal((64, 48)),
                    jnp.float32)
    w_q, scale = quantize_q8(w)
    assert w_q.dtype == jnp.int8 and scale.shape == (48,)
    err = np.abs(np.asarray(dequantize_q8(w_q, scale)) - np.asarray(w))
    # symmetric rounding: error <= scale/2 per element, per channel
    assert (err <= np.asarray(scale)[None, :] / 2 + 1e-7).all()


def test_q8_matmul_matches_dequant_reference():
    from tony_tpu.ops import dequantize_q8, q8_matmul, quantize_q8

    rng = np.random.default_rng(1)
    for m, k, n in ((1, 64, 48), (8, 128, 256), (5, 96, 33)):
        x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
        w_q, scale = quantize_q8(w)
        got = np.asarray(q8_matmul(x, w_q, scale))
        want = np.asarray(x) @ np.asarray(dequantize_q8(w_q, scale))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_q8_matmul_close_to_full_precision():
    from tony_tpu.ops import q8_matmul, quantize_q8

    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((4, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    w_q, scale = quantize_q8(w)
    got = np.asarray(q8_matmul(x, w_q, scale))
    ref = np.asarray(x) @ np.asarray(w)
    # int8 weight error ~0.4% relative for gaussian weights at this k
    rel = np.abs(got - ref).mean() / np.abs(ref).mean()
    assert rel < 0.01, rel


def test_q8_matmul_rejects_mismatched_shapes():
    from tony_tpu.ops import q8_matmul

    with pytest.raises(ValueError, match="shape mismatch"):
        q8_matmul(jnp.ones((2, 8)), jnp.ones((4, 8), jnp.int8),
                  jnp.ones((8,)))


def test_q8_matmul_undivisible_n_uses_divisor_block():
    """A non-divisible output dim (LM-head vocab shapes) must tile with a
    smaller divisor block, never a whole-n VMEM tile."""
    from tony_tpu.ops import dequantize_q8, q8_matmul, quantize_q8

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 384)), jnp.float32)  # 384%256!=0
    w_q, scale = quantize_q8(w)
    got = np.asarray(q8_matmul(x, w_q, scale, block_n=256))
    want = np.asarray(x) @ np.asarray(dequantize_q8(w_q, scale))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_chunked_xent_with_output_bias():
    """bias= (Phi lm_head_bias) must match full logits + bias exactly,
    across chunk boundaries."""
    from tony_tpu.ops import chunked_cross_entropy

    rng = np.random.default_rng(5)
    t, d, v = 6, 16, 50  # v not a chunk multiple
    hidden = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    emb = jnp.asarray(rng.standard_normal((v, d)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal((v,)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, t), jnp.int32)
    got = float(chunked_cross_entropy(hidden, emb, labels, chunk_size=16,
                                      bias=bias))
    logits = hidden @ emb.T + bias[None, :]
    logp = jax.nn.log_softmax(logits, axis=-1)
    want = float(-jnp.take_along_axis(
        logp, labels[:, None], axis=-1).mean())
    np.testing.assert_allclose(got, want, rtol=1e-5)
