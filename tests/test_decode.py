"""Flash-decode kernel + int8 KV cache (docs/PERF.md decode roofline
"next lever"; VERDICT r3 next #2). CPU runs the pallas interpreter, so
these pin exactness, not speed."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import Transformer, TransformerConfig
from tony_tpu.models.generate import generate
from tony_tpu.ops.decode import dequantize_kv, flash_decode, quantize_kv


def _ref_decode(q, k, v, length, window=0):
    """numpy reference: full softmax over valid cache positions."""
    b, h, d = q.shape
    s, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    kr = np.repeat(np.asarray(k, np.float32), g, axis=2)
    vr = np.repeat(np.asarray(v, np.float32), g, axis=2)
    scores = np.einsum("bhd,bshd->bhs", np.asarray(q, np.float32),
                       kr) / np.sqrt(d)
    pos = np.arange(s)[None, None, :]
    ln = np.asarray(length).reshape(-1, 1, 1)
    vis = pos < ln
    if window > 0:
        vis = vis & (pos >= np.maximum(ln - window, 0))
    scores = np.where(vis, scores, -1e30)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhs,bshd->bhd", p, vr)


@pytest.fixture(scope="module")
def qkv():
    b, s, h, kvh, d = 2, 64, 8, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kvh, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kvh, d))
    return q, k, v


@pytest.mark.parametrize("window", [0, 10])
def test_flash_decode_matches_reference(qkv, window):
    q, k, v = qkv
    length = jnp.asarray([37, 64], jnp.int32)
    out = flash_decode(q, k, v, length, window=window, block_k=16)
    ref = _ref_decode(q, k, v, length, window=window)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


def test_flash_decode_scalar_length_and_full_mha(qkv):
    q, k, v = qkv
    # scalar length broadcasts; MHA path (kvh == h) via repeat
    kf = jnp.repeat(k, 4, axis=2)
    vf = jnp.repeat(v, 4, axis=2)
    out = flash_decode(q, kf, vf, 40, block_k=16)
    ref = _ref_decode(q, kf, vf, np.asarray([40, 40]))
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


def test_flash_decode_int8_cache(qkv):
    q, k, v = qkv
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    length = jnp.asarray([37, 64], jnp.int32)
    out = flash_decode(q, kq, vq, length, block_k=16, k_scale=ks,
                       v_scale=vs)
    # exact vs the dequantized reference (the kernel's math), close to fp
    ref_q = _ref_decode(q, dequantize_kv(kq, ks).astype(jnp.float32),
                        dequantize_kv(vq, vs).astype(jnp.float32), length)
    np.testing.assert_allclose(np.asarray(out), ref_q, atol=2e-5, rtol=2e-5)
    ref_fp = _ref_decode(q, k, v, length)
    np.testing.assert_allclose(np.asarray(out), ref_fp, atol=0.05, rtol=0.05)


def test_flash_decode_zero_length_slot_rows(qkv):
    """Per-slot lengths (serve/): a length-0 row — an EMPTY continuous-
    batching slot — must emit EXACT zeros (never NaN, never a uniform
    average of junk V tiles) while live rows stay exact, at GQA and MHA
    head layouts (the `valid` mask in decode.py _finalize)."""
    q, k, v = qkv
    length = jnp.asarray([0, 37], jnp.int32)
    out = np.asarray(flash_decode(q, k, v, length, block_k=16))
    assert (out[0] == 0).all()
    ref = _ref_decode(q, k, v, np.asarray([37, 37]))
    np.testing.assert_allclose(out[1], ref[1], atol=2e-5, rtol=2e-5)
    # MHA: every q head its own kv head
    kf = jnp.repeat(k, 4, axis=2)
    vf = jnp.repeat(v, 4, axis=2)
    out = np.asarray(flash_decode(q, kf, vf, length, block_k=16))
    assert (out[0] == 0).all()
    reff = _ref_decode(q, kf, vf, np.asarray([37, 37]))
    np.testing.assert_allclose(out[1], reff[1], atol=2e-5, rtol=2e-5)


def test_quantize_kv_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 4, 32))
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (2, 8, 4)
    err = np.abs(np.asarray(dequantize_kv(q, s)) - np.asarray(x))
    # symmetric absmax: per-(b, pos, head) error <= scale/2
    bound = np.asarray(s)[..., None] * 0.5 + 1e-7
    assert (err <= bound).all()


def test_flash_decode_rejects_bad_shapes(qkv):
    q, k, v = qkv
    with pytest.raises(ValueError, match="divisible"):
        flash_decode(q[:, :5], k, v, 8)  # 5 q heads vs 2 kv heads
    kq, ks = quantize_kv(k)
    vq, _ = quantize_kv(v)
    with pytest.raises(ValueError, match="k_scale"):
        flash_decode(q, kq, vq, 8)


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = TransformerConfig(vocab_size=128, d_model=32, n_heads=4,
                            n_kv_heads=2, n_layers=2, d_ff=64,
                            max_seq_len=48, dtype=jnp.float32)
    model = Transformer(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(0), (2, 8), 0, 128)
    params = model.init(jax.random.PRNGKey(1), prompt)["params"]
    return cfg, params, prompt


def test_generate_flash_decode_greedy_exact(tiny_lm):
    cfg, params, prompt = tiny_lm
    ref = generate(Transformer(cfg), params, prompt, max_new_tokens=10,
                   temperature=0.0)
    out = generate(Transformer(dataclasses.replace(
        cfg, decode_attention="flash")), params, prompt,
        max_new_tokens=10, temperature=0.0)
    assert np.array_equal(np.asarray(ref), np.asarray(out))


def test_generate_int8_kv_cache_flash_matches_einsum(tiny_lm):
    """int8 cache: the flash kernel and the dequant einsum path must
    agree exactly (same quantized numbers either way)."""
    cfg, params, prompt = tiny_lm
    out_e = generate(Transformer(dataclasses.replace(
        cfg, kv_cache_quant=True)), params, prompt,
        max_new_tokens=10, temperature=0.0)
    out_f = generate(Transformer(dataclasses.replace(
        cfg, kv_cache_quant=True, decode_attention="flash")), params,
        prompt, max_new_tokens=10, temperature=0.0)
    assert np.array_equal(np.asarray(out_e), np.asarray(out_f))


def test_generate_windowed_flash_decode(tiny_lm):
    cfg, params, prompt = tiny_lm
    cfg_w = dataclasses.replace(cfg, sliding_window=16)
    ref = generate(Transformer(cfg_w), params, prompt, max_new_tokens=10,
                   temperature=0.0)
    out = generate(Transformer(dataclasses.replace(
        cfg_w, decode_attention="flash")), params, prompt,
        max_new_tokens=10, temperature=0.0)
    assert np.array_equal(np.asarray(ref), np.asarray(out))


def test_int8_cache_vars_allocated(tiny_lm):
    cfg, params, prompt = tiny_lm
    model = Transformer(dataclasses.replace(cfg, kv_cache_quant=True))
    variables = model.init(jax.random.PRNGKey(0), prompt, decode=True)
    cache = variables["cache"]
    flat = {"/".join(str(getattr(k_, "key", k_)) for k_ in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]}
    keys = [k_ for k_ in flat if "cached_key" in k_ and "scale" not in k_]
    scales = [k_ for k_ in flat if "cached_key_scale" in k_]
    assert keys and scales
    assert all(flat[k_].dtype == jnp.int8 for k_ in keys)
    assert all(flat[k_].dtype == jnp.float32 for k_ in scales)


@pytest.fixture(scope="module")
def qkv_mha():
    # h == kvh: one query row per kv head (group 1)
    b, s, h, d = 2, 64, 8, 16
    q = jax.random.normal(jax.random.PRNGKey(7), (b, h, d))
    k = jax.random.normal(jax.random.PRNGKey(8), (b, s, h, d))
    v = jax.random.normal(jax.random.PRNGKey(9), (b, s, h, d))
    return q, k, v


@pytest.mark.parametrize("window", [0, 10])
def test_flash_decode_mha_mixed_lengths(qkv_mha, window):
    """MHA (group 1): each batch row reads its own SMEM length —
    exactness against the numpy reference across mixed lengths and a
    sliding window."""
    q, k, v = qkv_mha
    length = jnp.asarray([37, 64], jnp.int32)
    out = flash_decode(q, k, v, length, window=window, block_k=16)
    ref = _ref_decode(q, k, v, length, window=window)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


def test_flash_decode_mha_int8_cache(qkv_mha):
    """int8 cache at group 1 through the scale-tile fold."""
    q, k, v = qkv_mha
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    length = jnp.asarray([29, 55], jnp.int32)
    out = flash_decode(q, kq, vq, length, block_k=16, k_scale=ks,
                       v_scale=vs)
    ref_q = _ref_decode(q, dequantize_kv(kq, ks).astype(jnp.float32),
                        dequantize_kv(vq, vs).astype(jnp.float32), length)
    np.testing.assert_allclose(np.asarray(out), ref_q, atol=2e-5, rtol=2e-5)


def test_flash_decode_mha_windowed_int8(qkv_mha):
    """Window + int8 + mixed lengths together at group 1 (the in_range
    gate must not skip a block the row's window still reaches)."""
    q, k, v = qkv_mha
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    length = jnp.asarray([18, 62], jnp.int32)
    out = flash_decode(q, kq, vq, length, window=12, block_k=16,
                       k_scale=ks, v_scale=vs)
    ref_q = _ref_decode(q, dequantize_kv(kq, ks).astype(jnp.float32),
                        dequantize_kv(vq, vs).astype(jnp.float32),
                        length, window=12)
    np.testing.assert_allclose(np.asarray(out), ref_q, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("h", [12, 16])
def test_flash_decode_mha_head_count_branches(h):
    """The tile-legality rule (``_head_block``): 16 heads ride 8 per
    instance (a sublane multiple); 12 heads have no 8-multiple divisor
    and ride all 12 in one full-dim block. Both must match the
    reference, int8 included (the kernel folds the transposed scale
    tiles onto scores/probs)."""
    b, s, d = 2, 64, 16
    q = jax.random.normal(jax.random.PRNGKey(20), (b, h, d))
    k = jax.random.normal(jax.random.PRNGKey(21), (b, s, h, d))
    v = jax.random.normal(jax.random.PRNGKey(22), (b, s, h, d))
    length = jnp.asarray([33, 64], jnp.int32)
    out = flash_decode(q, k, v, length, block_k=16)
    ref = _ref_decode(q, k, v, length)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5,
                               rtol=2e-5)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    out_q = flash_decode(q, kq, vq, length, block_k=16, k_scale=ks,
                         v_scale=vs)
    ref_q = _ref_decode(q, dequantize_kv(kq, ks).astype(jnp.float32),
                        dequantize_kv(vq, vs).astype(jnp.float32),
                        length)
    np.testing.assert_allclose(np.asarray(out_q), ref_q, atol=2e-5,
                               rtol=2e-5)


def test_flash_decode_mha_zero_length_row():
    """A zero-length batch row (an empty continuous-batching slot)
    beside a live one must emit exact zeros."""
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), s) for i, s in
               enumerate([(2, 8, 16), (2, 64, 8, 16), (2, 64, 8, 16)]))
    length = jnp.asarray([0, 40], jnp.int32)
    out = np.asarray(flash_decode(q, k, v, length, block_k=16))
    np.testing.assert_array_equal(out[0], np.zeros_like(out[0]))
    ref = _ref_decode(q, k, v, np.asarray([64, 40]))  # row1 vs its ref
    np.testing.assert_allclose(out[1], ref[1], atol=2e-5, rtol=2e-5)
