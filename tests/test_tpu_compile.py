"""The main path's pallas kernels, compiled for a described TPU v5e.

No chip is attached here: the TPU compiler (Mosaic included) is asked to
build each kernel at its real width for a ``v5e:2x2`` topology, which
raises what the chip's compiler would raise — block shapes the tiling
refuses, too much VMEM, a kernel that cannot be partitioned — and costs
no chip time. Interpret mode sees none of that. A compile that passes is
not a chip run; ``chip_smoke.py`` is.

Rules this file keeps (guide ``on-chip-measurement`` section 2): ONE file;
the topology is described inside a module-scoped fixture that skips when
it cannot be, never at import; compiles run in the test's own process;
``interpret=False`` is passed explicitly because ``on_tpu()`` sees the
CPU here; the persistent compile cache is off around these compiles (an
entry written without a chip cannot be read back and would warn).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def tpu_compile(topo):
    """``compile(fn, *shapes) -> hlo text`` for one described chip, with
    the persistent compile cache off for the life of the module."""
    from jax.experimental.compilation_cache import compilation_cache

    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
                for s in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def S(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _assert_kernel(hlo: str, n: int = 1):
    assert hlo.count("tpu_custom_call") >= n, \
        "no Mosaic kernel in the compiled program"


# flagship training shapes: micro-batch 4, seq 2048, 8 heads x 128,
# block_q 512 / block_k 1024 (what chip_smoke.py's TRAIN_MODEL trains)
_FLAG = S((4, 2048, 8, 128), BF16)
# the 0.99B serving model's attention: 16 q / 8 kv heads x 128
_GQA_Q, _GQA_KV = S((2, 2048, 16, 128), BF16), S((2, 2048, 8, 128), BF16)


def _flash(**kw):
    from tony_tpu.ops.attention import flash_attention

    return functools.partial(flash_attention, causal=True, block_q=512,
                             block_k=1024, interpret=False, **kw)


def _flash_grad(**kw):
    fa = _flash(**kw)

    def loss(q, k, v, seg=None):
        return fa(q, k, v, segment_ids=seg).astype(F32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))


_LONG = S((1, 8192, 8, 128), BF16)
# the training cell's attention (pythia-1.4b: 16 heads x 128, 4 x 2048)
_CELL = S((4, 2048, 16, 128), BF16)
# long enough that one head's f32 dQ (32768 x 128 x 4 B = 16 MiB, with
# its output buffers 32 MiB) passes the fused backward's VMEM budget
_LONGER = S((1, 32768, 8, 128), BF16)
_FUSED = ("flash_bwd_fused",)
_SPLIT = ("flash_bwd_dq", "flash_bwd_dkv")
# name -> (fn, argument shapes, Mosaic kernels expected, backward kernels
# by name): the forward's one kernel, and one fused backward kernel where
# the q-head group's f32 dQ fits VMEM, else a dq and a dk/dv kernel
_FLASH_CASES = {
    "fwd": (_flash, {}, (_FLAG, _FLAG, _FLAG), 1, ()),
    "fwd_bwd": (_flash_grad, {}, (_FLAG, _FLAG, _FLAG), 2, _FUSED),
    "window_fwd_bwd": (_flash_grad, {"window": 1024},
                       (_FLAG, _FLAG, _FLAG), 2, _FUSED),
    "segments_fwd_bwd": (_flash_grad, {},
                         (_FLAG, _FLAG, _FLAG, S((4, 2048), I32)), 2,
                         _FUSED),
    "gqa_fwd": (_flash, {}, (_GQA_Q, _GQA_KV, _GQA_KV), 1, ()),
    "gqa_fwd_bwd": (_flash_grad, {}, (_GQA_Q, _GQA_KV, _GQA_KV), 2,
                    _FUSED),
    "window_seq8192_fwd": (_flash, {"window": 1024},
                           (_LONG, _LONG, _LONG), 1, ()),
    "seq32768_fwd_bwd": (_flash_grad, {}, (_LONGER, _LONGER, _LONGER), 3,
                         _SPLIT),
}


def _assert_flash_kernels(hlo: str, n: int, backward: tuple):
    """Exactly ``n`` Mosaic kernels, the backward's by their names (a
    pallas call's name lands in its custom call's op name)."""
    assert hlo.count("tpu_custom_call") == n
    for name in ("flash_bwd_fused", "flash_bwd_dq", "flash_bwd_dkv"):
        assert (name in hlo) == (name in backward), name


@pytest.mark.parametrize("case", sorted(_FLASH_CASES))
def test_flash_attention_compiles(tpu_compile, case):
    make, kw, shapes, n_kernels, backward = _FLASH_CASES[case]
    _assert_flash_kernels(tpu_compile(make(**kw), *shapes), n_kernels,
                          backward)


def test_training_cells_attention_step_holds_two_kernels(tpu_compile):
    """The training cell's attention, value and gradient, at its blocks
    (512 x 1024): one forward and one fused backward kernel, where the
    split backward had a dq and a dk/dv kernel (three in all)."""
    fa = _flash()

    def loss(q, k, v):
        return fa(q, k, v).astype(F32).sum()

    hlo = tpu_compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                      _CELL, _CELL, _CELL)
    _assert_flash_kernels(hlo, 2, _FUSED)


def test_flash_attention_compiles_under_a_data_parallel_mesh(
        topo, tpu_compile, monkeypatch):
    """The chip's compiler refuses to partition a Mosaic call, so the
    model runs it per shard (``models.transformer._pallas_attention``
    with ``cfg.mesh``). Flagship shapes over the four described chips,
    forward and backward. The model asks ``on_tpu()`` which lowering
    to take and sees the CPU here, so the test steers it."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tony_tpu.models import TransformerConfig
    from tony_tpu.models.transformer import _pallas_attention

    monkeypatch.setattr("tony_tpu.ops.attention._on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    cfg = TransformerConfig(attention_backend="pallas", mesh=mesh,
                            attention_block_size=512,
                            attention_block_k=1024)
    sharded = NamedSharding(mesh, P("data"))
    qkv = [jax.ShapeDtypeStruct(_FLAG.shape, _FLAG.dtype, sharding=sharded)
           for _ in range(3)]

    def loss(q, k, v):
        return _pallas_attention(cfg, q, k, v, None).astype(F32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *qkv).compile().as_text()
    _assert_flash_kernels(hlo, 2, _FUSED)


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("heads", [(16, 8), (16, 16), (12, 12), (32, 8)],
                         ids=["gqa2", "mha16", "mha12", "gqa4"])
def test_flash_decode_compiles(tpu_compile, heads, kv_int8):
    """Batch 8 over a 2048-token cache of head dim 128: the 0.99B
    model's shape (16 q / 8 kv heads), MHA with and without an
    8-multiple head count, and a wider GQA group."""
    from tony_tpu.ops.decode import flash_decode

    h, kvh = heads
    q = S((8, h, 128), BF16)
    length = S((8,), I32)
    if kv_int8:
        kv, sc = S((8, 2048, kvh, 128), I8), S((8, 2048, kvh), F32)
        hlo = tpu_compile(
            lambda q, k, v, ln, ks, vs: flash_decode(
                q, k, v, ln, k_scale=ks, v_scale=vs, interpret=False),
            q, kv, kv, length, sc, sc)
    else:
        kv = S((8, 2048, kvh, 128), BF16)
        hlo = tpu_compile(
            lambda q, k, v, ln: flash_decode(q, k, v, ln, interpret=False),
            q, kv, kv, length)
    _assert_kernel(hlo)


@pytest.mark.parametrize("m", [8, 512], ids=["decode", "prefill"])
def test_q8_matmul_compiles(tpu_compile, m):
    """int8 dequant-matmul at the 0.99B MLP shape (2048 x 8192)."""
    from tony_tpu.ops.quant import q8_matmul

    hlo = tpu_compile(
        functools.partial(q8_matmul, interpret=False),
        S((m, 2048), BF16), S((2048, 8192), I8), S((8192,), F32))
    _assert_kernel(hlo)


def test_fused_adamw_kernel_compiles(tpu_compile):
    """One large leaf (the flagship's 1024 x 4096 MLP kernel): bf16
    grads in, fp32 master/moments and the bf16 compute copy out."""
    from tony_tpu.ops.adamw import _leaf_update_kernel

    leaf = S((1024, 4096), F32)
    hlo = tpu_compile(
        functools.partial(_leaf_update_kernel, b1=0.9, b2=0.999, eps=1e-8,
                          wd=1e-4, compute_dtype=BF16, interpret=False),
        S((1024, 4096), BF16), leaf, leaf, leaf, S((1, 128), F32))
    _assert_kernel(hlo)


@pytest.mark.parametrize("fn", ["rmsnorm", "add_rmsnorm"])
def test_rmsnorm_compiles_under_jit(tpu_compile, fn):
    """[batch, seq, d_model] activations of the flagship."""
    from tony_tpu.ops import fused

    x, scale = S((4, 2048, 1024), BF16), S((1024,), F32)
    if fn == "rmsnorm":
        hlo = tpu_compile(
            functools.partial(fused.rmsnorm, interpret=False), x, scale)
    else:
        hlo = tpu_compile(
            functools.partial(fused.add_rmsnorm, interpret=False),
            x, x, scale)
    _assert_kernel(hlo)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_serving_step_writes_the_page_pool_in_place(topo, tpu_compile,
                                                    program):
    """The engine's decode chunk and paged prefill, compiled for the
    chip over the serving cells' page pool (1,024 pages x 64 tokens x
    8 kv heads x 128, bf16; one layer, a narrow MLP and vocabulary to
    keep the compile short): the pool is donated, so the program holds
    NO op that copies a whole pool leaf — before the donation every
    step carried one ``copy(%cache__block_i__cached_key|value)`` per
    layer and K/V, half its device time (PERF.md, PR 28) — and every
    byte it returns aliases an argument. Nor does any op SELECT over
    the gathered page view: the gather clamps by its own mode
    (``take_pages``), where ``jnp.take``'s fill mode ran a second pass
    over every gathered byte (``broadcast_select_fusion``, the largest
    device op of the serving cells until PR 36)."""
    import re

    from tony_tpu.models import Transformer, TransformerConfig
    from tony_tpu.serve import engine
    from tony_tpu.serve.slots import STATE_COLS, paged_cache

    one_chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    def A(shape, dtype=I32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    model = Transformer(TransformerConfig(
        vocab_size=4096, d_model=1024, n_heads=8, n_kv_heads=8, n_layers=1,
        d_ff=2048, max_seq_len=2048, dtype=BF16, norm="rms",
        gated_mlp=True, tied_embeddings=False))
    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), I32))["params"]))
    pool = on_chip(jax.eval_shape(
        lambda p: paged_cache(model, p, 1024, 64), params))
    b, cols = 8, 8
    if program == "decode":
        lowered = engine._decode_chunk.lower(
            model, params, pool, A((b, STATE_COLS)),
            A((b, 1 + STATE_COLS)), A((b, cols)), n_steps=2,
            eos_ids=(2,))
    else:
        lowered = engine._paged_prefill_admit.lower(
            model, params, pool, A((1, 128)), A((1, 128)), A(()),
            A((1, cols)), A((), F32), A(()), A((2,), jnp.uint32))
    compiled = lowered.compile()
    whole_leaf = re.compile(r"= bf16\[1024,64,8,128\]\S* copy\(")
    over_view = re.compile(r"= bf16\[\d+,%d,64,8,128\]\S* select\(" % cols)
    assert not [ln for ln in compiled.as_text().splitlines()
                if whole_leaf.search(ln) or over_view.search(ln)]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * 1024 * 64 * 8 * 128 * 2


@pytest.mark.parametrize("pack", [True, False], ids=["packed", "heads"])
def test_heads_of_64_packed_into_lanes_keep_the_pool_row_major(
        topo, tpu_compile, pack):
    """A page pool of 8 kv heads of 64 (the mixed conv/attention
    configuration's widths; one attention and one conv layer, few
    experts): stored as heads, its minor dimension is under the 128
    lanes, the compiler lays the pool out pages-minor and every decode
    step copies each whole leaf to row-major and back; stored as rows of
    128 (``kv_pack_lanes``, derived from the widths: the control that
    stores heads is a subclass) it stays row-major and no whole leaf is
    copied. The conv layer's state, a row a slot, is returned in
    place too."""
    import re

    from tony_tpu.models import (Transformer, TransformerConfig,
                                 lfm2_moe_config)
    from tony_tpu.serve import engine
    from tony_tpu.serve.slots import STATE_COLS, paged_cache

    @dataclasses.dataclass(frozen=True)
    class StoredAsHeads(TransformerConfig):
        kv_pack_lanes = property(lambda self: False)

    one_chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    def A(shape, dtype=I32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg = lfm2_moe_config(dict(
        vocab_size=4096, hidden_size=2048, num_attention_heads=32,
        num_key_value_heads=8, num_hidden_layers=2, intermediate_size=1024,
        max_position_embeddings=2048, norm_eps=1e-5, rope_theta=1e6,
        layer_types=["full_attention", "conv"], conv_L_cache=3,
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=256,
        num_dense_layers=1, use_expert_bias=True,
        tie_word_embeddings=False), dtype=BF16)
    assert cfg.kv_pack_lanes
    if not pack:
        cfg = StoredAsHeads(**{f.name: getattr(cfg, f.name)
                               for f in dataclasses.fields(cfg)})
    model = Transformer(cfg)
    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), I32))["params"]))
    b, cols = 8, 8
    pool = on_chip(jax.eval_shape(
        lambda p: paged_cache(model, p, 1024, 64, slots=b), params))
    leaf = pool["block_0"]["attn"]["cached_key"].shape
    assert leaf == ((1024, 64, 4, 128) if pack else (1024, 64, 8, 64))
    assert pool["block_1"]["conv"]["conv_state"].shape == (b, 2, 2048)
    compiled = engine._decode_chunk.lower(
        model, params, pool, A((b, STATE_COLS)), A((b, 1 + STATE_COLS)),
        A((b, cols)), n_steps=1, eos_ids=(2,)).compile()
    copies = [ln for ln in compiled.as_text().splitlines() if re.search(
        r"= bf16\[1024,64,\d+,\d+\]\S* copy\(", ln)]
    assert bool(copies) != pack, copies[:2]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * 1024 * 64 * 512 * 2 \
        + b * 2 * 2048 * 2


def test_loss_head_gradient_is_one_pass_at_the_training_cells_shapes(
        tpu_compile, topo):
    """The training cell's head (8,188 rows of 2,048 bf16 against an
    untied 50,304-row head, float32 matmul operands): differentiated, it
    compiles to ONE loop over row tiles holding three matmuls (logits,
    dhidden, dW) and no recompute of the logits; the value alone keeps
    the vocab-chunked scan's one matmul."""
    import re

    from tony_tpu.ops import chunked_cross_entropy

    def loss(h, e, labels):
        return chunked_cross_entropy(h, e, labels, chunk_size=2048)

    shapes = (S((8188, 2048), BF16), S((50304, 2048), BF16),
              S((8188,), I32))
    for fn, scope, n_dots in (
            (jax.value_and_grad(loss, argnums=(0, 1)), "xent.fused", 3),
            (loss, "xent.lse", 1)):
        hlo = tpu_compile(fn, *shapes)
        assert len(re.findall(r"\) while\(", hlo)) == 1
        dots = [ln for ln in hlo.splitlines()
                if " convolution(" in ln and scope in ln]
        assert len(dots) == n_dots, dots
