"""``take_pages``: THE gather of pool pages through a page table.

Every reader of the page pool (the in-model paged branches, ``paged_view``,
``gather_pages``) goes through one helper that gathers with ``mode="clip"``:
XLA's gather clamps natively, where ``jnp.take``'s default ``fill`` mode
builds an in-bounds mask and runs a select over every gathered byte (on the
v5e ``broadcast_select_fusion``, the largest device op of the serving cells
until PR 36). Two things are pinned: the values are the bits the
clip-then-take pair gave, whatever the leaf's layout; and no compiled view
or page gather holds a select, so the second pass cannot come back unseen.
The served parity (paged against unpaged, speculation, the tier's round
trip, the latent paths) is held by the tests that already were.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import Transformer, TransformerConfig
from tony_tpu.models.transformer import LatentConfig, take_pages
from tony_tpu.serve.slots import (PagePool, cache_batch_axis, gather_pages,
                                  paged_view)

N_PAGES, PAGE = 7, 8


@functools.lru_cache(maxsize=None)
def _pool(kind: str):
    """``(cache, model)``: a small page pool of one layout, every paged
    leaf filled with random values of its own dtype (built once a kind:
    nothing here writes to it)."""
    latent = LatentConfig(q_rank=16, kv_rank=16, nope_dim=8, rope_dim=8,
                          v_dim=8) if kind == "latent" else None
    model = Transformer(TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq_len=64, dtype=jnp.bfloat16, positional="rope",
        scan_layers=kind == "scan_layers", kv_cache_quant=kind == "kv_int8",
        latent=latent, attention_backend="reference"))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    cache = PagePool(model, params, n_pages=N_PAGES, page_size=PAGE).cache
    rng = np.random.default_rng(0)

    def rnd(path, leaf):
        if cache_batch_axis(path, leaf) is None:
            return leaf
        if jnp.issubdtype(leaf.dtype, jnp.integer):
            vals = rng.integers(-120, 120, size=leaf.shape)
        else:
            vals = rng.standard_normal(leaf.shape)
        return jnp.asarray(vals).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(rnd, cache), model


def _leaf(cache, name: str):
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if str(getattr(path[-1], "key", path[-1])) == name:
            return leaf, cache_batch_axis(path, leaf)
    raise KeyError(name)


# live, repeated and sentinel (n_pages) entries, two rows of four columns
TABLE = np.array([[3, 0, 3, N_PAGES], [6, 6, N_PAGES, N_PAGES]], np.int32)


@pytest.mark.parametrize("kind, name", [
    ("kv", "cached_key"),
    ("kv_int8", "cached_value"),
    ("kv_int8", "cached_key_scale"),
    ("latent", "cached_latent"),
    ("latent", "cached_rope_key"),
    ("scan_layers", "cached_value"),
])
def test_take_pages_is_the_clip_then_take_pair_bit_for_bit(kind, name):
    leaf, ax = _leaf(_pool(kind)[0], name)
    assert (ax == 1) == (kind == "scan_layers")  # the page axis not first
    table = jnp.asarray(TABLE)
    want = jnp.take(leaf, jnp.clip(table, 0, N_PAGES - 1), axis=ax)
    got = take_pages(leaf, table, ax)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    # a sentinel reads the LAST page, as the docstrings say
    last = jnp.take(leaf, N_PAGES - 1, axis=ax)
    assert np.asarray(jnp.take(jnp.take(got, 1, axis=ax), 3, axis=ax)
                      ).tobytes() == np.asarray(last).tobytes()


def _compiled(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_the_probe_sees_the_fill_modes_select():
    """What the next test looks for IS in the compiled text of the pair
    this helper replaced: a probe that could not see the select would
    pass for ever."""
    leaf, _ = _leaf(_pool("kv")[0], "cached_key")
    table = jnp.asarray(TABLE)
    assert "select(" in _compiled(
        lambda x, t: jnp.take(x, jnp.clip(t, 0, N_PAGES - 1), axis=0),
        leaf, table)
    assert "select(" not in _compiled(take_pages, leaf, table)


@pytest.mark.parametrize("kind", ["kv", "latent"])
@pytest.mark.parametrize("fn", ["paged_view", "gather_pages"])
def test_no_page_gather_compiles_to_a_select(fn, kind):
    cache, model = _pool(kind)
    if fn == "paged_view":
        text = _compiled(
            lambda c, t: paged_view(c, t, model.cfg.max_seq_len),
            cache, jnp.asarray(TABLE))
    else:
        text = _compiled(gather_pages, cache, jnp.asarray(TABLE[0]))
    assert "gather(" in text
    assert "select(" not in text
