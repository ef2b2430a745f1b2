"""Continuous-batching scheduler over one resident jitted decode step.

Design (the Orca/vLLM iteration-level result, on the TPU static-shape
path):

- ONE decode step of fixed shape [batch_size, 1] over the fixed
  [batch_size, max_seq_len] cache compiles once and serves the whole
  session. Per-slot positions ride in as a traced [b] vector
  (``Transformer.__call__(..., positions=...)``); per-request
  temperature/top-k are traced too, so a new mix of requests NEVER
  recompiles anything.
- Prefill runs as a separate batch-1 jit at a few BUCKETED lengths
  (powers of two): O(log max_seq_len) compiles ever, right-padded —
  causal attention keeps pad junk out of the real positions' K/V, and
  the slot's length masks the tail until decode overwrites it.
- Each ``step()``: admit pending prompts into free slots (prefill,
  slot copy and first-token sample FUSED into one dispatch per
  request), enqueue a CHUNK of K batched decode micro-steps as one
  lax.scan dispatch (K adapts to the live slots' remaining budgets,
  rounded to a power of two so at most log2(chunk_steps)+1 programs
  ever compile; sampling is per-slot inside the chunk), then read the
  tokens of the OLDEST chunk in the device's queue, detect EOS /
  budget per slot host-side, evict finished slots and return their
  results. Two chunks are kept in flight (``Server._decode_round``):
  the device runs the younger while the host walks and streams the
  older one's tokens. A finished slot is refilled the next iteration
  — mixed-length traffic never waits on the longest sequence in the
  batch (the fixed-batch ``generate()`` failure mode). Chunking
  amortizes the per-dispatch host cost over K tokens; a slot that
  finishes mid-chunk FREEZES for the rest of it, and for the chunk
  already queued behind it (no K/V, no rng, its last token re-emitted,
  which the host trims before reporting), so results are unaffected.

Greedy outputs are token-for-token identical to a solo ``generate()``
of the same prompt (the exactness contract tests/test_serve.py pins):
prefill math is position-exact under bucket padding and the per-slot
step runs the same attention reduction over the same [max_seq_len]
buffer as the scalar-index path.
"""

from __future__ import annotations

import functools
import itertools
import logging
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from tony_tpu.models.generate import (_is_eos, init_cache,
                                      multi_decode_step,
                                      normalize_eos_ids,
                                      single_decode_step)
from tony_tpu.models.transformer import _serve_replicate
from tony_tpu.obs.goodput import (CostModel, detect_hbm_gbps,
                                  detect_peak_flops, ledger)
from tony_tpu.obs.phases import HostPhases
from tony_tpu.obs.timeline import DispatchRecord, DispatchTimeline
from tony_tpu.parallel.moe import N_COUNTS
from tony_tpu.serve.faults import FaultPlan
from tony_tpu.serve.migrate import SessionSnapshot, StaleDelta, \
    snapshot_from_doc
from tony_tpu.serve.prefix import PrefixStore
from tony_tpu.serve.slots import (PagePool, SlotCache, _copy_page,
                                  _gather_pages, _read_slot,
                                  _scatter_pages, apply_patch,
                                  cache_batch_axis, default_page_size,
                                  kv_page_nbytes, pack_state, page_axis,
                                  paged_view, paged_write_back, slot_rows,
                                  store_slot_rows, unpack_state)
from tony_tpu.serve.tier import (HostPageTier, decode_array,
                                 decode_payload, pad_host_pages,
                                 payload_pages)

log = logging.getLogger(__name__)


def bucket_len(n: int, max_len: int, minimum: int = 16) -> int:
    """Smallest power-of-two bucket >= n (floor ``minimum``, cap
    ``max_len``): prefill compiles once per bucket, not once per length."""
    b = minimum
    while b < n:
        b *= 2
    return min(b, max_len)


def _bucket_pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1). Quantizes the verify
    window's draft width so at most log2(speculate_k)+1 verify programs
    ever compile — same discipline as the prefill buckets."""
    b = 1
    while b < n:
        b *= 2
    return b


def _propose_draft(ctx: np.ndarray, k: int,
                   max_ngram: int = 3) -> np.ndarray:
    """Prompt-lookup drafting (the n-gram self-speculation vLLM/HF
    popularized): find the most RECENT earlier occurrence of the
    longest suffix n-gram of ``ctx`` (n from ``max_ngram`` down to 1)
    and propose the up-to-``k`` tokens that followed it. No draft
    model, no device work — one numpy scan over a <= max_seq_len
    context per live slot per round, so a miss costs essentially
    nothing. Extractive / repetitive continuations (quoting the prompt,
    structured output, greedy loops) hit constantly; free-form text
    mostly misses and the engine's per-slot EMA stops asking. Returns
    [0..k] proposed continuation tokens (empty = no match)."""
    n_ctx = len(ctx)
    for n in range(min(max_ngram, n_ctx - 1), 0, -1):
        pat = ctx[n_ctx - n:]
        # windows over ctx[:-1]: every start with >= 1 token following
        # the match; the suffix itself (ending at the last token) is
        # structurally excluded
        win = np.lib.stride_tricks.sliding_window_view(ctx[:-1], n)
        hits = np.flatnonzero((win == pat).all(axis=1))
        if hits.size:
            start = int(hits[-1]) + n
            return ctx[start:start + k]
    return ctx[:0]


def _seed_offset(cache, offset):
    """Set a cache pytree's shared position counters (per-layer
    ``cache_index``, learned-positional ``pos_index``) to ``offset`` —
    the scalar decode path then WRITES the next tokens at ``offset``,
    rotates them there (RoPE reads ``cache_index``), and lets their
    queries see everything at-or-before them: exactly the offset
    attention a suffix prefill over a seeded prefix row needs.
    ``offset`` is traced; scan_layers models carry stacked [n_layers]
    counters, which full_like broadcasts over."""
    def seed(path, leaf):
        name = str(path[-1].key if hasattr(path[-1], "key") else path[-1])
        if name in ("cache_index", "pos_index"):
            return jnp.full_like(leaf, offset)
        return leaf

    return jax.tree_util.tree_map_with_path(seed, cache)


@functools.partial(jax.jit, static_argnames=("model",))
def _prefill(model, params, prompt, length, offset=None, row=None):
    """Prefill ONE request's token window [1, Lb] (right-padded to its
    bucket) into a batch-1 cache. Returns (row_cache, logits [1, V] at
    the REAL last position ``length - 1`` of the window — the padded
    tail's logits are junk and never sampled).

    ``offset``/``row`` generalize this to SUFFIX prefill for the prefix
    store: ``row`` is a carried batch-1 cache whose positions
    ``[0, offset)`` already hold the shared prefix's K/V, and the
    window holds only the remaining prompt tokens, written/rotated/
    attended from position ``offset`` (counters seeded via
    ``_seed_offset``). With both None this is the classic full prefill
    of a fresh cache from position 0."""
    cache = init_cache(model, params, 1) if row is None else row
    if offset is not None:
        cache = _seed_offset(cache, offset)
    logits, vars_ = model.apply({"params": params, "cache": cache},
                                prompt, decode=True, mutable=["cache"])
    last = jax.lax.dynamic_slice_in_dim(logits, length - 1, 1, axis=1)
    return vars_["cache"], last[:, 0]


@functools.partial(jax.jit, static_argnames=("model", "with_row"),
                   donate_argnames=("cache",))
def _prefill_admit(model, params, cache, prompt, length, slot, temp,
                   top_k, key, offset=None, row=None, *, with_row=False):
    """The fused admit: prefill [1, Lb] (optionally a suffix seeded
    from a prefix-store ``row`` at ``offset``), copy the row into
    ``slot`` of the resident cache, sample the first continuation
    token — ONE dispatch per admitted request (three separate
    dispatches measured ~3x the whole per-request host cost at CPU
    proxy sizes). Compiles once per prefill bucket; slot / length /
    offset / sampling knobs are traced. ``with_row=True`` additionally
    returns the prefilled row and its last-position logits so the
    engine can donate them to the prefix store. ``cache`` is DONATED
    (every program that returns the tree's successor does — the rule
    is ``SlotCache.cache``'s); the carried ``row`` is not: a prefix
    entry's row outlives this admit."""
    from tony_tpu.serve.slots import write_slot_row

    new_row, last = _prefill(model, params, prompt, length, offset, row)
    cache = write_slot_row(cache, new_row, slot)
    tok, key = _sample_rows(last, key[None],
                            jnp.asarray(temp, jnp.float32)[None],
                            jnp.asarray(top_k, jnp.int32)[None])
    if with_row:
        return cache, tok[0].astype(jnp.int32), key[0], new_row, last
    return cache, tok[0].astype(jnp.int32), key[0]


@jax.jit
def _sample_first(logits, temp, top_k, key):
    """The PAGED exact-hit admit: the stored pages are aliased into the
    slot's table host-side (a refcount bump — no device copy at all,
    vs the unpaged path's full ``write_slot_row``), so the only device
    work left is sampling the first continuation from the stored
    last-position logits with THIS request's knobs. One tiny dispatch
    over [1, V]."""
    tok, key = _sample_rows(logits, key[None],
                            jnp.asarray(temp, jnp.float32)[None],
                            jnp.asarray(top_k, jnp.int32)[None])
    return tok[0].astype(jnp.int32), key[0]


@functools.partial(jax.jit, static_argnames=("model",),
                   donate_argnames=("cache",))
def _paged_prefill_admit(model, params, cache, window, positions, length,
                         table, temp, top_k, key, slot=None):
    """The paged fused admit: a prefill is ONE multi-token per-slot
    window over the resident page pool — ``window`` [1, Lb] holds the
    (suffix of the) prompt right-padded to its bucket, ``positions``
    [1, Lb] its absolute positions (padding = -1, whose writes DROP —
    unlike the unpaged bucket, no junk is ever written past the
    prompt), ``table`` [1, max_pages] the slot's page table. K/V land
    straight in the slot's pages (no separate row + slot-copy; the
    pool is DONATED, so the scatter writes the caller's buffers in
    place), the last REAL position's logits feed the first-token
    sample. Returns
    ``(cache, token, rng, last_logits [1, V])`` — the logits go to the
    prefix store so the next exact hit skips everything. ``slot`` (a
    model with slot-resident state, ``slots.slot_resident``): the
    window continues that slot's rows and leaves its own there."""
    cache, logits = _paged_window(model, params, cache, window, positions,
                                  table, slot)
    last = jax.lax.dynamic_slice_in_dim(logits, length - 1, 1,
                                        axis=1)[:, 0]
    tok, key = _sample_rows(last, key[None],
                            jnp.asarray(temp, jnp.float32)[None],
                            jnp.asarray(top_k, jnp.int32)[None])
    return cache, tok[0].astype(jnp.int32), key[0], last


def _paged_window(model, params, cache, window, positions, table, slot):
    """A one-row multi-token window into the page pool; with ``slot``
    the slot-resident leaves go in as that slot's rows and come back
    into it (``slots.slot_rows``)."""
    if slot is None:
        return multi_decode_step(model, params, cache, window, positions,
                                 page_table=table)
    out, logits = multi_decode_step(model, params, slot_rows(cache, slot),
                                    window, positions, page_table=table)
    return store_slot_rows(cache, out, slot), logits


@functools.partial(jax.jit, static_argnames=("model",),
                   donate_argnames=("cache",))
def _paged_prefill_chunk(model, params, cache, window, positions, table,
                         slot=None):
    """One INTERMEDIATE chunk of a chunked prefill: a multi-token
    window written straight into the slot's pages at absolute
    ``positions`` — ``_paged_prefill_admit`` minus the first-token
    sample (only the FINAL chunk holds the real last position, so
    sampling here would be junk work). Compiles once per chunk bucket
    x view span — and the chunk budget is quantized to the bucket
    grid, so in practice ONE chunk program serves a whole serving
    session. Returns ``(cache, mark)``: ``mark`` is one element read
    off a written pool leaf, there for the host to sync on — the tree
    itself may be donated onward (by a co-located engine on a shared
    pool) before the host gets to wait on it."""
    cache, _ = _paged_window(model, params, cache, window, positions,
                             table, slot)
    leaf = next(x for path, x
                in jax.tree_util.tree_flatten_with_path(cache)[0]
                if page_axis(path, x) is not None)
    return cache, leaf[(0,) * leaf.ndim]


@functools.partial(jax.jit, donate_argnames=("cache",))
def _hit_admit(cache, row, slot, logits, temp, top_k, key):
    """Exact-prompt prefix hit: NO prefill at all — copy the stored row
    into ``slot`` and sample the first continuation from the stored
    last-position logits with THIS request's sampling knobs (so a hit
    behaves identically across greedy/temperature/seed mixes). One
    dispatch, everything traced."""
    from tony_tpu.serve.slots import write_slot_row

    cache = write_slot_row(cache, row, slot)
    tok, key = _sample_rows(logits, key[None],
                            jnp.asarray(temp, jnp.float32)[None],
                            jnp.asarray(top_k, jnp.int32)[None])
    return cache, tok[0].astype(jnp.int32), key[0]


def _row_nbytes(cache) -> int:
    """Bytes one slot's row costs in the prefix store: batched leaves
    contribute one slot's share, shared counters their whole (tiny)
    size — what ``read_slot_row`` of this cache would occupy."""
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        nbytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        ax = cache_batch_axis(path, leaf)
        total += nbytes // leaf.shape[ax] if ax is not None else nbytes
    return total


def _padded_pages(pages: list, sentinel: int | None = None) -> list:
    """A page-id list pow2-padded to its gather/scatter bucket — the
    ONE place the padding convention lives: gathers duplicate the last
    page (junk rows the consumer slices or the receiving scatter
    drops), scatters pad with the pool's ``n_pages`` sentinel (writes
    drop)."""
    n_pad = _bucket_pow2(max(1, len(pages)))
    fill = pages[-1] if sentinel is None else sentinel
    return list(pages) + [fill] * (n_pad - len(pages))


def _usable_prefix(off: int, n: int, max_len: int, minimum: int) -> int:
    """Largest usable seed length <= ``off`` for an ``n``-token prompt:
    the suffix's power-of-two bucket must still fit the cache
    (``off + bucket <= max_len`` — dynamic_update_slice would otherwise
    clamp the write start and corrupt earlier positions). Shrinking
    ``off`` grows the suffix (and possibly its bucket), so iterate;
    terminates because ``off`` strictly decreases, and 0 (full prefill)
    always fits."""
    while off > 0:
        lb = bucket_len(n - off, max_len, minimum)
        if off + lb <= max_len:
            return off
        off = max(0, max_len - lb)
    return 0


def _sample_rows(logits, rngs, temps, top_ks):
    """Per-row sampling with TRACED temperature/top-k — one compiled
    program serves every request mix. Greedy rows (temp == 0) take
    argmax; sampled rows apply a per-row top-k cut by rank (ties beyond
    rank k are dropped, vs sample_logits' static-k threshold keeping
    them — indistinguishable for continuous logits), then draw from
    their own rng. Returns (tokens, advanced rngs).

    GATED on the live mix (lax.cond, traced preds): an all-greedy batch
    — the serving default — skips the rng splits and both sort passes
    entirely (measured 0.89 -> 0.04 ms per step at CPU proxy sizes,
    most of the micro-step gap to generate()'s scan body); the top-k
    sorts additionally skip whenever no live SAMPLED row requests a cut
    — a greedy row's top_k is dead weight (the final where discards its
    draw), so it must not force the two full-vocab sorts on the whole
    batch. Greedy rows never consume rng, so a request's draws stay
    reproducible regardless of what it is co-scheduled with."""
    greedy = jnp.argmax(logits, axis=-1)

    def sampled(_):
        scaled = logits / jnp.maximum(temps[:, None], 1e-6)

        def topk_cut(x):
            order = jnp.argsort(-x, axis=-1)
            ranks = jnp.argsort(order, axis=-1)
            keep = (top_ks[:, None] <= 0) | (ranks < top_ks[:, None])
            return jnp.where(keep, x, -1e30)

        cut = jax.lax.cond(jnp.any((temps > 0.0) & (top_ks > 0)),
                           topk_cut, lambda x: x, scaled)
        pair = jax.vmap(lambda k: jax.random.split(k, 2))(rngs)
        drawn = jax.vmap(jax.random.categorical)(pair[:, 1], cut)
        return jnp.where(temps == 0.0, greedy, drawn), pair[:, 0]

    return jax.lax.cond(jnp.any(temps > 0.0), sampled,
                        lambda _: (greedy, rngs), None)


def _frozen_body(model, params, temps, top_ks, eos_ids: tuple,
                 moe_stats: bool = False):
    """The decode micro-step: the scan body of ``_decode_chunk`` and of
    ``_verify_chunk``'s continuation. Carry is ``(cache, tok,
    positions, rngs, done, rem)``; a row whose emitted token hit EOS —
    or whose remaining budget ``rem`` ran out — FREEZES: its later
    micro-steps write to the dropped sentinel position (no KV bytes
    land), take the greedy sampling path (no rng advance — a frozen
    sampled row must not move any draw chain), and re-emit the frozen
    token, so the host's walk past a finish is a consistency check and
    the trailing positions land as padding. For a row that never
    freezes every ``where`` is the identity, which is what keeps
    chunk-invariance bitwise. With ``moe_stats`` (a model of routed
    experts, ``_decode_chunk`` only) each step also emits its layers'
    routed-expert counts beside its tokens: ``(nxt, counts [4])``."""
    def body(carry, _):
        cache, tok, positions, rngs, done, rem = carry
        eff_pos = jnp.where(done, -1, positions)
        cache, last, *counts = single_decode_step(
            model, params, cache, tok, positions=eff_pos,
            moe_stats=moe_stats)
        nxt, rngs = _sample_rows(last, rngs,
                                 jnp.where(done, 0.0, temps), top_ks)
        nxt = jnp.where(done, tok, nxt.astype(jnp.int32))
        positions = jnp.where(done | (positions < 0), positions,
                              positions + 1)
        rem = jnp.where(done, rem, rem - 1)
        done = done | _is_eos(nxt, eos_ids) | (rem <= 0)
        return (cache, nxt, positions, rngs, done, rem), \
            (nxt, counts[0]) if moe_stats else nxt

    return body


@functools.partial(jax.jit, static_argnames=("model", "n_steps",
                                             "eos_ids"),
                   donate_argnames=("cache",))
def _decode_chunk(model, params, cache, state, patch, table=None, *,
                  n_steps: int, eos_ids: tuple = ()):
    """The resident serving step: ``n_steps`` decode micro-steps for
    EVERY slot as one lax.scan dispatch (empty slots compute garbage
    that nothing reads — the price of a never-recompiled static shape).
    Per-slot sampling and rng advance ride inside the scan; returns
    (cache, tokens [b, n_steps], state). ``n_steps`` is static (the
    scheduler quantizes it to powers of two, so at most
    log2(chunk_steps)+1 programs ever compile).

    ``state`` [b, STATE_COLS] int32 is the round's small carry, which
    LIVES ON THE DEVICE between rounds (``SlotCache.state``): per slot
    the last token, the position (-1 = empty), the remaining budget,
    ``top_k``, and ``temperature`` and the rng key bit-cast. ``patch``
    [b, 1 + STATE_COLS] is the host's word on it: a mask column, then
    the row to take in the resident one's place (``apply_patch``, one
    ``where`` before the scan) — the rows admission, eviction or a
    host-fed round changed, or an all-clear patch that never left the
    device. What the scan carries out (token, position, budget, key) is
    the state handed back, whatever ``n_steps`` was; the sampling knobs
    pass through. One program serves a clean and a patched round.

    ``cache`` is DONATED: the returned tree is the caller's own
    buffers with the chunk's K/V written IN PLACE (each leaf aliases
    its output), so a step moves ``b x n_steps`` cache entries and not
    a second copy of the whole tree — and the tree passed in is dead
    the moment this is enqueued (``SlotCache.cache``). ``state``,
    ``patch`` and ``table`` are NOT: they are a few hundred bytes, the
    all-clear patch and the table are used again, and a dispatch that
    fails must leave the last state alive.

    The scan threads a per-slot ``done`` flag and the remaining budget
    (``_frozen_body``): a slot that samples EOS or exhausts its budget
    mid-chunk stops writing K/V (sentinel position), stops advancing
    rng, and re-emits its final token — so a deep chunk decodes
    nothing past a finish. The flag is SEEDED from the state itself
    (empty, budget spent, or last token a stop token), so the freeze
    holds ACROSS dispatches too: the engine enqueues a round before it
    has read its predecessor's tokens, and a row that finished there
    rides this one frozen from its first step (no K/V, no rng, its
    state row unchanged). ``eos_ids`` is static per engine (one
    compile).

    ``table`` [b, cols] switches to the paged cache layout — but
    NOT by gathering inside every micro-step: the slot view is
    gathered from the pools ONCE (``paged_view``), the whole scan runs
    the plain unpaged per-slot program against it (bitwise-identical
    math, and the gather cost amortizes over the chunk depth), and
    only the chunk's ``b x n_steps`` new K/V entries scatter back to
    their pages at the end (``paged_write_back``; a frozen row's
    unwritten tail positions copy their own gathered content back —
    an identity write). The table is fixed across the chunk, so the
    host pre-extends it to cover every position the chunk will write
    (engine ``_enqueue_round``).

    A model of routed experts (``cfg.routed``) hands its counts back IN
    the token array: ``N_COUNTS`` more columns, the round's sums of
    ``RoutedMLP``'s four counts, the same in every row — they ride the
    one copy back a round makes (``Server._land`` splits them off)."""
    max_len = model.cfg.max_seq_len
    moe_stats = model.cfg.routed is not None
    state = apply_patch(state, patch)
    tok, positions, rem, top_ks, temps, rngs = unpack_state(state)
    pool_cache, start = cache, positions
    if table is not None:
        cache = paged_view(cache, table, max_len)

    body = _frozen_body(model, params, temps, top_ks, eos_ids, moe_stats)
    # a row that finished in the round BEFORE this one, and that the
    # host has not seen yet (this round was enqueued before that one's
    # tokens were read), starts frozen: its budget is spent or its last
    # token is a stop token. No row the host has seen is either
    # (admission finishes such a request on the spot), so for those the
    # seed is ``positions < 0`` as it always was
    done = (positions < 0) | (rem <= 0) | _is_eos(tok, eos_ids)
    carry = (cache, tok, positions, rngs, done, rem)
    counts = None
    if n_steps > 1:
        carry, toks = jax.lax.scan(body, carry, None, length=n_steps)
        if moe_stats:
            toks, counts = toks[0], jnp.sum(toks[1], axis=0)
        toks = jnp.moveaxis(toks, 0, 1)  # [steps, b] -> [b, steps]
    else:
        carry, tok1 = body(carry, None)
        if moe_stats:
            tok1, counts = tok1
        toks = tok1[:, None]
    if moe_stats:
        toks = jnp.concatenate([toks, jnp.broadcast_to(
            counts, (toks.shape[0], N_COUNTS))], axis=1)
    cache, tok, positions, rngs, _, rem = carry
    if table is not None:
        cache = paged_write_back(pool_cache, cache, table, start,
                                 n_steps, max_len)
    # under a mesh pinned replicated, as the host sends it: the
    # successor meets the same executable and no round re-shards it
    state = _serve_replicate(
        model.cfg, pack_state(state, tok, positions, rem, rngs))
    return cache, toks, state


@functools.partial(jax.jit, static_argnames=("model", "window",
                                             "n_steps", "eos_ids"),
                   donate_argnames=("cache",))
def _verify_chunk(model, params, cache, toks, positions, draft_len,
                  temps, top_ks, rngs, rem, table=None, *,
                  window: int, n_steps: int, eos_ids: tuple = ()):
    """The speculative verify dispatch: score ``window`` positions for
    EVERY slot in one batched multi-token pass (multi_decode_step) and
    judge each row's draft against its own greedy verdicts — the
    Leviathan et al. draft-and-verify step on the resident cache.

    Row layout: ``toks[i] = [last_token, draft_1..draft_d, pad...]``
    at ``positions[i] = [p, p+1, .., p+d, -1...]`` (``d`` =
    ``draft_len[i]``; padding writes drop, padding logits are junk).
    Returns ``(cache, emit [b, window], accepted [b], cont [b,
    n_steps], rngs)``:

    - ``emit[i, 0]`` is the token following ``last_token`` under the
      row's OWN sampling knobs (_sample_rows: argmax for greedy rows,
      a real draw advancing the rng once for sampled rows — exactly
      one advance per emitted token, so a sampled request's draw chain
      is identical to the chunked path's). Non-speculating rows
      consume only this.
    - ``emit[i, 1:]`` are greedy verdicts: ``emit[i, j]`` follows the
      window prefix through ``draft_j``.
    - ``accepted[i]`` = length of the leading run of draft tokens
      equal to the previous position's greedy verdict. The scheduler
      appends ``emit[i, :accepted[i] + 1]`` — accepted drafts plus the
      bonus verdict after them — and rewinds nothing: K/V written for
      rejected drafts sits beyond the slot's advanced length, invisible
      under per-row masked visibility and overwritten as the slot
      decodes on.

    ``window`` is static and power-of-two-plus-one bucketed, so at most
    log2(speculate_k)+1 verify programs ever compile per depth.

    The same dispatch (a) caps ``accepted`` at the first emitted stop
    token, so a mid-window EOS costs zero bonus-past-finish waste,
    and (b) runs ``n_steps`` >= 1 ``_frozen_body`` decode micro-steps
    CONTINUING from each row's own bonus verdict (``rem`` [b] is each
    row's remaining budget before the window): a speculating round
    costs ONE dispatch for accepted+1+n_steps tokens, and a
    non-drafting co-tenant still advances a chunk's worth. ``table``
    [b, cols] switches to the paged cache layout (pre-extended by the
    host to cover window + continuation) and works like the chunk
    path: ONE ``paged_view`` gather feeds both the window pass and the
    continuation scan, and ``paged_write_back`` returns the whole
    written span (positions the row never wrote copy their own
    gathered content back — identity)."""
    max_len = model.cfg.max_seq_len
    pool_cache, start = cache, positions[:, 0]
    if table is not None:
        cache = paged_view(cache, table, max_len)
    cache, logits = multi_decode_step(model, params, cache, toks,
                                      positions)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [b, w]
    tok0, rngs = _sample_rows(logits[:, 0], rngs, temps, top_ks)
    emit = jnp.concatenate([tok0[:, None].astype(jnp.int32),
                            greedy[:, 1:]], axis=1)
    j = jnp.arange(window - 1)[None, :]
    match = (toks[:, 1:] == greedy[:, :-1]) & (j < draft_len[:, None])
    accepted = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                       axis=1)
    # EOS-capped acceptance: the host appends emit[:accepted + 1] and
    # stops at the first stop token — capping accepted AT that index
    # makes the device and host agree that nothing past it was ever
    # accepted (the "verify bonus past EOS" waste bucket goes to zero;
    # the consumed token run is unchanged, so outputs are identical)
    if eos_ids:
        idx = jnp.arange(window)[None, :]
        first_stop = jnp.min(jnp.where(_is_eos(emit, eos_ids), idx,
                                       window), axis=1)
        accepted = jnp.minimum(accepted, first_stop)
    # the continuation: each row resumes from its own bonus verdict at
    # its own position, with the frozen-body discipline bounding
    # EOS/budget — live rows decode n_steps more real tokens in THIS
    # dispatch, so non-drafting co-tenants are never dragged to one
    # token per round
    rows = jnp.arange(toks.shape[0])
    bonus = emit[rows, accepted]
    live = start >= 0
    consumed = accepted + 1
    rem_c = jnp.where(live, jnp.asarray(rem, jnp.int32) - consumed, 0)
    done = ~live | _is_eos(bonus, eos_ids) | (rem_c <= 0)
    cont_pos = jnp.where(live, start + consumed, -1)
    body = _frozen_body(model, params, temps, top_ks, eos_ids)
    carry = (cache, bonus, cont_pos, rngs, done, rem_c)
    if n_steps > 1:
        carry, cont = jax.lax.scan(body, carry, None, length=n_steps)
        cont = jnp.moveaxis(cont, 0, 1)  # [steps, b] -> [b, steps]
    else:
        carry, c1 = body(carry, None)
        cont = c1[:, None]
    cache, rngs = carry[0], carry[3]
    if table is not None:
        cache = paged_write_back(pool_cache, cache, table, start,
                                 window + n_steps, max_len)
    return cache, emit, accepted, cont, rngs


class QueueFull(RuntimeError):
    """``submit()`` refused: the pending queue is at ``max_pending``.

    The typed backpressure signal — callers (the gateway's admission
    layer, the JSONL loop) translate it into 429/shedding instead of
    letting the queue grow without bound and OOMing the host."""


class PoolExhausted(RuntimeError):
    """``submit()`` refused: the request's worst-case KV-page need
    (prompt + clamped max_new_tokens) exceeds the ENTIRE page pool, so
    it could never be admitted — waiting would wedge the queue behind
    it forever. Deliberately not a ValueError: the gateway sheds it
    503 (capacity), not 400 (malformed) — resubmitting against a
    bigger pool is legitimate. Transient pressure (the pool is
    momentarily full of live requests) never raises: the request just
    stays pending until pages free."""


@dataclass
class Request:
    """One generation request. ``prompt`` is token ids; sampling knobs
    are per-request (greedy default). ``id`` is echoed on the Result
    (auto-assigned when None).

    The disaggregation fields (both paged-engine-only):
    ``prefill_only`` makes the engine STOP after prefill — the Result
    comes back ``finish_reason="handoff"`` carrying the prompt's page
    content + last-position logits instead of tokens (the prefill
    pool's half of a role-split fleet). ``handoff`` is the other half:
    a payload produced by a prefill_only run; admission scatters it
    into fresh pages, samples the first token from the carried logits
    with THIS request's knobs/seed, and decodes — token-exact vs a
    single engine doing both (the first-token draw and every decode
    step see bitwise the state the donor engine would have had).

    ``migrate`` (ISSUE-18) is the live-migration entry: a
    ``SessionSnapshot`` (or its wire doc) another engine froze
    mid-stream via ``extract_session``. Admission adopts the carried
    pages + sampler state and resumes decode from the exact position
    — no prefill, no first-token sample (every emitted token,
    including the one the next step feeds, already rode over)."""

    prompt: list
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    id: Any = None
    prefill_only: bool = False
    handoff: Any = None
    migrate: Any = None


@dataclass
class Result:
    """A finished request: ``tokens`` = generated ids (the EOS token,
    when hit, included as the last element); ``finish_reason`` is
    "eos" or "length". ``prefix_hit_tokens`` = prompt tokens seeded
    from the prefix store instead of prefilled; ``prefill_tokens_saved``
    = bucketed prefill work skipped (both 0 with the store off).
    ``drafted``/``accepted`` = speculative-decoding draft tokens this
    request sent through verify dispatches / had accepted (both 0 with
    speculation off or for sampled requests)."""

    id: Any
    prompt: list
    tokens: list
    finish_reason: str
    prefix_hit_tokens: int = 0
    prefill_tokens_saved: int = 0
    drafted: int = 0
    accepted: int = 0
    # disaggregation surfaces: ``prefill_chunks`` = prefill dispatches
    # this request's prompt took (>= 2 means chunked; 0 = pure prefix
    # hit); ``handoff`` (finish_reason "handoff" only) = the page
    # payload + last-position logits a prefill_only run produced
    prefill_chunks: int = 0
    handoff: Any = None

    @property
    def draft_hit_rate(self) -> float:
        """Fraction of drafted tokens the verify step accepted (0.0
        when the request never drafted)."""
        return self.accepted / self.drafted if self.drafted else 0.0


@dataclass
class _Live:
    request: Request
    generated: list = field(default_factory=list)
    prefix_hit_tokens: int = 0
    prefill_tokens_saved: int = 0
    drafted: int = 0
    accepted: int = 0
    prefill_chunks: int = 0


@dataclass
class _Round:
    """A chunk round in the device's queue (``Server._inflight``): what
    its arrival needs, kept from its enqueue. ``riders`` maps a slot to
    the ``_Live`` that held it when the round was enqueued and could
    still move in it; a rider that leaves (it finished in an earlier
    round, or was extracted) is taken out, so every rider of a round
    in flight is its slot's present occupant, and a slot's later
    occupant rides no round enqueued before its admission."""

    rid: int         # the engine's ordinal of this round: on both
    #                  halves' spans and on the record's ``round`` tag
    toks: Any        # [b, k] on the device, not yet read
    k: int           # depth: decode micro-steps
    t0: float        # host clock at the enqueue
    riders: dict     # slot -> _Live
    view_tokens: int  # the gathered view's span (0 = unpaged)
    done_at: float = 0.0  # host clock when a later program's wait saw
    #                  this round done (``Server._wait_behind``)


@dataclass
class _PrefillState:
    """A slot mid-CHUNKED-prefill: admitted (reservation + any prefix
    seed already in place), prompt written up to ``done``, not yet
    decoding. The slot is excluded from both the free list and the
    decode batch until the final chunk samples its first token."""

    request: Request
    done: int       # prompt tokens already written/seeded
    chunks: int     # prefill dispatches so far (>= 1)
    hit_tokens: int
    saved: int
    row: Any = None  # unpaged: the carried batch-1 suffix-prefill cache


class Server:
    """Slot-based continuous-batching server.

    ``submit()`` enqueues; ``step()`` runs one scheduler iteration
    (admit -> enqueue a batched decode round -> read the oldest round
    in flight -> per-slot EOS/evict) and returns whatever finished;
    ``run()`` drives to completion as a generator. ``params``
    is the bare param tree (the ``generate()`` convention).

    eos_id follows generate(): an int (-1 = none) or a list/tuple
    (stop on any).

    Threading contract: ONE thread owns the decode loop (``step()`` /
    ``drain()`` / ``run()`` — the device cache and per-slot host arrays
    are single-writer), while ``submit()`` may be called from any
    thread: the pending queue is lock-protected, so a network front
    door can feed requests while the owner thread keeps stepping.
    ``max_pending`` bounds the queue; past it ``submit()`` raises
    ``QueueFull`` instead of growing without bound.

    ``paged`` (default on; ``False`` keeps the fixed-shape rows for
    A/B, sliding-window models auto-downgrade) stores the KV cache as
    block-granular PAGES (``kv_page_size`` tokens each, auto-sized
    when 0) in a ``kv_pages``-page pool (auto = the unpaged-equivalent
    ``batch_size * max_pages`` when 0) with per-slot page tables:
    HBM residency is bounded by actual tokens, admission reserves each
    request's worst case (no mid-stream preemption, pool pressure just
    delays admission; a request bigger than the whole pool sheds
    ``PoolExhausted``), and the prefix store shares pages
    copy-on-write — an exact hit costs one [1, V] sampling dispatch
    and donation is a refcount bump. Greedy outputs are token-exact
    vs the unpaged path (tests/test_paged.py pins the matrix).

    ``speculate_k`` > 0 turns on speculative decoding (prompt-lookup
    drafting + batched verify, module functions ``_propose_draft`` /
    ``_verify_chunk``): rounds where any greedy slot's n-gram lookup
    proposes a draft run ONE verify dispatch scoring up to k draft
    tokens per slot instead of a single micro-step — non-drafting and
    sampled slots ride the same dispatch at one token per round, so the
    batch never splits. Greedy outputs are token-for-token unchanged
    (acceptance compares drafts against the verify pass's own greedy
    verdicts; rejection is pointer arithmetic — junk K/V beyond the
    accepted length is invisible and overwritten). A per-slot
    acceptance EMA (decay ``SPEC_EMA_DECAY``, floor
    ``SPEC_EMA_DISABLE``) stops drafting for requests whose proposals
    keep getting rejected, so the worst case is the plain chunked path
    plus one host-side numpy scan per round.

    ``mesh`` (a ``jax.sharding.Mesh``, ISSUE-14) makes this replica a
    MULTI-CHIP tensor/expert-sharded engine: params place under the
    ``parallel.sharding`` serving preset (``shard_rules``, default
    "serve" — output-dim sharding with the row-parallel flip), KV page
    pools shard on the kv-head axis, and every dispatch runs GSPMD-
    partitioned with XLA-inserted ICI collectives — same dispatch
    count per token, no new host syncs, and byte-identical greedy +
    seeded streams vs mesh=1 on the CPU backend (all cross-chip
    traffic is all-gather; every float reduction runs whole on one
    chip). On TPU chips that identity was NOT seen (PR 24, v5e: the
    narrower per-chip matmuls round differently; logits agree to
    rounding, streams diverge at the first flipped argmax). Page
    tables and the
    free-list allocator stay host-side and unchanged; prefix-store
    entries, CoW pages, handoff payloads and host-tier spills become
    sharded pytrees transparently. The goodput ledger prices sharded
    dispatches PER CHIP (bytes/FLOPs over the shard counts against
    the single-chip roofline). ``decode_attention="flash"`` is
    refused (GSPMD cannot partition a pallas_call).
    """

    # speculative-decoding gate: a slot drafts while its acceptance EMA
    # (seeded at 1.0 on admit, updated a/d per verify round it drafted
    # in) stays >= SPEC_EMA_DISABLE; two-ish fully-rejected rounds shut
    # a hopeless slot up for the rest of its request
    SPEC_EMA_DECAY = 0.5
    SPEC_EMA_DISABLE = 0.25

    def __init__(self, model, params, *, batch_size: int = 4, eos_id=-1,
                 min_bucket: int = 16, chunk_steps: int = 8,
                 max_pending: int = 1024, prefix_cache_mb: float = 0.0,
                 prefix_donate: bool = True, speculate_k: int = 0,
                 fault_plan: FaultPlan | None = None,
                 timeline: bool = True, paged: bool | None = None,
                 kv_page_size: int = 0, kv_pages: int = 0,
                 hbm_gbps: float = 0.0, prefill_chunk_tokens: int = 0,
                 kv_host_mb: float = 0.0, mesh=None,
                 shard_rules: str = "serve",
                 page_pool: PagePool | None = None,
                 warm_views: bool = False):
        if model.cfg.quantized:
            # nothing structural in the way — the q8 apply is the same
            # model.apply — but untested here; fail loud, not wrong
            raise NotImplementedError(
                "serve over int8 weight-only models is untested")
        if prefix_cache_mb > 0 and model.cfg.sliding_window:
            # correctness is fine (causal K/V reuse holds under a
            # window) but the windowed prefill slices differently-sized
            # spans for full vs suffix prefill, so bitwise greedy
            # parity — the store's contract — is unpinned; fail loud
            raise NotImplementedError(
                "prefix cache over sliding-window models is untested")
        if model.cfg.latent is not None:
            # a latent cache is one leaf a layer with no head axis, and
            # the decode step runs absorbed. The page pool (private or
            # shared), the prefix store over it and the fixed-shape rows
            # carry that leaf as they carry K/V (``cache_batch_axis``;
            # tests/test_latent_moe.py pins them). What was never run
            # over the layout is refused by name, not guessed: a mesh
            # (``kv_cache_shardings`` splits a head axis the leaf does
            # not have), the verify window, and the host page tier.
            refused = {"mesh": mesh is not None,
                       "speculate_k": speculate_k > 0,
                       "kv_host_mb": kv_host_mb > 0}
            for option, asked in refused.items():
                if asked:
                    raise NotImplementedError(
                        f"{option} is untested over a latent-attention "
                        "model (cfg.latent): serve it on one chip, "
                        "without speculation or the host page tier")
        if model.cfg.conv_layers:
            # a short convolution's state is a row a SLOT beside the
            # page pool (``slots.slot_resident``): the paged prefill,
            # its chunks, the decode round, the resident carry and two
            # rounds in flight carry it. What would need the state AT A
            # PAGE BOUNDARY (a prefix hit, a spilled or migrated page
            # list, a verify window's rollback), or a pool several
            # engines' slots share, was never run over it and is
            # refused by name, not guessed.
            refused = {"prefix_cache_mb": prefix_cache_mb > 0,
                       "kv_host_mb": kv_host_mb > 0,
                       "speculate_k": speculate_k > 0,
                       "mesh": mesh is not None,
                       "page_pool": page_pool is not None,
                       "paged=False": paged is not None and not paged}
            for option, asked in refused.items():
                if asked:
                    raise NotImplementedError(
                        f"{option} is not implemented for a model with "
                        "conv layers (cfg.layer_types): their state is "
                        "slot-resident; serve it paged on one chip with "
                        "prefix_cache_mb=0 and a pool of its own "
                        "(--prefix-cache-mb 0 --no-shared-pool)")
        if paged and model.cfg.sliding_window:
            # same precedent: the paged gather itself is window-agnostic
            # but bitwise greedy parity against the unpaged windowed
            # slice path is unpinned; explicit paged=True fails loud,
            # the None default (and the CLIs) downgrade to unpaged
            raise NotImplementedError(
                "paged KV cache over sliding-window models is untested")
        # SHARDED replica (ISSUE-14): with a ``mesh``, the param tree
        # and the KV page pools are placed as NamedShardings under the
        # parallel.sharding serving preset — params shard on their
        # output dims (the row-parallel flip keeps every float
        # reduction whole on one chip), KV pools shard on the kv-head
        # axis, and EVERY dispatch below runs GSPMD-partitioned with
        # XLA-inserted ICI collectives. The page tables, free-list
        # allocator and reservation ledger stay host-side and
        # unchanged (a page id means the same thing on every chip);
        # dispatch counts per token are identical to single-chip — no
        # new host syncs. Greedy AND seeded streams are byte-identical
        # to mesh=1 on the CPU backend (tests/test_shard_serve.py pins
        # the matrix); on TPU chips they agree to rounding only.
        self.mesh = mesh
        self.shard_rules = shard_rules
        self.kv_shards = 1
        self._param_shardings = None
        if mesh is not None:
            if model.cfg.decode_attention == "flash":
                # GSPMD cannot partition a pallas_call: the kernel
                # would be silently all-gathered per step. Fail loud.
                raise NotImplementedError(
                    "sharded serving over the pallas flash-decode "
                    "kernel is untested; use decode_attention='einsum'")
            import dataclasses

            from tony_tpu.parallel.sharding import serving_shardings

            # re-cfg the model with the mesh + the replicate pins that
            # make sharded math reduction-order-identical (a distinct
            # static jit key, so sharded and unsharded servers in one
            # process never share a miscompiled program)
            model = model.__class__(dataclasses.replace(
                model.cfg, mesh=mesh, shard_activations=True))
            self._param_shardings = serving_shardings(mesh, params,
                                                      shard_rules)
            params = jax.device_put(params, self._param_shardings)
        self.model = model
        self.params = params
        # deterministic fault injection (serve/faults.py); None = off,
        # zero overhead. Hooked at the top of step() and before each
        # admission's prefill — the two places device work starts
        self.fault_plan = fault_plan
        self.eos_ids = normalize_eos_ids(eos_id)
        self.min_bucket = min_bucket
        # the decode chunk and the verify round carry a per-slot
        # ``done`` flag: a slot finishing mid-dispatch freezes instead
        # of decoding past its finish, and the host's walk over the
        # tail is a consistency check
        self.frozen_steps = 0  # decode/verify positions spent frozen
        #                        (re-emitting a finished slot's token);
        #                        they cost no KV writes and land in the
        #                        ledger's padding bucket
        self.freeze_faults = 0  # frozen-tail consistency violations
        #                         (must stay 0)
        # upper bound on decode micro-steps fused into one dispatch;
        # 1 = token-at-a-time (lowest latency to each token, highest
        # per-token dispatch cost — the right setting for streaming)
        self.chunk_steps = max(1, chunk_steps)
        self.max_pending = max(1, max_pending)
        # paged KV (the PagedAttention idea on the TPU static-shape
        # path): cache leaves become [n_pages, page_size, ...] pools,
        # slots hold page tables, residency is bounded by actual tokens
        # instead of batch * max_seq_len, and the prefix store shares
        # pages copy-on-write instead of copying rows. Default ON
        # (except sliding-window); paged=False keeps the fixed-shape
        # rows for A/B.
        self.paged = (not model.cfg.sliding_window) if paged is None \
            else bool(paged)
        if page_pool is not None and not self.paged:
            raise ValueError("a shared page_pool needs the paged KV "
                             "cache")
        if self.paged:
            if page_pool is not None:
                # SHARED pool (ISSUE-18): a gateway-owned fleet pool
                # lent to every co-located engine — the pool keeps
                # device-tree ownership (SlotCache delegates), and
                # the pool's lock is the single-writer dispatch
                # discipline serialized below
                pool = page_pool
            else:
                ps = int(kv_page_size) or default_page_size(model.cfg)
                ps = max(1, min(ps, model.cfg.max_seq_len))
                max_pages = -(-model.cfg.max_seq_len // ps)
                # auto pool: the unpaged-equivalent footprint — every
                # slot can still hold a full-length sequence, so
                # capacity parity with the fixed-shape path is the
                # floor; explicit kv_pages grows the batch into the
                # same HBM or shrinks the footprint for short-sequence
                # traffic
                n_pages = int(kv_pages) or batch_size * max_pages
                # mesh: the pool allocates DIRECTLY under its kv-head
                # shardings (slots._alloc_sharded) — a dense-then-
                # reshard order would transiently hold the whole pool
                # on one chip and OOM exactly the configurations the
                # mesh unlocks
                pool = PagePool(model, params, n_pages, ps, mesh=mesh,
                                slots=batch_size)
            self.slots = SlotCache(model, params, batch_size, pool=pool,
                                   mesh=mesh)
        else:
            self.slots = SlotCache(model, params, batch_size, mesh=mesh)
        # dispatch concurrency (ISSUE-19): every engine owns ITS OWN
        # scheduler lock, so co-located engines on a shared pool do not
        # serialize whole step() iterations. The shared device TREE is
        # protected at a finer grain: ``_tree_lock`` (the pool's lock
        # when shared, else this same per-engine lock — a free
        # re-entrant acquire) brackets each read-dispatch-reassign
        # window, held only while ENQUEUEING a dispatch, never across
        # the host sync — so two engines' device work overlaps while
        # the tree-version chain stays linear.
        self._dispatch_lock = threading.RLock()
        self._tree_lock = self.slots.pool.lock \
            if self.paged and self.slots.pool.shared \
            else self._dispatch_lock
        # the pool tree this engine's pages and prefix entries live in
        # (PagePool.tree_epoch; see _check_tree)
        self._tree_epoch = self.slots.pool.tree_epoch if self.paged \
            else 0
        cache_leaves = jax.tree_util.tree_leaves(self.slots.cache)
        self._kv_bytes_total = sum(
            int(np.prod(x.shape)) * x.dtype.itemsize for x in cache_leaves)
        self._kv_bytes_chip = self._kv_bytes_total
        if mesh is not None:
            # the cache (page pools, or fixed-shape rows) was ALLOCATED
            # under its kv-head shardings above; this block only does
            # the accounting — shard count + per-chip bytes — off the
            # same rule, so the two can never disagree. Host-side
            # tables and the allocator never see the difference.
            from tony_tpu.parallel.sharding import (kv_cache_shardings,
                                                    kv_shard_count,
                                                    tree_shard_bytes)

            csh = kv_cache_shardings(mesh, self.slots.cache)
            self.kv_shards = kv_shard_count(mesh, self.slots.cache)
            self._kv_bytes_chip = tree_shard_bytes(self.slots.cache, csh)
            if self.kv_shards == 1 and mesh.size > 1:
                log.warning(
                    "KV pools replicated on the %d-device mesh: the "
                    "tensor axis does not divide kv_heads=%d — params "
                    "still shard, KV capacity does not",
                    mesh.size, model.cfg.kv_heads)
        self.pending: deque[Request] = deque()
        self._pending_lock = threading.Lock()
        self._live: list[_Live | None] = [None] * batch_size
        # chunk rounds enqueued and not yet read, oldest first: the
        # engine keeps up to two in the device's queue and waits for
        # the older, so the host's work between two decode programs
        # runs under the younger (``_decode_round``). The host mirrors
        # (``slots.lengths``/``last_token``, ``_Live.generated``) lag
        # the device by these rounds; ``_settle`` catches them up.
        self._inflight: deque[_Round] = deque()
        # results of a settle outside ``step()`` (``extract_session``),
        # handed out by the next ``step()``/``drain()``
        self._held: list[Result] = []
        self.rounds_overlapped = 0  # rounds enqueued behind an unread one
        self.settles = 0            # times the queue was drained early
        self.rounds_dropped = 0     # rounds enqueued and never read
        # a model of routed experts (``cfg.routed``): RoutedMLP's counts,
        # summed over the decode rounds read so far (they ride each
        # round's token array: ``_decode_chunk``). Pairs a live token
        # chose, those whose expert is held here, and, summed over
        # every routed layer's evaluation, the most pairs one held
        # expert took in it (x held experts / pairs held = max over
        # mean load) and the held experts that took any
        self.moe_counts = np.zeros(N_COUNTS, np.int64) \
            if model.cfg.routed is not None else None
        # slot-resident state (a model with conv layers): a paged
        # window is told its slot; admissions whose window began at
        # position 0 (the state's predecessors read as zero there,
        # ``ShortConv``) and prefill chunks that began from a state
        self._state_slots = model.cfg.conv_layers > 0
        self.state_resets = 0
        self.state_carried_chunks = 0
        self._decode_end = 0.0      # host clock at the last round's end
        self._ids = itertools.count()
        self.steps = 0       # decode dispatch DEPTH, summed (chunk k /
        #                      verify window — once per dispatch, not
        #                      per slot)
        self.dispatches = 0  # decode dispatches (chunk + verify)
        self.prefills = 0    # prefill dispatches (exact hits skip one)
        self.wasted_steps = 0  # PER-SLOT token positions decoded and
        #                       thrown away: REJECTED DRAFT positions
        #                       only — positions past a finish are
        #                       frozen in-dispatch (frozen_steps), not
        #                       decoded. Different unit from `steps`:
        #                       compare against emitted tokens
        # per-dispatch timeline (obs/timeline.py): one record per
        # prefill / hit-admit / decode / verify dispatch with host-wall
        # duration and a first-call compile flag; False = off — the
        # layer itself is cheap enough to stay on in production
        self.timeline = DispatchTimeline() if timeline else None
        self._compiled: set = set()  # (kind, shape-bucket) pairs seen
        # host phase ledger (obs/phases.py): the stepping thread's wall
        # clock split into named leaves (decode.prepare / enqueue /
        # wait / emit / record, admit.host / wait / emit, ...), each
        # also a ``tony.*`` annotation in a profiler capture. Owned by
        # whoever drives step(); a gateway replica's loop books its own
        # phases into the same ledger.
        self.phases = HostPhases()
        # goodput attribution (obs/goodput.py): wall-clock anchor for
        # the ledger plus the analytic cost model that stamps
        # est_bytes/est_flops on every timeline record. The roofline
        # reference (peak HBM GB/s) comes from --hbm-gbps when given,
        # else the chip table; 0 on CPU — records still carry bytes,
        # utilization reports null.
        self._t0 = time.monotonic()
        self.hbm_gbps = float(hbm_gbps) if hbm_gbps > 0 \
            else detect_hbm_gbps()
        self.peak_flops = detect_peak_flops()
        leaves = jax.tree_util.tree_leaves(params)
        self._param_bytes_total = sum(
            int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves)
        param_count_total = sum(int(np.prod(x.shape)) for x in leaves)
        if mesh is not None:
            # per-chip param residency under the actual shardings
            # (replicated leaves count whole) — what one chip's HBM
            # holds and re-reads per decode micro-step
            from tony_tpu.parallel.sharding import (tree_shard_bytes,
                                                    tree_shard_count)

            self._param_bytes_chip = tree_shard_bytes(
                params, self._param_shardings)
            self._param_count_chip = tree_shard_count(
                params, self._param_shardings)
        else:
            self._param_bytes_chip = self._param_bytes_total
            self._param_count_chip = param_count_total
        self.cost = None
        if self.timeline is not None:
            cfg = model.cfg
            if self.paged:
                pool = self.slots.pool
                kv_tok = pool.page_nbytes / max(1, pool.page_size)
            else:
                kv_tok = _row_nbytes(self.slots.cache) \
                    / max(1, cfg.max_seq_len)
            head_dim = cfg.explicit_head_dim \
                or cfg.d_model // cfg.n_heads
            if cfg.latent is not None:
                # the absorbed step scores over the cache's whole width
                # and sums over the latent: (width + rank) MACs a head a
                # cached position where K/V attention has 2 head_dim
                head_dim = (cfg.latent.cache_width
                            + cfg.latent.kv_rank) // 2
            # sharded replicas price dispatches PER CHIP (the ISSUE-14
            # goodput rule): each chip reads its param shard and its
            # kv-head slice of the pools, so bytes/FLOPs divide by the
            # shard counts while hbm_gbps/peak_flops stay the SINGLE-
            # chip roofline — HBM-BW% stays a per-chip percentage
            # instead of reading >100% on a mesh. Attention work
            # splits with the kv pools (kv_shards divides kv_heads
            # divides n_heads); a replicated-pool fallback prices
            # attention unsharded, conservatively.
            self.cost = CostModel(
                param_bytes=self._param_bytes_chip,
                param_count=self._param_count_chip,
                kv_token_bytes=kv_tok / max(1, self.kv_shards),
                n_heads=cfg.n_heads // max(1, self.kv_shards),
                head_dim=head_dim, vocab_size=cfg.vocab_size,
                hbm_gbps=self.hbm_gbps, peak_flops=self.peak_flops)
        # speculative decoding (0 = off: zero overhead, no new programs)
        self.speculate_k = max(0, int(speculate_k))
        self._spec_ema = np.ones(batch_size, np.float64)
        self.spec_rounds = 0    # verify dispatches run
        self.spec_drafted = 0   # draft tokens sent through verify
        self.spec_accepted = 0  # draft tokens accepted
        # prefix KV reuse (serve/prefix.py); 0 MB = off, zero overhead.
        # Paged engines get a POOL-BACKED store: entries are page
        # references (refcounted, copy-on-write), not copied rows
        self.prefix = PrefixStore(
            int(prefix_cache_mb * (1 << 20)),
            pool=self.slots.pool if self.paged else None) \
            if prefix_cache_mb > 0 else None
        self.prefix_donate = prefix_donate
        self.prefix_lookups = 0       # admits that consulted the store
        self.prefix_hits = 0          # admits seeded >= 1 cached token
        self.prefix_hit_tokens = 0    # prompt tokens seeded, total
        self.prefill_tokens_saved = 0  # bucketed prefill work skipped
        self._row_nbytes = 0 if self.paged \
            else _row_nbytes(self.slots.cache)
        # the smallest useful entry: unpaged = one cache row + its
        # [1, V] fp32 logits; paged = one PAGE + the logits
        entry_nbytes = (self.slots.pool.page_nbytes if self.paged
                        else self._row_nbytes) + 4 * model.cfg.vocab_size
        if self.prefix is not None \
                and entry_nbytes > self.prefix.budget_bytes:
            # a budget that cannot hold even ONE entry would reject
            # every insert while still paying the row-returning prefill
            # variant per admit — pure overhead, so turn it off loudly
            log.warning(
                "prefix cache disabled: one cached entry needs %.1f MB, "
                "budget is %.1f MB (raise --prefix-cache-mb)",
                entry_nbytes / (1 << 20), prefix_cache_mb)
            self.prefix = None
        # chunked prefill (ISSUE-12): bound how many prompt tokens one
        # admission dispatch may consume; long prompts prefill in
        # chunks interleaved between decode rounds, so a 30k-token
        # prompt stops holding co-tenants' decode hostage for one
        # monolithic prefill. Quantized DOWN to the bucket grid
        # (min_bucket * 2^k) so intermediate chunk windows are
        # pad-free — on the unpaged path, bucket-tail junk between
        # chunks would otherwise need overwrite proofs per geometry.
        # 0 = off (the old monolithic behavior).
        chunk_budget = max(0, int(prefill_chunk_tokens))
        if chunk_budget:
            b = min_bucket
            while b * 2 <= chunk_budget:
                b *= 2
            chunk_budget = min(b, model.cfg.max_seq_len)
        self.prefill_chunk = chunk_budget
        self._prefilling: dict[int, _PrefillState] = {}
        self.prefill_chunk_dispatches = 0  # chunk dispatches run
        self.prefill_chunked = 0           # requests that took >1 chunk
        self.handoffs_out = 0  # prefill_only requests handed off
        self.handoffs_in = 0   # handoff admissions (decode pool)
        # live session migration (ISSUE-18)
        self.migrations_out = 0      # sessions frozen + extracted here
        self.migrations_in = 0       # sessions adopted + resumed here
        self.migrations_local = 0    # extracts as zero-copy owner swap
        self.migrations_remote = 0   # extracts as gathered content
        self.migrate_pages_moved = 0  # pages whose CONTENT moved
        self.migrate_bytes_avoided = 0  # bytes owner swaps did NOT
        #                                 move (migration + shared-pool
        #                                 handoff aliasing)
        self.migrate_freeze_resume_ms = 0.0  # summed freeze->resume
        #                                      wall ms (mean = / in)
        # prefix-delta wire migration (ISSUE-19)
        self.migrate_bytes_wire = 0  # page bytes that actually crossed
        #                              the wire INTO this engine
        #                              (adopter-side; full docs count n
        #                              pages, delta docs n - k)
        self.migrate_delta_in = 0    # adoptions that reconstructed the
        #                              prefix from this engine's own
        #                              store pages
        # prefix entries pinned (refcount held) between a delta doc's
        # submit-time check and its admission — eviction between the
        # two would free the very pages the adopt aliases. Keyed by
        # request id; released at admit, on post-check submit failure,
        # and on reset().
        self._migrate_pins: dict = {}
        self._cache_treedef = jax.tree_util.tree_structure(
            self.slots.cache)
        # (flat leaf index, page axis) of the first paged leaf: lets
        # submit() read a WIRE payload's page count straight off its
        # carried shapes, before any decoding
        self._payload_leaf_spec = None
        if self.paged:
            flat = jax.tree_util.tree_flatten_with_path(
                self.slots.cache)[0]
            for i, (path, leaf) in enumerate(flat):
                ax = page_axis(path, leaf)
                if ax is not None:
                    self._payload_leaf_spec = (i, ax)
                    break
        # host-RAM page tier (serve/tier.py): evicted prefix-store
        # entries spill device->host instead of vanishing, and page
        # back in on a prefix hit — million-session reuse bounded by
        # host RAM, not HBM. Needs page-granular state AND a device
        # store to feed it, so both are hard requirements.
        self.host_tier = None
        if kv_host_mb > 0:
            if not self.paged or self.prefix is None:
                raise ValueError(
                    "kv_host_mb needs the paged KV cache and a prefix "
                    "store (prefix_cache_mb > 0): the tier holds "
                    "evicted prefix-store pages")
            self.host_tier = HostPageTier(int(kv_host_mb * (1 << 20)))
            self.prefix.on_evict = self._spill_entry
        if self.paged and self.prefix is not None:
            # compile the copy-on-write fork NOW (page 0 onto itself:
            # an identity write): its first use is otherwise under
            # whichever admission first matches a stored prefix
            # mid-page — one chance first-token match among unrelated
            # prompts is enough — and stalls every live stream for the
            # compile (0.7 s on the v5e, PERF.md PR 28)
            with self._tree_lock:
                self.slots.cache = _copy_page(
                    self.slots.cache, jnp.int32(0), jnp.int32(0))

        if warm_views and self.paged:
            self.warm_views()

    def warm_views(self) -> int:
        """Compile the chunk program for EVERY view bucket and depth the
        scheduler can ask for, by running each once with every row
        empty (an empty row writes nothing). A bucket otherwise
        compiles when the longest live row first crosses into it, under
        live traffic, and every stream stalls for the compile — the
        same argument as the copy-on-write fork's above. What a
        deployment's warm-up traffic reaches needs none of this; the
        buckets past its longest PROMPT do (a row grows into them by
        decoding). With ``prefill_chunk_tokens`` also the chunked
        prefill's programs (``_warm_chunked_prefill``). Returns the
        programs run. Call it on an idle engine."""
        s = self.slots
        buckets, cols = [], 1
        while cols < s.max_pages:
            buckets.append(cols)
            cols *= 2
        buckets.append(s.max_pages)
        k, depths = 1, []
        while k <= self.chunk_steps:
            depths.append(k)
            k *= 2
        for cols in buckets:
            table = s.device_table(cols)
            for k in depths:
                with self._tree_lock:
                    s.cache, toks, _ = _decode_chunk(
                        self.model, self.params, s.cache, s.state,
                        s._no_patch, table, n_steps=k,
                        eos_ids=self.eos_ids)
                jax.block_until_ready(toks)
                self._compiled.add(("decode", k, cols * s.pool.page_size))
        return len(buckets) * len(depths) + self._warm_chunked_prefill()

    def _warm_chunked_prefill(self) -> int:
        """With ``prefill_chunk_tokens``, the programs a chunked
        prefill asks for: a chunk's is keyed by the VIEW SPAN its end
        reaches, the final chunk's by its suffix bucket and the span
        of the whole prompt, and a warm-up of one prompt a bucket
        reaches a handful of that product (on the v5e the rest
        compiled under traffic, 15 s each: PERF.md, Findings PR 37).
        Each runs once over a window of padding (position -1 writes
        nothing and leaves a slot's state as it was). Offsets a prefix
        hit leaves are not walked. Returns the programs run."""
        take = self.prefill_chunk
        if not take:
            return 0
        s = self.slots
        ps, max_len = s.pool.page_size, self.model.cfg.max_seq_len
        slot = jnp.int32(0) if self._state_slots else None

        def span(n):
            return min(_bucket_pow2(-(-n // ps)), s.max_pages)

        chunks, finals = set(), set()
        for off in range(take, max_len, take):  # where a final may start
            chunks.add(span(off))  # the chunk that ended there
            lo, lb = 1, self.min_bucket
            while lo <= min(take, max_len - off):
                hi = min(lb, take, max_len - off)
                finals |= {(lb, span(off + lo)), (lb, span(off + hi))}
                lo, lb = lb + 1, lb * 2

        def window(n, cols):
            return (jnp.zeros((1, n), jnp.int32),
                    jnp.full((1, n), -1, jnp.int32),
                    jnp.asarray(s.page_table[:1, :cols]))

        for cols in sorted(chunks):
            with self._tree_lock:
                s.cache, mark = _paged_prefill_chunk(
                    self.model, self.params, s.cache, *window(take, cols),
                    slot)
            jax.block_until_ready(mark)
            self._compiled.add(("prefill_chunk", take, cols * ps))
        for lb, cols in sorted(finals):
            toks, positions, table = window(lb, cols)
            with self._tree_lock:
                s.cache, tok, _, _ = _paged_prefill_admit(
                    self.model, self.params, s.cache, toks, positions,
                    jnp.int32(1), table, jnp.float32(0.0), jnp.int32(0),
                    jax.random.PRNGKey(0), slot)
            jax.block_until_ready(tok)
            self._compiled.add(("prefill", lb, cols * ps))
        return len(chunks) + len(finals)

    # ----------------------------------------------------- observability

    def _record_dispatch(self, kind: str, t0: float, dur_ms: float,
                         occ: int, bucket: int, tokens: int, key_,
                         *, request_id=None, tags: dict | None = None,
                         work: int = 0, fed: int = 0,
                         rejected: int = 0,
                         est: tuple = (0.0, 0.0)) -> None:
        """One timeline record, goodput-stamped: position accounting
        (work/fed/rejected — the ledger's exact duration split) plus
        the cost model's bytes/FLOPs estimate, with per-dispatch
        HBM-BW% / MFU tags when a roofline reference is known."""
        tags = tags or {}
        if kind != "decode" and self._inflight:
            # enqueued behind the rounds in flight, this program's sync
            # waited them out first (``_wait_behind`` stamped when they
            # were done): its record starts where theirs end
            late = self._inflight[-1].done_at - t0
            if late > 0:
                t0 += late
                dur_ms = max(0.0, dur_ms - late * 1e3)
        est_bytes, est_flops = est
        if self.cost is not None and est_bytes:
            bw, mfu = self.cost.utilization(est_bytes, est_flops,
                                            dur_ms)
            if bw is not None:
                tags["hbm_bw_pct"] = bw
            if mfu is not None:
                tags["mfu_pct"] = mfu
        self.timeline.record(DispatchRecord(
            kind, t0, dur_ms, occ, bucket, tokens,
            key_ not in self._compiled, request_id=request_id,
            tags=tags, work=work, fed=fed, rejected=rejected,
            est_bytes=est_bytes, est_flops=est_flops))
        self._compiled.add(key_)

    def goodput(self) -> dict | None:
        """The per-replica goodput ledger (obs/goodput.py): this
        engine's wall clock decomposed into useful/compile/padding/
        overshoot/spec-rejected/idle bucket fractions that sum to
        <= 1.0, with per-kind HBM-BW%/MFU when the roofline reference
        is known. None with the timeline off (no data to attribute)."""
        if self.timeline is None:
            return None
        wall_ms = (time.monotonic() - self._t0) * 1e3
        return ledger(self.timeline.summary(), wall_ms,
                      hbm_gbps=self.hbm_gbps,
                      peak_flops=self.peak_flops)

    def host_phases(self) -> dict:
        """The stepping thread's host phase ledger (``/stats``
        ``host_phases`` per replica, ``engine.host`` merged)."""
        return self.phases.snapshot()

    def mesh_info(self) -> dict | None:
        """Sharded-replica topology + per-chip residency (None on a
        single-chip engine): mesh axes, how many ways the KV pools
        split, and the per-chip vs total param/KV bytes — the numbers
        behind /stats ``engine.mesh`` and the capacity-unlock math
        (a model whose total footprint exceeds one chip serves when
        the per-chip numbers fit)."""
        if self.mesh is None:
            return None
        return {
            "devices": int(self.mesh.size),
            "axes": {str(k): int(v) for k, v in self.mesh.shape.items()
                     if int(v) > 1},
            "preset": self.shard_rules,
            "kv_shards": int(self.kv_shards),
            "param_bytes_total": int(self._param_bytes_total),
            "param_bytes_per_chip": int(self._param_bytes_chip),
            "kv_bytes_total": int(self._kv_bytes_total),
            "kv_bytes_per_chip": int(self._kv_bytes_chip),
        }

    # ------------------------------------------------------------ intake

    def submit(self, request: Request):
        """Enqueue a request; returns its id. Rejects prompts the cache
        cannot hold; clamps max_new_tokens to the remaining capacity
        (the generate() overflow contract, per slot). Raises
        ``QueueFull`` past ``max_pending`` queued requests — the
        caller's backpressure signal. Safe to call from any thread."""
        p = list(request.prompt)
        max_len = self.model.cfg.max_seq_len
        if not p:
            raise ValueError("empty prompt")
        if len(p) >= max_len:
            raise ValueError(
                f"prompt ({len(p)}) leaves no room for generation in "
                f"max_seq_len ({max_len})")
        if request.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if request.prefill_only and request.handoff is not None:
            raise ValueError("prefill_only and handoff are the two "
                             "HALVES of a disaggregated request — one "
                             "request cannot be both")
        if request.migrate is not None \
                and (request.prefill_only or request.handoff is not None):
            raise ValueError("a migrated session is already past "
                             "prefill — it cannot also be a "
                             "prefill_only/handoff half")
        if (request.prefill_only or request.handoff is not None
                or request.migrate is not None) \
                and self.model.cfg.latent is not None:
            raise NotImplementedError(
                "prefill_only/handoff/migrate are not implemented for a "
                "latent-attention model (cfg.latent)")
        if (request.prefill_only or request.handoff is not None
                or request.migrate is not None) and self._state_slots:
            raise NotImplementedError(
                "prefill_only/handoff/migrate are not implemented for a "
                "model with conv layers (cfg.layer_types): a page list "
                "does not carry the slot-resident state")
        if (request.prefill_only or request.handoff is not None
                or request.migrate is not None) and not self.paged:
            raise ValueError(
                "prefill/decode disaggregation needs the paged KV "
                "cache (the handoff unit is a page list)")
        if request.id is None:
            request.id = next(self._ids)
        if request.migrate is not None:
            # geometry + continuity checked HERE, where a mismatch is
            # one request's clean 400 refusal instead of a whole-
            # replica admission crash (the handoff precedent below).
            # Needs the id assigned above: a delta doc's check PINS a
            # prefix entry keyed by it.
            self._check_migrate(request, p)
        if request.handoff is not None:
            if int(request.handoff["n_tokens"]) != len(p):
                raise ValueError(
                    f"handoff payload covers "
                    f"{request.handoff['n_tokens']} tokens, prompt "
                    f"has {len(p)}")
            # geometry checked HERE, where a mismatch is one request's
            # clean refusal (the gateway sheds it 400): discovered at
            # admission inside step() it would instead fail the whole
            # replica and cascade the crash-reset through every decode
            # replica the failover retries
            self._check_handoff_geometry(request.handoff, len(p))
            if "page_ids" in request.handoff \
                    and request.handoff.get("pool") \
                    is not self.slots.pool:
                raise ValueError(
                    "an owner-swap handoff carries page ids in a "
                    "shared pool this engine does not hold — gather "
                    "it to wire form to cross pools")
        request.max_new_tokens = min(request.max_new_tokens,
                                     max_len - len(p))
        try:
            if self.paged:
                pool = self.slots.pool
                # a prefill_only request never decodes here: its worst
                # case is the prompt's pages alone (the decode pool
                # pays for the generation budget)
                life = len(p) if request.prefill_only \
                    else len(p) + request.max_new_tokens
                worst = -(-life // pool.page_size)
                if worst > pool.n_pages:
                    # could NEVER be admitted — shedding now (503 at
                    # the gateway) beats wedging the queue head forever
                    raise PoolExhausted(
                        f"request needs {worst} KV pages worst-case, "
                        f"the pool holds {pool.n_pages} (raise "
                        "--kv-pages or lower max_new_tokens)")
            with self._pending_lock:
                if len(self.pending) >= self.max_pending:
                    raise QueueFull(
                        f"pending queue at "
                        f"max_pending={self.max_pending}")
                self.pending.append(request)
        except BaseException:
            # a refusal after _check_migrate pinned a prefix entry
            # must not strand the pin (the request never reaches
            # admission, where the pin is consumed)
            self._release_migrate_pin(request.id)
            raise
        return request.id

    def _release_migrate_pin(self, rid) -> None:
        entry = self._migrate_pins.pop(rid, None)
        if entry is not None and self.prefix is not None:
            self.prefix.release(entry)

    @property
    def n_pending(self) -> int:
        return len(self.pending)

    @property
    def n_active(self) -> int:
        # mid-chunked-prefill slots count: they hold a request the
        # engine is working on (a busy/done signal that ignored them
        # would let a front door idle out a half-prefilled prompt)
        # ... and so do results a settle is holding for the next
        # step(): the front door must step once more to be given them
        return self.slots.n_active + len(self._prefilling) \
            + len(self._held)

    @property
    def n_prefilling(self) -> int:
        return len(self._prefilling)

    @property
    def done(self) -> bool:
        return not self.pending and self.slots.n_active == 0 \
            and not self._prefilling and not self._held

    def _free_slots(self) -> list[int]:
        """Slots admittable RIGHT NOW: free on the device AND not
        parked mid-chunked-prefill."""
        return [i for i in self.slots.free_slots()
                if i not in self._prefilling]

    def prefix_match_len(self, tokens) -> int:
        """Longest prompt prefix this engine could seed without
        prefill work — the gateway's prefix-affinity routing signal.
        Device store and host tier both count (a page-in is still far
        cheaper than a re-prefill); no counters move, so a routing
        probe cannot skew admission hit rates."""
        n = self.prefix.match_len(tokens) if self.prefix is not None \
            else 0
        if self.host_tier is not None:
            n = max(n, self.host_tier.match_len(tokens))
        return n

    def prefix_summary(self, max_items: int = 512) -> list:
        """Bounded ``[[n_tokens, crc32], ...]`` summary of every
        prefix this replica could seed (device store + host tier,
        deduplicated) — shipped on the agent heartbeat so the
        gateway's prefix-affinity probe can score a REMOTE replica
        (``serve.prefix.summary_match_len``) instead of assuming 0."""
        out: list = []
        seen: set = set()
        for store in (self.prefix, self.host_tier):
            if store is None:
                continue
            for ln, crc in store.summary(max_items):
                if (ln, crc) not in seen:
                    seen.add((ln, crc))
                    out.append([ln, crc])
        return out[:max_items]

    # --------------------------------------------------------- scheduling

    def _admit_one(self, req: Request, finished: list) -> bool:
        """Prefill ``req`` into a free slot (prefill + slot copy +
        first-token sample fused into one dispatch) — or finish it on
        the spot when the FIRST token already ends it (EOS, or a budget
        of one): no slot is burned on a request with nothing to decode.
        Returns False (paged engines only) when the page pool cannot
        grant the request's reservation right now — the caller requeues
        it and stops admitting until pages free.

        With the prefix store on, the prompt's longest cached prefix is
        looked up first: an exact-prompt hit (stored logits available)
        skips prefill entirely — one row-copy + first-token-sample
        dispatch; a partial hit seeds the slot from the stored row and
        prefills only the bucketed SUFFIX at a position offset. Either
        way the freshly covered prompt is (re)inserted so the next
        sharer hits."""
        if self.paged:
            return self._admit_one_paged(req, finished)
        if self.fault_plan is not None:
            self.fault_plan.on_admit(req.id)
        s = self.slots
        p = np.asarray(req.prompt, np.int32)
        max_len = self.model.cfg.max_seq_len
        slot = self._free_slots()[0]
        t0 = time.monotonic()  # timeline: the whole admit (lookup +
        occ = s.n_active       # dispatch + first-token sync)
        off, entry = 0, None
        lookup_ms = None
        if self.prefix is not None:
            self.prefix_lookups += 1
            off, entry = self.prefix.acquire(p)
            lookup_ms = (time.monotonic() - t0) * 1e3
        full_bucket = bucket_len(len(p), max_len, self.min_bucket)
        hit_tokens = saved = 0
        d_kind, d_bucket = "prefill", full_bucket
        try:
            if entry is not None and off == len(p) \
                    and len(entry.tokens) == len(p) \
                    and entry.logits is not None:
                # exact hit: the entry covers EXACTLY this prompt, with
                # its last-position logits — zero prefill work. (A
                # LONGER entry can also match the full prompt, but its
                # logits sit at the wrong position — partial path.)
                with self._tree_lock:
                    s.cache, tok, key = _hit_admit(
                        s.cache, entry.row, jnp.int32(slot),
                        entry.logits, jnp.float32(req.temperature),
                        jnp.int32(req.top_k),
                        jax.random.PRNGKey(req.seed))
                hit_tokens, saved = len(p), full_bucket
                d_kind, d_bucket = "hit_admit", 0
            else:
                if entry is not None:
                    # partial hit (or full-prompt match against a
                    # longer/logits-less entry): seed at most len(p)-1
                    # tokens so >= 1 real token remains to prefill the
                    # first-continuation logits from
                    off = _usable_prefix(min(off, len(p) - 1), len(p),
                                         max_len, self.min_bucket)
                    if off <= 0:
                        self.prefix.release(entry)
                        entry = None
                suffix = p[off:]
                if self.prefill_chunk \
                        and len(suffix) > self.prefill_chunk:
                    # chunked admission: dispatch only the FIRST chunk
                    # (a suffix prefill into a carried batch-1 row —
                    # the PR-3 offset machinery) and park the slot
                    # mid-prefill; step() advances one chunk per
                    # iteration between decode rounds
                    take = self.prefill_chunk  # == its own bucket
                    window = np.asarray(suffix[:take])[None, :]
                    row, _ = _prefill(
                        self.model, self.params, jnp.asarray(window),
                        jnp.int32(take),
                        jnp.int32(off) if self.prefix is not None
                        else None,
                        entry.row if entry is not None else None)
                    self.prefills += 1
                    self.prefill_chunk_dispatches += 1
                    if entry is not None:
                        hit_tokens = off
                        saved = full_bucket - bucket_len(
                            len(suffix), max_len, self.min_bucket)
                        self.prefix_hits += 1
                        self.prefix_hit_tokens += hit_tokens
                        self.prefill_tokens_saved += saved
                    if self.timeline is not None:
                        self._wait_behind("prefill_chunk.wait")
                        jax.block_until_ready(row)  # close the record
                        self.phases.switch("prefill_chunk.record")
                        tags = {"prompt_len": len(p), "chunk": 1}
                        if off:
                            tags["offset"] = int(off)
                        self._record_dispatch(
                            "prefill_chunk", t0,
                            (time.monotonic() - t0) * 1e3, occ, take,
                            0, ("prefill_chunk", take),
                            request_id=req.id, tags=tags, work=take,
                            fed=take, est=self.cost.prefill(take, off))
                    self._prefilling[slot] = _PrefillState(
                        req, off + take, 1, hit_tokens, saved, row=row)
                    return True
                lb = bucket_len(len(suffix), max_len, self.min_bucket)
                padded = np.zeros((1, lb), np.int32)
                padded[0, :len(suffix)] = suffix
                with self._tree_lock:
                    s.cache, tok, key, *row_last = _prefill_admit(
                        self.model, self.params, s.cache,
                        jnp.asarray(padded), jnp.int32(len(suffix)),
                        jnp.int32(slot), jnp.float32(req.temperature),
                        jnp.int32(req.top_k),
                        jax.random.PRNGKey(req.seed),
                        jnp.int32(off) if self.prefix is not None
                        else None,
                        entry.row if entry is not None else None,
                        with_row=self.prefix is not None)
                self.prefills += 1
                d_bucket = lb
                if self.prefix is not None:
                    self.prefix.insert(p, *row_last)
                if entry is not None:
                    hit_tokens, saved = off, full_bucket - lb
        finally:
            if entry is not None:
                self.prefix.release(entry)
        if hit_tokens:
            self.prefix_hits += 1
            self.prefix_hit_tokens += hit_tokens
            self.prefill_tokens_saved += saved
        self._wait_behind("admit.wait")
        tok = int(tok)  # host sync: the admit dispatch is done here
        self.phases.switch("admit.emit")
        if self.timeline is not None:
            tags = {"prompt_len": len(p)}
            if lookup_ms is not None:
                tags["lookup_ms"] = round(lookup_ms, 3)
            if hit_tokens:
                tags["prefix_hit_tokens"] = hit_tokens
            if off:
                tags["offset"] = int(off)
            if d_kind == "hit_admit":
                work = fed = 1
                est = self.cost.hit_admit(self._row_nbytes)
            else:
                work, fed = d_bucket, len(p) - off
                est = self.cost.prefill(d_bucket, off)
            self._record_dispatch(
                d_kind, t0, (time.monotonic() - t0) * 1e3, occ,
                d_bucket, 1, (d_kind, d_bucket), request_id=req.id,
                tags=tags, work=work, fed=fed, est=est)
        chunks = 0 if d_kind == "hit_admit" else 1
        if tok in self.eos_ids or req.max_new_tokens == 1:
            # the slot row was written but never armed — the next admit
            # simply overwrites it
            reason = "eos" if tok in self.eos_ids else "length"
            finished.append(Result(req.id, list(req.prompt), [tok],
                                   reason, hit_tokens, saved,
                                   prefill_chunks=chunks))
            return True
        s.admit(slot, len(p), tok, req.temperature, req.top_k, key)
        self._spec_ema[slot] = 1.0  # new tenant: drafting re-enabled
        self._live[slot] = _Live(req, [tok], hit_tokens, saved,
                                 prefill_chunks=chunks)
        return True

    def _admit_one_paged(self, req: Request, finished: list) -> bool:
        """The paged admission path. Ordering: (1) prefix lookup — the
        reservation size depends on how many pages the prompt can
        ALIAS; (2) reserve the worst-case PRIVATE page need (prompt +
        clamped budget, minus aliased pages, plus one for a
        copy-on-write fork when the seed boundary falls mid-page),
        squeezing LRU prefix-store entries when the pool is tight; on
        failure the request stays pending — no preemption, ever:
        ``free >= reserved`` means an admitted request can always
        allocate its way to its budget; (3) seed the slot's table by
        SHARING the entry's pages (refcount bumps; the boundary page is
        forked on device) — an exact hit's only other device work is
        sampling the first token from the stored logits (the
        ``cow_admit`` dispatch kind: NOT a prefill, and the timeline
        must not count it as one); a partial hit or miss prefills the
        bucketed suffix as one multi-token window writing straight
        into the slot's pages (no row copy — the unpaged path's
        ``write_slot_row`` admission copies are gone)."""
        if req.migrate is not None:
            return self._admit_migrate(req, finished)
        if req.handoff is not None:
            return self._admit_handoff(req, finished)
        s = self.slots
        pool = s.pool
        ps = pool.page_size
        p = np.asarray(req.prompt, np.int32)
        max_len = self.model.cfg.max_seq_len
        slot = self._free_slots()[0]
        t0 = time.monotonic()  # timeline: the whole admit
        occ = s.n_active
        off, entry = 0, None
        lookup_ms = None
        if self.prefix is not None:
            self.prefix_lookups += 1
            off, entry = self.prefix.acquire(p)
            if self.host_tier is not None:
                # the host tier may hold a LONGER prefix than the
                # device store: restore it into the pool + store so
                # the admission below hits it (host->device page-in)
                off, entry = self._maybe_page_in(p, off, entry)
            lookup_ms = (time.monotonic() - t0) * 1e3
        full_bucket = bucket_len(len(p), max_len, self.min_bucket)
        exact = (entry is not None and off == len(p)
                 and len(entry.tokens) == len(p)
                 and entry.logits is not None)
        if exact and req.prefill_only:
            # the fleet hot-prompt fast path: the whole prompt's pages
            # are already resident with their logits — no reservation,
            # no writes, no sampling: gather the content and hand off
            try:
                if self.fault_plan is not None:
                    self.fault_plan.on_admit(req.id)
                self._finish_handoff(req, entry.pages, len(p),
                                     entry.logits, finished,
                                     hit_tokens=len(p),
                                     saved=full_bucket, chunks=0)
            finally:
                self.prefix.release(entry)
            self.prefix_hits += 1
            self.prefix_hit_tokens += len(p)
            self.prefill_tokens_saved += full_bucket
            return True
        if not exact and entry is not None:
            # partial hit (or full-prompt match against a longer /
            # logits-less entry): seed at most len(p)-1 tokens so >= 1
            # real token remains to prefill the first-continuation
            # logits from. No bucket-overflow shrink needed here: the
            # paged window writes by absolute position and its padding
            # DROPS, so any offset alignment is safe.
            off = min(off, len(p) - 1)
            if off <= 0:
                self.prefix.release(entry)
                off, entry = 0, None
        seed = len(p) if exact else off
        # prefill_only reserves the PROMPT's pages only — the decode
        # pool pays for the generation budget (submit() sized the
        # PoolExhausted check the same way)
        budget_end = len(p) if req.prefill_only \
            else len(p) + req.max_new_tokens  # submit() clamped
        worst = -(-budget_end // ps)     # ceil: pages for the whole life
        n_alias = -(-seed // ps)         # pages the entry donates
        fork = 1 if seed % ps else 0     # mid-page boundary: CoW copy
        need = worst - n_alias + fork
        granted = pool.reserve(need)
        while not granted and self.prefix is not None \
                and self.prefix.evict_one():
            granted = pool.reserve(need)
        if not granted:
            # transient exhaustion: live slots still hold the pages.
            # Undo the lookup (the retry repeats it) and stay pending —
            # submit() guarantees need <= n_pages, so slots finishing
            # always unblocks this.
            if entry is not None:
                self.prefix.release(entry)
            if self.prefix is not None:
                self.prefix_lookups -= 1
            return False
        if self.fault_plan is not None:
            # after the capacity check: a requeued request must not
            # burn fault-injection triggers on every retry. Guarded:
            # the reservation is not yet attached to the slot (that
            # happens in seed_pages, after which reset()'s evicts
            # reclaim it), so an injected crash here must hand it back
            # or it leaks past the replica's recovery reset
            try:
                self.fault_plan.on_admit(req.id)
            except BaseException:
                pool.cancel(need)
                if entry is not None:
                    self.prefix.release(entry)
                raise
        hit_tokens = saved = 0
        d_kind, d_bucket = "prefill", full_bucket
        forked = False
        try:
            forked = s.seed_pages(
                slot, entry.pages if entry is not None else [], seed,
                need)
            if not exact and self.prefill_chunk \
                    and len(p) - off > self.prefill_chunk:
                # chunked admission: the reservation and any prefix
                # seed are in place; dispatch the FIRST chunk straight
                # into the slot's pages and park the slot mid-prefill
                # (step() advances one chunk per iteration between
                # decode rounds)
                if entry is not None:
                    hit_tokens = off
                    saved = full_bucket - bucket_len(
                        len(p) - off, max_len, self.min_bucket)
                    self.prefix_hits += 1
                    self.prefix_hit_tokens += hit_tokens
                    self.prefill_tokens_saved += saved
                st = _PrefillState(req, off, 0, hit_tokens, saved)
                self._prefilling[slot] = st
                self._prefill_chunk_paged(slot, st, t0=t0, occ=occ,
                                          forked=forked)
                return True
            if exact:
                # the aliasing admit: pages shared host-side, one
                # [1, V] sampling dispatch — near-free, and bytes
                # moved are the forked page (if any) instead of the
                # unpaged path's whole cache row
                tok, key = _sample_first(
                    entry.logits, jnp.float32(req.temperature),
                    jnp.int32(req.top_k), jax.random.PRNGKey(req.seed))
                hit_tokens, saved = len(p), full_bucket
                d_kind, d_bucket = "cow_admit", 0
                view_tokens = 0
            else:
                suffix = p[off:]
                lb = bucket_len(len(suffix), max_len, self.min_bucket)
                s.ensure_pages(slot, len(p))
                window = np.zeros((1, lb), np.int32)
                window[0, :len(suffix)] = suffix
                positions = np.full((1, lb), -1, np.int32)
                positions[0, :len(suffix)] = \
                    off + np.arange(len(suffix), dtype=np.int32)
                # column-sliced to the prompt's page bucket: the
                # prefill window's gather + attention span is O(prompt
                # bucket), not O(max_seq_len)
                cols = min(_bucket_pow2(-(-len(p) // ps)), s.max_pages)
                view_tokens = cols * ps
                # read-dispatch-reassign window on the (possibly
                # shared) tree — enqueue only; the host sync below
                # runs outside the lock
                with self._tree_lock:
                    s.cache, tok, key, last = _paged_prefill_admit(
                        self.model, self.params, s.cache,
                        jnp.asarray(window), jnp.asarray(positions),
                        jnp.int32(len(suffix)),
                        jnp.asarray(s.page_table[slot:slot + 1, :cols]),
                        jnp.float32(req.temperature),
                        jnp.int32(req.top_k),
                        jax.random.PRNGKey(req.seed),
                        self._state_slot(slot, off))
                self.prefills += 1
                d_bucket = lb
                if self.prefix is not None:
                    # pin the freshly covered prompt: a refcount bump
                    # on the slot's own pages plus the stored logits —
                    # the next exact sharer pays the cow_admit path
                    self.prefix.insert(p, pages=s.slot_pages(slot,
                                                             len(p)),
                                       logits=last)
                if entry is not None:
                    hit_tokens, saved = off, full_bucket - lb
        finally:
            if entry is not None:
                self.prefix.release(entry)
        if hit_tokens:
            self.prefix_hits += 1
            self.prefix_hit_tokens += hit_tokens
            self.prefill_tokens_saved += saved
        self._wait_behind("admit.wait")
        tok = int(tok)  # host sync: the admit dispatch is done here
        self.phases.switch("admit.emit")
        if self.timeline is not None:
            tags = {"prompt_len": len(p)}
            if lookup_ms is not None:
                tags["lookup_ms"] = round(lookup_ms, 3)
            if hit_tokens:
                tags["prefix_hit_tokens"] = hit_tokens
            if off and not exact:
                tags["offset"] = int(off)
            if forked:
                tags["cow_fork"] = True
            if view_tokens:
                tags["view_tokens"] = view_tokens
            if d_kind == "cow_admit":
                work = fed = 1
                est = self.cost.cow_admit(
                    pool.page_nbytes if forked else 0)
            else:
                work, fed = d_bucket, len(p) - off
                est = self.cost.prefill(d_bucket, off, view_tokens)
            # the view span is a second program-shape knob in paged
            # mode: the compile key must carry it or a recompile at a
            # new span would be mislabeled steady
            self._record_dispatch(
                d_kind, t0, (time.monotonic() - t0) * 1e3, occ,
                d_bucket, 1, (d_kind, d_bucket, view_tokens),
                request_id=req.id, tags=tags, work=work, fed=fed,
                est=est)
        chunks = 0 if d_kind == "cow_admit" else 1
        if req.prefill_only:
            # the prefill pool's exit: pages + last-position logits
            # hand off to a decode replica instead of arming the slot
            self._finish_handoff(req, s.slot_pages(slot, len(p)),
                                 len(p), last, finished,
                                 hit_tokens=hit_tokens, saved=saved,
                                 chunks=chunks)
            s.release_pages(slot)
            return True
        if tok in self.eos_ids or req.max_new_tokens == 1:
            # finished before ever decoding: the slot was never armed —
            # hand its page references straight back
            reason = "eos" if tok in self.eos_ids else "length"
            finished.append(Result(req.id, list(req.prompt), [tok],
                                   reason, hit_tokens, saved,
                                   prefill_chunks=chunks))
            s.release_pages(slot)
            return True
        s.admit(slot, len(p), tok, req.temperature, req.top_k, key)
        self._spec_ema[slot] = 1.0  # new tenant: drafting re-enabled
        self._live[slot] = _Live(req, [tok], hit_tokens, saved,
                                 prefill_chunks=chunks)
        return True

    def _state_slot(self, slot: int, first_pos: int):
        """What a paged window is told of its slot: nothing for a model
        whose cache is all pages, the slot for one with slot-resident
        state (counted: a window from position 0 starts a sequence, a
        later one continues the state its predecessor left)."""
        if not self._state_slots:
            return None
        if first_pos:
            self.state_carried_chunks += 1
        else:
            self.state_resets += 1
        return jnp.int32(slot)

    # ------------------------------------------------- chunked prefill

    def _advance_prefills(self, finished: list) -> None:
        """One chunk per mid-prefill slot per scheduler iteration —
        the starvation cap: between any two chunks of a long prompt,
        every live slot gets a full decode round, so a 30k-token
        prompt costs co-tenants one bounded chunk dispatch per round
        instead of one monolithic prefill."""
        for slot in sorted(self._prefilling):
            st = self._prefilling[slot]
            remaining = len(st.request.prompt) - st.done
            rid = st.request.id
            if remaining > self.prefill_chunk:
                with self.phases.phase("prefill_chunk.host", rid=rid):
                    if self.paged:
                        self._prefill_chunk_paged(slot, st)
                    else:
                        self._prefill_chunk_unpaged(slot, st)
                continue
            # final chunk: the fused suffix-prefill admit samples the
            # first token (or hands off) and un-parks the slot
            del self._prefilling[slot]
            with self.phases.phase("admit.host", rid=rid):
                if self.paged:
                    self._finalize_prefill_paged(slot, st, finished)
                else:
                    self._finalize_prefill_unpaged(slot, st, finished)

    def _prefill_chunk_paged(self, slot: int, st: _PrefillState, *,
                             t0: float | None = None, occ: int = 0,
                             forked: bool = False) -> None:
        """One INTERMEDIATE chunk straight into the slot's pages:
        ``prefill_chunk`` tokens at absolute positions from
        ``st.done`` — a window write with no sampling (only the final
        chunk holds the prompt's last position)."""
        s = self.slots
        ps = s.pool.page_size
        req = st.request
        p = np.asarray(req.prompt, np.int32)
        take = self.prefill_chunk
        if t0 is None:
            t0 = time.monotonic()
            occ = s.n_active
        s.ensure_pages(slot, st.done + take)
        window = np.asarray(p[st.done:st.done + take])[None, :]
        positions = (st.done
                     + np.arange(take, dtype=np.int32))[None, :]
        cols = min(_bucket_pow2(-(-(st.done + take) // ps)),
                   s.max_pages)
        view_tokens = cols * ps
        with self._tree_lock:
            s.cache, mark = _paged_prefill_chunk(
                self.model, self.params, s.cache, jnp.asarray(window),
                jnp.asarray(positions),
                jnp.asarray(s.page_table[slot:slot + 1, :cols]),
                self._state_slot(slot, st.done))
        self.prefills += 1
        self.prefill_chunk_dispatches += 1
        st.done += take
        st.chunks += 1
        if self.timeline is not None:
            # close the record at a real sync: without it the chunk
            # would bill its device time to whatever syncs next
            self._wait_behind("prefill_chunk.wait")
            jax.block_until_ready(mark)
            self.phases.switch("prefill_chunk.record")
            tags = {"prompt_len": len(p), "chunk": st.chunks,
                    "view_tokens": view_tokens}
            if forked:
                tags["cow_fork"] = True
            self._record_dispatch(
                "prefill_chunk", t0, (time.monotonic() - t0) * 1e3,
                occ, take, 0, ("prefill_chunk", take, view_tokens),
                request_id=req.id, tags=tags, work=take, fed=take,
                est=self.cost.prefill(take, st.done - take,
                                      view_tokens))

    def _prefill_chunk_unpaged(self, slot: int,
                               st: _PrefillState) -> None:
        """The unpaged intermediate chunk: a suffix prefill into the
        CARRIED batch-1 row (PR-3 offset machinery) — the row only
        lands in the slot on the final fused admit."""
        req = st.request
        p = np.asarray(req.prompt, np.int32)
        take = self.prefill_chunk
        t0 = time.monotonic()
        occ = self.slots.n_active
        window = np.asarray(p[st.done:st.done + take])[None, :]
        row, _ = _prefill(self.model, self.params, jnp.asarray(window),
                          jnp.int32(take), jnp.int32(st.done), st.row)
        st.row = row
        self.prefills += 1
        self.prefill_chunk_dispatches += 1
        st.done += take
        st.chunks += 1
        if self.timeline is not None:
            self._wait_behind("prefill_chunk.wait")
            jax.block_until_ready(row)
            self.phases.switch("prefill_chunk.record")
            self._record_dispatch(
                "prefill_chunk", t0, (time.monotonic() - t0) * 1e3,
                occ, take, 0, ("prefill_chunk", take),
                request_id=req.id,
                tags={"prompt_len": len(p), "chunk": st.chunks},
                work=take, fed=take,
                est=self.cost.prefill(take, st.done - take))

    def _finalize_prefill_paged(self, slot: int, st: _PrefillState,
                                finished: list) -> None:
        """The final chunk: the standard fused suffix-prefill admit at
        offset ``st.done`` — position-exact continuation of the chunks
        before it, so the armed slot is bit-identical to a monolithic
        prefill's (the chunked-parity tests pin the token stream)."""
        s = self.slots
        ps = s.pool.page_size
        req = st.request
        p = np.asarray(req.prompt, np.int32)
        max_len = self.model.cfg.max_seq_len
        t0 = time.monotonic()
        occ = s.n_active
        off = st.done
        suffix = p[off:]
        lb = bucket_len(len(suffix), max_len, self.min_bucket)
        s.ensure_pages(slot, len(p))
        window = np.zeros((1, lb), np.int32)
        window[0, :len(suffix)] = suffix
        positions = np.full((1, lb), -1, np.int32)
        positions[0, :len(suffix)] = \
            off + np.arange(len(suffix), dtype=np.int32)
        cols = min(_bucket_pow2(-(-len(p) // ps)), s.max_pages)
        view_tokens = cols * ps
        with self._tree_lock:
            s.cache, tok, key, last = _paged_prefill_admit(
                self.model, self.params, s.cache, jnp.asarray(window),
                jnp.asarray(positions), jnp.int32(len(suffix)),
                jnp.asarray(s.page_table[slot:slot + 1, :cols]),
                jnp.float32(req.temperature), jnp.int32(req.top_k),
                jax.random.PRNGKey(req.seed),
                self._state_slot(slot, off))
        self.prefills += 1
        self.prefill_chunk_dispatches += 1
        st.chunks += 1
        self.prefill_chunked += 1
        if self.prefix is not None:
            self.prefix.insert(p, pages=s.slot_pages(slot, len(p)),
                               logits=last)
        self._wait_behind("admit.wait")
        tok = int(tok)
        self.phases.switch("admit.emit")
        if self.timeline is not None:
            self._record_dispatch(
                "prefill", t0, (time.monotonic() - t0) * 1e3, occ, lb,
                1, ("prefill", lb, view_tokens), request_id=req.id,
                tags={"prompt_len": len(p), "chunk": st.chunks,
                      "offset": int(off), "view_tokens": view_tokens},
                work=lb, fed=len(suffix),
                est=self.cost.prefill(lb, off, view_tokens))
        if req.prefill_only:
            self._finish_handoff(req, s.slot_pages(slot, len(p)),
                                 len(p), last, finished,
                                 hit_tokens=st.hit_tokens,
                                 saved=st.saved, chunks=st.chunks)
            s.release_pages(slot)
            return
        if tok in self.eos_ids or req.max_new_tokens == 1:
            reason = "eos" if tok in self.eos_ids else "length"
            finished.append(Result(req.id, list(req.prompt), [tok],
                                   reason, st.hit_tokens, st.saved,
                                   prefill_chunks=st.chunks))
            s.release_pages(slot)
            return
        s.admit(slot, len(p), tok, req.temperature, req.top_k, key)
        self._spec_ema[slot] = 1.0
        self._live[slot] = _Live(req, [tok], st.hit_tokens, st.saved,
                                 prefill_chunks=st.chunks)

    def _finalize_prefill_unpaged(self, slot: int, st: _PrefillState,
                                  finished: list) -> None:
        s = self.slots
        req = st.request
        p = np.asarray(req.prompt, np.int32)
        max_len = self.model.cfg.max_seq_len
        t0 = time.monotonic()
        occ = s.n_active
        # the final window's bucket must still fit the cache row
        # (dynamic_update_slice would clamp and corrupt positions);
        # shrinking re-prefills a tail of already-written tokens —
        # identical values at identical positions, position-exact
        off = _usable_prefix(st.done, len(p), max_len, self.min_bucket)
        suffix = p[off:]
        lb = bucket_len(len(suffix), max_len, self.min_bucket)
        padded = np.zeros((1, lb), np.int32)
        padded[0, :len(suffix)] = suffix
        with self._tree_lock:
            s.cache, tok, key, *row_last = _prefill_admit(
                self.model, self.params, s.cache, jnp.asarray(padded),
                jnp.int32(len(suffix)), jnp.int32(slot),
                jnp.float32(req.temperature), jnp.int32(req.top_k),
                jax.random.PRNGKey(req.seed), jnp.int32(off), st.row,
                with_row=self.prefix is not None)
        self.prefills += 1
        self.prefill_chunk_dispatches += 1
        st.chunks += 1
        self.prefill_chunked += 1
        if self.prefix is not None:
            self.prefix.insert(p, *row_last)
        self._wait_behind("admit.wait")
        tok = int(tok)
        self.phases.switch("admit.emit")
        if self.timeline is not None:
            self._record_dispatch(
                "prefill", t0, (time.monotonic() - t0) * 1e3, occ, lb,
                1, ("prefill", lb), request_id=req.id,
                tags={"prompt_len": len(p), "chunk": st.chunks,
                      "offset": int(off)},
                work=lb, fed=len(suffix),
                est=self.cost.prefill(lb, off))
        if tok in self.eos_ids or req.max_new_tokens == 1:
            reason = "eos" if tok in self.eos_ids else "length"
            finished.append(Result(req.id, list(req.prompt), [tok],
                                   reason, st.hit_tokens, st.saved,
                                   prefill_chunks=st.chunks))
            return
        s.admit(slot, len(p), tok, req.temperature, req.top_k, key)
        self._spec_ema[slot] = 1.0
        self._live[slot] = _Live(req, [tok], st.hit_tokens, st.saved,
                                 prefill_chunks=st.chunks)

    def _gather(self, idx: list):
        """Enqueue the gather of pool pages ``idx`` (already padded to
        their bucket) against the live tree. Reading the tree and
        enqueueing happen in ONE tree-lock window: every writer
        donates, so a reference read here and used after a co-located
        engine's dispatch would name deleted arrays. The payload is
        its own buffers and outlives any later writer."""
        with self._tree_lock:
            return _gather_pages(self.slots.cache,
                                 jnp.asarray(idx, jnp.int32))

    # ------------------------------------------------ role-split handoff

    def _finish_handoff(self, req: Request, pages: list, n_tok: int,
                        logits, finished: list, *, hit_tokens: int = 0,
                        saved: int = 0, chunks: int = 0) -> None:
        """The prefill pool's exit: stack the prompt's page CONTENT
        into a portable payload (pow2-padded gather — the padding
        duplicates the last page and the receiving scatter drops it)
        plus the last-position logits, and finish the request
        ``finish_reason="handoff"``. The payload is an immutable
        device pytree: local decode replicas scatter it straight into
        their own pool (device->device, no host hop); the agent wire
        encodes it via serve/tier.py.

        On a SHARED pool (ISSUE-18) there is nothing to gather: the
        consumer reads the same device tree, so the payload is the
        page-ID list itself — pinned by one extra refcount that
        TRANSFERS to whoever consumes the doc (a co-located decode
        engine's owner-swap admit, or the remote stub's late gather)
        — and the local prefill->decode handoff becomes a pure
        pointer move."""
        pool = self.slots.pool
        n = len(pages)
        t0 = time.monotonic()
        occ = self.slots.n_active
        res = Result(req.id, list(req.prompt), [], "handoff",
                     hit_tokens, saved, prefill_chunks=chunks)
        if pool.shared:
            pool.share(pages)  # the doc's own ref; its consumer unrefs
            res.handoff = {"n_tokens": int(n_tok),
                           "page_ids": [int(pg) for pg in pages],
                           "pool": pool, "logits": jnp.asarray(logits)}
            finished.append(res)
            self.handoffs_out += 1
            if self.timeline is not None:
                self._record_dispatch(
                    "handoff_out", t0, (time.monotonic() - t0) * 1e3,
                    occ, n, 0, ("handoff_out", 0), request_id=req.id,
                    tags={"pages": n, "n_tokens": int(n_tok),
                          "owner_swap": True}, work=1, fed=1)
            return
        idx = _padded_pages(pages)
        n_pad = len(idx)
        payload = self._gather(idx)
        res.handoff = {"n_tokens": int(n_tok), "pages": payload,
                       "logits": jnp.asarray(logits)}
        finished.append(res)
        self.handoffs_out += 1
        if self.timeline is not None:
            jax.block_until_ready(payload)
            self._record_dispatch(
                "handoff_out", t0, (time.monotonic() - t0) * 1e3, occ,
                n_pad, 0, ("handoff_out", n_pad), request_id=req.id,
                tags={"pages": n, "n_tokens": int(n_tok)}, work=1,
                fed=1, est=self.cost.host_move(n * pool.page_nbytes))

    def _handoff_page_count(self, doc: dict) -> int:
        """Page-axis length of a handoff payload, for ALL forms —
        shared-pool page ids, wire (shapes carried per leaf), and
        device pytree — without decoding anything."""
        if "page_ids" in doc:
            return len(doc["page_ids"])
        pages = doc["pages"]
        if isinstance(pages, dict) and "leaves" in pages:
            if len(pages["leaves"]) != self._cache_treedef.num_leaves:
                raise ValueError(
                    f"handoff payload carries {len(pages['leaves'])} "
                    f"leaves, this engine's cache has "
                    f"{self._cache_treedef.num_leaves} — mismatched "
                    "model configs between the prefill and decode "
                    "pools")
            i, ax = self._payload_leaf_spec
            return int(pages["leaves"][i]["shape"][ax])
        return payload_pages(pages)

    def _check_handoff_geometry(self, doc: dict, n_tok: int) -> None:
        ps = self.slots.pool.page_size
        need = -(-n_tok // ps)
        have = self._handoff_page_count(doc)
        if have < need:
            raise ValueError(
                f"handoff payload holds {have} pages, the prompt "
                f"needs {need} at page_size {ps} — mismatched page "
                "geometry between the prefill and decode pools")

    def _decode_handoff(self, doc: dict) -> tuple:
        """A handoff payload's two forms: a device/numpy pytree (local
        handoff — used as-is) or the agent wire form (base64 leaves —
        rebuilt against THIS engine's cache treedef)."""
        pages, logits = doc["pages"], doc["logits"]
        if isinstance(pages, dict) and "leaves" in pages:
            pages = decode_payload(pages, self._cache_treedef)
        if isinstance(logits, dict) and "b64" in logits:
            logits = decode_array(logits)
        return pages, logits

    def _admit_handoff(self, req: Request, finished: list) -> bool:
        """The decode pool's entry: reserve the request's whole-life
        worst case, scatter the payload into fresh pages, sample the
        first token from the carried logits with THIS request's
        knobs/seed, arm the slot. Token-exact vs one engine doing
        prefill + decode itself: the pages round-trip bitwise and the
        first-token draw uses the same PRNGKey the fused admit would
        have.

        Shared-pool form (``page_ids``): no scatter at all — the pages
        are already resident, so the admit aliases them CoW-style via
        ``seed_pages`` (the fork matters: many decode requests can
        adopt the same hot prompt concurrently, and each needs its own
        writable tail page) and drops the doc's transfer ref."""
        s = self.slots
        pool = s.pool
        ps = pool.page_size
        p = np.asarray(req.prompt, np.int32)
        n_tok = int(req.handoff["n_tokens"])
        worst = -(-(len(p) + req.max_new_tokens) // ps)
        if "page_ids" in req.handoff:
            return self._admit_handoff_shared(req, finished, p, n_tok,
                                              worst)
        granted = pool.reserve(worst)
        while not granted and self.prefix is not None \
                and self.prefix.evict_one():
            granted = pool.reserve(worst)
        if not granted:
            return False  # transient: stays pending until pages free
        if self.fault_plan is not None:
            try:
                self.fault_plan.on_admit(req.id)
            except BaseException:
                pool.cancel(worst)
                raise
        slot = self._free_slots()[0]
        t0 = time.monotonic()
        occ = s.n_active
        pages_tree, logits = self._decode_handoff(req.handoff)
        s.seed_pages(slot, [], 0, worst)
        s.ensure_pages(slot, n_tok)
        n = -(-n_tok // ps)
        n_pad = payload_pages(pages_tree)
        # submit() already validated the geometry; this guards the
        # invariant without killing the replica over a caller bug
        if n_pad < n:
            s.release_pages(slot)
            raise ValueError(
                f"handoff payload holds {n_pad} pages, prompt needs "
                f"{n} at page_size {ps}")
        dst = s.page_table[slot, :n].tolist() \
            + [pool.n_pages] * (n_pad - n)
        with self._tree_lock:
            s.cache = _scatter_pages(s.cache, pages_tree,
                                     jnp.asarray(dst, jnp.int32))
        tok, key = _sample_first(
            jnp.asarray(logits), jnp.float32(req.temperature),
            jnp.int32(req.top_k), jax.random.PRNGKey(req.seed))
        if self.prefix is not None:
            # the decode pool learns the prompt too: the next sharer
            # routed here hits without another handoff
            self.prefix.insert(p, pages=s.slot_pages(slot, n_tok),
                               logits=jnp.asarray(logits))
        self.handoffs_in += 1
        self._wait_behind("admit.wait")
        tok = int(tok)
        self.phases.switch("admit.emit")
        if self.timeline is not None:
            self._record_dispatch(
                "handoff_admit", t0, (time.monotonic() - t0) * 1e3,
                occ, n_pad, 1, ("handoff_admit", n_pad),
                request_id=req.id,
                tags={"prompt_len": len(p), "pages": n}, work=1, fed=1,
                est=self.cost.host_move(n * pool.page_nbytes))
        if tok in self.eos_ids or req.max_new_tokens == 1:
            reason = "eos" if tok in self.eos_ids else "length"
            finished.append(Result(req.id, list(req.prompt), [tok],
                                   reason))
            s.release_pages(slot)
            return True
        s.admit(slot, len(p), tok, req.temperature, req.top_k, key)
        self._spec_ema[slot] = 1.0
        self._live[slot] = _Live(req, [tok])
        return True

    def _admit_handoff_shared(self, req: Request, finished: list,
                              p: np.ndarray, n_tok: int,
                              worst: int) -> bool:
        """Owner-swap admit: the handoff pages already live in THIS
        engine's pool, so admission is ``seed_pages`` aliasing — share
        each full page, fork the partial tail (many decode requests
        can adopt the same hot prompt concurrently, and each needs its
        own writable tail) — then drop the doc's transfer ref. KV
        bytes moved: one page when the prompt ends mid-page, else
        zero."""
        s = self.slots
        pool = s.pool
        ps = pool.page_size
        page_ids = [int(pg) for pg in req.handoff["page_ids"]]
        n_alias = -(-n_tok // ps)
        fork = 1 if n_tok % ps else 0
        need = worst - n_alias + fork
        granted = pool.reserve(need)
        while not granted and self.prefix is not None \
                and self.prefix.evict_one():
            granted = pool.reserve(need)
        if not granted:
            return False  # transient; the doc's ref keeps pages alive
        if self.fault_plan is not None:
            try:
                self.fault_plan.on_admit(req.id)
            except BaseException:
                pool.cancel(need)
                raise
        slot = self._free_slots()[0]
        t0 = time.monotonic()
        occ = s.n_active
        s.seed_pages(slot, page_ids[:n_alias], n_tok, need)
        pool.unref(page_ids)  # the transfer ref moves to the slot
        logits = req.handoff["logits"]
        tok, key = _sample_first(
            jnp.asarray(logits), jnp.float32(req.temperature),
            jnp.int32(req.top_k), jax.random.PRNGKey(req.seed))
        if self.prefix is not None:
            self.prefix.insert(p, pages=s.slot_pages(slot, n_tok),
                               logits=jnp.asarray(logits))
        self.handoffs_in += 1
        self.migrate_bytes_avoided += \
            (n_alias - fork) * pool.page_nbytes
        self._wait_behind("admit.wait")
        tok = int(tok)
        self.phases.switch("admit.emit")
        if self.timeline is not None:
            self._record_dispatch(
                "handoff_admit", t0, (time.monotonic() - t0) * 1e3,
                occ, n_alias, 1, ("handoff_admit", 0),
                request_id=req.id,
                tags={"prompt_len": len(p), "pages": n_alias,
                      "owner_swap": True}, work=1, fed=1,
                est=self.cost.host_move(fork * pool.page_nbytes))
        if tok in self.eos_ids or req.max_new_tokens == 1:
            reason = "eos" if tok in self.eos_ids else "length"
            finished.append(Result(req.id, list(req.prompt), [tok],
                                   reason))
            s.release_pages(slot)
            return True
        s.admit(slot, len(p), tok, req.temperature, req.top_k, key)
        self._spec_ema[slot] = 1.0
        self._live[slot] = _Live(req, [tok])
        return True

    # ------------------------------------------------- live migration

    def _check_migrate(self, req: Request, p: list) -> None:
        """Continuity + geometry of a migrate payload at submit time —
        a mismatch is one request's clean refusal (400 at the
        gateway), not a whole-replica admission crash (the handoff
        precedent). Accepts both forms: a ``SessionSnapshot`` (local
        owner swap or in-process remote) and the agent wire doc.

        A DELTA doc (suffix-only pages + ``delta.prefix_tokens``,
        ISSUE-19) is additionally checked against this engine's OWN
        prefix store: the covering entry is acquired and PINNED in
        ``_migrate_pins`` so eviction between this check (any thread)
        and admission (the scheduler thread) cannot free the prefix
        pages the adopt will alias. A store that no longer covers the
        assumed prefix raises ``StaleDelta`` — the sender's contract
        is to re-ship the full payload. The probe is device-store-only
        (no host-tier page-in: that dispatches device work, and this
        runs on the HTTP thread)."""
        snap = req.migrate
        delta = None
        if isinstance(snap, dict):
            gen = snap.get("generated") or []
            n_tok = int(snap.get("n_tokens", -1))
            prompt = [int(t) for t in snap.get("prompt", ())]
            pages = snap.get("pages")
            delta = snap.get("delta")
            if not (isinstance(pages, dict) and "leaves" in pages):
                raise ValueError(
                    "a wire migrate doc carries base64 leaf pages")
            have = self._handoff_page_count({"pages": pages})
        else:
            gen = list(snap.generated)
            n_tok = int(snap.n_tokens)
            prompt = [int(t) for t in snap.prompt]
            if snap.local:
                if snap.pool is not self.slots.pool:
                    raise ValueError(
                        "a local (owner-swap) snapshot holds page ids "
                        "in a pool this engine does not share — "
                        "extract with wire=True to cross pools")
                have = len(snap.pages)
            else:
                have = self._handoff_page_count({"pages": snap.pages})
        if not gen:
            raise ValueError(
                "a migrated session carries at least one generated "
                "token (pre-first-token sessions re-run as ordinary "
                "requests)")
        if prompt != [int(t) for t in p]:
            raise ValueError(
                "migrate snapshot prompt differs from the request "
                "prompt — the stream would not be continuous")
        if n_tok != len(p) + len(gen) - 1:
            raise ValueError(
                f"migrate snapshot holds {n_tok} KV positions, "
                f"prompt + generated - 1 is {len(p) + len(gen) - 1} "
                "— the final sampled token is never fed, so its K/V "
                "must not be present")
        ps = self.slots.pool.page_size
        need = -(-n_tok // ps)
        if delta is None:
            if have < need:
                raise ValueError(
                    f"migrate snapshot holds {have} pages, the "
                    f"session needs {need} at page_size {ps} — "
                    "mismatched page geometry between source and "
                    "target")
            return
        # ---- delta form: suffix pages only + an assumed prefix
        pt = int(delta.get("prefix_tokens", 0))
        if pt <= 0 or pt % ps:
            raise ValueError(
                f"delta prefix_tokens ({pt}) must be a positive "
                f"multiple of page_size {ps}")
        k = pt // ps
        if k > need - 1:
            raise ValueError(
                f"delta prefix covers {k} pages of a {need}-page "
                "session — at least one page always ships")
        if have < need - k:
            raise ValueError(
                f"delta payload holds {have} pages, the suffix needs "
                f"{need - k} at page_size {ps}")
        if self.prefix is None:
            raise StaleDelta(
                "delta migrate doc arrived but this engine runs no "
                "prefix store — nothing can cover the prefix")
        # the context whose KV the prefix pages must hold: prompt +
        # generated minus the never-fed-back final token (the
        # snapshot invariant checked above)
        ctx = prompt + [int(t) for t in gen][:-1]
        match, entry = self.prefix.acquire(ctx)
        if entry is None or match < pt or entry.pages is None \
                or len(entry.pages) < k:
            if entry is not None:
                self.prefix.release(entry)
            raise StaleDelta(
                f"adopter covers {match} prefix tokens on-device, the "
                f"delta assumed {pt} — the sender's radix summary was "
                "stale; re-ship the full payload")
        # consumed at admission; released on post-check submit
        # failure and reset(). A re-sent submit (the agent's
        # idempotency contract) must not leak the first pin.
        self._release_migrate_pin(req.id)
        self._migrate_pins[req.id] = entry

    def extract_session(self, request_id, *, wire: bool = False):
        """Freeze a live decode slot into a ``SessionSnapshot`` and
        evict it — the source half of a migration, called between
        steps (it takes the dispatch lock). Rounds in flight are
        settled first: the snapshot is cut from host mirrors that have
        caught up with the device.

        Returns None when ``request_id`` is not in a live decode slot
        (still pending or mid-prefill) — those carry no per-slot state
        worth moving, so the caller re-runs them as ordinary requests —
        and when the session finished under the settle (its remaining
        budget was all in flight): the next ``step()`` returns its
        result.

        ``wire=False`` (local owner swap): the snapshot holds page IDS
        pinned by one ``share()`` ref that transfers with it — zero KV
        bytes move, and adopt is a page-table install. ``wire=True``:
        the snapshot holds gathered page CONTENT (a device pytree) fit
        for ``snapshot_to_doc`` and the agent wire."""
        if self._state_slots:
            raise NotImplementedError(
                "extract_session (migration) is not implemented for a "
                "model with conv layers (cfg.layer_types): a snapshot "
                "carries pages, not the slot-resident state")
        if self.model.cfg.latent is not None:
            raise NotImplementedError(
                "extract_session (migration) is not implemented for a "
                "latent-attention model (cfg.latent)")
        with self._dispatch_lock:
            s = self.slots
            pool = s.pool
            if wire is False and not pool.shared:
                raise ValueError(
                    "a local owner-swap snapshot needs a shared pool "
                    "— extract with wire=True")
            slot = None
            for i, live in enumerate(self._live):
                if live is not None and live.request.id == request_id:
                    slot = i
                    break
            if slot is None:
                return None
            live = self._live[slot]
            # the snapshot is cut from the mirrors: catch them up with
            # the device first. What finishes meanwhile is handed out
            # by the next step() — this session too, and then there is
            # nothing left to move. The caller may be another thread
            # than the phase ledger's owner, so no span is booked
            self._settle(self._held, spans=False)
            if self._live[slot] is not live:
                return None
            req = live.request
            t0 = time.monotonic()
            occ = s.n_active
            n_tok = int(s.lengths[slot])
            n = -(-n_tok // pool.page_size)
            pages = [int(pg) for pg in s.page_table[slot, :n]]
            if wire:
                payload = self._gather(_padded_pages(pages))
                jax.block_until_ready(payload)
                self.migrations_remote += 1
                self.migrate_pages_moved += n
            else:
                pool.share(pages)  # the snapshot's transfer ref
                payload = pages
                self.migrations_local += 1
                self.migrate_bytes_avoided += n * pool.page_nbytes
            snap = SessionSnapshot(
                prompt=list(req.prompt),
                generated=list(live.generated),
                max_new_tokens=int(req.max_new_tokens),
                temperature=float(s.temperature[slot]),
                top_k=int(s.top_k[slot]),
                seed=int(req.seed),
                # the key as the device holds it now (``s.rng`` pulls
                # it back where chunk rounds have moved it)
                rng=np.array(s.rng[slot], np.uint32),
                spec_ema=float(self._spec_ema[slot]),
                n_tokens=n_tok,
                pages=payload,
                local=not wire,
                t_freeze=time.time(),
                pool=pool if not wire else None,
                page_size=pool.page_size)
            self._live[slot] = None
            s.evict(slot)
            self.migrations_out += 1
            if self.timeline is not None:
                est = self.cost.host_move(n * pool.page_nbytes) \
                    if wire else (0.0, 0.0)
                self._record_dispatch(
                    "migrate_out", t0, (time.monotonic() - t0) * 1e3,
                    occ, n, 0, ("migrate_out", n if wire else 0),
                    request_id=req.id,
                    tags={"pages": n, "n_tokens": n_tok,
                          "local": not wire}, work=1, fed=1, est=est)
            return snap

    def _admit_migrate(self, req: Request, finished: list) -> bool:
        """Adopt a frozen session: restore its pages (owner swap or
        scatter), then arm the slot DIRECTLY with the carried sampler
        state — no prefill, no first-token draw; every token of this
        stream so far was already sampled, and the PRNG key resumes at
        its exact chain position. The next decode round continues as
        if the slot had lived here all along."""
        snap = req.migrate
        delta_pt = 0
        if isinstance(snap, dict):
            delta_pt = int((snap.get("delta") or {})
                           .get("prefix_tokens", 0))
            snap = snapshot_from_doc(snap)
        s = self.slots
        pool = s.pool
        ps = pool.page_size
        p = np.asarray(req.prompt, np.int32)
        n_tok = int(snap.n_tokens)
        n = -(-n_tok // ps)
        worst = -(-(len(p) + req.max_new_tokens) // ps)
        t0 = time.monotonic()
        occ = s.n_active
        if snap.local:
            # owner swap: the snapshot's share() ref transfers to the
            # slot via a direct page-table install. No CoW fork — a
            # migration has exactly one writer (move semantics), and
            # the tail page's written extent stops where every other
            # holder's read extent does.
            if snap.pool is not pool:
                raise ValueError(
                    "local migrate snapshot is from a different pool")
            need = worst - n
            granted = pool.reserve(need)
            while not granted and self.prefix is not None \
                    and self.prefix.evict_one():
                granted = pool.reserve(need)
            if not granted:
                return False  # transient; snapshot ref pins the pages
            if self.fault_plan is not None:
                try:
                    self.fault_plan.on_admit(req.id)
                except BaseException:
                    pool.cancel(need)
                    raise
            slot = self._free_slots()[0]
            s.reserve_left[slot] = need
            s.n_slot_pages[slot] = n
            s.page_table[slot, :n] = np.asarray(snap.pages, np.int32)
            s.page_table[slot, n:] = pool.n_pages
            self.migrate_bytes_avoided += n * pool.page_nbytes
        else:
            # delta (ISSUE-19): pages [0, k) alias this engine's own
            # store pages instead of shipping — they need no fresh
            # allocation, so the reservation shrinks by k
            k = delta_pt // ps
            need = worst - k
            granted = pool.reserve(need)
            while not granted and self.prefix is not None \
                    and self.prefix.evict_one():
                # evict_one can never free the pinned covering entry
                # (its refcount is held by _migrate_pins)
                granted = pool.reserve(need)
            if not granted:
                return False  # transient; the pin keeps the prefix
            if self.fault_plan is not None:
                try:
                    self.fault_plan.on_admit(req.id)
                except BaseException:
                    pool.cancel(need)
                    raise
            slot = self._free_slots()[0]
            pages_tree = snap.pages
            if isinstance(pages_tree, dict) and "leaves" in pages_tree:
                pages_tree = decode_payload(pages_tree,
                                            self._cache_treedef)
            if k:
                # reconstruct the prefix by refcount-sharing the
                # entry pinned at _check_migrate time — the same
                # alias accounting local adoptions use. The seed is
                # page-aligned by the delta contract, so no CoW fork;
                # the slot's write positions live in shipped pages.
                entry = self._migrate_pins.pop(req.id)
                s.seed_pages(slot, [int(pg) for pg in entry.pages[:k]],
                             k * ps, need)
                self.prefix.release(entry)
            else:
                s.seed_pages(slot, [], 0, need)
            s.ensure_pages(slot, n_tok)
            n_ship = payload_pages(pages_tree)
            if n_ship < n - k:
                s.release_pages(slot)
                raise ValueError(
                    f"migrate payload holds {n_ship} pages, the "
                    f"session needs {n - k} at page_size {ps}")
            # delta payloads arrive trimmed pad-free; re-pad to the
            # pow2 scatter bucket so migrations compile one scatter
            # program per bucket, not one per page count
            n_pad = _bucket_pow2(max(1, n_ship))
            if n_pad > n_ship:
                pages_tree = pad_host_pages(pages_tree, n_pad)
            dst = s.page_table[slot, k:n].tolist() \
                + [pool.n_pages] * (n_pad - (n - k))
            with self._tree_lock:
                s.cache = _scatter_pages(s.cache, pages_tree,
                                         jnp.asarray(dst, jnp.int32))
            self.migrate_pages_moved += n - k
            self.migrate_bytes_wire += (n - k) * pool.page_nbytes
            if k:
                self.migrate_bytes_avoided += k * pool.page_nbytes
                self.migrate_delta_in += 1
        gen = [int(t) for t in snap.generated]
        s.admit(slot, n_tok, gen[-1], snap.temperature, snap.top_k,
                snap.rng)
        self._spec_ema[slot] = float(snap.spec_ema)
        self._live[slot] = _Live(req, gen)
        self.migrations_in += 1
        if snap.local:
            self.migrations_local += 1
        else:
            self.migrations_remote += 1
        self.migrate_freeze_resume_ms += \
            max(0.0, (time.time() - snap.t_freeze) * 1e3)
        if self.timeline is not None:
            moved = 0 if snap.local else n - (delta_pt // ps)
            est = (0.0, 0.0) if snap.local \
                else self.cost.host_move(moved * pool.page_nbytes)
            self._record_dispatch(
                "migrate_in", t0, (time.monotonic() - t0) * 1e3, occ,
                n, 0, ("migrate_in", 0 if snap.local else moved),
                request_id=req.id,
                tags={"pages": n, "n_tokens": n_tok,
                      "generated": len(gen), "local": snap.local,
                      "delta_prefix_pages": delta_pt // ps},
                work=1, fed=1, est=est)
        return True

    # --------------------------------------------------- host page tier

    def _spill_entry(self, entry) -> None:
        """``PrefixStore.on_evict`` hook: before a dying entry's pages
        are unpinned, copy their content device->host into the tier —
        eviction stops meaning re-prefill. Entries already resident in
        the tier only refresh LRU (zero device work)."""
        if entry.pages is None:
            return  # unpaged store entry: the tier is paged-only
        tier = self.host_tier
        tokens = entry.tokens
        if tier.has(tokens):
            tier.touch(tokens)
            return
        pool = self.slots.pool
        n = -(-int(tokens.size) // pool.page_size)
        pages = list(entry.pages[:n])
        idx = _padded_pages(pages)
        n_pad = len(idx)
        t0 = time.monotonic()
        # DISPATCH only: the gather is enqueued against the
        # pre-eviction tree, and the runtime orders every later
        # writer's donation after it, so page reuse cannot touch what
        # it reads; the payload is buffers of its own, and the
        # device->host sync runs on the tier's copy thread — decode
        # rounds proceed during the spill
        payload = self._gather(idx)
        tier.spill_async(tokens, payload, n, entry.logits)
        if self.timeline is not None:
            self._record_dispatch(
                "host_spill", t0, (time.monotonic() - t0) * 1e3,
                self.slots.n_active, n_pad, 0, ("host_spill", n_pad),
                tags={"pages": n, "tokens": int(tokens.size),
                      "async": True},
                work=1, fed=1,
                est=self.cost.host_move(n * pool.page_nbytes))

    def _maybe_page_in(self, p: np.ndarray, off: int, entry):
        """When the host tier holds a strictly longer prefix of ``p``
        than the device store matched, restore that tier entry into
        the pool + device store (host->device scatter) and re-run the
        device lookup — the admission that follows then hits it like
        it never left. Degrades silently when the pool cannot afford
        the pages (after squeezing the device store's LRU)."""
        tier = self.host_tier
        t_off, t_entry = tier.acquire(p)
        if t_entry is None or t_off <= off:
            if t_entry is not None:
                tier.release(t_entry)
            return off, entry
        pool = self.slots.pool
        n = -(-int(t_entry.tokens.size) // pool.page_size)
        try:
            while pool.available() < n and self.prefix.evict_one():
                pass
            if pool.available() < n:
                return off, entry
            t0 = time.monotonic()
            pages = pool.alloc(n)
            idx = _padded_pages(pages, sentinel=pool.n_pages)
            n_pad = len(idx)
            payload = pad_host_pages(t_entry.row, n_pad)
            with self._tree_lock:
                self.slots.cache = _scatter_pages(
                    self.slots.cache, payload,
                    jnp.asarray(idx, jnp.int32))
            logits = jnp.asarray(t_entry.logits) \
                if t_entry.logits is not None else None
            ok = self.prefix.insert(t_entry.tokens, pages=pages,
                                    logits=logits)
            # the store holds its own pins now (or, refused, nobody
            # does and the pages go straight back to the free list)
            pool.unref(pages)
            tier.note_page_in(n * pool.page_nbytes)
            if self.timeline is not None:
                self._record_dispatch(
                    "host_page_in", t0,
                    (time.monotonic() - t0) * 1e3,
                    self.slots.n_active, n_pad, 0,
                    ("host_page_in", n_pad),
                    tags={"pages": n,
                          "tokens": int(t_entry.tokens.size)},
                    work=1, fed=1,
                    est=self.cost.host_move(n * pool.page_nbytes))
            if not ok:
                return off, entry
        finally:
            tier.release(t_entry)
        if entry is not None:
            self.prefix.release(entry)
        return self.prefix.acquire(p)

    def _budgets(self) -> list[int]:
        """Per slot, the tokens a round enqueued NOW could still give
        its occupant: the request's budget less what it has generated
        AS THE MIRRORS KNOW IT, less the depth of every round in flight
        that it rides (the host plans a round ahead of its mirrors).
        0 for an empty slot; <= 0 for an occupant whose budget the
        rounds in flight spend, which the device will have frozen."""
        left = [0] * self.slots.batch_size
        for slot, live in enumerate(self._live):
            if live is not None:
                left[slot] = live.request.max_new_tokens \
                    - len(live.generated)
        for rnd in self._inflight:
            for slot in rnd.riders:
                left[slot] -= rnd.k
        return left

    def _chunk_size(self, left: list[int]) -> int:
        """Decode micro-steps for the next dispatch, from ``_budgets``:
        enough for the longest-remaining live slot but never past
        ``chunk_steps``, quantized DOWN to a power of two (bounded
        compile count). A slot finishing mid-chunk freezes for the
        rest of it — frozen slot-steps are free (the batched step runs
        every row regardless); a too-long chunk would only waste
        WHOLE-batch steps at the very tail, which the max-remaining
        bound prevents."""
        rem = max(left)
        k = 1
        while k * 2 <= min(self.chunk_steps, rem):
            k *= 2
        return k

    def step(self) -> list[Result]:
        """One scheduler iteration; returns requests that finished.
        The iteration holds this ENGINE's ``_dispatch_lock`` (its own
        scheduler state: slots, _live, pending). Co-located engines on
        a shared pool step CONCURRENTLY (ISSUE-19): the shared device
        tree is guarded per dispatch by ``_tree_lock`` around each
        read-dispatch-reassign window, and allocator state by the
        pool's fine ``_mu``."""
        with self.phases.rest("step.other"), self._dispatch_lock:
            return self._step_locked()

    def _next_seq(self) -> int:
        """The sequence number the next timeline record will take: the
        id a dispatch's host phases carry, so that a span in a capture
        leads to its ``DispatchRecord`` (0 with the timeline off)."""
        return self.timeline.seq + 1 if self.timeline is not None else 0

    def _step_locked(self) -> list[Result]:
        if self.fault_plan is not None:
            self.fault_plan.on_dispatch()
        self._check_tree()
        finished, self._held = self._held, []
        while self._free_slots():
            with self._pending_lock:
                if not self.pending:
                    break
                req = self.pending.popleft()
            if req.migrate is not None:
                # a session resumes from what ANOTHER engine's host
                # knew: adopt it with this one's mirrors caught up too
                self._settle(finished)
            with self.phases.phase("admit.host", rid=req.id):
                admitted = self._admit_one(req, finished)
            if not admitted:
                # paged pool cannot grant the reservation right now:
                # requeue at the FRONT (FIFO order preserved) and stop
                # admitting — live slots finishing will free pages
                with self._pending_lock:
                    self.pending.appendleft(req)
                break
        # mid-prefill slots advance ONE chunk, then every live slot
        # gets its decode round — the interleave that keeps a long
        # prompt from starving co-tenants' TPOT. A prefill program
        # enqueued here runs BEHIND a round in flight: the device takes
        # them in order, which is what lets a finisher's pages go to
        # the next occupant while that round is still queued
        self._advance_prefills(finished)
        if self.slots.n_active:
            finished.extend(self._decode_round())
        return finished

    def _decode_round(self) -> list[Result]:
        """One batched decode round's tokens over the live slots +
        EOS/evict — ``step()`` minus admission (``drain()`` runs it
        alone). With speculation on, a round where any slot drafts
        runs ONE verify dispatch (``_verify_round``); otherwise the
        plain chunk path, in two halves: ENQUEUE rounds until two are
        in the device's queue (or no live row could move in another),
        then ARRIVE on the oldest. From idle that is two enqueues, in
        the steady state one; the device runs round n+1 while the host
        copies back round n, walks and streams its tokens, and
        enqueues round n+2.

        That order needs no word from the host about round n: round
        n+1's inputs are round n's own outputs, resident on the device
        (``SlotCache.state``), and a row that finished in round n
        starts round n+1 frozen (``_decode_chunk``'s seed). Where the
        next dispatch's inputs are the HOST's — a verify round, and so
        any engine that speculates — one round is in flight at most
        and the order is serial, as it always was."""
        finished: list[Result] = []
        depth = 2
        if self.speculate_k > 0:
            depth = 1
            # nothing to settle unless speculation was switched on
            # since the last step (``serve/autotune.py`` may)
            self._settle(finished)
            with self.phases.phase("verify.draft"):
                drafts = self._collect_drafts()
            if drafts is not None:
                with self.phases.phase("verify.prepare",
                                       seq=self._next_seq()):
                    finished.extend(self._verify_round(drafts))
                return finished
        while len(self._inflight) < depth:
            plan = self._plan_round()
            if plan is None:
                break
            with self.phases.phase("decode.prepare",
                                   round=self.dispatches + 1):
                self._enqueue_round(*plan)
        if self._inflight:
            self._arrive(finished)
        return finished

    def _plan_round(self) -> tuple | None:
        """Whether another chunk round is worth enqueueing NOW, and for
        whom: ``(left, riders)``, the budgets (``_budgets``) and the
        occupants that could still move in it, by slot. None when no
        live row could move: every occupant's budget is spent by the
        rounds in flight, so a finish by length costs no all-frozen
        round."""
        left = self._budgets()
        riders = {slot: live for slot, live in enumerate(self._live)
                  if live is not None and left[slot] > 0}
        return (left, riders) if riders else None

    def _enqueue_round(self, left: list[int], riders: dict) -> None:
        """The enqueue half of a chunk round (``_plan_round`` says for
        whom), inside its open ``decode.prepare`` leaf (switched to
        ``decode.enqueue`` for what the host must send, and the jit
        call).

        The program's small inputs are on the device already
        (``SlotCache.state``): this round sends the rows the host
        changed since the last one as one packed patch, the page table
        if its live columns changed, and in most rounds neither. The
        mirrors lag the device by the rounds in flight, so the page
        cover and the view's extent are planned from a slot's mirror
        PLUS the depth of every round in flight it rides."""
        s = self.slots
        k = self._chunk_size(left)
        table = None
        if self.paged:
            # the table is frozen across the chunk: pre-extend every
            # rider to cover the positions this chunk will write
            # (capped at the slot's own budget — a frozen tail past a
            # finish writes through the sentinel and drops). The table
            # is read COLUMN-SLICED to a power-of-two bucket of the live
            # extent: the gathered view — and every micro-step's
            # attention read over it — is O(actual tokens), not
            # O(max_seq_len); the dropped columns held junk whose
            # masked softmax weight is exactly 0.0, so outputs are
            # bit-identical (at most log2(max_pages) programs per
            # chunk depth, the prefill-bucket discipline)
            hi = 0
            for slot, live in riders.items():
                req = live.request
                # where the slot will stand once the rounds in flight
                # it rides have landed, plus this chunk
                ahead = req.max_new_tokens - len(live.generated) \
                    - left[slot]
                upto = int(s.lengths[slot]) + ahead + k
                s.ensure_pages(slot, min(
                    upto, len(req.prompt) + req.max_new_tokens))
                hi = max(hi, upto)
            cols = min(_bucket_pow2(-(-hi // s.pool.page_size)),
                       s.max_pages)
            table = s.device_table(cols)
        t0 = time.monotonic()
        # the read-dispatch-reassign window on the (possibly shared)
        # tree: enqueue ONE dispatch against the current version and
        # reassign — the host sync (``_arrive``) runs OUTSIDE the lock,
        # so co-located engines' device work overlaps
        self.phases.switch("decode.enqueue")
        # ``left`` is the budget of the rows the patch carries (a row
        # the host changed rides no round in flight, so it is that
        # row's whole remainder): the device freezes a slot the moment
        # it samples EOS or exhausts it, so every emitted (non-frozen)
        # position is a token the request keeps
        patch = s.decode_patch(left)
        with self._tree_lock:
            s.cache, toks, state = _decode_chunk(
                self.model, self.params, s.cache, s.state, patch, table,
                n_steps=k, eos_ids=self.eos_ids)
        s.advance(state)
        self.steps += k
        self.dispatches += 1
        self.rounds_overlapped += bool(self._inflight)
        self._inflight.append(_Round(
            self.dispatches, toks, k, t0, riders,
            cols * s.pool.page_size if self.paged else 0))

    def _arrive(self, finished: list, *, spans: bool = True) -> None:
        """The arrive half of the OLDEST round in flight: ``decode.wait``
        (the host sync on its tokens, the one copy back), ``decode.emit``
        (the token walk, from which the mirrors follow the device) and
        ``decode.record`` (the timeline record with its cost and goodput
        stamps). ``spans=False`` books no phase: for a caller on
        another thread than the ledger's owner (``extract_session``).

        The walk is over the round's RIDERS, not ``_live``: a slot whose
        occupant left in an earlier round, or that was admitted to
        another request since, gets nothing from this one (its row was
        frozen or empty on the device; what it emitted is a re-emit or
        garbage). Finishers are evicted here; the device learns of it
        with the next enqueue's patch, and until then holds them frozen
        by itself."""
        rnd = self._inflight.popleft()
        if spans:
            with self.phases.phase("decode.wait", seq=self._next_seq(),
                                   round=rnd.rid):
                self._land(rnd, finished, self.phases.switch)
        else:
            self._land(rnd, finished, lambda name: None)
        # a round whose riders have all left (each on a stop token the
        # host could not plan for) is dropped unread: the engine goes
        # idle with nothing in its queue. The device still runs it,
        # every row frozen; it leaves no record, only this count
        while self._inflight and not self._inflight[-1].riders:
            self._inflight.pop()
            self.rounds_dropped += 1

    def _wait_behind(self, name: str) -> None:
        """Open the wait leaf ``name`` of a program enqueued BEHIND the
        rounds in flight (an admission's prefill, a prefill chunk). The
        device takes its programs in order, so the host sync that
        follows waits those rounds out too: wait for them first and
        stamp when each was done, so that the wall is billed where it
        was spent. A stamped round's record closes at its stamp
        (``_land``) and this program's record starts there
        (``_record_dispatch``). The rounds stay in flight and unread."""
        self.phases.switch(name)
        for rnd in self._inflight:
            if not rnd.done_at:
                jax.block_until_ready(rnd.toks)
                rnd.done_at = time.monotonic()

    def _settle(self, finished: list, *, spans: bool = True) -> None:
        """Arrive on EVERY round in flight: the host mirrors are the
        truth again. For whoever reads them as such: a verify round
        (host-fed), a session's extraction and its adoption."""
        if not self._inflight:
            return
        self.settles += 1
        while self._inflight:
            self._arrive(finished, spans=spans)

    def _land(self, rnd: _Round, finished: list, switch) -> None:
        """``_arrive``'s body, from the host sync on: ``switch`` moves
        the open phase leaf on, or does nothing."""
        s = self.slots
        k = rnd.k
        toks = np.asarray(rnd.toks)  # [b, k]: the host sync
        if self.moe_counts is not None:  # the counts ride behind them
            self.moe_counts += toks[0, k:]
            toks = toks[:, :k]
        # the round's clock starts at the LATER of its enqueue and its
        # predecessor's end: two rounds in flight share wall, and the
        # ledger's buckets must count it once. It closes at the host
        # sync, the latency a request experienced, or where a program
        # queued behind it saw it done (``_wait_behind``)
        now = rnd.done_at or time.monotonic()
        t0 = max(rnd.t0, self._decode_end)
        dur_ms = max(0.0, now - t0) * 1e3
        self._decode_end = now
        switch("decode.emit")
        occ = len(rnd.riders)
        names = [live.request.id for live in rnd.riders.values()]
        landed = 0
        for slot, live in list(rnd.riders.items()):
            req = live.request
            reason = None
            for j in range(k):
                tok = int(toks[slot, j])
                live.generated.append(tok)
                if tok in self.eos_ids:
                    reason = "eos"
                elif len(live.generated) >= req.max_new_tokens:
                    reason = "length"
                if reason:
                    # tokens past this point were frozen in-dispatch
                    # (re-emitted finals, no KV writes): never reported
                    break
            if reason is None:
                # the chunk wrote k tokens at advancing positions; the
                # slot's visible cache grew by k
                s.lengths[slot] += k
                s.last_token[slot] = int(toks[slot, k - 1])
                landed += k
                continue
            # the trailing positions were frozen re-emits: walking them
            # is a consistency check, and the waste counter stays put
            self.frozen_steps += k - (j + 1)
            if j + 1 < k and not (toks[slot, j + 1:]
                                  == toks[slot, j]).all():
                self.freeze_faults += 1
                log.warning(
                    "frozen slot %d re-emitted a different token "
                    "(%s after %d) — in-dispatch EOS consistency "
                    "violation", slot, toks[slot, j + 1:].tolist(),
                    int(toks[slot, j]))
            landed += j + 1
            finished.append(Result(req.id, list(req.prompt),
                                   live.generated, reason,
                                   live.prefix_hit_tokens,
                                   live.prefill_tokens_saved,
                                   live.drafted, live.accepted,
                                   live.prefill_chunks))
            # a finish by EOS is one the host could not plan for: the
            # rounds already queued hold this row frozen for their
            # whole depth, and it rides them no longer
            for later in self._inflight:
                if later.riders.pop(slot, None) is not None:
                    self.frozen_steps += later.k
            if self.prefix is not None and self.prefix_donate:
                self._donate(live, slot)
            self._live[slot] = None
            s.evict(slot)
        switch("decode.record")
        if self.timeline is not None:
            tags = {"requests": names, "round": rnd.rid}
            if rnd.view_tokens:
                tags["view_tokens"] = rnd.view_tokens
            view = rnd.view_tokens or self.model.cfg.max_seq_len
            # position accounting: every fed position landed a kept
            # token (fed == landed: a chunk round charges the ledger's
            # overshoot bucket nothing; frozen tails join the
            # empty-slot positions in padding)
            tags["frozen"] = k * occ - landed
            self._record_dispatch(
                "decode", t0, dur_ms, occ, k, landed,
                ("decode", k, rnd.view_tokens), tags=tags,
                work=k * s.batch_size, fed=landed,
                est=self.cost.decode(k, s.batch_size, view))

    # ------------------------------------------------- speculative decode

    def _collect_drafts(self) -> list | None:
        """Host-side prompt-lookup proposals, one per slot — or None
        when NOBODY drafts (the round then takes the plain chunk path,
        so a fleet of lookup misses costs one numpy scan per slot and
        zero extra device work). A slot drafts only when: greedy (the
        acceptance rule is argmax equality; sampled requests keep the
        chunked semantics), its acceptance EMA is above the disable
        floor, and >= 2 budget tokens remain (a draft of d can land
        d+1 tokens, so d is clamped to remaining-1 — which also keeps
        every window write inside max_seq_len).

        The verify round carries a chunk's worth of continuation
        (``_verify_chunk(n_steps=...)``): every live slot — drafting
        or not — decodes the full chunk depth inside the same dispatch,
        so a lone drafter drags nobody and any proposed draft is pure
        upside (accepted tokens on top of the chunk's) minus one window
        pass. The EMA silences hopeless drafters."""
        out: list = [None] * self.slots.batch_size
        any_draft = False
        for slot, live in enumerate(self._live):
            if live is None:
                continue
            req = live.request
            if req.temperature != 0.0 \
                    or self._spec_ema[slot] < self.SPEC_EMA_DISABLE:
                continue
            d_cap = min(self.speculate_k,
                        req.max_new_tokens - len(live.generated) - 1)
            if d_cap <= 0:
                continue
            ctx = np.asarray(list(req.prompt) + live.generated, np.int32)
            draft = _propose_draft(ctx, d_cap)
            if draft.size:
                out[slot] = draft
                any_draft = True
        return out if any_draft else None

    def _verify_round(self, drafts: list) -> list[Result]:
        """One speculative verify dispatch + acceptance/evict. Every
        live slot rides: drafting slots lay out [last_token, draft...]
        at their own positions, non-drafting slots just [last_token]
        (their padding writes drop), and acceptance advances each slot
        by accepted+1 tokens — the rewind for rejected drafts is
        POINTER ARITHMETIC ONLY: their K/V stays in the cache beyond
        the slot's length, invisible to every later query and
        overwritten as the slot decodes on (the prefix-store masked-
        visibility exactness argument). Donation reads the row whose
        [0, len) span covers only fed, accepted tokens.

        The dispatch continues every row ``chunk_size`` frozen-body
        micro-steps past its own bonus verdict: a speculating round
        lands accepted + 1 + chunk tokens per slot in ONE dispatch,
        and non-drafting co-tenants keep their full chunk cadence."""
        finished: list[Result] = []
        s = self.slots
        b = s.batch_size
        k_cont = self._chunk_size(self._budgets())
        window = _bucket_pow2(max(d.size for d in drafts
                                  if d is not None)) + 1
        toks = np.zeros((b, window), np.int32)
        positions = np.full((b, window), -1, np.int32)
        draft_len = np.zeros(b, np.int32)
        rem = np.zeros(b, np.int32)
        for slot, live in enumerate(self._live):
            if live is None:
                continue
            toks[slot, 0] = s.last_token[slot]
            positions[slot, 0] = s.lengths[slot]
            rem[slot] = live.request.max_new_tokens \
                - len(live.generated)
            d = drafts[slot]
            if d is not None:
                toks[slot, 1:1 + d.size] = d
                positions[slot, 1:1 + d.size] = \
                    s.lengths[slot] + 1 + np.arange(d.size)
                draft_len[slot] = d.size
        table = None
        if self.paged:
            # window row i writes positions [lengths, lengths + d_i]
            # (last_token + its drafts) — always within the slot's
            # budget (drafts are clamped to remaining - 1) — plus up
            # to k_cont continuation positions (a frozen tail there
            # writes through the sentinel and drops; ensure_pages
            # never grows past the reservation).
            # Column-sliced like the chunk path: the verify gather
            # reads O(live extent)
            hi = 0
            for slot, live in enumerate(self._live):
                if live is not None:
                    upto = int(s.lengths[slot]) \
                        + int(draft_len[slot]) + 1 + k_cont
                    s.ensure_pages(slot, upto)
                    hi = max(hi, upto)
            cols = min(_bucket_pow2(-(-hi // s.pool.page_size)),
                       s.max_pages)
            table = s.device_table(cols)
        view_tokens = cols * s.pool.page_size if self.paged else 0
        if self.timeline is not None:
            t0 = time.monotonic()
            occ = s.n_active
            riders = [lv.request.id for lv in self._live
                      if lv is not None]
        self.phases.switch("verify.enqueue")
        # this round is fed from the mirrors (``s.rng`` pulls the keys
        # back if a chunk round moved them), and hands the next chunk
        # round every live row to send (``host_fed`` below)
        with self._tree_lock:
            s.cache, emit, accepted, cont, rng = _verify_chunk(
                self.model, self.params, s.cache, jnp.asarray(toks),
                jnp.asarray(positions), jnp.asarray(draft_len),
                jnp.asarray(s.temperature), jnp.asarray(s.top_k),
                jnp.asarray(s.rng), jnp.asarray(rem), table,
                window=window, n_steps=k_cont, eos_ids=self.eos_ids)
        self.phases.switch("verify.wait")
        cont = np.asarray(cont)
        self.steps += window + k_cont
        self.dispatches += 1
        self.spec_rounds += 1
        emit = np.asarray(emit)
        accepted = np.asarray(accepted)
        s.host_fed(rng)
        if self.timeline is not None:
            dur_ms = (time.monotonic() - t0) * 1e3  # closes at the sync
        self.phases.switch("verify.emit")
        landed = 0
        cont_fed = 0  # live (non-frozen) continuation positions

        for slot in range(b):
            live = self._live[slot]
            if live is None:
                continue
            req = live.request
            d = int(draft_len[slot])
            a = int(accepted[slot])
            if d:
                live.drafted += d
                live.accepted += a
                self.spec_drafted += d
                self.spec_accepted += a
                # rejected drafts were scored and thrown away — the
                # waste the utilization counter reports (the EOS cap
                # folds accepted-but-discarded drafts past a stop token
                # in here too)
                self.wasted_steps += d - a
                self._spec_ema[slot] = (
                    self.SPEC_EMA_DECAY * self._spec_ema[slot]
                    + (1.0 - self.SPEC_EMA_DECAY) * a / d)
            reason = None
            # emit[:a] are the accepted drafts, emit[a] the bonus
            # verdict after them — appended in order with the same
            # EOS/budget walk as the chunk path. The walk always
            # reaches the bonus: the device capped ``a`` at the first
            # stop token, and a draft is at most remaining - 1 long
            for jj in range(a + 1):
                tok = int(emit[slot, jj])
                live.generated.append(tok)
                if tok in self.eos_ids:
                    reason = "eos"
                elif len(live.generated) >= req.max_new_tokens:
                    reason = "length"
                if reason:
                    break
            landed += a + 1
            cont_consumed = 0
            if reason is None:
                # the continuation: this slot's chunk tokens, same
                # EOS/budget walk; frozen tails re-emit
                for jj in range(k_cont):
                    tok = int(cont[slot, jj])
                    live.generated.append(tok)
                    cont_consumed += 1
                    if tok in self.eos_ids:
                        reason = "eos"
                    elif len(live.generated) >= req.max_new_tokens:
                        reason = "length"
                    if reason:
                        break
                if reason is not None \
                        and cont_consumed < k_cont \
                        and not (cont[slot, cont_consumed:]
                                 == cont[slot, cont_consumed - 1]).all():
                    self.freeze_faults += 1
                    log.warning(
                        "frozen slot %d re-emitted a different token "
                        "in a verify round's continuation — "
                        "in-dispatch EOS consistency violation", slot)
            # a slot that finished inside the window froze for the
            # whole continuation; mid-continuation finishes freeze the
            # tail — either way those positions are padding
            self.frozen_steps += k_cont - cont_consumed
            landed += cont_consumed
            cont_fed += cont_consumed
            if reason is None:
                # fed last_token + a accepted drafts + the
                # continuation: the slot's position-exact span grew by
                # accepted + 1 + k_cont
                s.lengths[slot] += a + 1 + k_cont
                s.last_token[slot] = int(cont[slot, k_cont - 1])
                continue
            finished.append(Result(req.id, list(req.prompt),
                                   live.generated, reason,
                                   live.prefix_hit_tokens,
                                   live.prefill_tokens_saved,
                                   live.drafted, live.accepted,
                                   live.prefill_chunks))
            if self.prefix is not None and self.prefix_donate:
                # the donated sequence prompt+generated[:-1] spans
                # [0, len(prompt) + consumed - 1 + generated_prev)
                # positions, all of them fed accepted tokens; junk
                # from rejected drafts sits beyond that span, where
                # prefix consumers mask or overwrite it
                self._donate(live, slot)
            self._live[slot] = None
            s.evict(slot)
        self.phases.switch("verify.record")
        if self.timeline is not None:
            drafted_n = int(draft_len.sum())
            accepted_n = int(accepted.sum())
            tags = {"requests": riders, "drafted": drafted_n,
                    "accepted": accepted_n}
            if view_tokens:
                tags["view_tokens"] = view_tokens
            tags["cont_steps"] = k_cont
            view = view_tokens or self.model.cfg.max_seq_len
            # fed = one seed token per live slot + every draft + the
            # live continuation positions; landed is the same minus
            # the rejected drafts, so fed - landed == rejected and the
            # ledger's overshoot bucket stays 0
            fed = occ + drafted_n + cont_fed
            est = self.cost.verify(window, b, view)
            dec = self.cost.decode(k_cont, b, view)
            est = (est[0] + dec[0], est[1] + dec[1])
            self._record_dispatch(
                "verify", t0, dur_ms, occ, window, landed,
                ("verify", window, k_cont, view_tokens), tags=tags,
                work=(window + k_cont) * b, fed=fed,
                rejected=drafted_n - accepted_n,
                est=est)
        return finished

    def _donate(self, live: _Live, slot: int) -> None:
        """Give a finished slot's sequence back to the prefix store:
        its cache row is position-exact over prompt + generated[:-1]
        (the final token was sampled but never fed, so its K/V was
        never written). The multi-turn win — the next turn's prompt
        extends this sequence and seeds from it instead of
        re-prefilling the whole conversation. ``wants()`` gates the
        row-extraction dispatch: already-stored or won't-fit sequences
        cost zero device work.

        Paged engines donate by REFERENCE: the store pins the slot's
        own pages (refcount bump, zero device work — the
        ``read_slot_row`` extraction dispatch is gone), so there is
        nothing to gate."""
        seq = np.asarray(list(live.request.prompt)
                         + live.generated[:-1], np.int32)
        if seq.size == 0:
            return
        if self.paged:
            self.prefix.insert(
                seq, pages=self.slots.slot_pages(slot, int(seq.size)))
            return
        if not self.prefix.wants(seq, self._row_nbytes):
            return
        with self._tree_lock:
            row = _read_slot(self.slots.cache, jnp.int32(slot))
        self.prefix.insert(seq, row)

    def drain(self) -> list[Result]:
        """Finish every IN-FLIGHT slot (no new admissions) and return
        their results. Pending requests stay queued — the caller
        decides whether to reject them, hand them to another replica,
        or resume stepping. The graceful-shutdown hook: a front door
        stops feeding, calls drain(), and every request that already
        holds a slot completes instead of being dropped mid-decode."""
        with self._dispatch_lock:
            finished, self._held = self._held, []
        while self.slots.n_active or self._prefilling:
            # lock PER ITERATION: on a shared pool, co-tenant engines
            # keep stepping between this engine's drain rounds
            with self._dispatch_lock:
                self._check_tree()
                self._advance_prefills(finished)
                if self.slots.n_active:
                    finished.extend(self._decode_round())
        # the last rider's finish leaves nothing in flight (a round
        # without riders is dropped unread): the engine is settled
        return finished

    def live_progress(self, since: dict | None = None) -> dict:
        """{request_id: tokens generated so far} for every in-flight
        request — the streaming hook: the loop owner snapshots it after
        each ``step()`` and emits the delta per request. ``since``
        (request_id -> count already seen) returns only each request's
        TAIL, keeping a long generation's repeated snapshots O(new
        tokens) instead of O(length^2). Copies, so the caller can hold
        them across the next step."""
        out = {}
        for live in self._live:
            if live is not None:
                start = since.get(live.request.id, 0) if since else 0
                out[live.request.id] = live.generated[start:]
        return out

    def counters(self) -> dict:
        """Engine-level counters for observability surfaces (gateway
        /stats, MetricsStore): flat numeric dict. Prefix-store
        state rides along when the store is on."""
        out = {
            "prefills": self.prefills,
            "decode_steps": self.steps,
            "dispatches": self.dispatches,
            "wasted_steps": self.wasted_steps,
            # positions a finished slot spent frozen (re-emits, no KV
            # writes — padding) and the tail-walk consistency
            # violations (must stay 0)
            "frozen_steps": self.frozen_steps,
            "freeze_faults": self.freeze_faults,
            # writer dispatches that consumed the KV tree they were
            # given / left it alive (SlotCache.cache): kept must stay
            # 0 — each one is a whole-tree copy on the device
            "kv_tree_donated": self.slots.tree_donated,
            "kv_tree_kept": self.slots.tree_kept,
            # the decode round's resident state (SlotCache): chunk
            # rounds, those that sent no state, dirty rows sent, page
            # tables sent, and copies of the rng keys back to the host
            "decode_rounds": self.slots.rounds,
            "decode_rounds_clean": self.slots.rounds_clean,
            "decode_rows_patched": self.slots.rows_patched,
            "decode_table_sends": self.slots.table_sends,
            "decode_rng_pulls": self.slots.rng_pulls,
            # the overlap (``_decode_round``): rounds enqueued while an
            # older one's tokens had not been read, times the queue
            # was drained early because a caller needed the mirrors,
            # and rounds dropped unread (their riders all gone, or a
            # reset): run by the device, in no timeline record
            "decode_rounds_overlapped": self.rounds_overlapped,
            "decode_settles": self.settles,
            "decode_rounds_dropped": self.rounds_dropped,
            "spec_rounds": self.spec_rounds,
            "spec_drafted": self.spec_drafted,
            "spec_accepted": self.spec_accepted,
            "prefix_lookups": self.prefix_lookups,
            "prefix_hits": self.prefix_hits,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefill_tokens_saved": self.prefill_tokens_saved,
            "prefill_chunk_dispatches": self.prefill_chunk_dispatches,
            "prefill_chunked_requests": self.prefill_chunked,
            "handoffs_out": self.handoffs_out,
            "handoffs_in": self.handoffs_in,
            "migrations_out": self.migrations_out,
            "migrations_in": self.migrations_in,
            "migrations_local": self.migrations_local,
            "migrations_remote": self.migrations_remote,
            "migrate_pages_moved": self.migrate_pages_moved,
            "migrate_bytes_avoided": self.migrate_bytes_avoided,
            "migrate_bytes_wire": self.migrate_bytes_wire,
            "migrate_delta_in": self.migrate_delta_in,
            "migrate_freeze_resume_ms": round(
                self.migrate_freeze_resume_ms, 3),
        }
        if self.moe_counts is not None:
            routed, held, load_max, hit = (int(c) for c in self.moe_counts)
            out["moe_tokens_routed"] = routed
            out["moe_tokens_held"] = held
            out["moe_expert_load_max"] = load_max
            out["moe_experts_hit"] = hit
            out["moe_experts_held"] = self.model.cfg.routed.held[1]
        if self.model.cfg.latent is not None:
            out["latent_bytes_per_token"] = kv_page_nbytes(
                self.model.cfg, 1)
        if self._state_slots:
            cfg = self.model.cfg
            out["conv_layers"] = cfg.conv_layers
            out["attn_layers"] = cfg.attn_layers
            out["state_bytes_per_slot"] = cfg.state_values_per_slot \
                * jnp.dtype(cfg.dtype).itemsize
            out["kv_bytes_per_token"] = kv_page_nbytes(cfg, 1)
            out["state_resets"] = self.state_resets
            out["state_carried_chunks"] = self.state_carried_chunks
        if self.mesh is not None:
            # flat numeric twins of mesh_info() so MetricsStore and
            # the remote agent's counters wire carry the topology
            out["mesh_devices"] = int(self.mesh.size)
            out["mesh_kv_shards"] = int(self.kv_shards)
            out["mesh_param_bytes_per_chip"] = int(self._param_bytes_chip)
            out["mesh_kv_bytes_per_chip"] = int(self._kv_bytes_chip)
        if self.host_tier is not None:
            hs = self.host_tier.stats()
            out["kv_host_entries"] = hs["entries"]
            out["kv_host_bytes"] = hs["bytes"]
            out["kv_host_budget_bytes"] = hs["budget_bytes"]
            out["kv_host_tokens"] = hs["tokens"]
            out["kv_host_spills"] = hs["spills"]
            out["kv_host_page_ins"] = hs["page_ins"]
            out["kv_host_spill_bytes"] = hs["bytes_spilled"]
            out["kv_host_page_in_bytes"] = hs["bytes_paged_in"]
            out["kv_host_evictions"] = hs["evictions"]
        if self.prefix is not None:
            st = self.prefix.stats()
            out["prefix_entries"] = st["entries"]
            out["prefix_bytes"] = st["bytes"]
            out["prefix_budget_bytes"] = st["budget_bytes"]
            out["prefix_evictions"] = st["evictions"]
        if self.paged:
            # the kv_pages block: the fixed-shape-waste sensor. The
            # unpaged cache is ALWAYS batch * max_seq_len resident;
            # here bytes_resident tracks allocated pages only, and
            # tokens_resident / bytes_resident says how much of that
            # is real tokens (live slots + pinned prefix entries;
            # positions shared copy-on-write count once per holder, so
            # treat the ratio as an upper bound under heavy sharing)
            ps = self.slots.pool.stats()
            s = self.slots
            tokens = int(s.lengths[s.active].sum())
            if self.prefix is not None:
                tokens += self.prefix.stats()["tokens"]
            out["kv_pages_total"] = ps["total"]
            out["kv_pages_used"] = ps["used"]
            out["kv_pages_free"] = ps["free"]
            out["kv_pages_reserved"] = ps["reserved"]
            out["kv_cow_shared"] = ps["cow_shared"]
            out["kv_cow_forks"] = ps["forks"]
            out["kv_page_size"] = ps["page_size"]
            out["kv_bytes_resident"] = ps["bytes_resident"]
            out["kv_tokens_resident"] = tokens
        return out

    def reset(self) -> None:
        """Hard reset after a failed ``step()``: drop pending and
        in-flight bookkeeping and free every slot. Dropped requests
        never get a Result; the caller sheds them. ``slots.reset()``
        alone leaves the engine inconsistent (``_live`` ghosts would
        decode garbage and emit phantom results), so external callers
        use this.

        While the device tree is alive this is pure host work (the
        next admit overwrites device rows): a fault that
        ``serve/faults.py`` injects, or any exception raised on the
        host side of a dispatch, leaves it so. A donating program that
        fails at RUN time may take the tree with it; then a zeroed one
        is allocated here and what pointed into the old one goes — the
        prefix store's by-reference page entries (unpaged entries are
        rows of their own and stay, as does the host tier). On a
        shared pool the co-located engines learn of it by the pool's
        ``tree_epoch`` (``_check_tree``)."""
        with self._dispatch_lock:
            with self._pending_lock:
                self.pending.clear()
            self._live = [None] * self.slots.batch_size
            # rounds in flight are dropped unread, not waited for: the
            # device runs them against rows nothing reads again, and
            # whatever is enqueued next runs after them
            self.rounds_dropped += len(self._inflight)
            self._inflight.clear()
            self._held.clear()
            # mid-chunked-prefill slots drop with their requests;
            # their page reservations are returned by slots.reset()'s
            # evicts
            self._prefilling.clear()
            # dropped migrate requests never reach admission — their
            # pinned prefix entries must not stay refcounted forever
            for rid in list(self._migrate_pins):
                self._release_migrate_pin(rid)
            self.slots.reset()
            with self._tree_lock:
                if self.slots.renew_tree():
                    log.warning("the KV tree was consumed by a failed "
                                "dispatch: allocated a fresh one")
            if self.paged and self._tree_epoch \
                    != self.slots.pool.tree_epoch:
                self._tree_epoch = self.slots.pool.tree_epoch
                if self.prefix is not None:
                    self.prefix.clear()  # pages of a tree that is gone

    def _check_tree(self) -> None:
        """Refuse to step over page content that is gone: a co-located
        engine's failed dispatch consumed the shared pool's tree and
        its ``reset()`` allocated the next one. Raising hands this
        engine's sessions to the caller's recovery, whose ``reset()``
        catches this engine up."""
        if self.paged and self._tree_epoch != self.slots.pool.tree_epoch:
            raise RuntimeError(
                "the shared KV pool's tree was lost to a co-located "
                "engine's failed dispatch; this engine's pages hold "
                "nothing — reset() it")

    def run(self, requests: Iterable[Request] = ()) -> Iterator[Result]:
        """Submit ``requests`` and drive the loop until everything
        (including anything submitted earlier) finishes."""
        for r in requests:
            self.submit(r)
        while not self.done:
            yield from self.step()
