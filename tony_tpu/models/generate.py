"""Autoregressive generation: prefill + KV-cache decode under one jit.

No reference analog (TonY orchestrates training jobs; inference is out of
scope there) — this is framework surface the TPU rebuild adds so the
flagship transformer is usable end-to-end. TPU-first design:

- the KV cache is a static [b, max_seq_len, kv_heads, dh] buffer per layer
  (Attention._decode_attention; GQA caches only n_kv_heads), so prefill and
  every decode step compile once each — no dynamic shapes, no recompiles
- the decode loop is a single lax.scan over max_new_tokens: one XLA
  program, device-resident carry (cache + last token + rng), zero
  host<->device traffic until the final token block comes back
- sampling (greedy / temperature / top-k) is branchless inside the scan
- under a Mesh the cache shards like activations (batch on "data", heads
  on "tensor"), so tensor-parallel decode works unchanged via jit+sharding
- serve with ``scan_layers=False`` (the checkpoint-import default):
  scanned layers stack the caches [n_layers, ...] and every token then
  pays a full per-layer-cache dynamic-slice/update-slice round trip —
  measured 2.1x slower decode at d768x12L (docs/PERF.md). scan_layers
  is a TRAINING compile-time optimization, not a serving one.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp


def init_cache(model, params, batch_size: int, dtype=None) -> Any:
    """Allocate the per-layer KV cache sized by cfg.max_seq_len. Only
    the SHAPES come from ``model.init`` (under ``jax.eval_shape``: no
    float32 model is ever built beside the served one, which at 2 B a
    served parameter was twice the served weights again); every cache
    variable starts at zero, the buffers and the counters alike."""
    cfg = model.cfg
    tokens = jax.ShapeDtypeStruct((batch_size, cfg.max_seq_len), jnp.int32)
    shapes = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t, decode=True)["cache"],
        tokens)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def sample_logits(logits, rng, temperature, top_k: int, top_p: float = 1.0):
    """Greedy when temperature==0, else softmax sampling with optional
    top-k and top-p (nucleus) cuts. ``temperature`` is a traced operand —
    changing it per call (a serving loop sweeping 0.7, 0.8, ...) never
    recompiles; the greedy case rides the same program via a where.
    ``top_k`` and ``top_p`` are static: they change the compiled program
    (top_k sets the sort slice; top_p=1.0 skips the nucleus sorts entirely
    so the default decode hot path pays zero extra work), recompiling once
    per distinct value."""
    scaled = logits / jnp.maximum(jnp.asarray(temperature, logits.dtype), 1e-6)
    if top_k > 0:
        kth = jnp.sort(scaled, axis=-1)[..., -top_k][..., None]
        scaled = jnp.where(scaled < kth, -1e30, scaled)
    if top_p < 1.0:
        # nucleus cut: drop tokens outside the smallest probability mass
        # >= p. One descending sort + cumsum; a token survives if the mass
        # strictly before it is < p; the top token always survives (so
        # top_p<=0 degrades to top-1 sampling, not uniform noise).
        order = jnp.argsort(-scaled, axis=-1)
        sorted_probs = jax.nn.softmax(
            jnp.take_along_axis(scaled, order, axis=-1).astype(jnp.float32),
            axis=-1)
        mass_before = jnp.cumsum(sorted_probs, axis=-1) - sorted_probs
        keep_sorted = (mass_before < top_p).at[..., 0].set(True)
        # scatter the mask back to vocab order via the inverse permutation
        keep = jnp.take_along_axis(
            keep_sorted, jnp.argsort(order, axis=-1), axis=-1)
        scaled = jnp.where(keep, scaled, -1e30)
    sampled = jax.random.categorical(rng, scaled, axis=-1)
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(jnp.asarray(temperature) == 0.0, greedy, sampled)


def _penalize_repeats(logits, seen, penalty):
    """CTRL-style repetition penalty: a token already in the sequence has
    its logit divided by ``penalty`` when positive, multiplied when
    negative (both push probability down for penalty > 1). Traced operand:
    penalty=1.0 rides the same compiled program as a no-op."""
    penalty = jnp.asarray(penalty, logits.dtype)
    penalized = jnp.where(logits > 0, logits / penalty, logits * penalty)
    return jnp.where(seen, penalized, logits)


def normalize_eos_ids(eos_id) -> tuple:
    """Normalize eos_id to a tuple of valid ids: int (-1/None = none) or
    a tuple/list of ids (HF configs ship lists — Llama-3 instruct:
    [128001, 128009]); negatives are dropped. Runs OUTSIDE jit (the >= 0
    filter inspects values), in the public generate/beam_search wrappers —
    both decoders see identical semantics for every input shape."""
    if isinstance(eos_id, (list, tuple)):
        return tuple(int(e) for e in eos_id if int(e) >= 0)
    return (int(eos_id),) if eos_id is not None and int(eos_id) >= 0 else ()


def _is_eos(tok, eos_ids):
    """True where ``tok`` equals ANY of the eos ids (stop on any; a
    generation must not run past end-of-turn just because it isn't the
    first listed id)."""
    if not eos_ids:
        return jnp.zeros(tok.shape, bool)
    hit = tok == eos_ids[0]
    for e in eos_ids[1:]:
        hit = hit | (tok == e)
    return hit


def single_decode_step(model, params, cache, tok, positions=None,
                       page_table=None, moe_stats: bool = False):
    """ONE token step through the KV cache: feed ``tok`` [b] at the
    current position(s), return ``(new_cache, last_logits [b, V])``;
    with ``moe_stats`` a third value, the step's routed-expert counts
    summed over its routed layers (``RoutedMLP``: [4] int32).

    The shared decode body of ``_generate``'s scan and the serving
    loop's resident step (serve/engine.py): the scalar-index path
    (``positions=None``, all rows in lockstep) and the per-slot path
    (``positions`` [b], every row at its own cache position — negative
    marks an empty slot) run the same model.apply; only the position
    bookkeeping differs (Attention._decode_attention). ``page_table``
    [b, max_pages] switches the per-slot path to the paged cache
    layout (serve/slots.PagePool — ``cache`` holds page pools instead
    of per-slot rows; same attention reduction over the gathered
    view)."""
    kwargs = {} if positions is None else {"positions": positions}
    if page_table is not None:
        kwargs["page_table"] = page_table
    logits, vars_ = model.apply(
        {"params": params, "cache": cache}, tok[:, None], decode=True,
        mutable=["cache", "moe_stats"] if moe_stats else ["cache"], **kwargs)
    if moe_stats:
        counts = sum(jax.tree_util.tree_leaves(vars_["moe_stats"]))
        return vars_["cache"], logits[:, -1], counts
    return vars_["cache"], logits[:, -1]


def multi_decode_step(model, params, cache, toks, positions,
                      page_table=None):
    """A ``k``-token per-slot window through the KV cache in ONE apply:
    feed ``toks`` [b, k] with every row at its own positions [b, k],
    return ``(new_cache, logits [b, k, V])`` — the logits AFTER each
    window token, i.e. logits[:, j] scores the token following
    ``toks[:, j]``.

    The speculative-decoding verify body (serve/engine._verify_chunk):
    ``single_decode_step`` scores one position per dispatch; this
    scores the whole draft window in one compute-dense batched pass —
    the Leviathan et al. trade of sequential memory-bound steps for one
    parallel verification. Row i's tokens write K/V at positions
    ``positions[i, :]`` and attend causally by position (intra-window
    included); entries with ``positions[i, j] < 0`` are padding whose
    cache writes are dropped and whose logits are garbage
    (Attention._decode_attention's [b, k] mode). ``page_table``
    [b, max_pages] switches to the paged cache layout (the paged
    serving engine's verify window AND its prefill: a prefill is just
    one big per-slot window writing straight into the slot's pages)."""
    kwargs = {} if page_table is None else {"page_table": page_table}
    logits, vars_ = model.apply({"params": params, "cache": cache},
                                toks, decode=True, mutable=["cache"],
                                positions=positions, **kwargs)
    return vars_["cache"], logits


def generate(model, params, prompt, *, max_new_tokens: int,
             temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
             rng: jax.Array | None = None, eos_id=-1,
             repetition_penalty: float = 1.0):
    """Generate max_new_tokens continuations of ``prompt`` [b, Lp].

    Returns [b, max_new_tokens] int32. ``eos_id`` is an int (-1 = no stop
    token) or a list/tuple of ids (stop on any; frozen rows re-emit the
    first) — normalized here, outside jit, so invalid ids never reach the
    compiled program. Tokens after an eos are frozen (computed but
    masked — fixed trip count keeps the scan static; early-exit would
    force a while_loop with dynamic shapes downstream).
    ``repetition_penalty`` > 1 discourages tokens already in the prompt or
    generated so far (CTRL-style; traced — sweeping values never
    recompiles).
    """
    return _generate(model, params, prompt, max_new_tokens=max_new_tokens,
                     temperature=temperature, top_k=top_k, top_p=top_p,
                     rng=rng, eos_ids=normalize_eos_ids(eos_id),
                     repetition_penalty=repetition_penalty)


@functools.partial(jax.jit, static_argnames=("model", "max_new_tokens",
                                             "top_k", "top_p", "eos_ids"))
def _generate(model, params, prompt, *, max_new_tokens: int,
              temperature: float, top_k: int, top_p: float,
              rng: jax.Array | None, eos_ids: tuple,
              repetition_penalty: float):
    freeze = eos_ids[0] if eos_ids else -1
    if rng is None:
        rng = jax.random.PRNGKey(0)
    b, prompt_len = prompt.shape
    if prompt_len + max_new_tokens > model.cfg.max_seq_len:
        raise ValueError(
            f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds cfg.max_seq_len ({model.cfg.max_seq_len}): the KV "
            "cache would overflow")
    vocab = model.cfg.vocab_size
    cache = init_cache(model, params, b)
    seen = jnp.zeros((b, vocab), bool)
    seen = seen.at[jnp.arange(b)[:, None], prompt].set(True)

    # prefill: one pass over the whole prompt fills every layer's cache
    logits, vars_ = model.apply({"params": params, "cache": cache}, prompt,
                                decode=True, mutable=["cache"])
    rng, sub = jax.random.split(rng)
    last = _penalize_repeats(logits[:, -1], seen, repetition_penalty)
    next_tok = sample_logits(last, sub, temperature, top_k, top_p)
    seen = seen.at[jnp.arange(b), next_tok].set(True)
    done = _is_eos(next_tok, eos_ids)

    def step(carry, _):
        cache, tok, rng, done, seen = carry
        cache, logits_last = single_decode_step(model, params, cache, tok)
        rng, sub = jax.random.split(rng)
        last = _penalize_repeats(logits_last, seen, repetition_penalty)
        nxt = sample_logits(last, sub, temperature, top_k, top_p)
        nxt = jnp.where(done, freeze, nxt)
        seen = seen.at[jnp.arange(b), nxt].set(True)
        done = done | _is_eos(nxt, eos_ids)
        return (cache, nxt, rng, done, seen), nxt

    carry = (vars_["cache"], next_tok, rng, done, seen)
    if max_new_tokens > 1:
        _, rest = jax.lax.scan(step, carry, None, length=max_new_tokens - 1)
        rest = jnp.moveaxis(rest, 0, 1)  # [steps, b] -> [b, steps]
        return jnp.concatenate([next_tok[:, None], rest], axis=1)
    return next_tok[:, None]


def beam_search(model, params, prompt, *, max_new_tokens: int,
                num_beams: int = 4, eos_id=-1,
                length_penalty: float = 1.0):
    """Beam-search decode: returns the highest-scoring continuation
    [b, max_new_tokens] (ties to the KV cache exactly like generate()).

    One jitted program (static num_beams/max_new_tokens): beams live as a
    widened batch [b*k] so the per-layer cache shards/updates like any
    batch; each step does one fused top-k over [k*V] joint candidates and
    reorders the cache with a batch-dim gather. ``eos_id`` is an int
    (-1 = none) or a list/tuple of ids — normalized here, outside jit, so
    lists never hit the static-arg hasher; beams finishing on any listed
    id are frozen: they re-emit the first eos at zero added score. The
    winner per batch row maximizes score / (generated_len **
    length_penalty), HF-style length normalization.
    """
    return _beam_search(model, params, prompt,
                        max_new_tokens=max_new_tokens, num_beams=num_beams,
                        eos_ids=normalize_eos_ids(eos_id),
                        length_penalty=length_penalty)


@functools.partial(jax.jit, static_argnames=("model", "max_new_tokens",
                                             "num_beams", "eos_ids"))
def _beam_search(model, params, prompt, *, max_new_tokens: int,
                 num_beams: int, eos_ids: tuple, length_penalty: float):
    freeze = eos_ids[0] if eos_ids else 0
    b, prompt_len = prompt.shape
    k = num_beams
    if prompt_len + max_new_tokens > model.cfg.max_seq_len:
        raise ValueError(
            f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds cfg.max_seq_len ({model.cfg.max_seq_len})")
    vocab = model.cfg.vocab_size
    neg = jnp.float32(-1e30)

    def _cache_batch_axis(path, leaf):
        """Batch axis of a cache leaf, or None for non-batched leaves.

        The KV buffers are [..., b, max_len, kvh, dh] — batch is always
        4th-from-last; scan_layers models prepend an n_layers axis, so
        keying on axis 0 (or on a dim happening to equal b) would widen or
        gather the LAYERS axis and silently corrupt the cache. Index
        counters (cache_index/pos_index) carry no batch dim."""
        name = str(path[-1].key if hasattr(path[-1], "key") else path[-1])
        if name in ("cached_key", "cached_value"):
            return leaf.ndim - 4
        return None

    def widen(path, c):
        ax = _cache_batch_axis(path, c)
        return c if ax is None else jnp.repeat(c, k, axis=ax)

    # prefill ONCE at batch b (all beams share the prompt), then widen the
    # cache rows to b*k — prefill dominates latency for long prompts and
    # repeating it per beam would compute k identical copies
    cache = init_cache(model, params, b)
    logits, vars_ = model.apply({"params": params, "cache": cache},
                                prompt, decode=True, mutable=["cache"])
    cache = jax.tree_util.tree_map_with_path(widen, vars_["cache"])
    logp0 = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)
    scores, first_tok = jax.lax.top_k(logp0, k)  # [b, k]
    finished = _is_eos(first_tok, eos_ids)
    out = jnp.full((b, k, max_new_tokens), freeze, jnp.int32)
    out = out.at[:, :, 0].set(first_tok)
    lengths = jnp.ones((b, k), jnp.int32)

    def step(carry, t):
        cache, tok, scores, finished, out, lengths = carry
        logits, vars_ = model.apply(
            {"params": params, "cache": cache},
            tok.reshape(b * k)[:, None], decode=True, mutable=["cache"])
        cache = vars_["cache"]
        logp = jax.nn.log_softmax(
            logits[:, -1].astype(jnp.float32), axis=-1).reshape(b, k, vocab)
        if eos_ids:
            # frozen beams: only the freeze eos continues, at no added score
            eos_only = jnp.full((vocab,), neg).at[freeze].set(0.0)
            logp = jnp.where(finished[:, :, None], eos_only[None, None],
                             logp)
        cand = scores[:, :, None] + logp  # [b, k, V]
        new_scores, flat = jax.lax.top_k(cand.reshape(b, k * vocab), k)
        beam_idx = flat // vocab  # [b, k]
        new_tok = flat % vocab
        # reorder beam-major state by the winning parent beams
        rows = (jnp.arange(b)[:, None] * k + beam_idx).reshape(-1)  # [b*k]
        cache = jax.tree_util.tree_map_with_path(
            lambda p, c: c if _cache_batch_axis(p, c) is None
            else jnp.take(c, rows, axis=_cache_batch_axis(p, c)), cache)
        out = jnp.take_along_axis(out, beam_idx[:, :, None], axis=1)
        lengths = jnp.take_along_axis(lengths, beam_idx, axis=1)
        was_finished = jnp.take_along_axis(finished, beam_idx, axis=1)
        out = out.at[:, :, t].set(jnp.where(was_finished, freeze, new_tok))
        lengths = jnp.where(was_finished, lengths, lengths + 1)
        finished = was_finished | _is_eos(new_tok, eos_ids)
        return (cache, new_tok, new_scores, finished, out, lengths), None

    carry = (cache, first_tok, scores, finished, out, lengths)
    if max_new_tokens > 1:
        carry, _ = jax.lax.scan(step, carry, jnp.arange(1, max_new_tokens))
    _, _, scores, finished, out, lengths = carry
    norm = scores / (lengths.astype(jnp.float32) ** length_penalty)
    best = jnp.argmax(norm, axis=1)  # [b]
    return jnp.take_along_axis(out, best[:, None, None], axis=1)[:, 0]
