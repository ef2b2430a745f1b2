"""SlotCache: batch_size resident KV-cache slots + per-slot decode state.

The device side is ONE fixed-shape cache pytree (``init_cache`` at
``batch_size``) that the resident decode step updates in place, and
beside it the decode round's small carry (``SlotCache.state``: last
token, position, budget, sampling knobs, rng key per slot), which the
step also takes and returns; the host side is a handful of small
per-slot arrays (length, last token, sampling knobs, rng) the scheduler
reads and writes between steps, and from which it sends the device only
the rows it changed itself. Admit copies a freshly prefilled single-row
cache into a free slot with one jitted dynamic-update-slice per leaf
(slot index traced — one compile total); evict is pure host
bookkeeping (the row's stale K/V is masked by the slot's length going
inactive and fully overwritten by the next admit, so no device work is
ever spent clearing it).
"""

from __future__ import annotations

import functools
import threading
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from tony_tpu.models.generate import init_cache
from tony_tpu.models.transformer import take_pages


def cache_batch_axis(path, leaf) -> int | None:
    """Batch (slot) axis of a cache leaf, or None for non-batched leaves.

    KV buffers are [..., b, max_len, kvh, dh] — batch is 4th-from-last;
    their quant scales are [..., b, max_len, kvh] — 3rd-from-last, as
    are a latent attention layer's two leaves [..., b, max_len, width].
    scan_layers models prepend an n_layers axis, which this arithmetic
    skips (keying on axis 0 would slice the LAYERS axis). Index counters
    (cache_index/pos_index) carry no batch dim: per-slot decode neither
    reads nor advances them (positions live per slot, in
    ``SlotCache``'s mirrors and resident state)."""
    name = str(path[-1].key if hasattr(path[-1], "key") else path[-1])
    if name in ("cached_key", "cached_value"):
        return leaf.ndim - 4
    if name in ("cached_key_scale", "cached_value_scale", "cached_latent",
                "cached_rope_key") or name in SLOT_RESIDENT:
        return leaf.ndim - 3
    return None


# Cache leaves that are a row a SLOT and have no ``max_len`` axis: a
# short convolution's ``conv_state`` [.., b, K-1, d] (``ShortConv``).
# They are a function of the sequence, not of a cached position, so the
# page fabric leaves them where they are: ``paged_cache`` sizes them by
# the slot count, a decode round takes and returns them whole
# (``paged_view`` / ``paged_write_back``), a one-row window takes the
# row of its slot (``slot_rows`` / ``store_slot_rows``), and what moves
# PAGES (copy, gather, scatter, the byte counts) passes them by.
SLOT_RESIDENT = ("conv_state",)


def slot_resident(path) -> bool:
    """Whether a cache leaf is slot-resident (above): the ONE place the
    functions below learn it from."""
    return str(path[-1].key if hasattr(path[-1], "key")
               else path[-1]) in SLOT_RESIDENT


def page_axis(path, leaf) -> int | None:
    """The page axis of a POOLED leaf of a paged tree (where the batch
    axis was); None for a slot-resident leaf and for the counters."""
    return None if slot_resident(path) else cache_batch_axis(path, leaf)


def slot_rows(cache: Any, slot) -> Any:
    """The tree a ONE-row window (a paged prefill) runs against: every
    slot-resident leaf cut to ``slot``'s row, the pools as they are
    (the window reaches those through its page table). Traceable."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jax.lax.dynamic_slice_in_dim(
            leaf, jnp.asarray(slot, jnp.int32), 1,
            axis=cache_batch_axis(path, leaf))
        if slot_resident(path) else leaf, cache)


def store_slot_rows(cache: Any, out: Any, slot) -> Any:
    """Inverse of ``slot_rows`` after the window ran: ``out``'s pools
    are the successors of ``cache``'s, its slot-resident rows go back
    into ``slot`` of ``cache``'s."""
    def put(path, leaf, oleaf):
        if not slot_resident(path):
            return oleaf
        return jax.lax.dynamic_update_slice_in_dim(
            leaf, oleaf.astype(leaf.dtype), jnp.asarray(slot, jnp.int32),
            axis=cache_batch_axis(path, leaf))

    return jax.tree_util.tree_map_with_path(put, cache, out)


def write_slot_row(cache: Any, row: Any, slot) -> Any:
    """Copy a batch-1 cache ``row`` into slot ``slot`` of ``cache``
    (pure tree transform, traceable — the ONE place that knows how to
    place a row; the engine's fused prefill-admit and the standalone
    jitted copy below both call it)."""
    def write(path, leaf, rleaf):
        ax = cache_batch_axis(path, leaf)
        if ax is None:
            return leaf  # shared counters: per-slot mode ignores them
        start = [jnp.int32(0)] * leaf.ndim
        start[ax] = jnp.asarray(slot, jnp.int32)
        return jax.lax.dynamic_update_slice(leaf, rleaf.astype(leaf.dtype),
                                            tuple(start))

    return jax.tree_util.tree_map_with_path(write, cache, row)


@functools.partial(jax.jit, donate_argnames=("cache",))
def _write_slot(cache: Any, row: Any, slot) -> Any:
    """Jitted ``write_slot_row``; ``slot`` is traced — every admit
    reuses one compiled program. ``cache`` is DONATED (the writers'
    rule, see ``SlotCache.cache``): the row lands in place."""
    return write_slot_row(cache, row, slot)


def read_slot_row(cache: Any, slot) -> Any:
    """Extract slot ``slot`` of ``cache`` as a batch-1 row — the exact
    inverse of ``write_slot_row`` (write then read round-trips every
    batched leaf). Non-batched leaves (the shared counters per-slot
    decode neither reads nor advances) pass through unchanged; a
    consumer seeding a prefill from the row re-seeds them anyway. The
    prefix store (serve/prefix.py) uses this to donate a finished
    slot's sequence back to the cache."""
    def read(path, leaf):
        ax = cache_batch_axis(path, leaf)
        if ax is None:
            return leaf
        return jax.lax.dynamic_slice_in_dim(
            leaf, jnp.asarray(slot, jnp.int32), 1, axis=ax)

    return jax.tree_util.tree_map_with_path(read, cache)


@jax.jit
def _read_slot(cache: Any, slot) -> Any:
    """Jitted ``read_slot_row``; ``slot`` is traced — every donation
    reuses one compiled program."""
    return read_slot_row(cache, slot)


# --------------------------------------------------------- paged cache


def _alloc_sharded(structs: Any, mesh) -> Any:
    """Allocate a cache pytree of zeros DIRECTLY under its kv-head
    shardings (``parallel.sharding.kv_cache_shardings``): one jitted
    nullary program with out_shardings, so each chip only ever
    materializes its own shard. The naive order — allocate dense,
    then ``device_put`` to the shardings — holds the WHOLE pool on
    one chip transiently at boot, which OOMs exactly the
    bigger-than-one-chip configurations the mesh exists to serve."""
    from tony_tpu.parallel.sharding import kv_cache_shardings

    shardings = kv_cache_shardings(mesh, structs)
    make = jax.jit(
        lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             structs),
        out_shardings=shardings)
    return make()


def paged_cache(model, params, n_pages: int, page_size: int,
                mesh=None, slots: int = 1) -> Any:
    """A PAGED cache pytree: every batched leaf of a batch-1
    ``init_cache`` tree — KV buffers ``[.., 1, max_len, kvh, dh]``,
    int8 scales ``[.., 1, max_len, kvh]`` — becomes a page POOL with
    ``(batch, max_len)`` replaced by ``(n_pages, page_size)``; shared
    counters pass through (per-slot decode neither reads nor advances
    them), and a slot-resident leaf (``slot_resident``) gets ``slots``
    rows. The tree STRUCTURE is unchanged, so ``model.apply`` with a
    ``page_table`` consumes it directly (flax returns the supplied
    value — the declared init shape only matters on the init pass),
    and scan_layers' stacked ``[n_layers, ...]`` leading axis is
    preserved by the same from-the-right axis arithmetic
    ``cache_batch_axis`` uses.

    Shapes come from ``eval_shape``: no batch-1 row is materialized on
    the way. ``mesh`` (sharded serving, ISSUE-14): the pool allocates
    DIRECTLY under its kv-head shardings, so one chip never holds more
    than its shard (``_alloc_sharded``)."""
    def remap(path, leaf):
        ax = cache_batch_axis(path, leaf)
        if ax is None:
            shape = leaf.shape
        elif slot_resident(path):
            shape = leaf.shape[:ax] + (slots,) + leaf.shape[ax + 1:]
        else:
            shape = leaf.shape[:ax] + (n_pages, page_size) \
                + leaf.shape[ax + 2:]
        return jax.ShapeDtypeStruct(shape, leaf.dtype)

    structs = jax.tree_util.tree_map_with_path(
        remap, jax.eval_shape(lambda p: init_cache(model, p, 1), params))
    if mesh is not None:
        return _alloc_sharded(structs, mesh)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), structs)


def default_page_size(cfg) -> int:
    """The auto ``kv_page_size`` for a model config: 64 tokens, scaled
    down (floor 16, never past max_seq_len) for short-context models —
    the ONE place this rule lives; ``Server`` and the CLI resolvers
    both call it so their page geometries can never drift apart."""
    ps = min(64, max(16, cfg.max_seq_len // 4))
    return max(1, min(ps, cfg.max_seq_len))


def kv_page_nbytes(cfg, page_size: int) -> int:
    """Analytic bytes of ONE KV page for a model config (agrees with
    ``page_nbytes`` of the built pool): attention layers x page_size x what the
    config says a layer caches a token (``cache_values_per_token``: K +
    V of every kv head, or a latent layer's one vector) at the cache
    dtype, plus the int8 mode's fp32 scales. Lets the CLIs size
    ``--kv-pages`` from HBM before any device allocation exists."""
    item = 1 if cfg.kv_cache_quant else jnp.dtype(cfg.dtype).itemsize
    per = page_size * cfg.cache_values_per_token * item
    if cfg.kv_cache_quant:
        per += 2 * page_size * cfg.kv_heads * 4
    return cfg.attn_layers * per


def tree_consumed(cache: Any) -> bool:
    """Whether a donating program took ``cache``'s buffers: donation
    deletes every leaf of the tree at once, so the first one tells."""
    return jax.tree_util.tree_leaves(cache)[0].is_deleted()


def page_nbytes(cache: Any) -> int:
    """Bytes ONE page occupies across a paged cache tree's pool leaves
    (all layers; scales included) — the unit the allocator's stats and
    the prefix store's paged byte budget account in."""
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        ax = page_axis(path, leaf)
        if ax is not None:
            nbytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            total += nbytes // leaf.shape[ax]
    return total


def copy_page(cache: Any, src, dst) -> Any:
    """Copy pool page ``src`` onto page ``dst`` in every paged leaf —
    the copy-on-write FORK: a slot aliasing a shared page that it must
    write into (a prefix boundary falling mid-page) gets its own copy
    of the whole page and writes there; the shared original stays
    byte-identical for every other holder. Pure tree transform,
    traceable (``_copy_page`` jits it with traced indices — one
    compile ever)."""
    def cp(path, leaf):
        ax = page_axis(path, leaf)
        if ax is None:
            return leaf
        row = jax.lax.dynamic_index_in_dim(leaf, jnp.asarray(src, jnp.int32),
                                           axis=ax, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(
            leaf, row, jnp.asarray(dst, jnp.int32), axis=ax)

    return jax.tree_util.tree_map_with_path(cp, cache)


@functools.partial(jax.jit, donate_argnames=("cache",))
def _copy_page(cache: Any, src, dst) -> Any:
    """Jitted ``copy_page``; ``cache`` is DONATED — one page moves,
    the pool stays where it is."""
    return copy_page(cache, src, dst)


def gather_pages(cache: Any, idx) -> Any:
    """Stack the CONTENT of pool pages ``idx`` ([n] int32) into a
    standalone pytree: every paged leaf ``[.., n_pages, ps, ..]``
    becomes ``[.., n, ps, ..]`` — the portable form of a page list,
    shared by the role-split handoff (device->device between two
    replicas' pools, or over the agent wire) and the host-RAM tier
    (device->host spill). Out-of-range entries clamp, by the gather's
    own mode (``take_pages``: padding rows carry junk the consumer
    drops); non-paged leaves (the shared counters) pass through so the
    tree STRUCTURE round-trips."""
    def g(path, leaf):
        ax = page_axis(path, leaf)
        return leaf if ax is None else take_pages(leaf, idx, ax)

    return jax.tree_util.tree_map_with_path(g, cache)


@jax.jit
def _gather_pages(cache: Any, idx) -> Any:
    """Jitted ``gather_pages``; ``idx`` is traced, so one program
    compiles per (pow2-bucketed) page count."""
    return gather_pages(cache, idx)


def scatter_pages(cache: Any, payload: Any, idx) -> Any:
    """Inverse of ``gather_pages``: write ``payload``'s page rows onto
    pool pages ``idx`` of ``cache``. Sentinel entries (``>= n_pages``)
    DROP — the bucket-padding discipline every paged scatter here
    follows — so a pow2-padded payload lands exactly its real pages.
    The round trip gather -> (optional host hop) -> scatter is
    bitwise: both directions are pure copies, no arithmetic touches
    the values (tests/test_tier.py pins it across dtype x scan_layers
    x int8-KV scale leaves)."""
    def sc(path, leaf, pleaf):
        ax = page_axis(path, leaf)
        if ax is None:
            return leaf  # dest counters win; payload's ride-alongs drop
        # indexed on the page axis where it lies (scan_layers' stacked
        # leaves carry a layers axis before it): moving the axis to the
        # front and back costs a transposed copy of the whole leaf
        return leaf.at[(slice(None),) * ax + (idx,)].set(
            jnp.asarray(pleaf).astype(leaf.dtype), mode="drop")

    return jax.tree_util.tree_map_with_path(sc, cache, payload)


@functools.partial(jax.jit, donate_argnames=("cache",))
def _scatter_pages(cache: Any, payload: Any, idx) -> Any:
    """Jitted ``scatter_pages``; ``idx`` traced — one program per
    page-count bucket. ``cache`` is DONATED (the payload is not: a
    handoff doc or a host-tier row outlives the scatter)."""
    return scatter_pages(cache, payload, idx)


def paged_view(cache: Any, table, max_len: int) -> Any:
    """Gather each slot's pages into an UNPAGED-looking cache: every
    pool leaf ``[.., n_pages, ps, ..]`` becomes ``[.., b, span, ..]``
    via one gather through ``table`` [b, cols] (sentinel entries clamp
    to a junk page the visibility mask hides; the clamp is the gather's
    own mode, ``take_pages``, so the view is written in ONE pass: no
    in-bounds mask, no select over it). The decode chunk runs
    its whole lax.scan against this view — the per-micro-step compute
    is then literally the unpaged program (bitwise parity for free: a
    masked column contributes softmax weight exactly 0.0, so a view
    holding fewer junk columns than the full buffer sums to the exact
    same attention output), and the gather cost is paid once per
    DISPATCH instead of once per micro-step (``paged_write_back``
    returns the chunk's new K/V to the pool afterwards).

    The engine passes a COLUMN-SLICED table covering a power-of-two
    bucket of the live slots' extent, so the view — and with it every
    micro-step's attention read — is O(actual tokens), not
    O(max_seq_len): the fixed-shape path's biggest per-step waste
    (scanning a mostly-empty [max_seq_len] buffer) disappears along
    with the residency waste."""
    def to_view(path, leaf):
        ax = page_axis(path, leaf)
        if ax is None:  # counters; slot-resident rows as they are
            return leaf
        v = take_pages(leaf, table, ax)  # [.., b, cols, ps, ..]
        shape = v.shape[:ax] + (v.shape[ax],
                                v.shape[ax + 1] * v.shape[ax + 2]) \
            + v.shape[ax + 3:]
        v = v.reshape(shape)
        # span = cols * ps may exceed max_len (page-size rounding);
        # the unpaged per-slot branch sizes its drop-redirect index by
        # max_len, so never exceed it
        limit = min(max_len, shape[ax + 1])
        return jax.lax.slice_in_dim(v, 0, limit, axis=ax + 1)

    return jax.tree_util.tree_map_with_path(to_view, cache)


def paged_write_back(pool: Any, view: Any, table, start, n_steps: int,
                     max_len: int) -> Any:
    """Return a decode chunk's writes from the gathered ``view`` to the
    page ``pool``: slot i's micro-step j wrote position ``start[i] + j``
    (start < 0 = empty slot), so only those ``b x n_steps`` tokens move
    — everything else in the view is an unmodified copy the pool
    already holds. Out-of-range positions and sentinel table entries
    drop, exactly like the direct paged scatter. A slot-resident leaf
    is stored whole, by slot: the view's rows are the slots' own."""
    b = table.shape[0]
    pos_w = jnp.where(start[:, None] >= 0,
                      start[:, None]
                      + jnp.arange(n_steps, dtype=jnp.int32)[None, :], -1)
    rows = jnp.arange(b)[:, None]

    def wb(path, pleaf, vleaf):
        if slot_resident(path):
            return vleaf  # the round's rows ARE the slots' rows
        ax = cache_batch_axis(path, pleaf)
        if ax is None:
            return pleaf
        n_pg, ps = pleaf.shape[ax], pleaf.shape[ax + 1]
        # the view (and a column-sliced table) may be shorter than
        # max_len; positions past either bound must drop, never clamp
        limit = min(max_len, table.shape[1] * ps, vleaf.shape[ax + 1])
        valid = (pos_w >= 0) & (pos_w < limit)
        safe = jnp.where(valid, pos_w, 0)
        page = jnp.take_along_axis(table, safe // ps, axis=1)
        page = jnp.where(valid, page, n_pg)  # drop via OOB
        off = safe % ps
        lead = (slice(None),) * ax           # scan_layers' layers axis
        vals = vleaf[lead + (rows, safe)]    # [.., b, n_steps, ..rest]
        return pleaf.at[lead + (page, off)].set(vals, mode="drop")

    return jax.tree_util.tree_map_with_path(wb, pool, view)


# ------------------------------------------------- resident decode state

# columns of the packed per-slot decode state, ``[slots, STATE_COLS]``
# int32 (``SlotCache.state``); a patch carries one mask column before
# them. Temperature and the two rng words travel bit-cast.
_TOK, _POS, _REM, _TOPK, _TEMP, _RNG = 0, 1, 2, 3, 4, 5
STATE_COLS = 7


def unpack_state(state):
    """``(tok, positions, rem, top_ks, temps, rngs)`` of a packed
    decode state, in the dtypes the decode body computes in
    (traceable)."""
    return (state[:, _TOK], state[:, _POS], state[:, _REM],
            state[:, _TOPK],
            jax.lax.bitcast_convert_type(state[:, _TEMP], jnp.float32),
            jax.lax.bitcast_convert_type(state[:, _RNG:], jnp.uint32))


def pack_state(state, tok, positions, rem, rngs):
    """The successor of ``state`` after a chunk: the carry the scan
    hands out laid over the columns it owns (the sampling knobs are the
    host's alone and pass through). Traceable."""
    return jnp.concatenate(
        [jnp.stack([tok, positions, rem], axis=1),
         state[:, _TOPK:_RNG],
         jax.lax.bitcast_convert_type(rngs, jnp.int32)], axis=1)


def apply_patch(state, patch):
    """Lay the host's patch (``[slots, 1 + STATE_COLS]``: a mask
    column, then the row) over the resident state: one ``where``."""
    return jnp.where(patch[:, :1] != 0, patch[:, 1:], state)


class PagePool:
    """Block-granular KV-cache pages + a host-side free-list allocator.

    The device side is ONE paged cache pytree (``paged_cache``): KV
    leaves are ``[n_pages, page_size, kvh, dh]`` pools shared by every
    slot AND the prefix store — built here, then handed off to the
    owning ``SlotCache`` (which keeps the LIVE tree across dispatches;
    ``self.cache`` is None afterwards so the t=0 allocation is not
    pinned twice). The host side owns which page belongs to
    whom: a free list, a per-page refcount (a page may be held by one
    slot table and any number of prefix-store entries — copy-on-write
    sharing), and a RESERVATION ledger.

    Reservations are the no-preemption admission discipline: a slot
    reserves its worst-case page count (prompt + clamped max_new,
    minus aliased prefix pages) up front and allocates lazily from
    that reservation as decode advances, so a mid-stream allocation
    can never fail — ``free >= reserved`` is the invariant (allocation
    from a reservation consumes one unit of each; unref only grows
    free). Admission blocks (stays pending) when a reservation cannot
    be granted, after the engine has squeezed the prefix store; it
    never kills an in-flight request.

    With ``shared=True`` the pool is a FLEET resource lent to several
    co-located engines at once (live session migration, ISSUE-18): the
    pool RETAINS ownership of the device tree — every attached
    ``SlotCache`` delegates its ``cache`` attribute here, so one
    engine's dispatch reassignment is immediately visible to the
    others, and moving a session between two attached engines is a
    pure page-table/refcount swap with zero KV bytes copied.

    Concurrency is TWO locks at two granularities (ISSUE-19; the old
    discipline serialized every co-located engine's whole step through
    one pool-wide writer lock):

    - every allocator mutation (free list, refcounts, the reservation
      ledger) is atomic under the internal fine lock ``_mu`` — held
      for microseconds, never across device work — so engines
      alloc/free/share concurrently and ``free >= reserved`` holds
      under any interleaving (tests/test_paged.py pins it with a
      multi-thread churn property test);
    - ``lock`` guards only the shared device TREE's
      read-dispatch-reassign window: an engine takes it to read
      ``pool.cache``, enqueue ONE dispatch against that version, and
      reassign the result. Page ownership is disjoint by construction
      (each slot writes only its own table's pages), so two engines'
      dispatches chain safely through tree versions — engine B's
      dispatch reads engine A's output buffers, XLA sequences them —
      and the lock is released before the host ever blocks on the
      result. What it prevents is two engines reading the SAME version
      and both reassigning (the second would silently drop the first's
      writes).

    Every writer DONATES the version it was given (the device follows
    the chain: the successor is the same buffers, written in place —
    no second pool, no copy), so an old version is a tree of deleted
    arrays the moment its writer was enqueued. The tree lock therefore
    guards READERS too: a gather must take its reference and be
    enqueued inside one lock window (a gather enqueued BEFORE a
    donating dispatch is safe — the runtime orders the donation after
    pending reads; a Python reference kept ACROSS one raises "Array
    has been deleted"). ``tree_epoch`` counts the trees this pool has
    held: a donating program that fails at run time may take the tree
    with it, the engine that notices allocates the next one
    (``SlotCache.renew_tree``), and every engine lent the pool compares
    epochs before it steps — page CONTENT from an older epoch is gone,
    whatever the refcounts say.
    """

    def __init__(self, model, params, n_pages: int, page_size: int,
                 mesh=None, shared: bool = False, slots: int = 1):
        if n_pages < 1 or page_size < 1:
            raise ValueError("n_pages and page_size must be >= 1")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self.shared = bool(shared)
        # the TREE lock (see class docstring): reentrant, because a
        # shared-pool engine's dispatch window may nest an evict /
        # adopt that takes it again on the same thread
        self.lock = threading.RLock()
        # the fine ALLOCATOR lock: free list + refcounts + reservation
        # ledger mutate atomically under it; reentrant so compound ops
        # (stats -> cow_shared, reserve -> available) self-nest
        self._mu = threading.RLock()
        self.cache = paged_cache(model, params, n_pages, page_size,
                                 mesh=mesh, slots=slots)
        self.tree_epoch = 0  # trees lost to a failed dispatch so far
        self.page_nbytes = page_nbytes(self.cache)
        self.refcount = np.zeros(self.n_pages, np.int32)
        # LIFO free list: recently freed pages are re-issued first
        # (their content is junk either way; reuse keeps the hot set
        # small)
        self._free = list(range(self.n_pages - 1, -1, -1))
        self.reserved = 0   # granted-not-yet-allocated pages
        self.allocs = 0     # pages handed out, lifetime
        self.frees = 0      # pages returned to the free list, lifetime
        self.forks = 0      # copy-on-write page copies, lifetime
        self.peak_used = 0  # high-water mark of allocated pages

    # ------------------------------------------------------ accounting

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return self.n_pages - len(self._free)

    def available(self) -> int:
        """Pages grantable to a NEW reservation right now."""
        with self._mu:
            return len(self._free) - self.reserved

    def cow_shared(self) -> int:
        """Pages currently held by more than one owner (a slot table
        plus prefix-store entries, or several entries) — the
        copy-on-write sharing the fixed-shape path paid row copies
        for."""
        with self._mu:
            return int((self.refcount > 1).sum())

    # ------------------------------------------------------ allocation

    def reserve(self, n: int) -> bool:
        """Set aside ``n`` future pages; False when they are not there
        (the caller sheds load or frees store pages and retries)."""
        with self._mu:
            if n > self.available():
                return False
            self.reserved += n
            return True

    def cancel(self, n: int) -> None:
        """Return ``n`` unused reserved pages (evict, or a request
        finishing under its worst case)."""
        with self._mu:
            if n > self.reserved:
                raise ValueError(f"cancel({n}) exceeds reserved "
                                 f"{self.reserved}")
            self.reserved -= n

    def alloc(self, n: int, *, from_reservation: bool = False) -> list[int]:
        """Pop ``n`` pages (refcount 1 each). ``from_reservation``
        consumes previously reserved units — guaranteed to succeed by
        the invariant; a bare alloc must fit ``available()``."""
        with self._mu:
            if from_reservation:
                if n > self.reserved:
                    raise RuntimeError(
                        f"alloc({n}) exceeds reservation {self.reserved}"
                        " — engine reservation accounting bug")
                self.reserved -= n
            elif n > self.available():
                raise RuntimeError(
                    f"alloc({n}) exceeds available {self.available()}")
            pages = [self._free.pop() for _ in range(n)]
            self.refcount[pages] = 1
            self.allocs += n
            self.peak_used = max(self.peak_used, self.n_used)
            return pages

    def share(self, pages) -> None:
        """One more holder for each of ``pages`` (aliasing a prefix
        entry's pages into a slot table, or pinning a slot's pages
        into a store entry — the refcount bump that replaced
        ``read_slot_row``/``write_slot_row`` copies)."""
        with self._mu:
            for p in pages:
                if self.refcount[p] <= 0:
                    raise ValueError(f"share() of free page {p}")
                self.refcount[p] += 1

    def unref(self, pages) -> None:
        """Drop one holder; pages reaching refcount 0 return to the
        free list (their content is junk from that moment)."""
        with self._mu:
            for p in pages:
                if self.refcount[p] <= 0:
                    raise ValueError(f"unref() of free page {p}")
                self.refcount[p] -= 1
                if self.refcount[p] == 0:
                    self._free.append(p)
                    self.frees += 1

    # ----------------------------------------------------------- stats

    def stats(self) -> dict:
        with self._mu:
            return {
                "total": self.n_pages,
                "used": self.n_used,
                "free": self.n_free,
                "reserved": self.reserved,
                "cow_shared": self.cow_shared(),
                "page_size": self.page_size,
                "page_nbytes": self.page_nbytes,
                "bytes_resident": self.n_used * self.page_nbytes,
                "allocs": self.allocs,
                "frees": self.frees,
                "forks": self.forks,
                "peak_used": self.peak_used,
            }


class SlotCache:
    """``batch_size`` cache slots + per-slot length/rng/EOS-side state.

    The cache pytree stays on device across the whole serve session,
    and so does the decode round's carry: ``state`` is one packed
    ``[slots, STATE_COLS]`` int32 device array (last token, position
    with -1 for an empty slot, remaining budget, ``top_k``, and
    ``temperature`` and the two rng words bit-cast) that the chunk
    program takes and returns (``engine._decode_chunk``). It is NOT
    donated: a dispatch that fails leaves the last version alive.

    The host arrays are numpy and stay the scheduler's truth for
    everything off the round's critical path (admission, the token
    walk, the verify round, the migration snapshot, ``/stats``); the
    engine still updates them from each round's tokens, as it reads
    them: they lag the device by the rounds in flight
    (``Server._settle`` catches them up for a caller that needs the
    truth). They feed the
    program only where the HOST changed a row: ``admit`` and ``evict``
    (and ``host_fed``, after a round that ran on host-fed inputs) mark
    the row ``dirty``, and the next chunk round sends ONE packed patch
    (``decode_patch``) that the program lays over the resident state
    before its scan; a round with no dirty row sends nothing, it reuses
    an all-clear patch that already lives on the device. The paged
    table goes by the same rule (``device_table``: sent again only when
    the columns the round reads changed). The rng key is the one value
    the host cannot follow: while a sampled row is clean the device's
    key is the truth, and reading ``rng`` pulls it back first (a greedy
    row's key never moves, so a greedy batch never pulls). Counters:
    ``rounds`` / ``rounds_clean`` / ``rows_patched`` / ``table_sends``
    / ``rng_pulls``.

    With ``pool`` (a ``PagePool``) the cache is PAGED: ``self.cache``
    is the pool's page tree, and each slot additionally owns a page
    table row ``[max_pages] int32`` (unallocated tail = the
    ``pool.n_pages`` sentinel, which the device scatter drops and the
    gather clamps), a count of allocated pages, and the remainder of
    its admission-time page reservation. Admit never copies a row —
    prefill writes land straight in the slot's pages; evict returns
    the slot's page references (shared pages survive under their other
    holders) and cancels its remaining reservation.
    """

    def __init__(self, model, params, batch_size: int,
                 pool: PagePool | None = None, mesh=None):
        self.batch_size = batch_size
        self.max_seq_len = model.cfg.max_seq_len
        self.pool = pool
        self._cache = None
        # writer dispatches whose input tree was consumed / survived
        # (see the ``cache`` property)
        self.tree_donated = 0
        self.tree_kept = 0
        if pool is not None:
            if not pool.shared:
                # take OWNERSHIP of the device tree: the live pools are
                # reassigned onto self.cache after every dispatch, and a
                # reference left on the pool would pin the t=0
                # allocation (a full duplicate of the KV pool) for the
                # server's life
                self.cache = pool.cache
                pool.cache = None
            # shared pool: ownership stays with the pool — several
            # SlotCaches delegate to pool.cache through the property
            # below, so no duplicate reference exists to pin
            self.max_pages = -(-self.max_seq_len // pool.page_size)
            self.page_table = np.full((batch_size, self.max_pages),
                                      pool.n_pages, np.int32)
            self.n_slot_pages = np.zeros(batch_size, np.int32)
            self.reserve_left = np.zeros(batch_size, np.int32)
        elif mesh is not None:
            # fixed-shape rows, sharded serving: allocate the cache
            # directly under its kv-head shardings (see _alloc_sharded
            # — no dense transient on one chip)
            self.cache = _alloc_sharded(
                jax.eval_shape(
                    lambda p: init_cache(model, p, batch_size), params),
                mesh)
        else:
            self.cache = init_cache(model, params, batch_size)
        self.lengths = np.zeros(batch_size, np.int32)
        self.active = np.zeros(batch_size, bool)
        self.last_token = np.zeros(batch_size, np.int32)
        self.temperature = np.zeros(batch_size, np.float32)
        self.top_k = np.zeros(batch_size, np.int32)
        self._rng = np.zeros((batch_size, 2), np.uint32)
        # rows whose key the device has moved past the host's copy
        self._rng_stale = np.zeros(batch_size, bool)
        # rows the host changed since the last chunk round
        self.dirty = np.zeros(batch_size, bool)
        # where the small arrays go, so that the first state, a patch
        # and a successor all meet ONE executable: what the chunk
        # program hands back is replicated under a mesh (no round
        # re-shards it), and otherwise committed to a device exactly
        # when the parameters are — jit keys its programs on that too
        leaf = jax.tree_util.tree_leaves(params)[0]
        if mesh is not None:
            from tony_tpu.parallel.sharding import replicated

            self._small = replicated(mesh)
        elif getattr(leaf, "committed", False):
            self._small = leaf.sharding
        else:
            self._small = None
        self._no_patch = jax.device_put(
            np.zeros((batch_size, 1 + STATE_COLS), np.int32), self._small)
        self._table_sent = None  # host copy of the table on the device
        self._table_dev = None
        self.state = self._empty_state()
        self.rounds = 0        # chunk rounds enqueued
        self.rounds_clean = 0  # ... that sent no state
        self.rows_patched = 0  # dirty rows sent, summed over rounds
        self.table_sends = 0   # page tables sent
        self.rng_pulls = 0     # device-to-host copies of the keys

    @property
    def cache(self) -> Any:
        """The LIVE version of the device tree (the pool's, when the
        pool is shared). One rule for every program that takes the
        tree and returns its successor: it DONATES the tree, and its
        caller assigns the successor here before releasing the tree
        lock — so this attribute is the only reference to the only
        version, and the write lands in place instead of in a second
        pool. Assigning counts whether the version replaced was indeed
        consumed (``tree_donated`` / ``tree_kept``: one ``is_deleted()``
        on one leaf, no device sync) — ``kept`` growing means a writer
        lost its donation and pays a whole-tree copy per dispatch."""
        pool = self.pool
        if pool is not None and pool.shared:
            return pool.cache
        return self._cache

    @cache.setter
    def cache(self, value: Any) -> None:
        old = self.cache
        if old is not None and value is not None:
            if tree_consumed(old):
                self.tree_donated += 1
            else:
                self.tree_kept += 1
        self._store(value)

    def _store(self, value: Any) -> None:
        pool = self.pool
        if pool is not None and pool.shared:
            pool.cache = value
        else:
            self._cache = value

    def renew_tree(self) -> bool:
        """Replace a tree that a failed donating dispatch consumed by
        a zeroed one of the same shapes and shardings; False (and
        nothing done) while the tree is alive. With the old tree went
        every page's and slot's CONTENT, so the caller drops what
        pointed into it, and a paged pool's ``tree_epoch`` moves so
        that co-located engines notice. Takes the caller's tree lock
        for granted."""
        old = self.cache
        if not tree_consumed(old):
            return False
        self._store(jax.tree.map(
            lambda x: jnp.zeros(x.shape, x.dtype, device=x.sharding), old))
        if self.pool is not None:
            self.pool.tree_epoch += 1
        return True

    def free_slots(self) -> list[int]:
        return [i for i in range(self.batch_size) if not self.active[i]]

    @property
    def n_active(self) -> int:
        return int(self.active.sum())

    def positions(self) -> np.ndarray:
        """Per-slot decode positions for the next step: the slot's
        current length (where the next token is written and up to which
        attention looks), -1 for empty slots (no visible keys)."""
        return np.where(self.active, self.lengths, -1).astype(np.int32)

    def admit(self, slot: int, length: int, last_token: int,
              temperature: float, top_k: int, rng_key,
              row_cache: Any = None) -> None:
        """Arm ``slot``'s per-slot state; with ``row_cache`` also copy
        that prefilled batch-1 cache row into the slot (the serving
        engine fuses the copy into its prefill dispatch instead and
        passes None). ``length`` = real prompt length (bucket padding
        beyond it is invisible: masked now, overwritten as the slot
        advances). ``last_token`` is the first sampled continuation —
        the next step feeds it at position ``length``. The row is
        marked dirty: the next chunk round sends it to the device."""
        if self.active[slot]:
            raise ValueError(f"slot {slot} is occupied")
        if not 0 < length <= self.max_seq_len:
            raise ValueError(f"bad prompt length {length}")
        if row_cache is not None:
            if self.pool is not None:
                raise ValueError("paged slots take no row_cache — "
                                 "prefill writes land in the slot's "
                                 "pages directly")
            self.cache = _write_slot(self.cache, row_cache,
                                     jnp.int32(slot))
        self.lengths[slot] = length
        self.last_token[slot] = last_token
        self.temperature[slot] = temperature
        self.top_k[slot] = top_k
        self._rng[slot] = np.asarray(rng_key, np.uint32).reshape(2)
        self._rng_stale[slot] = False
        self.active[slot] = True
        self.dirty[slot] = True

    def evict(self, slot: int) -> None:
        """Free a slot (EOS / budget exhausted). The K/V is left in
        place — an inactive slot's position is -1, so nothing reads it,
        and the next admit overwrites the whole row; the row of the
        resident decode state is marked dirty, so that the next chunk
        round ENQUEUED tells the device the slot is empty. A round may
        already be in the device's queue when the host evicts (the
        engine keeps two in flight), and there the row still reads
        live: what keeps it from writing into pages that are no longer
        its own is not the patch's timing but the chunk program's own
        seed — a row whose budget is spent or whose last token is a
        stop token starts the round frozen (no K/V, no rng:
        ``engine._decode_chunk``). So the pages may go to the prefix
        store or to the next occupant at once: that round writes
        nothing there, and the occupant's prefill runs after it (the
        device takes its programs in order, along one donated tree).
        Paged: the slot's page references are dropped (pages a
        prefix-store entry also holds stay resident under their
        remaining refcount) and its unspent reservation is returned."""
        self.active[slot] = False
        self.lengths[slot] = 0
        self.last_token[slot] = 0
        self.temperature[slot] = 0.0
        self.top_k[slot] = 0
        self._rng[slot] = 0
        self._rng_stale[slot] = False
        self.dirty[slot] = True
        if self.pool is not None:
            self.release_pages(slot)

    # ------------------------------------------ resident decode state

    def _empty_state(self):
        empty = np.zeros((self.batch_size, STATE_COLS), np.int32)
        empty[:, _POS] = -1
        return jax.device_put(empty, self._small)

    @property
    def rng(self) -> np.ndarray:
        """The per-slot rng keys ``[slots, 2]`` uint32 on the host,
        pulled back from the device first where a chunk round has
        advanced them since (one small copy for all rows; none while
        every live row is greedy)."""
        if self._rng_stale.any():
            keys = np.array(self.state)[:, _RNG:].view(np.uint32)
            self._rng[self._rng_stale] = keys[self._rng_stale]
            self._rng_stale[:] = False
            self.rng_pulls += 1
        return self._rng

    def decode_patch(self, budgets):
        """What the next chunk round must tell the device: the rows the
        host changed since the last one, packed from the mirrors and
        ``budgets`` (remaining tokens, one per slot) behind a mask column —
        or, with no such row, the all-clear patch that already lives
        there (nothing is sent). ``advance`` closes the round."""
        if not self.dirty.any():
            return self._no_patch
        patch = np.empty((self.batch_size, 1 + STATE_COLS), np.int32)
        patch[:, 0] = self.dirty
        row = patch[:, 1:]
        row[:, _TOK] = self.last_token
        row[:, _POS] = self.positions()
        row[:, _REM] = budgets
        row[:, _TOPK] = self.top_k
        row[:, _TEMP] = self.temperature.view(np.int32)
        row[:, _RNG:] = self._rng.view(np.int32)
        return jax.device_put(patch, self._small)

    def advance(self, state) -> None:
        """A chunk round was enqueued on ``decode_patch``'s patch and
        handed back ``state``: it is the resident state now, every row
        is clean, and a live sampled row's key has moved on the
        device."""
        n = int(self.dirty.sum())
        self.rounds += 1
        self.rounds_clean += n == 0
        self.rows_patched += n
        self.dirty[:] = False
        self._rng_stale |= self.active & (self.temperature > 0.0)
        self.state = state

    def host_fed(self, rng) -> None:
        """A round ran on inputs made from the mirrors and handed back
        ``rng`` (the verify round): the host is the truth for every
        live row again, and the next chunk round sends them."""
        self._rng = np.array(rng, np.uint32)  # a copy: admit writes it
        self._rng_stale[:] = False
        self.dirty |= self.active

    def device_table(self, cols: int):
        """``page_table[:, :cols]`` on the device: sent only when those
        columns, or ``cols``, changed since the last send."""
        want = self.page_table[:, :cols]
        if self._table_sent is None \
                or not np.array_equal(self._table_sent, want):
            self._table_sent = want.copy()
            self._table_dev = jax.device_put(self._table_sent, self._small)
            self.table_sends += 1
        return self._table_dev

    # --------------------------------------------------- paged helpers

    def release_pages(self, slot: int) -> None:
        """Drop the slot's page references + unspent reservation (also
        used directly for an admitted-then-immediately-finished request
        whose slot was never armed)."""
        n = int(self.n_slot_pages[slot])
        if n:
            self.pool.unref(self.page_table[slot, :n].tolist())
        self.pool.cancel(int(self.reserve_left[slot]))
        self.page_table[slot] = self.pool.n_pages
        self.n_slot_pages[slot] = 0
        self.reserve_left[slot] = 0

    def seed_pages(self, slot: int, pages: list, seed_len: int,
                   reserve: int) -> bool:
        """Arm a fresh slot's table with a prefix-store entry's shared
        pages covering positions ``[0, seed_len)`` plus a reservation
        of ``reserve`` future pages. When ``seed_len`` falls mid-page,
        the boundary page — shared, but about to be written at offsets
        ``>= seed_len % page_size`` — is FORKED: one page copy on
        device, the original stays pinned for its other holders.
        Returns whether a fork happened. ``reserve`` must already be
        granted by ``pool.reserve()`` and include the fork page."""
        ps = self.pool.page_size
        n_alias = -(-seed_len // ps) if seed_len else 0
        use = [int(p) for p in pages[:n_alias]]
        self.pool.share(use)
        self.reserve_left[slot] = reserve
        self.n_slot_pages[slot] = n_alias
        self.page_table[slot, :n_alias] = use
        self.page_table[slot, n_alias:] = self.pool.n_pages
        if seed_len % ps == 0:
            return False
        (fresh,) = self.pool.alloc(1, from_reservation=True)
        self.reserve_left[slot] -= 1
        shared = use[-1]
        # the fork's read-dispatch-reassign window on the (possibly
        # shared) device tree — see PagePool docstring; reentrant, so
        # callers already inside their own window nest harmlessly
        with self.pool.lock:
            self.cache = _copy_page(self.cache, jnp.int32(shared),
                                    jnp.int32(fresh))
        self.pool.unref([shared])
        self.page_table[slot, n_alias - 1] = fresh
        with self.pool._mu:
            self.pool.forks += 1
        return True

    def ensure_pages(self, slot: int, upto_pos: int) -> None:
        """Grow the slot's table (from its reservation) until its pages
        cover positions ``[0, upto_pos)`` — called before any dispatch
        that writes those positions. Never allocates past the
        reservation: positions beyond it are budget overshoot whose
        writes the device scatter drops through the sentinel."""
        ps = self.pool.page_size
        have = int(self.n_slot_pages[slot])
        want = min(-(-upto_pos // ps), self.max_pages)
        grow = min(want - have, int(self.reserve_left[slot]))
        if grow <= 0:
            return
        pages = self.pool.alloc(grow, from_reservation=True)
        self.reserve_left[slot] -= grow
        self.page_table[slot, have:have + grow] = pages
        self.n_slot_pages[slot] = have + grow

    def slot_pages(self, slot: int, n_tokens: int) -> list[int]:
        """The slot's page ids covering positions ``[0, n_tokens)``
        (all allocated by construction — donation reads only written
        extents)."""
        n = -(-n_tokens // self.pool.page_size)
        if n > int(self.n_slot_pages[slot]):
            raise ValueError(
                f"slot {slot} holds {int(self.n_slot_pages[slot])} pages, "
                f"{n} needed for {n_tokens} tokens")
        return self.page_table[slot, :n].tolist()

    def reset(self) -> None:
        """Evict everything (a fresh serving session on the same cache
        allocation — no reallocation, no recompile). The resident state
        is made anew, all slots empty: a failed dispatch may have left
        a successor that can never be read."""
        for i in range(self.batch_size):
            self.evict(i)
        self.state = self._empty_state()
        self.dirty[:] = False
