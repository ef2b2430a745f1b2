"""PrefixStore: radix-keyed KV-cache rows for automatic prefix reuse.

The RadixAttention idea (SGLang; vLLM's prefix caching) on this repo's
static-shape serving path: thousands of requests share a system prompt
or few-shot preamble, and every one of them re-prefills the same
tokens. The store keeps previously prefilled batch-1 cache rows keyed
by their TOKEN SEQUENCE in a radix tree; on admit the engine looks up
the longest cached prefix of the incoming prompt and either skips
prefill entirely (exact-prompt hit: copy the row into the slot, sample
the first token from the stored last-position logits) or seeds the
slot from the row and prefills only the bucketed SUFFIX at a position
offset (engine._prefill with ``offset``/``row``).

Why a whole stored row is usable even on a PARTIAL match: a cache
position's K/V depends only on tokens at-or-before it (causal
attention), so a row stored for sequence S is position-exact over
``[0, k)`` for any prompt sharing S's first ``k`` tokens. Content
beyond the matched region is junk to the consumer — and harmless: the
suffix prefill overwrites ``[k, k+suffix_bucket)``, the slot's length
masks everything past the prompt, and decode overwrites each position
before it ever becomes visible. Masked scores are set to -1e30, whose
softmax weight underflows to exactly 0.0, so junk K/V contributes
nothing — greedy outputs through the store are token-for-token
identical to store-off serving (tests/test_prefix.py pins it).

Bookkeeping contract:

- Entries are REF-COUNTED: ``acquire()`` pins the matched entry until
  ``release()``; eviction never touches an entry with a nonzero
  refcount (an admit that is mid-copy must not lose its row).
- An explicit BYTE BUDGET, computed from the stored pytrees' leaf
  sizes, bounds device memory; inserts past it evict the
  least-recently-used unreferenced entries, and an insert that cannot
  fit (all remaining bytes pinned, or the entry alone exceeds the
  budget) is refused rather than overflowing.
- Single-writer like the engine: the owning scheduler thread drives
  acquire/insert/release. The internal lock only keeps cross-thread
  STAT reads (gateway /stats) consistent.
"""

from __future__ import annotations

import itertools
import logging
import threading
import zlib
from typing import Any

import jax
import numpy as np

log = logging.getLogger(__name__)


def tree_nbytes(tree: Any) -> int:
    """Total bytes of a pytree's array leaves (shape x itemsize — the
    device-memory cost the store's budget accounts in)."""
    return sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree))


class _Entry:
    """One stored sequence: a batch-1 cache row covering exactly
    ``tokens``, optionally the last-position logits (prefill-donated
    entries have them — the exact-hit fast path needs them to sample
    the first continuation; EOS-donated rows don't).

    PAGED stores (``PrefixStore(pool=...)``) keep ``pages`` — the page
    ids whose concatenation covers ``tokens`` — instead of a copied
    ``row``: the entry is a refcount on live pool pages, so donation
    costs no device work and a hit aliases pages instead of copying
    them (copy-on-write, serve/slots.PagePool)."""

    __slots__ = ("tokens", "row", "logits", "pages", "nbytes", "node",
                 "refcount", "tick")

    def __init__(self, tokens: np.ndarray, row: Any, logits: Any,
                 nbytes: int, node: "_Node", tick: int,
                 pages: list | None = None):
        self.tokens = tokens
        self.row = row
        self.logits = logits
        self.pages = pages
        self.nbytes = nbytes
        self.node = node
        self.refcount = 0
        self.tick = tick


class _Node:
    """Radix-tree node: ``edge`` is the token run from the parent
    (root's is empty); an entry, when present, covers exactly the path
    from the root through this node."""

    __slots__ = ("edge", "children", "entry", "parent")

    def __init__(self, edge: np.ndarray, parent: "_Node | None"):
        self.edge = edge
        self.children: dict[int, _Node] = {}
        self.entry: _Entry | None = None
        self.parent = parent


def _common_len(a: np.ndarray, b: np.ndarray) -> int:
    n = min(len(a), len(b))
    neq = np.nonzero(a[:n] != b[:n])[0]
    return int(neq[0]) if neq.size else n


class PrefixStore:
    """Radix store of prefilled cache rows under a byte budget.

    With ``pool`` (a ``serve.slots.PagePool``) the store holds PAGE
    REFERENCES instead of copied rows: an insert pins the sequence's
    pool pages (one ``pool.share()`` per entry — zero device work), an
    eviction unpins them, and the byte budget counts each UNIQUE page
    once (entries sharing a prefix share its pages; double-charging
    them would make the budget lie about pool residency). The byte
    budget bounds how much of the pool the store may hog; the engine
    additionally squeezes it (``evict_one``) when a slot admission
    cannot reserve pages."""

    def __init__(self, budget_bytes: int, pool: Any = None):
        self.budget_bytes = max(0, int(budget_bytes))
        self.bytes_used = 0
        self.pool = pool
        # eviction hook (serve/tier.py): called with the dying entry
        # BEFORE its pages are unpinned, so a host-RAM tier can copy
        # the content out. Runs under this store's lock on the owning
        # engine thread; a raising hook must never break the eviction.
        self.on_evict = None
        self._page_refs: dict[int, int] = {}  # page -> #entries holding
        self.tokens_stored = 0
        self.root = _Node(np.empty(0, np.int32), None)
        self._entries: dict[bytes, _Entry] = {}
        self._lock = threading.Lock()
        self._ticks = itertools.count(1)
        self.lookups = 0
        self.matched = 0
        self.inserts = 0
        self.evictions = 0
        self.rejected = 0

    # ------------------------------------------------------------ lookup

    def acquire(self, tokens) -> tuple[int, _Entry | None]:
        """Longest stored prefix of ``tokens``: ``(match_len, entry)``
        with the entry's refcount bumped (caller MUST ``release()``),
        or ``(0, None)`` on a miss. ``entry.tokens[:match_len] ==
        tokens[:match_len]`` always holds; ``match_len`` may be shorter
        than the entry's own sequence (partial match — usable, see the
        module docstring) or equal to ``len(tokens)`` against a LONGER
        entry (a donated conversation the new prompt extends)."""
        tokens = np.asarray(tokens, np.int32)
        with self._lock:
            self.lookups += 1
            hit = self._lookup(tokens)
            if hit is None:
                return 0, None
            match, entry = hit
            entry.refcount += 1
            entry.tick = next(self._ticks)
            self.matched += 1
            return match, entry

    def release(self, entry: _Entry) -> None:
        with self._lock:
            if entry.refcount <= 0:
                raise ValueError("release() without matching acquire()")
            entry.refcount -= 1

    def match_len(self, tokens) -> int:
        """Longest stored prefix of ``tokens`` WITHOUT pinning the
        entry or moving the lookup counters — the gateway's
        prefix-affinity routing probe (a routing decision must not
        skew this replica's admission hit rate)."""
        tokens = np.asarray(tokens, np.int32)
        with self._lock:
            hit = self._lookup(tokens)
            return 0 if hit is None else hit[0]

    def has(self, tokens) -> bool:
        """Whether this exact sequence is stored (the host tier's
        skip-the-copy check before a spill)."""
        key = np.asarray(tokens, np.int32).tobytes()
        with self._lock:
            return key in self._entries

    def _lookup(self, tokens: np.ndarray) -> tuple[int, _Entry] | None:
        node, consumed = self.root, 0
        best: tuple[int, _Entry] | None = None
        while True:
            if node.entry is not None and consumed > 0:
                best = (consumed, node.entry)
            if consumed == len(tokens):
                # the whole prompt matched a stored path: the node's own
                # entry is the EXACT match (preferred — it may carry
                # logits); otherwise any longer entry below covers it
                if node.entry is None:
                    deeper = _freshest_entry(node)
                    if deeper is not None:
                        best = (consumed, deeper)
                return best
            child = node.children.get(int(tokens[consumed]))
            if child is None:
                # dead end at a node: every entry below it still shares
                # the ``consumed`` tokens walked so far (node.entry,
                # when present, was already recorded at the same depth)
                if consumed > 0 and (best is None or best[0] < consumed):
                    deeper = _freshest_entry(node)
                    if deeper is not None:
                        best = (consumed, deeper)
                return best
            common = _common_len(child.edge, tokens[consumed:])
            if common < len(child.edge):
                # partial way down an edge: every entry in the child's
                # subtree shares exactly consumed+common tokens
                deeper = _freshest_entry(child)
                if deeper is not None:
                    best = (consumed + common, deeper)
                return best
            node = child
            consumed += len(child.edge)

    # ------------------------------------------------------------ insert

    def wants(self, tokens, nbytes: int) -> bool:
        """Cheap pre-check before a donor pays the row-extraction
        dispatch: False when the sequence is already stored or when
        ``nbytes`` cannot fit even after evicting every unreferenced
        entry."""
        key = np.asarray(tokens, np.int32).tobytes()
        with self._lock:
            if key in self._entries:
                return False
            pinned = sum(e.nbytes for e in self._entries.values()
                         if e.refcount > 0)
            return nbytes + pinned <= self.budget_bytes

    def insert(self, tokens, row: Any = None, logits: Any = None,
               pages: list | None = None) -> bool:
        """Store ``row`` (a batch-1 cache pytree covering exactly
        ``tokens``) with optional last-position ``logits``. Returns
        False when refused (budget); re-inserting an existing sequence
        just refreshes its LRU position.

        Paged stores take ``pages`` instead of ``row``: the pool pages
        covering ``tokens``, pinned by refcount — pages already held
        by another entry cost zero additional budget."""
        tokens = np.asarray(tokens, np.int32)
        if tokens.size == 0 or self.budget_bytes <= 0:
            return False
        if (pages is not None) != (self.pool is not None):
            raise ValueError("pages= requires a pool-backed store "
                             "(and vice versa)")
        key = tokens.tobytes()
        with self._lock:
            existing = self._entries.get(key)
            if existing is not None:
                existing.tick = next(self._ticks)
                return True
            if pages is not None:
                return self._insert_pages(tokens, key, list(pages), logits)
            nbytes = tree_nbytes(row)
            if logits is not None:
                nbytes += tree_nbytes(logits)
            if not self._make_room(nbytes):
                self.rejected += 1
                return False
            node = self._insert_node(tokens)
            entry = _Entry(tokens, row, logits, nbytes, node,
                           next(self._ticks))
            node.entry = entry
            self._entries[key] = entry
            self.bytes_used += nbytes
            self.tokens_stored += int(tokens.size)
            self.inserts += 1
            return True

    def _insert_pages(self, tokens: np.ndarray, key: bytes,
                      pages: list, logits: Any) -> bool:
        """Paged insert under ``self._lock``. The bytes a paged entry
        charges depend on what is ALREADY pinned (shared pages are
        free), and evicting an LRU entry can un-share a page — so the
        charge is recomputed after every eviction instead of once."""
        logits_b = tree_nbytes(logits) if logits is not None else 0
        while True:
            fresh = sum(1 for p in set(pages)
                        if self._page_refs.get(p, 0) == 0)
            nbytes = fresh * self.pool.page_nbytes + logits_b
            if self.bytes_used + nbytes <= self.budget_bytes:
                break
            victim = min(
                (e for e in self._entries.values() if e.refcount == 0),
                key=lambda e: e.tick, default=None)
            if victim is None or nbytes > self.budget_bytes:
                self.rejected += 1
                return False
            self._evict(victim)
        node = self._insert_node(tokens)
        entry = _Entry(tokens, None, logits, nbytes, node,
                       next(self._ticks), pages=pages)
        node.entry = entry
        self._entries[key] = entry
        self.pool.share(pages)
        for p in pages:
            self._page_refs[p] = self._page_refs.get(p, 0) + 1
        self.bytes_used += nbytes
        self.tokens_stored += int(tokens.size)
        self.inserts += 1
        return True

    def evict_one(self) -> bool:
        """Evict the least-recently-used unpinned entry (the engine's
        pool-pressure squeeze: a slot admission that cannot reserve
        pages frees store pages before giving up). False when every
        entry is pinned by an in-flight acquire (or the store is
        empty)."""
        with self._lock:
            victim = min(
                (e for e in self._entries.values() if e.refcount == 0),
                key=lambda e: e.tick, default=None)
            if victim is None:
                return False
            self._evict(victim)
            return True

    def clear(self) -> None:
        """Drop every entry WITHOUT the eviction hook: for a store
        whose pool lost its device tree, where a spill would copy
        zeros into the host tier under real tokens. Page pins go back
        to the pool as in any eviction."""
        with self._lock:
            hook, self.on_evict = self.on_evict, None
            try:
                for entry in list(self._entries.values()):
                    self._evict(entry)
            finally:
                self.on_evict = hook

    def _insert_node(self, tokens: np.ndarray) -> _Node:
        node, consumed = self.root, 0
        while consumed < len(tokens):
            first = int(tokens[consumed])
            child = node.children.get(first)
            if child is None:
                leaf = _Node(tokens[consumed:].copy(), node)
                node.children[first] = leaf
                return leaf
            common = _common_len(child.edge, tokens[consumed:])
            if common < len(child.edge):
                # split the edge at the divergence point; the next loop
                # iteration hangs the new sequence's tail under ``mid``
                # (or, when the tokens are exhausted, ``mid`` IS the
                # new sequence's node)
                mid = _Node(child.edge[:common].copy(), node)
                node.children[first] = mid
                child.edge = child.edge[common:]
                child.parent = mid
                mid.children[int(child.edge[0])] = child
                node = mid
            else:
                node = child
            consumed += common
        return node

    # ---------------------------------------------------------- eviction

    def _make_room(self, nbytes: int) -> bool:
        if nbytes > self.budget_bytes:
            return False
        while self.bytes_used + nbytes > self.budget_bytes:
            victim = min(
                (e for e in self._entries.values() if e.refcount == 0),
                key=lambda e: e.tick, default=None)
            if victim is None:  # everything left is pinned
                return False
            self._evict(victim)
        return True

    def _evict(self, entry: _Entry) -> None:
        if self.on_evict is not None:
            # before any unpinning: the hook may still read the
            # entry's pages/row off the device
            try:
                self.on_evict(entry)
            except Exception:
                log.exception("prefix on_evict hook failed")
        del self._entries[entry.tokens.tobytes()]
        if entry.pages is not None:
            # release the entry's page pins; only pages no OTHER entry
            # still holds stop being charged (and, once every holder —
            # store entries and slot tables alike — lets go, return to
            # the pool's free list)
            released = 0
            for p in entry.pages:
                self._page_refs[p] -= 1
                if self._page_refs[p] == 0:
                    del self._page_refs[p]
                    released += self.pool.page_nbytes
            self.pool.unref(entry.pages)
            if entry.logits is not None:
                released += tree_nbytes(entry.logits)
            self.bytes_used -= released
        else:
            self.bytes_used -= entry.nbytes
        self.tokens_stored -= int(entry.tokens.size)
        self.evictions += 1
        node = entry.node
        node.entry = None
        # prune entry-less leaves, then merge single-child pass-throughs
        # so the tree stays proportional to what is stored
        while node.parent is not None and node.entry is None \
                and not node.children:
            parent = node.parent
            del parent.children[int(node.edge[0])]
            node = parent
        if node.parent is not None and node.entry is None \
                and len(node.children) == 1:
            (child,) = node.children.values()
            child.edge = np.concatenate([node.edge, child.edge])
            child.parent = node.parent
            node.parent.children[int(child.edge[0])] = child

    # ----------------------------------------------------------- summary

    def summary(self, max_items: int = 512, grain: int = 8) -> list:
        """Bounded wire summary of what this store could seed:
        ``[[n_tokens, crc32], ...]`` pairs, one per stored-sequence
        PREFIX on a ``grain``-token grid (plus each entry's full
        length), most-recent entries first, deduplicated. Shipped on
        the agent heartbeat (ISSUE-18) so the gateway's prefix-
        affinity probe can score a REMOTE replica's warmth via
        ``summary_match_len`` without shipping the radix tree. The
        grid makes PARTIAL matches visible — a prompt sharing only
        the system preamble of a longer stored conversation still
        hashes equal at the preamble's grid points."""
        with self._lock:
            entries = sorted(self._entries.values(),
                             key=lambda e: e.tick, reverse=True)
        out: list = []
        seen: set = set()
        for e in entries:
            n = int(e.tokens.size)
            lens = list(range(grain, n + 1, grain))
            if not lens or lens[-1] != n:
                lens.append(n)
            for ln in reversed(lens):
                item = (ln, zlib.crc32(e.tokens[:ln].tobytes()))
                if item in seen:
                    continue
                seen.add(item)
                out.append([item[0], item[1]])
                if len(out) >= max_items:
                    return out
        return out

    # ------------------------------------------------------------- stats

    def __len__(self) -> int:
        return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            # radix shape (root included; depth in TOKENS): what the
            # gateway's affinity router exports per replica — a tree
            # whose max_depth dwarfs its entry count is one long
            # conversation, a bushy shallow tree is a shared preamble
            nodes, max_depth = 0, 0
            stack: list[tuple[_Node, int]] = [(self.root, 0)]
            while stack:
                node, depth = stack.pop()
                nodes += 1
                max_depth = max(max_depth, depth)
                for child in node.children.values():
                    stack.append((child, depth + len(child.edge)))
            return {
                "entries": len(self._entries),
                "bytes": self.bytes_used,
                "budget_bytes": self.budget_bytes,
                "tokens": self.tokens_stored,
                "nodes": nodes,
                "max_depth": max_depth,
                "lookups": self.lookups,
                "matched": self.matched,
                "inserts": self.inserts,
                "evictions": self.evictions,
                "rejected": self.rejected,
            }


def summary_match_len(summary, tokens) -> int:
    """Longest summarized prefix of ``tokens`` — the probe side of
    ``PrefixStore.summary()``, run by the gateway's remote stub against
    the pairs a heartbeat shipped. Hashing convention (int32 bytes,
    crc32) matches the producer exactly; a crc collision costs one
    mis-routed request, never a wrong token."""
    toks = np.asarray(tokens, np.int32)
    by_len: dict[int, set] = {}
    for ln, crc in summary or ():
        if 0 < int(ln) <= toks.size:
            by_len.setdefault(int(ln), set()).add(int(crc))
    for ln in sorted(by_len, reverse=True):
        if zlib.crc32(toks[:ln].tobytes()) in by_len[ln]:
            return ln
    return 0


def _freshest_entry(node: _Node) -> _Entry | None:
    """Most-recently-used entry in ``node``'s subtree (ties on LRU
    keep hot rows hot; any entry is equally CORRECT for a partial
    match)."""
    best = node.entry
    for child in node.children.values():
        e = _freshest_entry(child)
        if e is not None and (best is None or e.tick > best.tick):
            best = e
    return best
