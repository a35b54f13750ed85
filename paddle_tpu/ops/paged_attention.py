"""Paged KV-cache attention for continuous-batching inference.

Beyond-parity (the reference era predates it; see PAPERS.md "Ragged
Paged Attention ... for TPU"): decode-time KV memory is allocated in
fixed-size PAGES shared by all sequences, so a batch of requests with
wildly different lengths wastes no HBM on padding and sequences can
join/leave the batch without reshaping anything static.

TPU-native formulation: the page pool is one [n_pages, page_size, H, D]
array per layer; a per-sequence page table [B, max_pages] turns decode
attention into ONE XLA gather (pages → [B, max_pages*page_size, H, D])
plus a masked flash-style softmax — static shapes, jit-stable across
steps, no per-token recompilation. The allocator is host-side Python
(free-list of page ids), exactly the part that should not be traced.

Pages are REFCOUNTED, which buys two serving-scale features on top:

- **prefix caching** — finished prompts register their pages in a
  chain-keyed registry (each node: one page's token block, keyed under
  its parent block), so a new request whose prompt matches a registered
  chain `acquire_prefix()`s those pages instead of recomputing their KV
  — N users behind one system prompt pay for its KV once. Registered
  pages survive their sequence (the registry is a holder too) and are
  reclaimed LRU-first when the allocator runs dry.
- **copy-on-write** — a write into a page referenced by more than one
  holder first materializes a private copy (one dynamic-slice device
  copy per layer), so divergence after a shared prefix never corrupts a
  neighbor — and the original snapshot stays valid for future sharers.

Every write site (extend / plan_decode / plan_ragged) funnels through
`_ensure_capacity`, which enforces the invariant: a page is never
written while its refcount is above one.

`plan_ragged` is the host planner for the Pallas ragged kernel
(ops/pallas/paged_attention.py): ONE jitted step advances mixed
decode rows and prefill chunks with per-token write coordinates and
causal bounds — no row pays for another row's padding.

Two engines can SHARE one pool (prefill/decode disaggregation — the
serving front door, docs/SERVING.md "The front door"):

- `cache.lock` (an RLock) serializes the host-side allocator and the
  donated-pool swap; every engine-facing mutation path acquires it, so
  a prefill engine and a decode engine driving the same pool from two
  scheduler threads interleave safely (the device work itself is
  ordered by XLA's data dependency on the donated pool buffers).
- the CLAIMS ledger (`set_claim`/`outstanding_claims`) makes worst-case
  admission reservations POOL-wide: each live sequence's claim is
  (reserved pages - pages drawn so far), summed across every engine on
  the pool — two engines admitting against one free list can no longer
  double-book it.
- `export_chain`/`adopt_chain` move a fully-prefilled sequence's pages
  between sequences (and engines) WITHOUT copying: the chain handle
  keeps every page's hold and the sequence's claim alive in limbo, the
  adopting side reattaches them under a new seq id — page ids,
  refcounts, and the cumulative draw counter are all invariant across
  the handoff (asserted by tests/test_frontdoor.py).
"""
import functools
import itertools
import math
import threading
from collections import OrderedDict

import numpy as np
import jax
import jax.numpy as jnp

__all__ = ["PagedKVCache", "KVChainHandle", "paged_attention"]


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_block(pool, block, page, in_page):
    """In-place page write: the pool buffer is DONATED, so XLA updates
    it without copying the whole [n_pages, page_size, H, D] array (an
    eager dynamic_update_slice would copy the pool per token). page/
    in_page are traced, so one program serves every position."""
    return jax.lax.dynamic_update_slice(
        pool, block, (page, in_page,
                      jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)))


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_page(pool, src, dst):
    """Copy-on-write materialization: duplicate one page inside the
    donated pool (src/dst traced — one program per pool shape)."""
    z = jnp.zeros((), jnp.int32)
    page = jax.lax.dynamic_slice(pool, (src, z, z, z),
                                 (1,) + pool.shape[1:])
    return jax.lax.dynamic_update_slice(pool, page, (dst, z, z, z))


def paged_attention(q, k_pages, v_pages, page_table, lengths, scale=None):
    """q: [B, H, D] (one decode token per sequence);
    k_pages/v_pages: [n_pages, page_size, H, D];
    page_table: [B, max_pages] int32 page ids (0-padded);
    lengths: [B] int32 — tokens currently stored per sequence.
    Returns [B, H, D]."""
    B, H, D = q.shape
    P = k_pages.shape[1]
    max_pages = page_table.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    # one gather: each sequence's pages, flattened to a token axis
    k = k_pages[page_table].reshape(B, max_pages * P, H, D)
    v = v_pages[page_table].reshape(B, max_pages * P, H, D)
    s = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    t = jnp.arange(max_pages * P)[None, None, :]
    s = jnp.where(t < lengths[:, None, None], s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bht,bthd->bhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


_ROOT = 0  # prefix-chain id of the empty prefix

_CHAIN_IDS = itertools.count()


class KVChainHandle:
    """A detached, fully-written KV chain in flight between two
    sequences (the prefill→decode handoff unit — docs/SERVING.md "The
    front door"). Holds the exported sequence's page list, token
    length, cumulative draw count, and admission claim; while the
    handle is live the pool keeps every page's hold AND counts the
    claim in `outstanding_claims()`, so the handoff window can never
    be double-booked by a concurrent admission. Consume exactly once
    via `adopt_chain` (same pool only — the move is page IDS, no
    copies) or `release_chain`."""

    __slots__ = ("chain_id", "pages", "length", "drawn", "claim",
                 "consumed", "request_id", "t_export", "draft_chain")

    # cache-strategy stamp (inference/cache_strategy.py duck type):
    # journey/route records carry it, and the recurrent/hybrid handles
    # override it
    strategy = "paged"

    def __init__(self, pages, length, drawn, claim):
        self.chain_id = next(_CHAIN_IDS)
        self.pages = pages
        self.length = length
        self.drawn = drawn
        self.claim = claim
        self.consumed = False
        # journey telemetry riders (profiler/fleet_observatory.py): the
        # originating request's id and the export timestamp, stamped by
        # the prefill engine so the handoff gap is MEASURED at the
        # export site, never inferred downstream
        self.request_id = None
        self.t_export = None
        # speculative-decoding rider (inference/speculative.py): the
        # DRAFT model's exported chain for the same request, carried
        # alongside the target chain so a mid-speculation handoff moves
        # both caches' state in one unit. None for non-speculative
        # engines and for cross-pool adoptions (the decode engine then
        # rebuilds draft state from the token history)
        self.draft_chain = None


class PagedKVCache:
    """Host-side page allocator + device-side page pools (per layer).

    write()/extend() copy new k/v into pages with one dynamic_update per
    page touched; sequences allocate pages lazily and release them on
    free() — the pool is shared, so peak HBM tracks the TOTAL tokens in
    flight, not batch * max_len. Pages are refcounted: prefix caching
    shares prompt pages across sequences (and retains them LRU past
    their sequence), copy-on-write materializes a private page before
    any write to a shared one."""

    # strategy stamp consumed by inference/cache_strategy.strategy_of
    # (the serving engine/schema key on it); the recurrent and hybrid
    # caches override it
    strategy = "paged"

    def __init__(self, n_layers, n_pages, page_size, n_heads, head_dim,
                 dtype=jnp.float32):
        self.n_layers = n_layers
        self.n_pages = n_pages
        self.page_size = page_size
        self.n_heads = n_heads
        self.head_dim = head_dim
        shape = (n_pages, page_size, n_heads, head_dim)
        self.k = [jnp.zeros(shape, dtype) for _ in range(n_layers)]
        self.v = [jnp.zeros(shape, dtype) for _ in range(n_layers)]
        # serializes the host allocator + the donated-pool swap when
        # more than one engine drives this pool (prefill/decode
        # disaggregation); re-entrant so an engine holding it can call
        # any cache method. Uncontended cost for the single-engine
        # case is one C-level RLock acquire per step.
        self.lock = threading.RLock()
        # page 0 is reserved as the pad page so 0-padded tables are safe
        self._free = list(range(1, n_pages))
        self._tables = {}   # seq_id -> list of page ids
        self._len = {}      # seq_id -> tokens stored
        self._ref = {}      # page id -> holders (sequences + registry)
        self._claims = {}   # seq_id -> worst-case pages reserved at
        # admission (see set_claim); outstanding_claims() is the
        # POOL-wide reservation view a multi-engine scheduler needs
        self._chains = {}   # chain_id -> in-flight KVChainHandle
        self._drawn = {}    # seq_id -> pages DRAWN from the pool (a
        # shared prefix page is held but was never drawn — reservation
        # accounting must compare against draws, see pages_drawn)
        # prefix registry: a trie of page-sized token blocks. Node ids
        # chain parent -> child; each node owns one registry hold on its
        # page. _lru orders nodes for reclaim (oldest unused first).
        self._chain_kids = {}   # parent id -> {token tuple: child id}
        self._chain_info = {}   # id -> {page, tokens, parent}
        self._lru = OrderedDict()  # id -> None (insertion/touch order)
        self._next_chain = _ROOT + 1
        self._stats = {"prefix_hits": 0, "prefix_hit_tokens": 0,
                       "prefix_misses": 0, "cow_copies": 0,
                       "prefix_evictions": 0, "pages_drawn": 0}

    # ---- allocator ----------------------------------------------------
    def add_sequence(self, seq_id):
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id!r} already present")
        self._tables[seq_id] = []
        self._len[seq_id] = 0
        self._drawn[seq_id] = 0

    def free_sequence(self, seq_id):
        """Release a sequence's holds. A page returns to the free list
        only when NO other holder (sequence or prefix registry) still
        references it — evicting one sharer never frees shared pages."""
        for page in self._tables.pop(seq_id):
            self._deref(page)
        self._len.pop(seq_id)
        self._drawn.pop(seq_id)
        self._claims.pop(seq_id, None)

    def length(self, seq_id):
        return self._len[seq_id]

    def n_free_pages(self):
        return len(self._free)

    def device_arrays(self):
        """The pool's live device arrays (per-layer K and V tables) —
        the memory observatory's attribution surface. List copy:
        callers iterate while the engine swaps layers functionally."""
        return list(self.k) + list(self.v)

    def n_evictable_pages(self):
        """Registered pages held ONLY by the registry — reclaimable on
        demand (prefix cache retention is best-effort memory). The
        registry is snapshot-copied (C-level list()) so lock-free
        telemetry readers (load_report) never race a mutation."""
        return sum(1 for info in list(self._chain_info.values())
                   if self._ref.get(info["page"], 0) == 1)

    def pages_needed(self, n_tokens):
        """Pages a FRESH sequence of n_tokens would consume, ignoring
        prefix-cache credit (admission subtracts `match_prefix`'s full
        pages itself — a partially-matched page earns no credit, its
        copy-on-write target falls inside this count)."""
        return -(-int(n_tokens) // self.page_size)

    def pages_held(self, seq_id):
        """Pages currently in a sequence's table (shared prefix pages
        count — each table slot is a hold)."""
        return len(self._tables[seq_id])

    def pages_drawn(self, seq_id):
        """Pages this sequence has DRAWN from the pool (fresh
        allocations + copy-on-write copies; acquired shared pages are
        NOT draws). Allocation is lazy, so a scheduler reserving worst
        cases must count each active sequence's outstanding claim as
        (reservation - drawn) — with prefix sharing, pages_held
        overstates draws by the acquired pages and would let claims
        vanish while copy-on-write + tail pages are still owed."""
        return self._drawn[seq_id]

    def shared_page_count(self):
        """Pages with more than one holder (sequences sharing a prefix,
        or a live page also retained by the prefix registry)."""
        return sum(1 for r in self._ref.values() if r > 1)

    def can_allocate(self, n_tokens, reserved=0):
        """Admission control: True when a new sequence of n_tokens fits
        the free list PLUS the prefix registry's evictable retention,
        AFTER `reserved` pages of outstanding claims. Allocation is
        lazy, so the free list alone overstates what is safely
        available: a scheduler reserving each request's worst case
        (prompt + max_new_tokens, credited with fully-matched prefix
        pages) must pass the sum of (reservation - pages_drawn) over
        its active sequences — with that term a mid-decode
        out-of-pages is impossible (see GenerationEngine._admit)."""
        return self.pages_needed(n_tokens) + int(reserved) \
            <= len(self._free) + self.n_evictable_pages()

    # ---- pool-wide admission claims ----------------------------------
    def set_claim(self, seq_id, n_pages):
        """Record a sequence's worst-case page reservation (admission
        time, AFTER prefix credit). The claim lives in the POOL, not
        the admitting engine: with several engines sharing one pool,
        each one's capacity gate must see every other's outstanding
        reservations (`outstanding_claims`). Cleared by free_sequence;
        carried through export_chain/adopt_chain."""
        if seq_id not in self._tables:
            raise KeyError(f"set_claim: unknown sequence {seq_id!r}")
        self._claims[seq_id] = int(n_pages)

    def outstanding_claims(self):
        """Σ max(claim - pages drawn, 0) over live claimed sequences
        PLUS in-flight exported chains — the pages admission promised
        but the pool has not handed out yet. Admission passing this as
        `reserved` to can_allocate (or subtracting it from the
        free+evictable supply) keeps mid-decode out-of-pages impossible
        even with multiple engines admitting against one pool.
        Snapshot-copies (C-level list()/dict()) make the read safe
        from any thread; admission itself calls it under `lock`."""
        drawn = dict(self._drawn)
        out = sum(max(c - drawn.get(s, 0), 0)
                  for s, c in list(self._claims.items()))
        out += sum(max(h.claim - h.drawn, 0)
                   for h in list(self._chains.values()))
        return out

    # ---- chain handoff (prefill/decode disaggregation) ----------------
    def export_chain(self, seq_id):
        """Detach a sequence's fully-written KV chain into a
        KVChainHandle WITHOUT touching refcounts or copying a single
        page: the handle inherits every page hold, the token length,
        the cumulative draw count, and the admission claim, and the
        sequence id disappears from the pool. The handoff unit of
        prefill/decode disaggregation — `adopt_chain` on the SAME pool
        reattaches it under a new sequence id, so the decode engine
        continues on the exact pages the prefill engine wrote."""
        handle = KVChainHandle(
            pages=self._tables.pop(seq_id),
            length=self._len.pop(seq_id),
            drawn=self._drawn.pop(seq_id),
            claim=self._claims.pop(seq_id, 0))
        self._chains[handle.chain_id] = handle
        return handle

    def adopt_chain(self, seq_id, chain):
        """Attach an exported chain to a FRESH sequence id on the SAME
        pool: page ids move, nothing is copied, refcounts are exactly
        what export_chain left (the handle's holds become the new
        sequence's holds), and the admission claim resumes under the
        new id. Returns the adopted token length."""
        if chain.consumed:
            raise ValueError("adopt_chain: chain handle already "
                             "consumed (adopted or released)")
        if self._chains.pop(chain.chain_id, None) is None:
            raise ValueError(
                "adopt_chain: chain was not exported from THIS pool — "
                "cross-pool handoff would need a device copy; share "
                "the PagedKVCache between the two engines instead")
        if seq_id in self._tables:
            raise ValueError(f"adopt_chain: sequence {seq_id!r} "
                             "already present")
        chain.consumed = True
        self._tables[seq_id] = chain.pages
        self._len[seq_id] = chain.length
        self._drawn[seq_id] = chain.drawn
        if chain.claim:
            self._claims[seq_id] = chain.claim
        return chain.length

    def release_chain(self, chain):
        """Drop an exported chain that will never be adopted (the
        decode side rejected the handoff): every page loses the
        handle's hold, the limbo claim disappears."""
        if chain.consumed:
            return
        chain.consumed = True
        self._chains.pop(chain.chain_id, None)
        for page in chain.pages:
            self._deref(page)

    def _deref(self, page):
        self._ref[page] -= 1
        if self._ref[page] == 0:
            del self._ref[page]
            self._free.append(page)

    def _alloc_page(self):
        if not self._free:
            self._reclaim(1)
        if not self._free:
            raise RuntimeError(
                f"PagedKVCache out of pages (free 0, evictable 0) — "
                "free finished sequences or grow n_pages")
        page = self._free.pop()
        self._ref[page] = 1
        self._stats["pages_drawn"] += 1  # cumulative pool draws (fresh
        # allocations + CoW copies — the one choke point every draw
        # passes through; pool_stats() reports it)
        return page

    def _materialize(self, seq_id, page_idx):
        """Copy-on-write: give seq_id a private copy of its table entry
        `page_idx` (device copy of the page in every layer's pool)."""
        old = self._tables[seq_id][page_idx]
        new = self._alloc_page()
        for layer in range(self.n_layers):
            self.k[layer] = _copy_page(self.k[layer], jnp.int32(old),
                                       jnp.int32(new))
            self.v[layer] = _copy_page(self.v[layer], jnp.int32(old),
                                       jnp.int32(new))
        self._tables[seq_id][page_idx] = new
        self._deref(old)
        self._drawn[seq_id] += 1
        self._stats["cow_copies"] += 1
        return new

    def _ensure_capacity(self, seq_id, n_new):
        """Make the next n_new token writes safe: enough pages appended
        to cover them, and every page in the write range OWNED (copy-
        on-write materialization of shared ones). Atomic: raises BEFORE
        touching the pool, so a caught allocation failure leaves it
        consistent (a scheduler can defer this sequence and admit a
        smaller one)."""
        P = self.page_size
        table = self._tables[seq_id]
        pos = self._len[seq_id]
        need = pos + n_new
        have = len(table) * P
        n_pages = -(-max(need - have, 0) // P)
        last = (need - 1) // P
        cow = [i for i in range(pos // P, min(len(table), last + 1))
               if self._ref[table[i]] > 1]
        # fast path first: n_evictable_pages() walks the whole prefix
        # registry, and this runs per row per decode step — only pay
        # the scan when the free list alone cannot cover the writes
        if n_pages + len(cow) > len(self._free) and \
                n_pages + len(cow) > len(self._free) \
                + self.n_evictable_pages():
            raise RuntimeError(
                f"PagedKVCache out of pages (need {n_pages + len(cow)}, "
                f"free {len(self._free)}, evictable "
                f"{self.n_evictable_pages()}) — free finished sequences "
                "or grow n_pages")
        for i in cow:
            self._materialize(seq_id, i)
        for _ in range(n_pages):
            table.append(self._alloc_page())
        self._drawn[seq_id] += n_pages

    # ---- prefix caching ----------------------------------------------
    def _walk_prefix(self, token_ids, max_tokens=None):
        """Longest registered chain matching token_ids[:max_tokens]:
        [(chain id, page, tokens taken)]. The final entry may take a
        page PARTIALLY (a divergence point or the max_tokens cap) — the
        sharer's first write there goes through copy-on-write."""
        tokens = [int(t) for t in np.asarray(token_ids).reshape(-1)]
        limit = len(tokens) if max_tokens is None \
            else min(len(tokens), int(max_tokens))
        out, parent, off = [], _ROOT, 0
        while off < limit:
            kids = self._chain_kids.get(parent)
            if not kids:
                break
            span = tokens[off:limit]
            exact = tuple(span[:self.page_size])
            cid = kids.get(exact) \
                if len(exact) == self.page_size else None
            if cid is not None:
                out.append((cid, self._chain_info[cid]["page"],
                            self.page_size))
                parent, off = cid, off + self.page_size
                continue
            best, best_n = None, 0
            for ktoks, kcid in kids.items():
                n = 0
                for a, b in zip(ktoks, span):
                    if a != b:
                        break
                    n += 1
                if n > best_n:
                    best, best_n = kcid, n
            if best is not None:
                out.append((best, self._chain_info[best]["page"], best_n))
            break
        return out

    def match_prefix(self, token_ids, max_tokens=None):
        """Peek (no side effects): (cached tokens, FULLY-matched pages)
        for this prompt. Admission credit = full pages only — a partial
        match still shares KV but its page will be copy-on-written, so
        it earns no reservation credit."""
        n, full, _ = self.match_prefix_credit(token_ids, max_tokens)
        return n, full

    def match_prefix_credit(self, token_ids, max_tokens=None):
        """match_prefix plus the supply-side correction a scheduler
        needs: (cached tokens, fully-matched pages, pinned). `pinned`
        counts matched pages currently held ONLY by the registry —
        today's evictable supply that acquire_prefix will PIN (ref 2).
        Admission must subtract it from the evictable pool or the
        prefix credit double-counts: the same pages would back both
        the reduced need AND the supply, over-admitting into a
        mid-decode out-of-pages."""
        chain = self._walk_prefix(token_ids, max_tokens)
        n = sum(took for _, _, took in chain)
        full = sum(1 for _, _, took in chain if took == self.page_size)
        pinned = sum(1 for _, page, _ in chain
                     if self._ref.get(page, 0) == 1)
        return n, full, pinned

    def acquire_prefix(self, seq_id, token_ids, max_tokens=None):
        """Attach the longest matching registered chain to a FRESH
        sequence (one hold per page) and set its length to the cached
        token count — the caller prefills only what remains. Returns
        the cached token count (0 = miss)."""
        if self._tables[seq_id] or self._len[seq_id]:
            raise ValueError(
                f"acquire_prefix: sequence {seq_id!r} is not fresh")
        chain = self._walk_prefix(token_ids, max_tokens)
        n = 0
        for cid, page, took in chain:
            self._tables[seq_id].append(page)
            self._ref[page] += 1
            self._lru.move_to_end(cid)
            n += took
        self._len[seq_id] = n
        if n:
            self._stats["prefix_hits"] += 1
            self._stats["prefix_hit_tokens"] += n
        else:
            self._stats["prefix_misses"] += 1
        return n

    def register_prefix(self, seq_id, token_ids):
        """Register a fully-written prompt's pages in the prefix
        registry (call AFTER the prompt's KV is in the pool). Each new
        node adds a registry hold, so the pages outlive the sequence —
        until LRU reclaim needs them back. Already-registered blocks
        (an earlier identical prompt) are only LRU-touched; the
        sequence's own duplicate pages stay private."""
        tokens = [int(t) for t in np.asarray(token_ids).reshape(-1)]
        if self._len[seq_id] < len(tokens):
            raise ValueError(
                f"register_prefix: sequence {seq_id!r} holds "
                f"{self._len[seq_id]} tokens < prompt {len(tokens)}")
        table = self._tables[seq_id]
        P = self.page_size
        parent, off, idx = _ROOT, 0, 0
        while off < len(tokens):
            took = min(P, len(tokens) - off)
            toks = tuple(tokens[off:off + took])
            kids = self._chain_kids.setdefault(parent, {})
            cid = kids.get(toks)
            if cid is None:
                cid = self._next_chain
                self._next_chain += 1
                kids[toks] = cid
                page = table[idx]
                self._chain_info[cid] = {"page": page, "tokens": toks,
                                         "parent": parent}
                self._ref[page] += 1
                self._lru[cid] = None
            else:
                self._lru.move_to_end(cid)
            if took < P:
                break  # a partial block is a leaf (children start
                # page-aligned), and nothing past the prompt registers
            parent, off, idx = cid, off + took, idx + 1

    def _evict_chain(self, cid):
        """Deregister the subtree rooted at cid (a parent's KV is
        useless for matching once gone). Pages drop their registry
        hold; those no live sequence shares free immediately.
        Iterative walk: a registered chain is one node per PAGE, so a
        long-context prompt would blow Python's recursion limit."""
        stack, subtree = [cid], []
        while stack:
            node = stack.pop()
            subtree.append(node)
            stack.extend(self._chain_kids.get(node, {}).values())
        for node in subtree:
            self._chain_kids.pop(node, None)
            info = self._chain_info.pop(node)
            parent_kids = self._chain_kids.get(info["parent"])
            if parent_kids is not None:
                parent_kids.pop(info["tokens"], None)
            self._lru.pop(node, None)
            self._stats["prefix_evictions"] += 1
            self._deref(info["page"])

    def _reclaim(self, n_pages):
        """Evict LRU prefix chains until n_pages are free (or the
        registry is empty — shared pages never free from under a live
        sequence, they only lose future matchability)."""
        while len(self._free) < n_pages and self._lru:
            self._evict_chain(next(iter(self._lru)))

    def prefix_stats(self):
        """Counters + current registry shape (hits/misses are per
        acquire_prefix call; hit_tokens the KV tokens served from
        cache; cow_copies the materialized divergences)."""
        return dict(self._stats,
                    registered_pages=len(self._chain_info),
                    shared_pages=self.shared_page_count(),
                    evictable_pages=self.n_evictable_pages())

    def pool_stats(self):
        """The pool observatory's snapshot (profiler/serve_observatory
        `record_pool_stats` emits it as a `kind:"kvcache"` record):
        instantaneous free/held/shared/registered/evictable page counts,
        the refcount histogram, prefix-registry size, and the cumulative
        draw / copy-on-write / LRU-reclaim counters. Pure host dict
        math — safe inside the serving hot loop (lint-fenced). Note
        free + held == n_pages - 1: the reserved pad page 0 is neither
        free nor held.

        Callable from ANY thread (debug bundles snapshot a live
        engine's pool mid-decode): the allocator dicts are copied
        first via C-level dict()/list() — which the decode thread
        cannot interleave — so iteration never races a mutation."""
        ref = dict(self._ref)
        chain = list(self._chain_info.values())
        refcounts = {}
        for r in ref.values():
            refcounts[r] = refcounts.get(r, 0) + 1
        reg_pages = {info["page"] for info in chain}
        return {
            "cache_strategy": "paged",
            "n_pages": int(self.n_pages),
            "page_size": int(self.page_size),
            "free_pages": len(self._free),
            "held_pages": len(ref),
            "shared_pages": sum(1 for r in ref.values() if r > 1),
            "registered_pages": len(reg_pages),
            "evictable_pages": sum(
                1 for info in chain if ref.get(info["page"], 0) == 1),
            "prefix_nodes": len(chain),
            "sequences": len(self._tables),
            "pages_drawn": int(self._stats["pages_drawn"]),
            "cow_copies": int(self._stats["cow_copies"]),
            "lru_reclaims": int(self._stats["prefix_evictions"]),
            "refcounts": {str(r): n
                          for r, n in sorted(refcounts.items())},
        }

    # ---- writes -------------------------------------------------------
    def extend(self, seq_id, layer, k_new, v_new):
        """Append k/v [T, H, D] for one layer. Call for every layer with
        the same T before advance()."""
        self._ensure_capacity(seq_id, k_new.shape[0])
        k_new = k_new.astype(self.k[layer].dtype)
        v_new = v_new.astype(self.v[layer].dtype)
        pos = self._len[seq_id]
        T = k_new.shape[0]
        P = self.page_size
        table = self._tables[seq_id]
        off = 0
        while off < T:
            page = table[(pos + off) // P]
            in_page = (pos + off) % P
            n = min(P - in_page, T - off)
            self.k[layer] = _write_block(
                self.k[layer], k_new[off:off + n][None],
                jnp.int32(page), jnp.int32(in_page))
            self.v[layer] = _write_block(
                self.v[layer], v_new[off:off + n][None],
                jnp.int32(page), jnp.int32(in_page))
            off += n

    def advance(self, seq_id, n_tokens):
        """Commit n_tokens appended to EVERY layer."""
        self._len[seq_id] += n_tokens

    def rollback(self, seq_id, n_tokens):
        """Un-commit the LAST n_tokens of seq_id: move the write cursor
        back without touching page tables, refcounts, or claims — the
        speculative-decoding rejection path (inference/speculative.py).

        Pages stay held (the admission claim already reserved them, and
        the cursor will advance over the same slots again next step);
        stale k/v past the cursor is dead by construction — every read
        is bounded by the pre-write length the ragged planner snapshots
        from `_len`, and the slots are overwritten before the cursor
        ever crosses them again. Shared (CoW) pages cannot be affected:
        `_ensure_capacity` materialized a private copy before any write
        in the rolled-back range, so a prefix sharer never observes a
        speculated-then-rejected token."""
        n_tokens = int(n_tokens)
        if n_tokens < 0:
            raise ValueError(f"rollback of {n_tokens} tokens")
        if seq_id not in self._len:
            raise KeyError(f"unknown sequence {seq_id!r}")
        if n_tokens > self._len[seq_id]:
            raise ValueError(
                f"rollback of {n_tokens} tokens exceeds sequence "
                f"{seq_id!r} length {self._len[seq_id]}")
        self._len[seq_id] -= n_tokens

    def plan_decode(self, seq_ids, pad_to=None):
        """Host-side plan for ONE fully-jitted decode step: allocate
        capacity for one new token per sequence and return
        (pages [B], in_pages [B], page_table [B, width], lengths [B])
        — the write coordinates and read views the jitted step needs.
        Lengths are the PRE-write token counts; call advance(sid, 1)
        after the step commits.

        pad_to > B pads the plan with rows that scatter into the
        reserved pad page 0 (in_page 0, empty table, length 0): a
        continuous-batching scheduler keeps the decode step's compiled
        shape FIXED while sequences join and leave the batch — pad-row
        outputs are garbage by construction and must be sliced off."""
        if len(set(seq_ids)) != len(seq_ids):
            # duplicates would scatter two rows to the same (page,
            # in_page) — one silently lost — then advance twice
            raise ValueError(f"duplicate seq_ids in decode batch: "
                             f"{seq_ids!r}")
        for s in seq_ids:
            self._ensure_capacity(s, 1)
        P = self.page_size
        B = len(seq_ids)
        n_pad = 0
        if pad_to is not None:
            if pad_to < B:
                raise ValueError(f"pad_to={pad_to} < batch size {B}")
            n_pad = int(pad_to) - B
        pages = np.asarray(
            [self._tables[s][self._len[s] // P] for s in seq_ids]
            + [0] * n_pad, np.int32)
        in_pages = np.asarray([self._len[s] % P for s in seq_ids]
                              + [0] * n_pad, np.int32)
        pt, lens = self.batch_views(seq_ids)
        if n_pad:
            pt = jnp.concatenate(
                [pt, jnp.zeros((n_pad, pt.shape[1]), jnp.int32)])
            lens = jnp.concatenate([lens, jnp.zeros((n_pad,), jnp.int32)])
        return jnp.asarray(pages), jnp.asarray(in_pages), pt, lens

    def plan_ragged(self, rows, pad_to_tokens=None, pad_to_rows=None,
                    q_heads=None):
        """Host-side plan for ONE jitted RAGGED step (the Pallas kernel
        in ops/pallas/paged_attention.py): `rows` is a list of
        (seq_id, n_new_tokens) mixing decode rows (1) and prefill
        chunks (n). Capacity is ensured (with copy-on-write) for every
        row, then per-token write coordinates and causal bounds come
        back as a dict of host arrays:

            tok_pages/tok_in_pages [T]  scatter coordinates
            token_seq [T]   row index into page_table per token
            positions [T]   absolute position (pre-write len + offset)
            bounds [T]      kv tokens visible (position + 1; 0 = pad)
            page_table [B, W] int32 (width pow2-bucketed, 0-padded)
            out_idx [B]     flat index of each row's LAST token
            n_tokens/n_rows the REAL counts before padding
            blk_pages/blk_seq/blk_start [QB, B*W], blk_n [QB]  the
                kernel's q-block kv-page walk (build_block_plan): per
                q-block, the compacted slot list its double-buffered
                DMA loop visits — planned HERE on the host so the
                serving scheduler stays free of device round-trips

        pad_to_tokens/pad_to_rows pad to fixed compiled shapes: pad
        tokens scatter into the reserved pad page with bound 0 — the
        kernel SKIPS them, so padding costs no attention work (the
        whole point vs plan_decode's bucket rows). Lengths are
        pre-write; advance(sid, n) after the step commits.

        q_heads: the model's QUERY head count when it exceeds this
        cache's kv heads (grouped-query attention) — the kernel folds
        the group into the q-block rows, so the block cap shrinks by
        the same factor; defaults to the kv head count (fold 1)."""
        sids = [s for s, _ in rows]
        if len(set(sids)) != len(sids):
            raise ValueError(f"duplicate seq_ids in ragged step: {sids!r}")
        for s, n in rows:
            if n < 1:
                raise ValueError(f"row {s!r}: n_new_tokens must be >= 1")
            self._ensure_capacity(s, n)
        P = self.page_size
        tok_pages, tok_in, tok_seq, tok_pos, bounds, out_idx = \
            [], [], [], [], [], []
        for i, (s, n) in enumerate(rows):
            start = self._len[s]
            table = self._tables[s]
            for k in range(n):
                pos = start + k
                tok_pages.append(table[pos // P])
                tok_in.append(pos % P)
                tok_seq.append(i)
                tok_pos.append(pos)
                bounds.append(pos + 1)
            out_idx.append(len(tok_pages) - 1)
        T, B = len(tok_pages), len(rows)
        n_tok_pad = 0
        if pad_to_tokens is not None:
            n_tok_pad = int(pad_to_tokens) - T
            if n_tok_pad < 0:
                raise ValueError(f"pad_to_tokens={pad_to_tokens} < {T}")
        n_row_pad = 0
        if pad_to_rows is not None:
            n_row_pad = int(pad_to_rows) - B
            if n_row_pad < 0:
                raise ValueError(f"pad_to_rows={pad_to_rows} < {B}")
        # host-built table (NOT batch_views: that returns a device
        # array, and a np.asarray round-trip here would be a blocking
        # D2H read in the decode hot loop)
        tables = [self._tables[s] for s in sids]
        width = max(1, max(len(t) for t in tables))
        width = 1 << (width - 1).bit_length()  # pow2 bucket, as views
        pt = np.zeros((B + n_row_pad, width), np.int32)
        for i, t in enumerate(tables):
            pt[i, :len(t)] = t
        # pad tokens: pad page 0 / slot 0, bound 0 (kernel skips), row
        # index pointing at a zeroed pad row when one exists
        pad_row = B if n_row_pad else 0
        tok_pages += [0] * n_tok_pad
        tok_in += [0] * n_tok_pad
        tok_seq += [pad_row] * n_tok_pad
        tok_pos += [0] * n_tok_pad
        bounds += [0] * n_tok_pad
        out_idx += [0] * n_row_pad
        bounds = np.asarray(bounds, np.int32)
        tok_seq = np.asarray(tok_seq, np.int32)
        # q-block plan for the blocked kernel — the same
        # choose_ragged_q_block the kernel wrapper would apply,
        # computed here so the serving step ships a ready-made plan (no
        # in-trace derivation, no device round-trips in the scheduler)
        from .pallas.attention_core import choose_ragged_q_block
        from .pallas.paged_attention import build_block_plan
        fold = max(int(q_heads or self.n_heads) // self.n_heads, 1)
        q_block = choose_ragged_q_block(len(bounds), fold)
        blk_pages, blk_seq, blk_start, blk_n = build_block_plan(
            pt, tok_seq, bounds, P, q_block)
        return {
            "tok_pages": np.asarray(tok_pages, np.int32),
            "tok_in_pages": np.asarray(tok_in, np.int32),
            "token_seq": tok_seq,
            "positions": np.asarray(tok_pos, np.int32),
            "bounds": bounds,
            "page_table": pt.astype(np.int32),
            "out_idx": np.asarray(out_idx, np.int32),
            "n_tokens": T,
            "n_rows": B,
            "blk_pages": blk_pages,
            "blk_seq": blk_seq,
            "blk_start": blk_start,
            "blk_n": blk_n,
        }

    # ---- reads --------------------------------------------------------
    def batch_views(self, seq_ids):
        """(page_table [B, width] i32, lengths [B] i32) for a decode
        batch — tables pad with the reserved page 0 and width rounds up
        to the next power of two, so the jitted attention compiles once
        per bucket instead of every time the longest sequence crosses a
        page boundary. Build ONCE per decode step and pass to attend()
        for every layer (the views are layer-independent)."""
        if not seq_ids:
            raise ValueError("batch_views() needs at least one sequence")
        tables = [self._tables[s] for s in seq_ids]
        width = max(1, max(len(t) for t in tables))
        width = 1 << (width - 1).bit_length()  # bucket: power of two
        pt = np.zeros((len(seq_ids), width), np.int32)
        for i, t in enumerate(tables):
            pt[i, :len(t)] = t
        lens = np.asarray([self._len[s] for s in seq_ids], np.int32)
        return jnp.asarray(pt), jnp.asarray(lens)

    def attend(self, layer, q, seq_ids=None, views=None):
        """Decode attention for one layer: q [B, H, D] against each
        sequence's paged history. Pass `views=batch_views(seq_ids)`
        (computed once per step) to avoid rebuilding the host-side
        tables + H2D transfer per layer."""
        if views is None:
            views = self.batch_views(seq_ids)
        pt, lens = views
        return paged_attention(q, self.k[layer], self.v[layer], pt, lens)
