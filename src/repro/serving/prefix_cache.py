"""Prefix-aware KV reuse for chunked prefill: a token-level radix trie over
chunk-aligned prompt prefixes.

Real serving traffic is dominated by shared prompt prefixes (system
prompts, RAG context, few-shot headers).  The chunked-prefill architecture
makes prefix reuse unusually cheap to make *exact*: scoring is causal and
streamed per chunk, so at any chunk boundary the pair

    (materialized KV buffer columns [0, n),  scoring.ScoreState)

is a pure function of the first ``n`` prompt tokens — bit-identical across
every request that shares them (per-request randomness such as
``Request.seed`` never touches streamed state; the random policy folds
seeds in at finalize).  A cache hit therefore skips not just the prefix's
attention FLOPs but its eviction-score accumulation too, and the resumed
request still finishes with exactly the tokens and kept sets it would have
produced uncached (``tests/test_prefix_cache.py`` proves this
differentially over randomized traces).

Structure
---------
``PrefixCache`` is a radix trie (compressed token edges) whose nodes may
carry an entry at *chunk-aligned* depths only — partial-chunk prefixes are
never cached and never match.  Each entry owns

* the KV **block** spanning ``(parent_entry.depth, depth]`` — blocks are
  deduplicated along the chain, so a 3-chunk entry and a 2-chunk entry
  sharing two chunks store those two chunks once;
* a full (trimmed) ``ScoreState`` snapshot at its boundary;
* the last chunk's next-token logits, so a prompt that *is* a cached
  prefix admits with zero prefill chunks (TTFT ~ one finalize).

Entries are refcounted: ``refs`` counts child entries (a parent's blocks
are part of every descendant's chain) plus in-flight pins
(``acquire``/``release`` around a request's streaming prefill).  Eviction
is LRU over unpinned, childless entries under a byte budget — the budget
is respected after every insert, and an insert that cannot fit by evicting
unpinned entries is simply skipped (the request still serves; it just
doesn't populate the cache).

With a ``KVBlockPool`` bound (paged serving), an entry's KV span lives as
a **pinned run of pool blocks** instead of a private device copy: shared
prefixes occupy the same physical pool the decode caches draw from (one
copy, refcount-shared along the chain via parent entries), the
``max_bytes`` budget caps how much of the pool the cache may pin, and the
engine reclaims unpinned entries on demand when live requests need the
blocks — cached prefixes never outrank running traffic.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as tf
from repro.serving.kv_pool import from_blocks

__all__ = ["PrefixCache", "PrefixEntry"]


class _Node:
    """Radix-trie node: a compressed token edge, children keyed by their
    edge's first token, and (at chunk-aligned depths) a cache entry."""

    __slots__ = ("edge", "children", "entry", "depth", "parent")

    def __init__(self, edge: np.ndarray, depth: int,
                 parent: Optional["_Node"]):
        self.edge = edge  # (len,) int token segment labelling the in-edge
        self.children: dict[int, _Node] = {}
        self.entry: Optional[PrefixEntry] = None
        self.depth = depth  # tokens from root through this edge
        self.parent = parent


class PrefixEntry:
    """One cached chunk-boundary snapshot (see module docstring)."""

    __slots__ = ("depth", "start", "parent", "k_block", "v_block", "blocks",
                 "score", "logits", "nbytes", "refs", "node", "src_capacity")

    def __init__(self, *, depth, start, parent, score, logits, node,
                 src_capacity, k_block=None, v_block=None, blocks=None,
                 block_bytes=0):
        self.depth = depth  # prefix length (chunk-aligned)
        self.start = start  # parent entry's depth; blocks cover [start, depth)
        self.parent: Optional[PrefixEntry] = parent
        # KV-buffer depth the donor streamed under.  Bit-exactness of a
        # resumed prefill is guaranteed only when the requester computes
        # under the *same* buffer shape (identical compiled programs,
        # identical reduction order) — lookup filters on it, and chains are
        # capacity-homogeneous by construction (insert only links parents
        # of the same src_capacity), so a hit never mixes rungs.
        self.src_capacity = src_capacity
        self.k_block = k_block  # (L, 1, depth-start, KV, hd), or None when
        self.v_block = v_block  # the span lives in the shared block pool:
        self.blocks = blocks  # (n,) int32 pinned pool block ids
        self.score = score  # trimmed scoring.ScoreState at ``depth``
        self.logits = logits  # (1, V) last-chunk logits (row depth-1)
        self.node = node
        self.refs = 0  # child entries + in-flight pins; evictable at 0
        if k_block is not None:
            span_bytes = k_block.nbytes + v_block.nbytes
        else:  # pool-backed: caller sizes the span in whole blocks
            span_bytes = (0 if blocks is None else len(blocks)) * block_bytes
        self.nbytes = (
            span_bytes + logits.nbytes
            + sum(leaf.nbytes for leaf in jax.tree.leaves(score))
        )


def _common_len(a: np.ndarray, b: np.ndarray) -> int:
    n = min(len(a), len(b))
    neq = np.nonzero(a[:n] != b[:n])[0]
    return int(neq[0]) if len(neq) else n


class PrefixCache:
    """Radix-trie prompt cache with refcount pinning, LRU order, and a hard
    byte budget.  One cache serves one ``(chunk, policy, model)`` binding —
    the engine asserts/binds on construction (``ScoreState`` is
    policy-shaped; chunk alignment defines which depths are cacheable)."""

    def __init__(self, *, chunk: int, max_bytes: int,
                 policy: Optional[str] = None, pool=None):
        assert chunk > 0 and max_bytes > 0
        self.chunk = chunk
        self.max_bytes = max_bytes
        self.policy = policy  # bound by the first engine that adopts it
        # paged mode: entry KV spans are pinned runs of this KVBlockPool's
        # blocks (one physical copy shared with decode) instead of private
        # device arrays.  Chunk boundaries must land on block boundaries.
        self.pool = pool
        if pool is not None:
            assert chunk % pool.block_size == 0, \
                "chunk must be a multiple of the pool block size"
        # the bound params tree, held strongly: identity (``is``) stays
        # valid for the cache's lifetime (a bare id() could be reused
        # after GC and let a different model's weights silently pass)
        self._model = None
        self._root = _Node(np.zeros(0, np.int32), 0, None)
        self._lru: OrderedDict[PrefixEntry, None] = OrderedDict()
        # jitted chain-concat programs keyed (block spans, capacity): hot
        # prefixes rematerialize through one fused program instead of a
        # string of eagerly dispatched concat/pad ops (full-hit TTFT).
        # LRU-bounded so long-lived servers with varied chain shapes don't
        # retain compiled programs forever.  A dropped shape recompiles on
        # its next materialize — which can be a hit — so the cap sits well
        # above realistic chain-shape counts (chains are (chunk,)*n for
        # n <= max_context/chunk, times a handful of capacity rungs).
        self._mat_fns: OrderedDict = OrderedDict()
        self.max_materialize_programs = 128
        self.bytes = 0
        self.hits = 0
        self.misses = 0
        self.inserts = 0
        self.evictions = 0
        self.tokens_hit = 0  # prefix tokens served from cache

    # -- binding ---------------------------------------------------------
    def bind(self, *, chunk: int, policy: str, model=None) -> None:
        """Adopt (or verify) the serving binding; a cache never serves two
        policies, chunk sizes, or models — its snapshots would not be
        resumable.  ``model`` is the params tree itself (engines sharing
        one cache must share the same parameter object)."""
        assert chunk == self.chunk, \
            f"cache built for chunk {self.chunk}, engine uses {chunk}"
        assert self.policy in (None, policy), \
            f"cache bound to policy {self.policy!r}, engine uses {policy!r}"
        assert model is None or self._model is None or self._model is model, \
            "cache bound to a different model's parameters — snapshots " \
            "computed under one set of weights cannot serve another"
        self.policy = policy
        if model is not None:
            self._model = model

    # -- lookup / pinning ------------------------------------------------
    def lookup(self, prompt: np.ndarray,
               capacity: Optional[int] = None) -> Optional[PrefixEntry]:
        """Deepest cached chunk-aligned prefix of ``prompt`` (token-exact),
        or None.  With ``capacity`` given, only entries whose donor
        streamed under that same KV-buffer depth match — the condition
        under which the resumed state is bitwise what the requester would
        have computed itself.  Touches the hit chain's LRU recency; the
        caller pins the returned entry with ``acquire`` while resuming."""
        prompt = np.asarray(prompt)
        node, pos = self._root, 0
        best: Optional[PrefixEntry] = None
        while pos < len(prompt):
            child = node.children.get(int(prompt[pos]))
            if child is None:
                break
            m = _common_len(child.edge, prompt[pos:])
            if m < len(child.edge):
                break  # partial edge: no entry can sit mid-edge
            pos += m
            node = child
            if node.entry is not None and (
                    capacity is None
                    or node.entry.src_capacity == capacity):
                best = node.entry
        if best is None:
            self.misses += 1
            return None
        self.hits += 1
        self.tokens_hit += best.depth
        e = best
        while e is not None:  # whole chain was effectively used
            self._lru.move_to_end(e)
            e = e.parent
        return best

    def acquire(self, entry: PrefixEntry) -> None:
        """Pin ``entry`` (and, transitively via child refs, its chain)."""
        entry.refs += 1

    def release(self, entry: PrefixEntry) -> None:
        assert entry.refs > 0, "refcount underflow"
        entry.refs -= 1

    # -- insert ----------------------------------------------------------
    def insert(
        self,
        prefix: np.ndarray,  # the first ``depth`` prompt tokens
        *,
        state: tf.ChunkState,  # streaming state with pos >= len(prefix)
        logits: jnp.ndarray,  # (1, V) the boundary chunk's logits
        parent: Optional[PrefixEntry] = None,  # the request's current tip
    ) -> Optional[PrefixEntry]:
        """Cache the chunk boundary at ``len(prefix)``.  Returns the entry
        (existing or new, unpinned — the caller re-pins), or None when the
        byte budget cannot admit it or the boundary is already cached for
        a different KV-buffer depth (rung) than ``state`` streams under —
        chains stay capacity-homogeneous so hits are bitwise sound.

        A freshly created entry's chain-materialize program is built here
        (via one throwaway materialize) rather than on the first hit: the
        chain shape is fixed at insert, and a hit at the entry always
        materializes at its ``src_capacity``, so hits never pay the
        compile on the TTFT path.  The cost lands on cold misses instead —
        a first-seen prompt depth compiles one program per novel chain
        shape during its own (already slow, streaming) prefill; shapes are
        shared process-wide, so warm traffic never compiles."""
        prefix = np.asarray(prefix)
        depth = len(prefix)
        src_capacity = state.k.shape[2]
        assert depth > 0 and depth % self.chunk == 0, \
            "only whole-chunk prefixes are cacheable"
        assert parent is None or parent.src_capacity == src_capacity, \
            "chain would mix KV-buffer rungs"
        node = self._insert_node(prefix)
        if node.entry is not None:
            if node.entry.src_capacity != src_capacity:
                return None  # boundary owned by another rung's snapshot
            self._lru.move_to_end(node.entry)
            return node.entry
        start = parent.depth if parent is not None else 0
        if self.pool is not None:
            nblk = (depth - start) // self.pool.block_size
            entry = PrefixEntry(
                depth=depth, start=start, parent=parent, blocks=None,
                block_bytes=self.pool.block_bytes,
                score=state.score.snapshot(depth), logits=logits, node=node,
                src_capacity=src_capacity,
            )
            entry.nbytes += nblk * self.pool.block_bytes
            if not self._make_room(entry.nbytes):
                self._prune_node(node)
                return None
            ids = self.pool.alloc(nblk)
            if ids is None:
                # budget ok but the pool itself is consumed by live decode
                # caches — running traffic outranks cached prefixes
                self._prune_node(node)
                return None
            self.pool.write_span(state.k[:, :, start:depth],
                                 state.v[:, :, start:depth], ids)
            self.pool.note_pinned(nblk)
            entry.blocks = ids
        else:
            entry = PrefixEntry(
                depth=depth, start=start, parent=parent,
                k_block=state.k[:, :, start:depth],
                v_block=state.v[:, :, start:depth],
                score=state.score.snapshot(depth), logits=logits, node=node,
                src_capacity=src_capacity,
            )
            if not self._make_room(entry.nbytes):
                self._prune_node(node)  # drop the entry-less leaf we created
                return None
        node.entry = entry
        if parent is not None:
            parent.refs += 1
        self._lru[entry] = None
        self.bytes += entry.nbytes
        self.inserts += 1
        if self.pool is not None:
            key = ("pool", depth // self.pool.block_size, src_capacity)
        else:
            spans = tuple(c.depth - c.start for c in self._chain(entry))
            key = (spans, src_capacity)
        if key not in self._mat_fns:
            self.materialize(entry, src_capacity)  # compile + warm
        return entry

    def _insert_node(self, tokens: np.ndarray) -> _Node:
        """Walk/extend the trie to the node ending exactly at ``tokens``,
        splitting edges as needed."""
        node, pos = self._root, 0
        while pos < len(tokens):
            first = int(tokens[pos])
            child = node.children.get(first)
            if child is None:
                new = _Node(tokens[pos:].copy(), len(tokens), node)
                node.children[first] = new
                return new
            m = _common_len(child.edge, tokens[pos:])
            if m == len(child.edge):
                pos += m
                node = child
                continue
            # split the edge at the divergence (or early-end) point
            split = _Node(child.edge[:m].copy(), node.depth + m, node)
            child.edge = child.edge[m:]
            child.parent = split
            split.children[int(child.edge[0])] = child
            node.children[first] = split
            pos += m
            node = split
        return node

    # -- eviction --------------------------------------------------------
    def _protected_bytes(self) -> int:
        """Bytes that eviction can never reclaim right now: entries with an
        in-flight pin plus their ancestor chains (child refs alone cascade
        away once the leaves go; pins do not)."""
        children: dict[int, int] = {}
        for e in self._lru:
            if e.parent is not None:
                children[id(e.parent)] = children.get(id(e.parent), 0) + 1
        protected: set[int] = set()
        for e in self._lru:
            if e.refs > children.get(id(e), 0):  # has at least one pin
                a: Optional[PrefixEntry] = e
                while a is not None and id(a) not in protected:
                    protected.add(id(a))
                    a = a.parent
        return sum(e.nbytes for e in self._lru if id(e) in protected)

    def _make_room(self, need: int) -> bool:
        if self.bytes + need <= self.max_bytes:
            return True
        # feasibility first: refuse before evicting anything, so a doomed
        # insert can't churn cached prefixes it gains nothing from
        if self._protected_bytes() + need > self.max_bytes:
            return False
        while self.bytes + need > self.max_bytes:
            if not self._evict_one():
                return False  # defensive; feasibility said this can't hit
        return True

    def _evict_one(self) -> bool:
        for entry in self._lru:  # OrderedDict iterates LRU -> MRU
            if entry.refs == 0:
                self._remove(entry)
                return True
        return False

    def _remove(self, entry: PrefixEntry) -> None:
        assert entry.refs == 0, "evicting a pinned or parented entry"
        del self._lru[entry]
        self.bytes -= entry.nbytes
        self.evictions += 1
        entry.node.entry = None
        if entry.blocks is not None:  # return the pinned run to the pool
            self.pool.free(entry.blocks)
            self.pool.note_pinned(-len(entry.blocks))
            entry.blocks = None
        if entry.parent is not None:
            self.release(entry.parent)
        self._prune_node(entry.node)

    # -- pool reclaim (paged serving) -------------------------------------
    def evictable_pool_blocks(self) -> int:
        """Pool blocks reclaimable *right now* (unpinned childless
        entries).  An underestimate — evicting a leaf can make its parent
        evictable — which only makes the admission gate conservative."""
        if self.pool is None:
            return 0
        return sum(len(e.blocks) for e in self._lru
                   if e.refs == 0 and e.blocks is not None)

    def evict_pool_blocks(self, need: int) -> bool:
        """Evict LRU unpinned entries until at least ``need`` pool blocks
        returned to the free list (cascading up freed chains).  Returns
        True iff the need was fully met — live decode traffic calls this
        when the pool runs dry, so cached prefixes yield to admissions."""
        if self.pool is None:
            return False
        freed = 0
        while freed < need:
            victim = next((e for e in self._lru
                           if e.refs == 0 and e.blocks is not None), None)
            if victim is None:
                return False
            freed += len(victim.blocks)
            self._remove(victim)
        return True

    @staticmethod
    def _prune_node(node: _Node) -> None:
        """Drop now-useless trie nodes (no entry, no children) so token
        edges don't leak host memory — after an eviction and after a
        budget-rejected insert alike."""
        while (node.parent is not None and node.entry is None
               and not node.children):
            del node.parent.children[int(node.edge[0])]
            node = node.parent

    # -- materialization -------------------------------------------------
    @staticmethod
    def _chain(entry: PrefixEntry) -> list:
        chain = []
        e: Optional[PrefixEntry] = entry
        while e is not None:
            chain.append(e)
            e = e.parent
        chain.reverse()
        return chain

    def materialize(self, entry: PrefixEntry, capacity: int
                    ) -> tuple[tf.ChunkState, jnp.ndarray]:
        """Rebuild a resumable ``ChunkState`` (capacity-deep buffers,
        ``pos = entry.depth``) from the entry's block chain, plus the
        boundary logits (the next-token distribution when the requesting
        prompt is exactly the cached prefix)."""
        chain = self._chain(entry)
        depth = entry.depth
        if self.pool is not None:
            # pool-backed: the whole prefix is one block-id gather — the
            # chain's runs concatenate in depth order, and gathers are
            # exact, so the resumed state is bitwise the streamed one
            ids = np.concatenate([c.blocks for c in chain])
            key = ("pool", len(ids), capacity)
            fn = self._mat_fns.get(key)
            if fn is None:
                bs = self.pool.block_size

                def build(pk, pv, ids, score):
                    def flat(x):  # (L, n, KV, bs, hd) -> (L, 1, depth, ...)
                        return from_blocks(x)[:, None]

                    snap = tf.ChunkState(
                        k=flat(pk[:, ids]), v=flat(pv[:, ids]), score=score,
                        pos=jnp.asarray(len(ids) * bs, jnp.int32))
                    return tf.resume_chunk_state(snap, capacity)

                fn = jax.jit(build)
                self._mat_fns[key] = fn
                while len(self._mat_fns) > self.max_materialize_programs:
                    self._mat_fns.popitem(last=False)
            else:
                self._mat_fns.move_to_end(key)
            state = fn(self.pool.k, self.pool.v, jnp.asarray(ids),
                       entry.score)
            return state, entry.logits
        spans = tuple(c.depth - c.start for c in chain)
        fn = self._mat_fns.get((spans, capacity))
        if fn is None:
            def build(ks, vs, score):
                snap = tf.ChunkState(
                    k=jnp.concatenate(ks, axis=2),
                    v=jnp.concatenate(vs, axis=2),
                    score=score, pos=jnp.asarray(depth, jnp.int32))
                return tf.resume_chunk_state(snap, capacity)

            fn = jax.jit(build)
            self._mat_fns[(spans, capacity)] = fn
            while len(self._mat_fns) > self.max_materialize_programs:
                self._mat_fns.popitem(last=False)
        else:
            self._mat_fns.move_to_end((spans, capacity))
        state = fn([c.k_block for c in chain], [c.v_block for c in chain],
                   entry.score)
        return state, entry.logits

    # -- observability ---------------------------------------------------
    def stats(self) -> dict:
        total = self.hits + self.misses
        pool_blocks = (sum(len(e.blocks) for e in self._lru
                           if e.blocks is not None)
                       if self.pool is not None else 0)
        return {
            "entries": len(self._lru),
            "pool_blocks_pinned": pool_blocks,
            "materialize_programs": len(self._mat_fns),
            "bytes": self.bytes,
            "max_bytes": self.max_bytes,
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": self.hits / total if total else 0.0,
            "inserts": self.inserts,
            "evictions": self.evictions,
            "tokens_hit": self.tokens_hit,
        }

    def bind_metrics(self, registry) -> None:
        """Mirror ``stats()`` as ``prefix_cache_*`` callback gauges on the
        engine's registry (collection-time reads, no hot-path writes)."""
        from repro.obs.metrics import bind_stat_gauges
        bind_stat_gauges(registry, "prefix_cache", self.stats)
