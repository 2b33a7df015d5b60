"""Paged KV memory: a global device-resident block pool for decode caches.

The eviction paper's value proposition is a *smaller* KV cache — but a
dense slot cache pads every request to one uniform ``capacity + margin``
depth, so evicted positions free zero device bytes and concurrency is
fixed at engine construction.  ``KVBlockPool`` converts eviction quality
into actual capacity: the decode KV of every live request lives in
fixed-size **blocks** drawn from one shared pool, a request only holds
blocks for rows it actually uses (kept post-eviction rows plus the decode
tokens generated so far), and retiring / preempting a request returns its
blocks to the free list for the next admission.  Better eviction → fewer
kept rows → fewer blocks per request → more concurrent requests at a
fixed ``--kv-pool-mb`` byte budget.

Layout (head-major blocks, per layer)
------------------------------------
One ``(num_blocks, kv_heads, block_size, head_dim)`` array per layer for
each of K and V (stacked along a leading ``L`` axis so the decode layer
scan strips it), plus matching ``(num_blocks, kv_heads, block_size)``
``pos``/``mask`` metadata — eviction keeps *different token positions per
kv head*, so validity is per-head exactly as in the dense cache.  Blocks
are head-major so that one kv head's rows of a block form a contiguous
``(block_size, head_dim)`` tile — the shape the TPU decode kernel DMAs.
A request's **block table** is a ``(nb,)`` int32 row of physical block
ids: logical cache row ``c`` of kv head ``h`` lives at
``(table[c // bs], h, c % bs)``.  The table
is shared across layers (block ``j`` holds the same logical rows of every
layer), so one table gather reconstructs the whole per-slot view.

Block 0 is the reserved **null block**: never allocated, its mask rows
are permanently False.  Unallocated table entries point at it, so a
ragged table (kept rows << capacity, appends not yet grown) reads as a
dense cache whose missing rows are simply masked invalid — the property
that makes paged decode bit-identical to the dense path.

Allocation is host-side (a free list + per-block refcounts — refcounts
let prefix-cache entries share one physical copy of a common prompt
prefix across requests); all device mutation goes through the jitted
write helpers below, keyed by block count so a serving lifetime compiles
O(distinct admission sizes) tiny scatter programs.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import ModelConfig

__all__ = ["KVBlockPool", "to_blocks", "from_blocks"]


def to_blocks(x: jnp.ndarray, n: int, bs: int) -> jnp.ndarray:
    """Dense rows (L, rows, KV, ...) -> pool blocks (L, n, KV, bs, ...);
    rows are zero-padded (or cut) to ``n * bs``."""
    rows = n * bs
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, max(rows - x.shape[1], 0))
    x = jnp.pad(x, pad)[:, :rows]
    x = x.reshape((x.shape[0], n, bs) + x.shape[2:])
    return jnp.swapaxes(x, 2, 3)


def from_blocks(g: jnp.ndarray) -> jnp.ndarray:
    """Pool blocks (L, n, KV, bs, ...) -> dense rows (L, n*bs, KV, ...)."""
    g = jnp.swapaxes(g, 2, 3)
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


class KVBlockPool:
    """Global paged KV store: device block arrays + a host free-list
    allocator with per-block refcounts.

    Exactly one of ``num_blocks`` / ``pool_mb`` sizes the pool; ``pool_mb``
    counts K+V payload bytes (the headline the paper budgets), with the
    int32/bool ``pos``/``mask`` metadata reported separately in
    ``stats()``.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        *,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        pool_mb: Optional[float] = None,
        mesh=None,
    ):
        assert cfg.attn is not None, "paged KV serves attention archs"
        assert block_size > 0
        a = cfg.attn
        L, KV, hd = cfg.num_layers, a.num_kv_heads, a.head_dim
        dtype = jnp.dtype(cfg.dtype)
        self.block_size = block_size
        # K+V payload bytes of one block across all layers
        self.block_bytes = 2 * L * block_size * KV * hd * dtype.itemsize
        if num_blocks is None:
            assert pool_mb is not None, "size the pool: num_blocks or pool_mb"
            num_blocks = int(pool_mb * (1 << 20)) // self.block_bytes
        num_blocks += 1  # block 0 is the reserved null block
        assert num_blocks >= 2, "pool too small for even one block"
        self.num_blocks = num_blocks
        N = num_blocks
        self.k = jnp.zeros((L, N, KV, block_size, hd), dtype)
        self.v = jnp.zeros((L, N, KV, block_size, hd), dtype)
        self.pos = jnp.zeros((L, N, KV, block_size), jnp.int32)
        self.mask = jnp.zeros((L, N, KV, block_size), bool)
        # tensor-parallel serving: the device arrays shard their kv-head
        # dim over "model" (each shard holds whole blocks of its local
        # head slice), while the host allocator below is head-oblivious —
        # every block id means the same rows on every shard, so the free
        # list and block tables need no mesh awareness at all.
        self.mesh = None
        self.model_shards = 1
        if mesh is not None:
            from repro.common.sharding import pool_specs

            specs = pool_specs(cfg, mesh)
            assert specs is not None, (
                f"kv heads ({KV}) must divide the model axis "
                f"({dict(getattr(mesh, 'shape', {}))}) to shard the pool")
            self.mesh = mesh
            self.model_shards = int(mesh.shape["model"])
            put = {
                n: jax.device_put(
                    getattr(self, n),
                    jax.sharding.NamedSharding(mesh, specs[n]))
                for n in ("k", "v", "pos", "mask")
            }
            self.set_tree(put)
        # host allocator state: ids 1..N-1 are allocatable
        self._free: list[int] = list(range(N - 1, 0, -1))
        self._refs = np.zeros(N, np.int32)
        # blocks promised to admitted requests' future decode appends but
        # not yet handed out — ordinary allocs may not dip into them, so
        # an admitted request can always grow to its cap without
        # preempting anyone (the preempt path stays as the safety valve
        # for optimistic admission, see ContinuousEngine.reserve_appends)
        self.reserved = 0
        self.high_water = 0  # peak blocks in use over the pool's lifetime
        self.pinned_blocks = 0  # blocks held by prefix-cache entries
        # blocks returned mid-generation by decode-eviction sweeps (the
        # engine's evict-and-compact step) — retirement frees not included
        self.blocks_reclaimed_decode = 0
        self._write_fns: dict = {}  # jitted scatter programs, keyed by shape

    # -- geometry ---------------------------------------------------------
    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1  # minus the null block

    def blocks_for(self, rows: int) -> int:
        """Blocks needed to hold ``rows`` logical cache rows."""
        return -(-max(rows, 0) // self.block_size)

    def free_blocks(self) -> int:
        return len(self._free)

    def available_blocks(self) -> int:
        """Free blocks not promised to an admitted request's growth."""
        return len(self._free) - self.reserved

    def used_blocks(self) -> int:
        return self.usable_blocks - len(self._free)

    # -- allocator --------------------------------------------------------
    def alloc(self, n: int, *,
              from_reserved: bool = False) -> Optional[np.ndarray]:
        """Take ``n`` blocks (each with refcount 1), or None if the free
        list cannot cover them — the caller decides whether to preempt,
        evict a prefix entry, or queue-wait.  Never partially allocates.

        ``from_reserved`` redeems part of an earlier ``reserve``: it may
        consume the promised headroom ordinary allocations must not touch.
        """
        assert n >= 0
        limit = len(self._free) if from_reserved \
            else len(self._free) - self.reserved
        if n > limit:
            return None
        if from_reserved:
            assert self.reserved >= n, "redeeming more than was reserved"
            self.reserved -= n
        ids = np.asarray([self._free.pop() for _ in range(n)], np.int32)
        self._refs[ids] = 1
        self.high_water = max(self.high_water, self.used_blocks())
        return ids

    def reserve(self, n: int) -> bool:
        """Promise ``n`` free blocks to a request's future appends (its
        decode growth can then never run the pool dry).  False when the
        unreserved headroom cannot cover the promise."""
        assert n >= 0
        if n > len(self._free) - self.reserved:
            return False
        self.reserved += n
        return True

    def unreserve(self, n: int) -> None:
        """Return an unredeemed promise (retirement / preemption)."""
        assert 0 <= n <= self.reserved
        self.reserved -= n

    def incref(self, ids) -> None:
        """Share blocks (prefix-cache chains): one more owner per block."""
        ids = np.asarray(ids, np.int32)
        assert (self._refs[ids] > 0).all(), "incref of an unallocated block"
        self._refs[ids] += 1

    def free(self, ids) -> None:
        """Drop one reference per block; blocks return to the free list at
        refcount zero.  Double-frees fail loudly — a freed block may
        already belong to another request."""
        for b in np.asarray(ids, np.int32).tolist():
            assert b != 0, "freeing the null block"
            assert self._refs[b] > 0, f"double-free of block {b}"
            self._refs[b] -= 1
            if self._refs[b] == 0:
                self._free.append(int(b))

    def free_run(self, ids) -> None:
        """Return a *partial* block run of a **live** request — the tail
        blocks a decode-eviction sweep compacted away mid-generation.
        Semantically ``free`` (the request keeps its remaining blocks and
        its slot), tracked separately as ``blocks_reclaimed_decode`` so
        observability distinguishes eviction-driven reclaim from ordinary
        retirement frees."""
        ids = np.asarray(ids, np.int32)
        self.free(ids)
        self.blocks_reclaimed_decode += len(ids)

    def note_pinned(self, delta: int) -> None:
        """Prefix-cache accounting: blocks pinned by resident prompt-prefix
        entries (they are allocated, but no decode slot owns them)."""
        self.pinned_blocks += delta
        assert self.pinned_blocks >= 0

    # -- device views -----------------------------------------------------
    def tree(self) -> dict:
        """The pool's device arrays as the pytree the paged decode step
        consumes (and returns updated — see ``set_tree``)."""
        return {"k": self.k, "v": self.v, "pos": self.pos, "mask": self.mask}

    def set_tree(self, tree: dict) -> None:
        self.k, self.v = tree["k"], tree["v"]
        self.pos, self.mask = tree["pos"], tree["mask"]

    # -- jitted device mutation -------------------------------------------
    def write_cache(self, attn_cache: dict, ids: np.ndarray) -> None:
        """Scatter a freshly admitted request's dense decode cache (the
        ``prefill_finalize`` output: k/v (L, 1, C, KV, hd), pos/mask
        (L, 1, C, KV)) into blocks ``ids`` — rows [0, len(ids)·bs), i.e.
        every row up to the last valid kept row, rounded up to whole
        blocks.  Rows past C pad with mask=False (a partial tail block)."""
        n = len(ids)
        assert n > 0
        fn = self._write_fns.get(("cache", n))
        if fn is None:
            bs = self.block_size

            def write(pool, cache, ids):
                def blk(x):  # (L, 1, C, KV, ...) -> (L, n, KV, bs, ...)
                    return to_blocks(x[:, 0], len(ids), bs)

                return {
                    "k": pool["k"].at[:, ids].set(blk(cache["k"])),
                    "v": pool["v"].at[:, ids].set(blk(cache["v"])),
                    "pos": pool["pos"].at[:, ids].set(blk(cache["pos"])),
                    "mask": pool["mask"].at[:, ids].set(blk(cache["mask"])),
                }

            fn = jax.jit(write)
            self._write_fns[("cache", n)] = fn
        self.set_tree(fn(self.tree(), attn_cache, jnp.asarray(ids)))

    def write_span(self, k: jnp.ndarray, v: jnp.ndarray,
                   ids: np.ndarray) -> None:
        """Store a prefix-cache span — streaming-prefill KV columns
        (L, 1, span, KV, hd) with span = len(ids)·bs — into blocks
        ``ids``.  Only K/V payload: prefix blocks never enter a slot's
        block table, so their pos/mask metadata is never read."""
        n = len(ids)
        assert n > 0 and k.shape[2] == n * self.block_size
        fn = self._write_fns.get(("span", n))
        if fn is None:
            bs = self.block_size

            def write(pk, pv, k, v, ids):
                def blk(x):  # (L, 1, n*bs, KV, hd) -> (L, n, KV, bs, hd)
                    return to_blocks(x[:, 0], len(ids), bs)

                return pk.at[:, ids].set(blk(k)), pv.at[:, ids].set(blk(v))

            fn = jax.jit(write)
            self._write_fns[("span", n)] = fn
        self.k, self.v = fn(self.k, self.v, k, v, jnp.asarray(ids))

    def zero_mask(self, ids) -> None:
        """Invalidate every row of blocks ``ids`` — required when a freed
        block is reallocated as a decode *append* block: its previous
        owner's stale mask rows would otherwise read as valid cache
        entries.  (Admission data blocks need no zeroing: ``write_cache``
        overwrites the full mask.)  Padding with the null block id is
        harmless — its mask is already all-False."""
        ids = np.asarray(ids, np.int32)
        W = 4  # fixed scatter width: one compiled program, not one per count
        fn = self._write_fns.get(("zero", W))
        if fn is None:
            def zero(mask, ids):
                upd = jnp.zeros((mask.shape[0], len(ids)) + mask.shape[2:],
                                bool)
                return mask.at[:, ids].set(upd)

            fn = jax.jit(zero)
            self._write_fns[("zero", W)] = fn
        for s in range(0, len(ids), W):
            grp = np.zeros(W, np.int32)
            seg = ids[s:s + W]
            grp[:len(seg)] = seg
            self.mask = fn(self.mask, jnp.asarray(grp))

    # -- observability ----------------------------------------------------
    def check(self) -> None:
        """Allocator invariants (cheap; the kv-pool test suite calls this
        after every adversarial step): the pool is conserved, the free
        list holds no duplicates or live blocks, and the null block is
        never handed out."""
        assert len(set(self._free)) == len(self._free), "free-list duplicate"
        assert 0 not in self._free, "null block on the free list"
        assert (self._refs[self._free] == 0).all(), "live block marked free"
        live = int((self._refs[1:] > 0).sum())
        assert live + len(self._free) == self.usable_blocks, "pool leak"
        assert 0 <= self.reserved <= len(self._free), "reservation overhang"
        assert self._refs[0] == 0

    def stats(self) -> dict:
        used = self.used_blocks()
        shards = self.model_shards
        return {
            # mesh shape + per-shard utilization: block counts are global
            # (the allocator is shard-oblivious), bytes divide evenly over
            # the kv-head shards
            "mesh_model": shards,
            "bytes_total_per_shard":
                self.usable_blocks * self.block_bytes // shards,
            "bytes_used_per_shard": used * self.block_bytes // shards,
            "block_size": self.block_size,
            "block_bytes": self.block_bytes,
            "blocks_total": self.usable_blocks,
            "blocks_used": used,
            "blocks_free": len(self._free),
            "blocks_reserved": self.reserved,
            "blocks_pinned_prefix": self.pinned_blocks,
            "blocks_reclaimed_decode": self.blocks_reclaimed_decode,
            "high_water_blocks": self.high_water,
            "bytes_total": self.usable_blocks * self.block_bytes,
            "bytes_used": used * self.block_bytes,
            "bytes_pinned_prefix": self.pinned_blocks * self.block_bytes,
            "bytes_high_water": self.high_water * self.block_bytes,
            # int32 pos + bool mask metadata, outside the K+V budget
            "metadata_bytes": int(self.pos.nbytes + self.mask.nbytes),
        }

    def bind_metrics(self, registry) -> None:
        """Mirror ``stats()`` as ``kv_pool_*`` callback gauges on the
        engine's registry (collection-time reads, no hot-path writes)."""
        from repro.obs.metrics import bind_stat_gauges
        bind_stat_gauges(registry, "kv_pool", self.stats)
