"""Paged KV cache: block-pool storage for ragged continuous batching.

The ring buffer (kv_cache.py) bakes ``(batch, max_len)`` into one dense
allocation, so every row of a served batch must share a sequence budget.
This module replaces that with vLLM-style paging:

  pool   = {
    "k": [L, num_blocks, block_size, Kv * D],  # one block pool per layer stack
    "v": [L, num_blocks, block_size, Kv * D],
    "block_table": [B, max_blocks_per_row] int32,  # row -> pool block ids
    "index": [B] int32                             # committed tokens per row
  }

Token at absolute position ``p`` of row ``b`` in layer ``l`` lives in
``pool[l, block_table[b, p // block_size], p % block_size]``: the pool is
token-major, one token's kv heads side by side in one ``Kv * D`` row, and
stored rank-4 so nothing reshapes it between the model and the kernels (on
the TPU's tiled layouts such a reshape is a copy). A block is one
``[block_size, Kv * D]`` tile whose two dims are whole array dims, so the
Pallas kernels DMA it as it lies at every width and cut the heads out in
VMEM; cutting ONE head out through the BlockSpec would be a ``D``-wide
block of the minor dim, which the TPU takes only when ``D`` is a multiple
of 128. The layer axis is part of every address: the model's layer
scan carries the whole stack and writes each token's row in place at
``(layer, block, offset)``, and the kernels take the layer index by scalar
prefetch, so no layer's pool is ever sliced out or copied back. A head-
sharded kernel (``kernels/ops.py``) splits the minor axis into ``Kv/n * D``
pieces, whole heads each.

Block 0 is the NULL block: unallocated table entries point at it, so writes
from frozen/empty batch slots land somewhere harmless and gathers of
unallocated slots are causally masked (their positions exceed every live
query position). The allocator never hands out block 0.

Speculative rollback is O(1) exactly as for the ring cache: attention masks
on *positions* recovered from ``index``, so ``cache | {"index": smaller}``
drops the rejected tail; stale slots are overwritten by the next append
before they can become causally visible. ``BlockAllocator.free_tail``
returns whole blocks beyond an accepted length to the free list (host-side,
because scheduling is host-driven). The serving path reclaims via
``free_row`` at request completion AND at preemption: under overcommitted
admission (serving/scheduler.py) the server may evict a victim row's whole
allocation mid-flight and re-queue the request for prefix recompute — see
docs/DESIGN.md §9. ``seize``/``release_seized`` let the fault-injection
layer withhold free blocks to force that pressure deterministically, and
``audit`` is the leak oracle the chaos suite runs after every test. See
docs/DESIGN.md §3 for the layout comparison.

Tree drafting adds copy-on-write branch forks: ``fork_row`` hands each
draft branch a table that shares the row's full prefix blocks (refcounted)
and owns a private copy of the partial tail block, so branches append
independently; ``adopt_branch`` commits the winner and drops every other
reference. Within a row family blocks may be multiply referenced; across
rows they stay disjoint (``audit`` enforces both). See docs/DESIGN.md §5.

Prefix caching (cache/prefix_pool.py) adds a fourth block partition:
``cache_ref`` pins a row's fully-written prompt-prefix blocks into the
pool (one extra reference each), ``attach`` installs them at the front of
another row's table so that row prefills only its unique suffix, and
``uncache`` drops the pool's pin at LRU eviction. Cached blocks are the
one sanctioned exception to family-disjoint sharing — they are immutable
by construction (every attaching row writes strictly past them), so
``audit`` exempts them and counts them as their own partition. When the
free list runs dry the allocator calls the installed ``reclaimer`` (the
prefix pool's LRU eviction) before failing. See docs/DESIGN.md §10.
"""
from __future__ import annotations

from collections import deque
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache.kv_cache import _to_buf_dtype

NULL_BLOCK = 0


def init_pool(num_layers, num_blocks, block_size, num_kv_heads, head_dim,
              dtype=jnp.bfloat16):
    """Per-layer-stack block pools (no table — tables are per cache, pools may
    be grouped, e.g. MoE sub-stacks sharing one table)."""
    shape = (num_layers, num_blocks, block_size, num_kv_heads * head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_cache(num_layers, batch, num_blocks, block_size, max_blocks_per_row,
               num_kv_heads, head_dim, dtype=jnp.bfloat16):
    cache = init_pool(num_layers, num_blocks, block_size, num_kv_heads,
                      head_dim, dtype)
    cache["block_table"] = jnp.full((batch, max_blocks_per_row), NULL_BLOCK,
                                    jnp.int32)
    cache["index"] = jnp.zeros((batch,), jnp.int32)
    return cache


def is_paged(cache) -> bool:
    return isinstance(cache, dict) and "block_table" in cache


def write(pools, k_new, v_new, block_table, index, layer=0):
    """Paged WRITE (pool update only — the write half of the write/read
    split; ``models.attention.attn_paged`` is the read half).

    pools: {"k": [L, NB, BS, Kv*D], "v": ...} — a layer stack's pools (a
    single layer's pool is a stack of one, ``layer`` 0).
    k_new/v_new: [B, Q, Kv, D] written into layer ``layer`` at positions
    index..index+Q-1 per row: one scatter of ``Kv*D`` rows per pool, in
    place when the pools are a loop carry.

    Returns the new pools. Deliberately does NOT return a gathered per-row
    view: the old ``extend`` materialized ``[B, MB*BS, Kv, D]`` per layer per
    step, so attention traffic scaled with worst-case row capacity instead of
    live tokens. Readers scan blocks via the block table directly.

    Unlike the ring buffer, appends never evict: the write happens first and
    attention reads the post-write pool even for Q > 1.
    """
    BS = pools["k"].shape[2]
    B, Q = k_new.shape[0], k_new.shape[1]
    MB = block_table.shape[1]
    idx = jnp.asarray(index)
    if idx.ndim == 0:
        idx = jnp.broadcast_to(idx, (B,))
    pos = idx[:, None] + jnp.arange(Q, dtype=jnp.int32)      # [B, Q]
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    # frozen batch slots keep getting speculative writes at their (fixed)
    # index; clamp the table lookup so an over-capacity position resolves to
    # the row's last table entry (NULL for released rows) instead of OOB
    blk = block_table[rows, jnp.minimum(pos // BS, MB - 1)]  # [B, Q]
    off = pos % BS

    def put(pool, new):
        rows_new = _to_buf_dtype(new.reshape(B, Q, -1), pool.dtype)
        return pool.at[layer, blk, off].set(rows_new)
    return {"k": put(pools["k"], k_new), "v": put(pools["v"], v_new)}


def copy_blocks(cache, pairs):
    """Device-side half of a copy-on-write fork: copy whole pool blocks
    ``src -> dst`` across every layer. ``pairs`` is the (src, dst) list
    returned by ``BlockAllocator.fork_row`` — the partial tail block of a
    forked row is duplicated so each branch can append without clobbering
    its siblings; full prefix blocks are shared (refcounted), never copied."""
    if not pairs:
        return cache
    src = jnp.asarray([s for s, _ in pairs], jnp.int32)
    dst = jnp.asarray([d for _, d in pairs], jnp.int32)
    out = dict(cache)
    out["k"] = cache["k"].at[:, dst].set(cache["k"][:, src])
    out["v"] = cache["v"].at[:, dst].set(cache["v"][:, src])
    return out


def compact_positions(cache, block_table, src_pos, dst_pos):
    """Tree-verify commit-by-compaction: gather KV at scattered ``src_pos``
    and rewrite it at ``dst_pos`` (both [B, P] absolute positions), all
    layers at once. The gather completes before the scatter, so overlapping
    src/dst are safe; the tree layout guarantees src >= dst per step (winner
    slots always sit at-or-beyond their committed destination)."""
    BS = cache["k"].shape[2]
    MB = block_table.shape[1]
    B = src_pos.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    sblk = block_table[rows, jnp.minimum(src_pos // BS, MB - 1)]
    dblk = block_table[rows, jnp.minimum(dst_pos // BS, MB - 1)]
    # adjacent advanced indices gather as [L, B, P, Kv*D]; the scatter
    # below uses the same index form, so the shapes line up
    k = cache["k"][:, sblk, src_pos % BS]
    v = cache["v"][:, sblk, src_pos % BS]
    out = dict(cache)
    out["k"] = cache["k"].at[:, dblk, dst_pos % BS].set(k)
    out["v"] = cache["v"].at[:, dblk, dst_pos % BS].set(v)
    return out


def rollback(cache, accepted_index):
    """O(1) speculative rollback: drop everything after ``accepted_index``
    ([B] or scalar). Physical blocks stay resident (the next round rewrites
    them); reclaim whole tail blocks via BlockAllocator.free_tail."""
    idx = jnp.asarray(accepted_index, jnp.int32)
    if idx.ndim == 0:
        idx = jnp.broadcast_to(idx, cache["index"].shape)
    return {**cache, "index": idx}


def memory_bytes(cache) -> int:
    """Total resident cache bytes (pools + tables + indices)."""
    return sum(int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
               for leaf in jax.tree_util.tree_leaves(cache))


class BlockAllocator:
    """Host-side free-list allocator for one (pool, table) pair.

    The device ``block_table`` array is the jit-visible mirror of the host
    table; callers push ``device_table()`` into the cache dict after any
    allocation change (tables only change between rounds, on the host).
    """

    def __init__(self, num_blocks: int, block_size: int,
                 max_blocks_per_row: int, batch: int):
        assert num_blocks >= 2, "need at least the null block + one real block"
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.max_blocks_per_row = max_blocks_per_row
        self.batch = batch
        self.free: deque = deque(range(1, num_blocks))   # block 0 reserved
        self.table = np.full((batch, max_blocks_per_row), NULL_BLOCK, np.int32)
        self.n_alloc = np.zeros((batch,), np.int64)      # allocated blocks/row
        self.peak_in_use = 0                             # residency high-water
        self.version = 0     # bumped on every table mutation; callers gate
                             # device pushes on it (see PagedSpecServer)
        self._seized: deque = deque()  # blocks withheld by fault injection
        # copy-on-write state: refcnt[b] counts table references to block b
        # (main tables + branch tables); a block returns to the free list
        # only when its last reference drops. Without forks every count is 1
        # and the allocator behaves exactly as before.
        self.refcnt = np.zeros((num_blocks,), np.int64)
        self._branches: Dict[int, np.ndarray] = {}       # row -> [n_br, MB]
        self._branch_alloc: Dict[int, np.ndarray] = {}   # row -> [n_br]
        # prefix-cache state: blocks pinned by the prefix pool (one extra
        # reference each; immutable, shareable across row families) and the
        # pool's LRU eviction hook, tried before any allocation fails
        self.cached: set = set()
        self.reclaimer = None            # callable(n_blocks) -> n_freed

    # ------------------------------------------------------------- queries
    @property
    def num_free(self) -> int:
        return len(self.free)

    def blocks_for(self, n_tokens: int) -> int:
        return -(-max(n_tokens, 0) // self.block_size)

    def can_allocate(self, n_tokens: int) -> bool:
        need = self.blocks_for(n_tokens)
        return need <= self.max_blocks_per_row and need <= self.num_free

    def device_table(self) -> jnp.ndarray:
        return jnp.asarray(self.table)

    # ----------------------------------------------------------- mutation
    def _want_free(self, n: int) -> bool:
        """True if ``n`` free blocks are available, evicting idle cached
        prefix blocks through the installed ``reclaimer`` if needed."""
        if n <= len(self.free):
            return True
        if self.reclaimer is not None:
            self.reclaimer(n - len(self.free))
        return n <= len(self.free)

    def ensure(self, row: int, n_tokens: int) -> bool:
        """Grow row's allocation to cover ``n_tokens`` positions. Returns
        False (allocating nothing) if the pool cannot satisfy the request."""
        need = self.blocks_for(n_tokens)
        if need > self.max_blocks_per_row:
            return False
        have = int(self.n_alloc[row])
        if need <= have:
            return True
        if not self._want_free(need - have):
            return False
        for j in range(have, need):
            self.table[row, j] = self._take_fresh()
        self.n_alloc[row] = need
        self.peak_in_use = max(self.peak_in_use, int(self.n_alloc.sum()))
        self.version += 1
        return True

    def _take_fresh(self) -> int:
        blk = self.free.popleft()
        self.refcnt[blk] = 1
        return blk

    def _release_ref(self, blk: int) -> int:
        """Drop one table reference; returns 1 if the block actually went
        back to the free list (refcount hit zero), else 0."""
        self.refcnt[blk] -= 1
        assert self.refcnt[blk] >= 0, f"refcount underflow on block {blk}"
        if self.refcnt[blk] == 0:
            self.free.append(blk)
            return 1
        return 0

    def free_tail(self, row: int, n_tokens: int) -> int:
        """Release blocks beyond the one holding token ``n_tokens - 1``
        (speculative-rollback reclamation). Returns #blocks actually
        returned to the free list (CoW-shared blocks stay resident until
        their last reference drops)."""
        keep = self.blocks_for(n_tokens)
        have = int(self.n_alloc[row])
        freed = 0
        for j in range(keep, have):
            freed += self._release_ref(int(self.table[row, j]))
            self.table[row, j] = NULL_BLOCK
        self.n_alloc[row] = min(keep, have)
        if have > keep:
            self.version += 1
        return freed

    def free_row(self, row: int) -> int:
        freed = self.release_branches(row) if row in self._branches else 0
        return freed + self.free_tail(row, 0)

    # -------------------------------------------- copy-on-write branch forks
    def fork_row(self, row: int, n_tokens: int, n_branches: int):
        """Fork ``row`` (committed length ``n_tokens``) into ``n_branches``
        copy-on-write branch tables for tree drafting. Full prefix blocks
        are shared (refcount bumped per branch); the partial tail block, if
        any, is duplicated per branch so branches can append independently.

        Returns the list of (src, dst) pool-copy pairs the caller must apply
        with ``copy_blocks`` — or None if the pool cannot supply the tail
        copies (caller falls back to linear drafting). The parent row's own
        table is left untouched, so dropping every branch is a no-op
        rollback."""
        assert row not in self._branches, f"row {row} already forked"
        BS = self.block_size
        full = max(n_tokens, 0) // BS
        tail = 1 if n_tokens % BS else 0
        assert full + tail <= int(self.n_alloc[row]), \
            f"fork of row {row} beyond its allocation"
        if not self._want_free(tail * n_branches):
            return None
        MB = self.max_blocks_per_row
        tables = np.full((n_branches, MB), NULL_BLOCK, np.int32)
        alloc = np.zeros((n_branches,), np.int64)
        pairs = []
        for w in range(n_branches):
            for j in range(full):
                blk = int(self.table[row, j])
                tables[w, j] = blk
                self.refcnt[blk] += 1
            if tail:
                src = int(self.table[row, full])
                dst = self._take_fresh()
                tables[w, full] = dst
                pairs.append((src, dst))
            alloc[w] = full + tail
        self._branches[row] = tables
        self._branch_alloc[row] = alloc
        self.peak_in_use = max(self.peak_in_use,
                               int(self.n_alloc.sum()) + tail * n_branches)
        self.version += 1
        return pairs

    def ensure_branch(self, row: int, branch: int, n_tokens: int) -> bool:
        """Grow one branch's allocation to cover ``n_tokens`` positions
        (fresh blocks only — the shared prefix never regrows)."""
        tables = self._branches[row]
        alloc = self._branch_alloc[row]
        need = self.blocks_for(n_tokens)
        if need > self.max_blocks_per_row:
            return False
        have = int(alloc[branch])
        if need <= have:
            return True
        if not self._want_free(need - have):
            return False
        for j in range(have, need):
            tables[branch, j] = self._take_fresh()
        alloc[branch] = need
        self.version += 1
        return True

    def branch_tables(self, row: int) -> np.ndarray:
        """Host-side [n_branches, MB] table stack for a forked row."""
        return self._branches[row]

    def adopt_branch(self, row: int, branch: int) -> int:
        """Commit the winning branch: the row's main table becomes the
        branch's table; every other branch reference and the old main-table
        references are dropped. Returns #blocks returned to the free list."""
        tables = self._branches.pop(row)
        alloc = self._branch_alloc.pop(row)
        freed = 0
        for w in range(tables.shape[0]):
            if w == branch:
                continue
            for j in range(int(alloc[w])):
                freed += self._release_ref(int(tables[w, j]))
        for j in range(int(self.n_alloc[row])):
            freed += self._release_ref(int(self.table[row, j]))
        self.table[row, :] = NULL_BLOCK
        n = int(alloc[branch])
        self.table[row, :n] = tables[branch, :n]
        self.n_alloc[row] = n
        self.version += 1
        return freed

    def release_branches(self, row: int) -> int:
        """Drop every branch of a forked row (tree-round rollback / abort);
        the parent row's own table is untouched. Returns #blocks freed."""
        if row not in self._branches:
            return 0
        tables = self._branches.pop(row)
        alloc = self._branch_alloc.pop(row)
        freed = 0
        for w in range(tables.shape[0]):
            for j in range(int(alloc[w])):
                freed += self._release_ref(int(tables[w, j]))
        self.version += 1
        return freed

    # -------------------------------------------------- prefix-cache blocks
    def cache_ref(self, blk: int):
        """Pin ``blk`` into the prefix cache: one extra reference held by the
        prefix pool. The block must be live (a row's table references it) and
        fully written — the pool only registers blocks strictly below the
        owner's first decode position, so pinned blocks are immutable."""
        assert blk != NULL_BLOCK, "cannot cache the null block"
        assert self.refcnt[blk] > 0, f"caching unreferenced block {blk}"
        assert blk not in self.cached, f"block {blk} cached twice"
        self.refcnt[blk] += 1
        self.cached.add(blk)

    def uncache(self, blk: int) -> int:
        """Drop the prefix pool's pin on ``blk`` (LRU eviction). Returns 1
        if the block actually returned to the free list (no row was still
        attached to it), else 0."""
        assert blk in self.cached, f"uncaching non-cached block {blk}"
        self.cached.discard(blk)
        return self._release_ref(blk)

    def attach(self, row: int, blocks) -> int:
        """Install cached prefix blocks at the FRONT of an EMPTY row's table
        (prefix-cache hit: the row reuses their KV and prefills only its
        suffix). Each block gains one table reference; returns the number of
        tokens covered."""
        assert int(self.n_alloc[row]) == 0, \
            f"attach into non-empty row {row}"
        assert len(blocks) <= self.max_blocks_per_row
        for j, blk in enumerate(blocks):
            blk = int(blk)
            assert blk in self.cached, f"attaching non-cached block {blk}"
            self.refcnt[blk] += 1
            self.table[row, j] = blk
        self.n_alloc[row] = len(blocks)
        self.peak_in_use = max(self.peak_in_use, int(self.n_alloc.sum()))
        if blocks:
            self.version += 1
        return len(blocks) * self.block_size

    # ------------------------------------------- fault injection + auditing
    @property
    def num_seized(self) -> int:
        return len(self._seized)

    def seize(self, n: int) -> int:
        """Withhold up to ``n`` FREE blocks from the pool (forced memory
        pressure for chaos testing). Live rows are never touched — seizure
        can only shrink headroom, not corrupt allocations. Returns the
        number actually seized."""
        taken = 0
        while taken < n and self.free:
            self._seized.append(self.free.popleft())
            taken += 1
        return taken

    def release_seized(self, n: Optional[int] = None) -> int:
        """Return ``n`` (default: all) seized blocks to the free list."""
        n = len(self._seized) if n is None else min(n, len(self._seized))
        for _ in range(n):
            self.free.append(self._seized.popleft())
        return n

    def audit(self) -> Dict[str, int]:
        """Full block census; raises AssertionError on any inconsistency.

        Invariants: free + live + cached + seized == num_blocks - 1 (block 0
        is the null block; 'live' = DISTINCT blocks referenced by any main
        or branch table and NOT pinned in the prefix cache; 'cached' =
        blocks pinned by the prefix pool, attached to rows or idle), every
        refcount equals the number of table references plus the prefix
        pool's pin, no free/seized block is referenced or cached, table
        entries beyond each row's/branch's allocation are NULL, and
        copy-on-write sharing never crosses row families (a block referenced
        by row b's tables — main or branch — is referenced by no other
        row's) EXCEPT for cached blocks, which are immutable and shared by
        design. The chaos suite calls this after every run — 'zero leaked
        blocks' means this census balances, not merely that ``num_free``
        looks right."""
        refs: Dict[int, int] = {}        # block -> #table references
        families: Dict[int, int] = {}    # block -> owning row
        def _count(row, tbl, n, what):
            for x in tbl[:n]:
                x = int(x)
                assert x != NULL_BLOCK, f"null block handed out to {what}"
                refs[x] = refs.get(x, 0) + 1
                if x not in self.cached:
                    owner = families.setdefault(x, row)
                    assert owner == row, \
                        (f"block {x} shared across row families "
                         f"{owner} and {row}")
            tail = tbl[n:]
            assert (tail == NULL_BLOCK).all(), \
                f"{what}: non-NULL table entries beyond allocation {n}"
        for b in range(self.batch):
            _count(b, self.table[b], int(self.n_alloc[b]), f"row {b}")
        for b, tables in self._branches.items():
            alloc = self._branch_alloc[b]
            for w in range(tables.shape[0]):
                _count(b, tables[w], int(alloc[w]), f"row {b} branch {w}")
        for blk, n in refs.items():
            want = n + (1 if blk in self.cached else 0)
            assert int(self.refcnt[blk]) == want, \
                (f"block {blk}: refcount {int(self.refcnt[blk])} != "
                 f"{n} table references"
                 + (" + 1 cache pin" if blk in self.cached else ""))
        for blk in self.cached:
            if blk not in refs:          # idle cached block: pool pin only
                assert int(self.refcnt[blk]) == 1, \
                    (f"idle cached block {blk} has refcount "
                     f"{int(self.refcnt[blk])}, expected 1 (pool pin)")
        for blk in list(self.free) + list(self._seized):
            assert blk not in refs, \
                f"block {blk} is free/seized but still referenced"
            assert blk not in self.cached, \
                f"block {blk} is free/seized but still cached"
            assert int(self.refcnt[blk]) == 0, \
                f"free/seized block {blk} has refcount {int(self.refcnt[blk])}"
        live = [blk for blk in refs if blk not in self.cached]
        counts = {"free": len(self.free), "live": len(live),
                  "cached": len(self.cached), "seized": len(self._seized)}
        all_ids = (list(self.free) + list(self._seized) + live
                   + list(self.cached))
        assert len(all_ids) == len(set(all_ids)), \
            "block appears in more than one of free/seized/live/cached"
        total = sum(counts.values())
        assert total == self.num_blocks - 1, \
            (f"block census mismatch: {counts} sums to {total}, "
             f"expected {self.num_blocks - 1}")
        return counts
