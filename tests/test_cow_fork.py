"""Copy-on-write block-table forks: allocator semantics (seeded fallback of
the hypothesis interleaving model), pool-side block copies, branch write
isolation, and commit-by-compaction for both cache layouts."""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _allocator_model import (BATCH, BLOCK_SIZE, OP_KINDS,
                              run_allocator_model)
from repro.cache import kv_cache, paged_kv
from repro.cache.ops import PAGED, RING
from repro.cache.paged_kv import BlockAllocator


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_seeded_lifecycles_never_leak_or_alias_blocks(seed):
    """Same interleaving model the hypothesis property test drives, on a
    seeded RNG so the invariant coverage survives bare checkouts."""
    rng = random.Random(seed)
    ops = [(rng.choice(OP_KINDS), rng.randrange(BATCH),
            rng.randrange(3 * BLOCK_SIZE + 1)) for _ in range(200)]
    run_allocator_model(ops)


def test_fork_shares_prefix_and_copies_tail():
    a = BlockAllocator(32, 4, 8, 2)
    assert a.ensure(0, 10)                       # blocks [f, f, partial]
    prefix = [int(x) for x in a.table[0, :2]]
    tail = int(a.table[0, 2])
    pairs = a.fork_row(0, 10, 3)
    assert pairs is not None and len(pairs) == 3
    assert all(src == tail for src, _ in pairs)
    tbls = a.branch_tables(0)
    for w in range(3):
        assert [int(x) for x in tbls[w, :2]] == prefix   # shared
        assert int(tbls[w, 2]) == pairs[w][1]            # private copy
    assert int(a.refcnt[prefix[0]]) == 4                 # parent + 3 branches
    assert int(a.refcnt[tail]) == 1                      # parent only
    a.audit()
    # adopt branch 1: losers + parent tail drop; shared prefix survives
    a.adopt_branch(0, 1)
    assert [int(x) for x in a.table[0, :2]] == prefix
    assert int(a.table[0, 2]) == pairs[1][1]
    assert int(a.refcnt[prefix[0]]) == 1
    a.audit()


def test_fork_full_tail_needs_no_copies():
    a = BlockAllocator(16, 4, 8, 1)
    assert a.ensure(0, 8)                        # exactly two full blocks
    free_before = a.num_free
    assert a.fork_row(0, 8, 2) == []             # nothing to copy
    assert a.num_free == free_before             # nothing taken either
    a.audit()
    assert a.release_branches(0) == 0            # all refs were shared
    assert a.audit()["live"] == 2


def test_fork_declines_under_pressure():
    a = BlockAllocator(6, 4, 4, 1)               # 5 usable blocks
    assert a.ensure(0, 6)                        # 2 blocks, partial tail
    a.seize(2)                                   # 1 free block left
    assert a.fork_row(0, 6, 2) is None           # needs 2 tail copies
    a.audit()
    a.release_seized()
    assert a.fork_row(0, 6, 2) is not None
    a.audit()


def _pool(L, NB, BS, Kv, D, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {"k": jax.random.normal(k1, (L, NB, BS, Kv * D), jnp.float32),
            "v": jax.random.normal(k2, (L, NB, BS, Kv * D), jnp.float32)}


def test_copy_blocks_duplicates_pool_blocks():
    cache = _pool(2, 8, 4, 2, 4)
    out = paged_kv.copy_blocks(cache, [(1, 5), (2, 6)])
    np.testing.assert_array_equal(np.asarray(out["k"][:, 5]),
                                  np.asarray(cache["k"][:, 1]))
    np.testing.assert_array_equal(np.asarray(out["v"][:, 6]),
                                  np.asarray(cache["v"][:, 2]))
    # untouched blocks unchanged
    np.testing.assert_array_equal(np.asarray(out["k"][:, 3]),
                                  np.asarray(cache["k"][:, 3]))
    assert paged_kv.copy_blocks(cache, []) is cache or \
        paged_kv.copy_blocks(cache, [])["k"] is cache["k"]


def test_branch_writes_are_isolated():
    """After a fork, each branch appends its own continuation: siblings and
    the parent row must see none of it; the shared prefix reads back
    identically through every branch table."""
    L, BS, MB, Kv, D, B = 2, 4, 6, 2, 4, 1
    a = BlockAllocator(32, BS, MB, B)
    n_committed = 6
    assert a.ensure(0, n_committed)
    cache = _pool(L, 32, BS, Kv, D)
    cache["k"] = jnp.zeros_like(cache["k"])
    cache["v"] = jnp.zeros_like(cache["v"])
    prefix_k = jax.random.normal(jax.random.PRNGKey(3),
                                 (B, n_committed, Kv, D), jnp.float32)
    for layer in range(L):
        cache = paged_kv.write(cache, prefix_k, prefix_k, a.device_table(),
                               jnp.zeros((B,), jnp.int32), layer)
    pairs = a.fork_row(0, n_committed, 2)
    assert pairs is not None
    for w in range(2):
        assert a.ensure_branch(0, w, n_committed + 3)
    cache = paged_kv.copy_blocks(cache, pairs)
    tbls = jnp.asarray(a.branch_tables(0))       # [2, MB]
    # branch w appends value (w+1) at positions 6..8
    for w in range(2):
        val = jnp.full((B, 3, Kv, D), float(w + 1), jnp.float32)
        for layer in range(L):
            cache = paged_kv.write(cache, val, val, tbls[w:w + 1],
                                   jnp.full((B,), n_committed, jnp.int32),
                                   layer)

    def read(table, pos):
        blk = table[pos // BS]
        return np.asarray(cache["k"][:, blk, pos % BS])

    for w in range(2):
        for p in range(n_committed):             # shared prefix intact
            np.testing.assert_array_equal(read(tbls[w], p),
                                          read(a.device_table()[0], p))
        for p in range(n_committed, n_committed + 3):
            got = read(tbls[w], p)
            np.testing.assert_array_equal(got, np.full_like(got, w + 1))
    # parent row's own tail slot (position 6 in ITS tail block) is untouched
    parent = read(a.device_table()[0], n_committed)
    np.testing.assert_array_equal(parent, np.zeros_like(parent))
    a.adopt_branch(0, 1)
    a.audit()
    # winner's tokens are now the row's own
    for p in range(n_committed, n_committed + 3):
        got = read(a.device_table()[0], p)
        np.testing.assert_array_equal(got, np.full_like(got, 2.0))


def test_compact_positions_paged_and_ring_agree():
    """CacheOps.compact moves winner-path KV to the committed tail — paged
    gather/scatter and ring slot-moves must implement the same function."""
    L, B, Kv, D, BS, MB, W = 2, 2, 2, 4, 4, 6, 16
    n = 9
    key = jax.random.PRNGKey(11)
    dense = jax.random.normal(key, (B, W, Kv, D), jnp.float32)
    # paged cache holding tokens 0..n+4
    a = BlockAllocator(32, BS, MB, B)
    for b in range(B):
        assert a.ensure(b, n + 5)
    paged = {"k": jnp.zeros((L, 32, BS, Kv * D), jnp.float32),
             "v": jnp.zeros((L, 32, BS, Kv * D), jnp.float32),
             "block_table": a.device_table(),
             "index": jnp.full((B,), n, jnp.int32)}
    ring = {"k": jnp.zeros((L, B, W, Kv, D), jnp.float32),
            "v": jnp.zeros((L, B, W, Kv, D), jnp.float32),
            "index": jnp.zeros((), jnp.int32)}
    for layer in range(L):
        paged.update(paged_kv.write(paged, dense[:, :n + 5], dense[:, :n + 5],
                                    paged["block_table"],
                                    jnp.zeros((B,), jnp.int32), layer))
        kb, vb = kv_cache.write(ring["k"][layer], ring["v"][layer],
                                dense[:, :n + 5], dense[:, :n + 5],
                                jnp.zeros((), jnp.int32))
        ring["k"] = ring["k"].at[layer].set(kb)
        ring["v"] = ring["v"].at[layer].set(vb)
    # winner slots scattered beyond n -> commit to contiguous n..n+2
    src = jnp.asarray([[n + 1, n + 3, n + 4]] * B, jnp.int32)
    dst = jnp.asarray([[n, n + 1, n + 2]] * B, jnp.int32)
    outp = PAGED.compact(paged, src, dst)
    outr = RING.compact(ring, src, dst)
    rows = jnp.arange(B)[:, None]
    blk = outp["block_table"][rows, dst // BS]
    got_p = np.asarray(outp["k"][:, blk, dst % BS]).reshape(L, B, 3, Kv, D)
    got_r = np.asarray(outr["k"][:, rows, dst % W])
    want = np.asarray(dense[:, [n + 1, n + 3, n + 4]])   # [B, 3, Kv, D]
    for layer in range(L):
        np.testing.assert_array_equal(got_p[layer], want)
        np.testing.assert_array_equal(got_r[layer], want)
    # positions before n untouched
    np.testing.assert_array_equal(
        np.asarray(outr["k"][:, rows, jnp.asarray([[0, 1]]) % W]),
        np.asarray(ring["k"][:, rows, jnp.asarray([[0, 1]])]))
