"""Paged KV cache: ring-buffer equivalence, speculative rollback (index +
block reclamation), and the host-side block allocator."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import paged_kv
from repro.cache.paged_kv import BlockAllocator
from repro.configs import registry
from repro.models.model import build_model


def _model(arch):
    cfg = registry.smoke_config(arch)
    if cfg.family == "vlm":
        cfg = cfg.replace(num_vision_tokens=0)
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0)), cfg


def _paged_cache(m, B, num_blocks=32, block_size=4, max_blocks=8, n_tokens=24):
    alloc = BlockAllocator(num_blocks, block_size, max_blocks, B)
    for b in range(B):
        assert alloc.ensure(b, n_tokens)
    cache = m.init_paged_cache(B, num_blocks, block_size, max_blocks)
    return {**cache, "block_table": alloc.device_table()}, alloc


def _assert_same_kv(ring, paged, n):
    """Every layer's K/V of positions 0..n-1: the ring's slot p against the
    paged pool's token row at (layer, table[b, p // BS], p % BS)."""
    def kv_leaves(cache):
        flat = jax.tree_util.tree_flatten_with_path(cache)[0]
        return [x for path, x in flat
                if getattr(path[-1], "key", None) in ("k", "v")]
    tbl = np.asarray(paged["block_table"])
    pos = np.arange(n)
    pairs = list(zip(kv_leaves(ring), kv_leaves(paged)))
    assert pairs and len(kv_leaves(ring)) == len(kv_leaves(paged))
    for r, pl_ in pairs:
        L, B, _, Kv, D = r.shape
        BS = pl_.shape[2]
        for b in range(B):
            got = np.asarray(pl_)[:, tbl[b, pos // BS], pos % BS]
            np.testing.assert_allclose(got.reshape(L, n, Kv, D),
                                       np.asarray(r)[:, b, :n], atol=2e-4)


@pytest.mark.parametrize("arch", ["llama3.2-1b", "mixtral-8x7b", "internvl2-26b"])
def test_paged_matches_ring_logits(arch):
    """Same token stream through ring and paged caches -> same logits and
    the same K/V written for every layer, at every phase: multi-token
    prefill, single-token decode, multi-token (speculative-verify-shaped)
    extension. The dense paged path carries the whole pool stack through
    its layer scan; the MoE path passes per-layer slices as stacks of
    one."""
    m, p, cfg = _model(arch)
    B, P, G = 2, 6, 3
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0, cfg.vocab_size)
    ring = m.init_cache(B, 32, spec_slack=G + 2)
    paged, _ = _paged_cache(m, B)

    lr, ring, _ = m.apply(p, toks, ring)
    lp, paged, _ = m.apply(p, toks, paged)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lp), atol=2e-4)
    _assert_same_kv(ring, paged, P)

    nxt = jnp.argmax(lr[:, -1], -1)[:, None]
    lr, ring, _ = m.apply(p, nxt, ring)            # decode fast-path (Q=1)
    lp, paged, _ = m.apply(p, nxt, paged)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lp), atol=2e-4)
    _assert_same_kv(ring, paged, P + 1)

    multi = jax.random.randint(jax.random.PRNGKey(2), (B, G + 1), 0,
                               cfg.vocab_size)
    lr, ring, _ = m.apply(p, multi, ring)          # verify-shaped Q>1 extend
    lp, paged, _ = m.apply(p, multi, paged)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lp), atol=2e-4)
    _assert_same_kv(ring, paged, P + G + 2)


def test_paged_rollback_then_reextend_matches_ring():
    """The speculative pattern: write gamma+1 unverified tokens, roll back to
    the accepted prefix (per-row), extend again — paged equals ring."""
    m, p, cfg = _model("llama3.2-1b")
    B, P, G = 2, 5, 3
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, P), 0, cfg.vocab_size)
    ring = m.init_cache(B, 32, spec_slack=G + 2)
    paged, _ = _paged_cache(m, B)
    _, ring, _ = m.apply(p, toks, ring)
    _, paged, _ = m.apply(p, toks, paged)

    spec = jax.random.randint(jax.random.PRNGKey(4), (B, G + 1), 0,
                              cfg.vocab_size)
    _, ring, _ = m.apply(p, spec, ring)
    _, paged, _ = m.apply(p, spec, paged)

    accepted = jnp.asarray([P + 1, P + 3], jnp.int32)   # ragged acceptance
    ring = {**ring, "index": accepted}
    paged = paged_kv.rollback(paged, accepted)

    re_ext = jax.random.randint(jax.random.PRNGKey(5), (B, G + 1), 0,
                                cfg.vocab_size)
    lr, _, _ = m.apply(p, re_ext, ring)
    lp, _, _ = m.apply(p, re_ext, paged)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lp), atol=2e-4)


def test_rollback_frees_tail_blocks():
    alloc = BlockAllocator(num_blocks=16, block_size=4, max_blocks_per_row=8,
                           batch=2)
    assert alloc.ensure(0, 20)                 # 5 blocks
    assert alloc.num_free == 15 - 5
    assert int(alloc.n_alloc[0]) == 5
    freed = alloc.free_tail(0, 9)              # keep ceil(9/4) = 3 blocks
    assert freed == 2
    assert alloc.num_free == 15 - 3
    assert int(alloc.n_alloc[0]) == 3
    # freed table entries reset to the null block
    assert (alloc.table[0, 3:] == paged_kv.NULL_BLOCK).all()
    # released blocks are reusable by another row
    assert alloc.ensure(1, 16)
    assert alloc.num_free == 15 - 3 - 4


def test_free_tail_to_zero_and_double_free():
    """free_tail edge cases: freeing to zero equals free_row, and a second
    free of the same tail is a no-op (no block enters the free list twice)."""
    alloc = BlockAllocator(num_blocks=16, block_size=4, max_blocks_per_row=8,
                           batch=2)
    assert alloc.ensure(0, 10)                 # 3 blocks
    v0 = alloc.version
    assert alloc.free_tail(0, 0) == 3          # free to zero
    assert int(alloc.n_alloc[0]) == 0
    assert alloc.num_free == 15
    assert (alloc.table[0] == paged_kv.NULL_BLOCK).all()
    assert alloc.version == v0 + 1
    # double free: nothing left to release, version untouched
    assert alloc.free_tail(0, 0) == 0
    assert alloc.free_row(0) == 0
    assert alloc.num_free == 15
    assert alloc.version == v0 + 1
    alloc.audit()


def test_free_tail_across_block_boundary():
    """n_tokens landing exactly on a block boundary keeps exactly
    n_tokens/block_size blocks — the boundary block is NOT freed."""
    alloc = BlockAllocator(num_blocks=16, block_size=4, max_blocks_per_row=8,
                           batch=1)
    assert alloc.ensure(0, 17)                 # 5 blocks
    assert alloc.free_tail(0, 8) == 3          # exact boundary: keep 2
    assert int(alloc.n_alloc[0]) == 2
    assert alloc.free_tail(0, 8) == 0          # idempotent at the boundary
    assert alloc.free_tail(0, 5) == 0          # 5 tokens still need 2 blocks
    assert alloc.free_tail(0, 4) == 1          # boundary again: keep exactly 1
    assert int(alloc.n_alloc[0]) == 1
    alloc.audit()


def test_seize_and_release_only_touch_free_blocks():
    """Fault-injection seizure: live rows keep their blocks; seized blocks
    are withheld from allocation and auditable, then fully returned."""
    alloc = BlockAllocator(num_blocks=8, block_size=4, max_blocks_per_row=4,
                           batch=1)
    assert alloc.ensure(0, 12)                 # 3 of 7 usable blocks
    live = [int(x) for x in alloc.table[0, :3]]
    assert alloc.seize(100) == 4               # only the free ones
    assert alloc.num_free == 0
    assert [int(x) for x in alloc.table[0, :3]] == live
    assert not alloc.ensure(0, 16)             # pool dry under seizure
    assert alloc.audit() == {"free": 0, "live": 3, "cached": 0, "seized": 4}
    assert alloc.release_seized(2) == 2
    assert alloc.ensure(0, 16)                 # headroom back
    assert alloc.release_seized() == 2
    assert alloc.audit() == {"free": 3, "live": 4, "cached": 0, "seized": 0}


def test_allocator_reserves_null_block_and_bounds():
    alloc = BlockAllocator(num_blocks=4, block_size=2, max_blocks_per_row=4,
                           batch=1)
    assert alloc.num_free == 3                 # block 0 reserved
    assert alloc.ensure(0, 6)                  # 3 blocks
    assert paged_kv.NULL_BLOCK not in alloc.table[0, :3]
    assert not alloc.ensure(0, 8)              # pool exhausted
    assert not alloc.can_allocate(100)         # exceeds max_blocks_per_row
    assert alloc.free_row(0) == 3
    assert alloc.num_free == 3


def test_disjoint_rows_dont_interfere():
    """Appending to one row must not change what another row gathers."""
    m, p, cfg = _model("llama3.2-1b")
    B, P = 2, 6
    toks = jax.random.randint(jax.random.PRNGKey(6), (B, P), 0, cfg.vocab_size)
    paged, _ = _paged_cache(m, B)
    _, paged, _ = m.apply(p, toks, paged)

    # row 1 advances alone (row 0 'frozen' at its index, as in serving)
    one = jax.random.randint(jax.random.PRNGKey(7), (B, 1), 0, cfg.vocab_size)
    l_before, _, _ = m.apply(p, one, paged)
    # same query again: row 0's logits must be identical even though row 1's
    # previous write also hit the shared pool
    l_after, _, _ = m.apply(p, one, paged)
    np.testing.assert_allclose(np.asarray(l_before[0]), np.asarray(l_after[0]),
                               atol=1e-6)


def test_memory_bytes_counts_pool():
    m, _, cfg = _model("llama3.2-1b")
    cache = m.init_paged_cache(2, 16, 4, 8)
    got = paged_kv.memory_bytes(cache)
    pool = 2 * cfg.num_layers * 16 * 4 * cfg.num_kv_heads * cfg.head_dim \
        * jnp.dtype(cfg.act_dtype).itemsize
    assert got >= pool
    assert got <= pool + 10_000   # tables/indices are small
