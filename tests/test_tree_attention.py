"""Tree-verify attention: the paged jnp oracle vs the dense ring-path mask,
the Pallas kernel (interpret mode) vs the oracle, across tree shapes / GQA /
windows / ragged lengths — and the width-1 degenerate tree vs plain causal
paged attention (a chain IS a tree)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import paged_kv
from repro.cache.paged_kv import BlockAllocator
from repro.core.tree import TreeShape, chain_tree
from repro.kernels import ops, ref
from repro.models.attention import attn_paged, attn_tree, attn_tree_ring

SHAPES = {
    "chain2x2": chain_tree(2, 2),                      # span 5
    "chain3x3": chain_tree(3, 3),                      # span 10
    "chain2x4": chain_tree(2, 4),                      # span 9
    "chain1x4": chain_tree(1, 4),                      # degenerate linear
    # irregular: root -> {1, 2}; 1 -> {3, 4}; 2 -> {5}; 4 -> {6}
    "irregular": TreeShape(parents=(0, 0, 1, 1, 2, 4)),
}


def _pool_cache(key, B, n_tokens, BS, MB, Kv, D, dtype=jnp.float32, L=1,
                layer=0):
    """An ``L``-layer pool stack whose layer ``layer`` holds the dense K/V
    (every other layer holds other values at the same slots)."""
    NB = B * MB + 1
    alloc = BlockAllocator(NB, BS, MB, B)
    S = max(n_tokens)
    for b in range(B):
        assert alloc.ensure(b, n_tokens[b])
    table = alloc.device_table()
    kk, kv_ = jax.random.split(key)
    k_dense = jax.random.normal(kk, (B, S, Kv, D), jnp.float32)
    v_dense = jax.random.normal(kv_, (B, S, Kv, D), jnp.float32)
    pools = paged_kv.init_pool(L, NB, BS, Kv, D, dtype)
    start = jnp.zeros((B,), jnp.int32)
    for l in range(L):
        k_l, v_l = (k_dense, v_dense) if l == layer else (-k_dense, v_dense + 1)
        pools = paged_kv.write(pools, k_l, v_l, table, start, l)
    return pools, table, k_dense, v_dense


def _setup(shape, B, H, Kv, D, BS, MB, roots, seed=0, dtype=jnp.float32,
           L=1, layer=0):
    span = shape.span
    idx = jnp.asarray(roots, jnp.int32)                 # root positions
    n_tokens = [r + span for r in roots]
    layer, table, k_dense, v_dense = _pool_cache(
        jax.random.PRNGKey(seed), B, n_tokens, BS, MB, Kv, D, dtype=dtype,
        L=L, layer=layer)
    q = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, span, H, D),
                          jnp.float32).astype(dtype)
    depths = jnp.asarray(shape.depths)
    bits = jnp.asarray(shape.bits)
    return layer, table, k_dense, v_dense, q, idx, depths, bits


@pytest.mark.parametrize("name", sorted(SHAPES))
@pytest.mark.parametrize("H,Kv", [(4, 4), (8, 2)])
def test_oracle_matches_dense_tree_mask(name, H, Kv):
    shape = SHAPES[name]
    B, D, BS, MB = 3, 16, 4, 8
    layer, table, k_dense, v_dense, q, idx, depths, bits = _setup(
        shape, B, H, Kv, D, BS, MB, roots=[9, 16, 4])
    got = attn_tree(q, layer["k"], layer["v"], table, idx, depths, bits)
    S = int(jnp.max(idx)) + shape.span
    want = attn_tree_ring(q, k_dense[:, :S], v_dense[:, :S], idx,
                          depths, bits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_width1_tree_is_plain_causal_attention():
    """A width-1 chain's ancestor masks reduce the tree mask to causal —
    the degenerate tree must agree with the linear paged verify read."""
    shape = SHAPES["chain1x4"]
    B, H, Kv, D, BS, MB = 2, 4, 2, 16, 4, 8
    layer, table, _, _, q, idx, depths, bits = _setup(
        shape, B, H, Kv, D, BS, MB, roots=[7, 12])
    got = attn_tree(q, layer["k"], layer["v"], table, idx, depths, bits)
    want = attn_paged(q, layer["k"], layer["v"], table, idx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_sibling_branches_do_not_leak():
    """Scores must differ between a tree mask and full causal attention over
    the same span — if siblings were visible the two would coincide."""
    shape = SHAPES["chain3x3"]
    B, H, Kv, D, BS, MB = 1, 4, 2, 16, 4, 8
    layer, table, _, _, q, idx, depths, bits = _setup(
        shape, B, H, Kv, D, BS, MB, roots=[6])
    tree = attn_tree(q, layer["k"], layer["v"], table, idx, depths, bits)
    causal = attn_paged(q, layer["k"], layer["v"], table, idx)
    # root (slot 0) sees only the prefix either way
    np.testing.assert_allclose(np.asarray(tree[:, 0]),
                               np.asarray(causal[:, 0]), rtol=2e-5, atol=2e-5)
    # deeper slots have sibling KV in causal range but masked in the tree
    assert not np.allclose(np.asarray(tree[:, 1:]), np.asarray(causal[:, 1:]),
                           rtol=1e-3, atol=1e-3)


# ------------------------------------------------------------ Pallas kernel
@pytest.mark.parametrize("name", sorted(SHAPES))
@pytest.mark.parametrize("BS,MB,H,Kv", [(4, 8, 4, 4), (8, 4, 8, 2),
                                        (16, 2, 4, 1)])
@pytest.mark.parametrize("L,lyr", [(1, 0), (2, 1)])
def test_kernel_matches_oracle(name, BS, MB, H, Kv, L, lyr):
    """The kernel (interpret mode) against the jnp oracle and the float32
    dense ring-path mask, reading one layer of a stack by its index."""
    shape = SHAPES[name]
    B, D = 3, 32
    layer, table, k_dense, v_dense, q, idx, depths, bits = _setup(
        shape, B, H, Kv, D, BS, MB, roots=[11, 19, 3], seed=20, L=L,
        layer=lyr)
    got = ops.tree_attention(q, layer["k"], layer["v"], table, idx,
                             depths, bits, layer=lyr)
    want = ref.tree_attention_ref(q, layer["k"], layer["v"], table, idx,
                                  depths, bits, layer=lyr)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    S = int(jnp.max(idx)) + shape.span
    ring = attn_tree_ring(q, k_dense[:, :S], v_dense[:, :S], idx, depths,
                          bits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ring),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [5, 12])
def test_kernel_sliding_window(window):
    shape = SHAPES["chain2x4"]
    B, H, Kv, D, BS, MB = 2, 8, 2, 32, 8, 4
    layer, table, k_dense, v_dense, q, idx, depths, bits = _setup(
        shape, B, H, Kv, D, BS, MB, roots=[14, 8], seed=30)
    got = ops.tree_attention(q, layer["k"], layer["v"], table, idx,
                             depths, bits, window=window)
    want = ref.tree_attention_ref(q, layer["k"], layer["v"], table, idx,
                                  depths, bits, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    S = int(jnp.max(idx)) + shape.span
    ring = attn_tree_ring(q, k_dense[:, :S], v_dense[:, :S], idx, depths,
                          bits, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ring),
                               rtol=2e-5, atol=2e-5)


def test_kernel_bf16():
    shape = SHAPES["chain2x2"]
    B, H, Kv, D, BS, MB = 2, 8, 4, 32, 8, 4
    layer, table, _, _, q, idx, depths, bits = _setup(
        shape, B, H, Kv, D, BS, MB, roots=[10, 6], seed=40,
        dtype=jnp.bfloat16)
    got = ops.tree_attention(q, layer["k"], layer["v"], table, idx,
                             depths, bits)
    want = ref.tree_attention_ref(q, layer["k"], layer["v"], table, idx,
                                  depths, bits)
    assert got.shape == (B, shape.span, H, D) and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_shape_validation():
    with pytest.raises(ValueError, match="span"):
        chain_tree(5, 7)                                 # span 36 > 31
    with pytest.raises(ValueError, match="parent"):
        TreeShape(parents=(1,))                          # self/forward parent
    t = chain_tree(2, 3)
    assert t.span == 7 and t.max_depth == 3
    assert t.paths == ((1, 3, 5), (2, 4, 6))
    # ancestor masks: chain 1 level 3 sees root, 2, 4, 6 — not chain 0
    assert t.bits[6] == (1 | (1 << 2) | (1 << 4) | (1 << 6))
