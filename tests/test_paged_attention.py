"""Block-table-native paged attention: the jnp oracle vs the dense gathered
view, the Pallas kernel (interpret mode) vs the oracle, and the traffic
bound — reads scale with LIVE blocks, not worst-case row capacity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import paged_kv
from repro.cache.paged_kv import BlockAllocator
from repro.kernels import ops, ref
from repro.models.attention import attn_dense, attn_paged

def _pool_cache(key, B, n_tokens, BS, MB, Kv, D, num_blocks=None,
                dtype=jnp.float32, L=1, layer=0):
    """Build an ``L``-layer pool stack whose layer ``layer`` holds
    ``n_tokens[b]`` KV tokens per row (written via paged_kv.write), plus the
    dense [B, S, Kv, D] mirror. Every other layer holds other values at the
    same slots, so a reader of the wrong layer disagrees."""
    NB = num_blocks or (B * MB + 1)
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


def _dense_ref(q, k_dense, v_dense, index, window=None):
    """Oracle-of-the-oracle: dense attention over absolute positions with
    per-row query offsets (exactly what the old gathered read computed)."""
    B, Q = q.shape[0], q.shape[1]
    S = k_dense.shape[1]
    q_pos = jnp.asarray(index)[:, None] + jnp.arange(Q, dtype=jnp.int32)
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    return attn_dense(q, k_dense, v_dense, q_pos, kv_pos, window=window)


@pytest.mark.parametrize("BS,MB", [(4, 8), (8, 4), (16, 2), (3, 9)])
@pytest.mark.parametrize("H,Kv", [(4, 4), (8, 2), (6, 1)])
@pytest.mark.parametrize("L,lyr", [(1, 0), (3, 2)])
def test_oracle_matches_dense_blocksizes_gqa(BS, MB, H, Kv, L, lyr):
    B, Q, D = 3, 4, 16
    n_tokens = [10, 17, 6]                      # ragged committed lengths
    key = jax.random.PRNGKey(0)
    layer, table, k_dense, v_dense = _pool_cache(key, B, [n + Q for n in n_tokens],
                                                 BS, MB, Kv, D, L=L, layer=lyr)
    q = jax.random.normal(jax.random.PRNGKey(1), (B, Q, H, D), jnp.float32)
    index = jnp.asarray(n_tokens, jnp.int32)
    got = attn_paged(q, layer["k"], layer["v"], table, index, layer=lyr)
    S = max(n_tokens) + Q
    want = _dense_ref(q, k_dense[:, :S], v_dense[:, :S], index)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_pool_is_head_major():
    """The pool layout: token p of row b in layer l sits at pool[l,
    table[b, p // BS], p % BS], its kv heads side by side in one Kv*D row
    (head h in columns h*D..h*D+D), so one block is a contiguous
    [BS, Kv*D] tile, the unit the TPU kernels copy."""
    B, BS, MB, Kv, D, L = 2, 4, 4, 3, 8, 2
    layer, table, k_dense, _ = _pool_cache(jax.random.PRNGKey(7), B, [11, 6],
                                           BS, MB, Kv, D, L=L, layer=1)
    assert layer["k"].shape == (L, B * MB + 1, BS, Kv * D)
    pool, tbl = np.asarray(layer["k"]), np.asarray(table)
    for b, n in enumerate([11, 6]):
        for p in range(n):
            for h in range(Kv):
                np.testing.assert_array_equal(
                    pool[1, tbl[b, p // BS], p % BS, h * D:(h + 1) * D],
                    np.asarray(k_dense[b, p, h]))


def test_oracle_int8_pool_matches_dense_dequantized():
    """int8 pools (fixed-scale KV) take the oracle path on every backend;
    it reads the token-major int8 blocks and matches dense attention over
    the dequantized values."""
    from repro.cache.kv_cache import _from_buf
    B, Q, H, Kv, D, BS, MB = 2, 3, 4, 2, 8, 4, 6
    n_tokens = [9, 13]
    layer, table, _, _ = _pool_cache(jax.random.PRNGKey(8), B,
                                     [n + Q for n in n_tokens], BS, MB, Kv,
                                     D, dtype=jnp.int8)
    q = jax.random.normal(jax.random.PRNGKey(9), (B, Q, H, D), jnp.float32)
    index = jnp.asarray(n_tokens, jnp.int32)
    got = attn_paged(q, layer["k"], layer["v"], table, index)
    S = max(n_tokens) + Q
    tbl = np.asarray(table)
    deq = lambda pool: jnp.stack([
        _from_buf(pool[0, tbl[b, np.arange(S) // BS], np.arange(S) % BS],
                  jnp.float32).reshape(S, Kv, D) for b in range(B)])
    want = _dense_ref(q, deq(layer["k"]), deq(layer["v"]), index)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [None, 5, 12])
def test_oracle_sliding_window(window):
    B, Q, H, Kv, D, BS, MB = 2, 3, 4, 2, 8, 4, 8
    n_tokens = [14, 9]
    layer, table, k_dense, v_dense = _pool_cache(jax.random.PRNGKey(2), B,
                                                 [n + Q for n in n_tokens],
                                                 BS, MB, Kv, D)
    q = jax.random.normal(jax.random.PRNGKey(3), (B, Q, H, D), jnp.float32)
    index = jnp.asarray(n_tokens, jnp.int32)
    got = attn_paged(q, layer["k"], layer["v"], table, index, window=window)
    S = max(n_tokens) + Q
    want = _dense_ref(q, k_dense[:, :S], v_dense[:, :S], index, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_oracle_single_token_decode_and_scalar_index():
    B, H, Kv, D, BS, MB = 2, 4, 2, 8, 4, 6
    layer, table, k_dense, v_dense = _pool_cache(jax.random.PRNGKey(4), B,
                                                 [8, 8], BS, MB, Kv, D)
    q = jax.random.normal(jax.random.PRNGKey(5), (B, 1, H, D), jnp.float32)
    got = attn_paged(q, layer["k"], layer["v"], table, jnp.int32(7))
    want = _dense_ref(q, k_dense[:, :8], v_dense[:, :8],
                      jnp.full((B,), 7, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_traffic_bounded_by_live_blocks_not_capacity():
    """THE point of the read-path split: with a worst-case row capacity of
    64 blocks but only ~2 live blocks, the block-scan reads ~2 blocks/row —
    the old gathered view always read all 64."""
    B, Q, H, Kv, D, BS, MB = 4, 2, 4, 2, 8, 8, 64
    live_tokens = 12                             # 2 blocks of 8 once Q lands
    layer, table, _, _ = _pool_cache(jax.random.PRNGKey(6), B,
                                     [live_tokens + Q] * B, BS, MB, Kv, D,
                                     num_blocks=2 * B * 8 + 1)
    q = jax.random.normal(jax.random.PRNGKey(7), (B, Q, H, D), jnp.float32)
    index = jnp.full((B,), live_tokens, jnp.int32)
    _, stats = attn_paged(q, layer["k"], layer["v"], table, index,
                          return_stats=True)
    live_blocks = -(-(live_tokens + Q) // BS)
    assert int(stats["blocks_read"]) == B * live_blocks
    assert int(stats["blocks_read"]) < int(stats["max_blocks"]) // 16
    # the bound follows the longest LIVE row, not the capacity
    _, stats2 = attn_paged(q, layer["k"], layer["v"], table,
                           jnp.asarray([2, 2, 2, live_tokens], jnp.int32),
                           return_stats=True)
    assert int(stats2["blocks_read"]) == B * live_blocks


def test_explicit_max_live_bound_is_honored():
    B, Q, H, Kv, D, BS, MB = 2, 1, 4, 2, 8, 4, 16
    layer, table, k_dense, v_dense = _pool_cache(jax.random.PRNGKey(8), B,
                                                 [9, 5], BS, MB, Kv, D)
    q = jax.random.normal(jax.random.PRNGKey(9), (B, Q, H, D), jnp.float32)
    index = jnp.asarray([8, 4], jnp.int32)
    got, stats = attn_paged(q, layer["k"], layer["v"], table, index,
                            max_live=jnp.int32(9), return_stats=True)
    assert int(stats["blocks_read"]) == B * -(-9 // BS)
    want = _dense_ref(q, k_dense[:, :9], v_dense[:, :9], index)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # the Pallas kernel path honors the same explicit bound, including a
    # TRUNCATING one (max_live=5 hides keys row 0 could otherwise see)
    for bound in (9, 5):
        got_k = ops.paged_attention(q, layer["k"], layer["v"], table, index,
                                    max_live=jnp.int32(bound))
        want_k = attn_paged(q, layer["k"], layer["v"], table, index,
                            max_live=jnp.int32(bound))
        np.testing.assert_allclose(np.asarray(got_k), np.asarray(want_k),
                                   rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ Pallas kernel
@pytest.mark.parametrize("BS,MB", [(8, 4), (4, 8), (16, 2)])
@pytest.mark.parametrize("H,Kv,window", [(4, 4, None), (8, 2, None),
                                         (8, 2, 7), (4, 1, None)])
@pytest.mark.parametrize("L,lyr", [(1, 0), (3, 1)])
def test_kernel_matches_oracle(BS, MB, H, Kv, window, L, lyr):
    """The kernel (interpret mode) against the jnp oracle and the float32
    dense reference, reading one layer of a stack by its index."""
    B, Q, D = 3, 3, 32
    n_tokens = [13, 21, 5]
    layer, table, k_dense, v_dense = _pool_cache(
        jax.random.PRNGKey(10), B, [n + Q for n in n_tokens], BS, MB, Kv, D,
        L=L, layer=lyr)
    q = jax.random.normal(jax.random.PRNGKey(11), (B, Q, H, D), jnp.float32)
    index = jnp.asarray(n_tokens, jnp.int32)
    got = ops.paged_attention(q, layer["k"], layer["v"], table, index,
                              layer=lyr, window=window)
    want = ref.paged_attention_ref(q, layer["k"], layer["v"], table, index,
                                   layer=lyr, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    S = max(n_tokens) + Q
    dense = _dense_ref(q, k_dense[:, :S], v_dense[:, :S], index,
                       window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("index", [0, 19])
def test_kernel_prefill_chunk_row_tiles(index):
    """A prefill chunk's Q*group rows (280 here) run as several row tiles of
    the kernel's grid; the result matches the oracle and dense attention."""
    from repro.kernels.paged_attention import ROW_TILE
    B, Q, H, Kv, D, BS, MB = 1, 70, 8, 2, 16, 8, 12
    assert Q * H // Kv > ROW_TILE
    layer, table, k_dense, v_dense = _pool_cache(
        jax.random.PRNGKey(16), B, [index + Q], BS, MB, Kv, D, L=2, layer=1)
    q = jax.random.normal(jax.random.PRNGKey(17), (B, Q, H, D), jnp.float32)
    idx = jnp.asarray([index], jnp.int32)
    got = ops.paged_attention(q, layer["k"], layer["v"], table, idx, layer=1)
    want = ref.paged_attention_ref(q, layer["k"], layer["v"], table, idx,
                                   layer=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    dense = _dense_ref(q, k_dense, v_dense, idx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_kernel_bf16_and_decode_shape():
    B, Q, H, Kv, D, BS, MB = 2, 1, 8, 4, 32, 8, 4
    layer, table, _, _ = _pool_cache(jax.random.PRNGKey(12), B, [17, 9],
                                     BS, MB, Kv, D, dtype=jnp.bfloat16)
    q = jax.random.normal(jax.random.PRNGKey(13), (B, Q, H, D), jnp.bfloat16)
    index = jnp.asarray([16, 8], jnp.int32)
    got = ops.paged_attention(q, layer["k"], layer["v"], table, index)
    want = ref.paged_attention_ref(q, layer["k"], layer["v"], table, index)
    assert got.shape == (B, Q, H, D) and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_kernel_speculative_verify_shape():
    """gamma+1-query verify round over ragged rows (the serving hot path)."""
    B, Q, H, Kv, D, BS, MB = 4, 5, 8, 2, 16, 8, 8
    n_tokens = [7, 30, 18, 1]
    layer, table, _, _ = _pool_cache(jax.random.PRNGKey(14), B,
                                     [n + Q for n in n_tokens], BS, MB, Kv, D)
    q = jax.random.normal(jax.random.PRNGKey(15), (B, Q, H, D), jnp.float32)
    index = jnp.asarray(n_tokens, jnp.int32)
    got = ops.paged_attention(q, layer["k"], layer["v"], table, index)
    want = ref.paged_attention_ref(q, layer["k"], layer["v"], table, index)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_no_full_capacity_gather_on_model_path():
    """End-to-end guard for the acceptance criterion: a paged decode step
    through the model stack must not materialize the [B, MB*BS, Kv, D]
    gathered view. paged_kv exposes only write() now; this asserts the
    jaxpr of a paged decode contains no gather/reshape to MB*BS rows."""
    from repro.configs import registry
    from repro.models.model import build_model

    cfg = registry.smoke_config("llama3.2-1b")
    m = build_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    B, BS, MB = 2, 4, 32                      # heavily over-provisioned rows
    alloc = BlockAllocator(B * MB + 1, BS, MB, B)
    for b in range(B):
        alloc.ensure(b, 8)
    cache = m.init_paged_cache(B, B * MB + 1, BS, MB)
    cache = {**cache, "block_table": alloc.device_table(),
             "index": jnp.full((B,), 7, jnp.int32)}
    tok = jnp.zeros((B, 1), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda pp, c: m.apply(pp, tok, c)[0])(p, cache)

    full = MB * BS

    def walk(jx, found):
        for eqn in jx.eqns:
            for v in eqn.outvars:
                shape = getattr(getattr(v, "aval", None), "shape", ())
                if len(shape) == 4 and shape[1] == full:
                    found.append((eqn.primitive.name, shape))
            for pv in eqn.params.values():
                inner = getattr(pv, "jaxpr", None)
                if inner is not None:
                    walk(inner, found)
        return found

    bad = walk(jaxpr.jaxpr, [])
    assert not bad, f"full-capacity [B, MB*BS, ...] gather found: {bad[:3]}"
    assert hasattr(paged_kv, "write")
    assert not hasattr(paged_kv, "extend")
