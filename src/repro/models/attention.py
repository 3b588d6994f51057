"""Attention: GQA with causal / sliding-window masking, cache-aware.

Three execution paths with identical semantics (tests assert allclose):

  * ``attn_dense``   — materializes the [B,H,Q,S] score matrix. Used for short
                       sequences and single-token decode.
  * ``attn_chunked`` — lax.scan over KV chunks with an online softmax
                       (flash-attention-style, O(S·chunk) memory). Used for long
                       prefill so the 32k/500k shapes lower without an S×S tensor.
  * ``attn_paged``   — block-table-native read path for paged block-pool caches
                       (cache/paged_kv.py): a bounded loop over KV *blocks* with
                       an online softmax that stops at the batch-max live block,
                       so per-step reads scale with resident tokens instead of
                       ``max_blocks_per_row * block_size`` worst-case capacity.

The Pallas TPU kernels in repro.kernels.flash_attention (prefill) and
repro.kernels.paged_attention (paged decode) are the hardware-targeted drop-ins;
model code selects them on TPU via ``attention_paged`` below. The pure-jnp
paths here are the oracles and the CPU/dry-run path.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-but-finite; avoids NaNs from (-inf) - (-inf)


def _mask(q_pos, kv_pos, window, causal=True):
    """Boolean mask [Q,S] (shared positions) or [B,Q,S] (per-row positions,
    the batched-speculation path): causal + optional sliding window."""
    qp = q_pos[..., :, None]
    kp = kv_pos[..., None, :]
    if causal:
        m = qp >= kp
    else:
        m = jnp.broadcast_to(kp >= -1, jnp.broadcast_shapes(qp.shape, kp.shape))
    if window is not None:
        m = m & (jnp.abs(qp - kp) < window)
    m = m & (kp >= 0)  # invalid cache slots carry position -1
    return m


def _expand_mask(m):
    """[Q,S] -> [1,1,1,Q,S]; [B,Q,S] -> [B,1,1,Q,S] (scores are [B,Kv,G,Q,S])."""
    if m.ndim == 2:
        return m[None, None, None]
    return m[:, None, None]


def _gqa_scores(q, k):
    """q:[B,Q,H,D] k:[B,S,Kv,D] -> [B,Kv,H/Kv,Q,S] fp32."""
    B, Q, H, D = q.shape
    Kv = k.shape[2]
    q = q.reshape(B, Q, Kv, H // Kv, D)
    return jnp.einsum("bqkgd,bskd->bkgqs", q.astype(jnp.float32), k.astype(jnp.float32))


def attn_dense(q, k, v, q_pos, kv_pos, *, window=None, scale=None, causal=True,
               mask=None):
    """q:[B,Q,H,D] k,v:[B,S,Kv,D] positions int32 -> [B,Q,H,D].

    ``mask`` overrides the causal/window mask (the tree-speculation path
    builds its ancestor-bitmask visibility explicitly)."""
    B, Q, H, D = q.shape
    Kv = k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    s = _gqa_scores(q, k) * scale                             # [B,Kv,G,Q,S]
    m = mask if mask is not None else _mask(q_pos, kv_pos, window, causal)
    s = jnp.where(_expand_mask(m), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Q, H, D).astype(q.dtype)


def _online_carry(B, Kv, G, Q, D):
    return (jnp.zeros((B, Kv, G, Q, D), jnp.float32),
            jnp.full((B, Kv, G, Q), NEG_INF, jnp.float32),
            jnp.zeros((B, Kv, G, Q), jnp.float32))


def _online_step(carry, qf, k_i, v_i, q_pos, kv_pos, window, scale,
                 causal=True, mask=None):
    """One online-softmax update over a KV slab — the shared inner step of
    attn_chunked (pre-chunked scan) and attn_paged (block-table fetch); the
    Pallas kernels implement the same recurrence in-VMEM. ``mask`` overrides
    the causal/window mask (tree-speculation visibility)."""
    acc, mx, den = carry
    s = jnp.einsum("bqkgd,bskd->bkgqs", qf, k_i.astype(jnp.float32)) * scale
    m = mask if mask is not None else _mask(q_pos, kv_pos, window, causal)
    s = jnp.where(_expand_mask(m), s, NEG_INF)
    mx_new = jnp.maximum(mx, s.max(axis=-1))
    alpha = jnp.exp(mx - mx_new)
    p = jnp.exp(s - mx_new[..., None])
    den = den * alpha + p.sum(axis=-1)
    acc = acc * alpha[..., None] + jnp.einsum("bkgqs,bskd->bkgqd", p,
                                              v_i.astype(jnp.float32))
    return acc, mx_new, den


def _online_emit(acc, den, B, Q, H, D, dtype):
    o = acc / jnp.maximum(den, 1e-30)[..., None]              # [B,Kv,G,Q,D]
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Q, H, D).astype(dtype)


def attn_chunked(q, k, v, q_pos, kv_pos, *, window=None, scale=None, chunk=512, causal=True):
    """Online-softmax attention scanning over KV chunks. Same semantics as attn_dense."""
    B, Q, H, D = q.shape
    S, Kv = k.shape[1], k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    n_chunks = -(-S // chunk)
    pad = n_chunks * chunk - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=-1)
    kc = k.reshape(B, n_chunks, chunk, Kv, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, Kv, D).transpose(1, 0, 2, 3, 4)
    pc = kv_pos.reshape(n_chunks, chunk)
    qf = q.reshape(B, Q, Kv, H // Kv, D).astype(jnp.float32)

    def step(carry, x):
        k_i, v_i, p_i = x
        return _online_step(carry, qf, k_i, v_i, q_pos, p_i, window, scale,
                            causal), None

    (acc, _, den), _ = jax.lax.scan(step, _online_carry(B, Kv, H // Kv, Q, D),
                                    (kc, vc, pc))
    return _online_emit(acc, den, B, Q, H, D, q.dtype)


def attention(q, k, v, q_pos, kv_pos, *, window=None, scale=None,
              chunk=512, force_dense=False, causal=True):
    """Dispatch: dense path for short KV, chunked for long KV."""
    S = k.shape[1]
    if force_dense or S <= 2 * chunk:
        return attn_dense(q, k, v, q_pos, kv_pos, window=window, scale=scale, causal=causal)
    return attn_chunked(q, k, v, q_pos, kv_pos, window=window, scale=scale, chunk=chunk,
                        causal=causal)


# ------------------------------------------------------------- paged read path
def _take_block(pool, layer, blk, Kv, dtype):
    """Gather layer ``layer``'s pool block ``blk[b]`` for every row:
    [L, NB, BS, Kv*D] -> [B, BS, Kv, D] (the layout ``_online_step``
    reads)."""
    from repro.cache.kv_cache import _from_buf
    x = _from_buf(pool[layer, blk], dtype)                        # [B, BS, Kv*D]
    return x.reshape(x.shape[:2] + (Kv, x.shape[-1] // Kv))


def attn_paged(q, k_pool, v_pool, block_table, index, *, layer=0,
               window=None, scale=None, max_live=None, return_stats=False):
    """Block-table-native attention over a paged KV pool (jnp oracle).

    q:            [B, Q, H, D] queries at absolute positions index..index+Q-1
                  (already written into the pool by ``paged_kv.write``).
    k_pool/v_pool:[L, NB, BS, Kv*D] a layer stack's block pools, post-write
                  (cache/paged_kv.py); ``layer`` picks the layer read.
    block_table:  [B, MB] int32 row -> pool block ids (NULL block = 0).
    index:        [B] (or scalar) committed tokens per row BEFORE this write.
    max_live:     optional live-token bound (max over rows of index+Q); when
                  None it is computed in-graph. Engines thread it down so one
                  round-level bound drives every layer.

    The loop runs ``ceil(max_live / BS)`` block steps — NOT ``MB`` — so KV
    reads are bounded by the batch-max live block count, never the worst-case
    row capacity. The gathered ``[B, MB*BS, Kv, D]`` view of the old read path
    is never materialized. Slot j*BS+o of a row holds absolute position
    j*BS+o, so the causal mask alone hides stale and unallocated slots.

    return_stats=True also returns {"blocks_read", "max_blocks"}: the counter
    is carried through the actual loop, so tests can assert the traffic bound.
    """
    B, Q, H, D = q.shape
    BS, Kv = k_pool.shape[2], k_pool.shape[3] // D
    MB = block_table.shape[1]
    G = H // Kv
    scale = scale if scale is not None else D ** -0.5
    idx = jnp.asarray(index)
    if idx.ndim == 0:
        idx = jnp.broadcast_to(idx, (B,))
    q_pos = idx[:, None] + jnp.arange(Q, dtype=jnp.int32)         # [B, Q]
    live = (jnp.max(idx) + Q) if max_live is None else jnp.asarray(max_live)
    n_blocks = jnp.clip((live + BS - 1) // BS, 1, MB).astype(jnp.int32)

    qf = q.reshape(B, Q, Kv, G, D).astype(jnp.float32)

    def body(j, carry):
        softmax_carry, n_read = carry
        blk = jnp.take(block_table, j, axis=1)                    # [B]
        k_j = _take_block(k_pool, layer, blk, Kv, q.dtype)        # [B, BS, Kv, D]
        v_j = _take_block(v_pool, layer, blk, Kv, q.dtype)
        kv_pos = j * BS + jnp.arange(BS, dtype=jnp.int32)         # [BS]
        softmax_carry = _online_step(softmax_carry, qf, k_j, v_j, q_pos,
                                     kv_pos, window, scale)
        return softmax_carry, n_read + B

    (acc, _, den), n_read = jax.lax.fori_loop(
        0, n_blocks, body, (_online_carry(B, Kv, G, Q, D),
                            jnp.zeros((), jnp.int32)))
    o = _online_emit(acc, den, B, Q, H, D, q.dtype)
    if return_stats:
        return o, {"blocks_read": n_read, "max_blocks": B * MB}
    return o


def attention_paged(q, k_pool, v_pool, block_table, index, *, layer=0,
                    window=None, scale=None, max_live=None):
    """Paged-attention dispatch: Pallas kernel on TPU (float pools), jnp
    oracle everywhere else (CPU, dry-run, int8 KV pools)."""
    if jax.default_backend() == "tpu" and k_pool.dtype != jnp.int8 \
            and scale is None:
        from repro.kernels import ops
        return ops.paged_attention(q, k_pool, v_pool, block_table, index,
                                   layer=layer, window=window,
                                   max_live=max_live)
    return attn_paged(q, k_pool, v_pool, block_table, index, layer=layer,
                      window=window, scale=scale, max_live=max_live)


# -------------------------------------------------------------- tree read path
def _tree_mask(idx, kv_pos, depths, bits, window):
    """[B, span, S] visibility for one stacked tree-verify pass.

    Query slot ``s`` sits at RoPE position ``idx + depths[s]``; its KV row is
    physically written at cache slot ``idx + s``.  Visibility:

      * committed prefix (kv_pos < idx): ordinary causal (+ window vs the
        query's RoPE position);
      * in-span slot t (idx <= kv_pos < idx + span): visible iff bit t of the
        query's ancestor mask is set — i.e. only along the query's own
        root path (+ window over the depth gap);
      * beyond the span: stale slots, never visible.
    """
    span = depths.shape[0]
    if kv_pos.ndim == 1:                                         # [S] shared
        kv_pos = jnp.broadcast_to(kv_pos[None, :], (idx.shape[0],
                                                    kv_pos.shape[0]))
    rel = kv_pos - idx[:, None]                                  # [B, S]
    span_vis = ((bits[:, None] >> jnp.arange(span, dtype=jnp.int32)[None, :])
                & 1) > 0                                         # [span, span]
    if window is not None:
        span_vis &= (depths[:, None] - depths[None, :]) < window
    prefix = (rel < 0)[:, None, :] & (kv_pos >= 0)[:, None, :]
    if window is not None:
        q_pos = idx[:, None] + depths[None, :]
        prefix &= (q_pos[:, :, None] - kv_pos[:, None, :]) < window
    relc = jnp.clip(rel, 0, span - 1)
    # span_vis[:, relc]: [span, B, S] -> [B, span, S]
    inspan = jnp.take(span_vis, relc, axis=1).transpose(1, 0, 2)
    inspan &= ((rel >= 0) & (rel < span))[:, None, :]
    return prefix | inspan


def attn_tree_ring(q, k, v, index, depths, bits, *, window=None, scale=None):
    """Tree-verify attention over a ring cache (jnp path).

    q: [B, span, H, D] — the packed [root, node_1..node_N] verify span, whose
    KV was just written at contiguous cache slots index..index+span-1."""
    B = q.shape[0]
    S = k.shape[1]
    idx = jnp.asarray(index)
    if idx.ndim == 0:
        idx = jnp.broadcast_to(idx, (B,))
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    m = _tree_mask(idx, kv_pos, depths, bits, window)
    q_pos = idx[:, None] + depths[None, :]
    return attn_dense(q, k, v, q_pos, kv_pos, window=window, scale=scale,
                      mask=m)


def attn_tree(q, k_pool, v_pool, block_table, index, depths, bits, *,
              layer=0, window=None, scale=None, max_live=None):
    """Tree-verify attention over a paged block pool (jnp oracle).

    Same block-bounded online-softmax loop as ``attn_paged``, with the
    causal mask replaced by ``_tree_mask``: the span slots written at
    index..index+span-1 are only visible along each query's root path."""
    B, S, H, D = q.shape                                        # S = span
    BS, Kv = k_pool.shape[2], k_pool.shape[3] // D
    MB = block_table.shape[1]
    G = H // Kv
    scale = scale if scale is not None else D ** -0.5
    idx = jnp.asarray(index)
    if idx.ndim == 0:
        idx = jnp.broadcast_to(idx, (B,))
    live = (jnp.max(idx) + S) if max_live is None else jnp.asarray(max_live)
    n_blocks = jnp.clip((live + BS - 1) // BS, 1, MB).astype(jnp.int32)
    depths = jnp.asarray(depths, jnp.int32)
    bits = jnp.asarray(bits, jnp.int32)
    q_pos = idx[:, None] + depths[None, :]
    qf = q.reshape(B, S, Kv, G, D).astype(jnp.float32)

    def body(j, carry):
        blk = jnp.take(block_table, j, axis=1)                   # [B]
        k_j = _take_block(k_pool, layer, blk, Kv, q.dtype)
        v_j = _take_block(v_pool, layer, blk, Kv, q.dtype)
        kv_pos = j * BS + jnp.arange(BS, dtype=jnp.int32)
        m = _tree_mask(idx, kv_pos, depths, bits, window)
        return _online_step(carry, qf, k_j, v_j, q_pos, kv_pos, window,
                            scale, mask=m)

    acc, _, den = jax.lax.fori_loop(0, n_blocks, body,
                                    _online_carry(B, Kv, G, S, D))
    return _online_emit(acc, den, B, S, H, D, q.dtype)


def attention_tree(q, k_pool, v_pool, block_table, index, depths, bits, *,
                   layer=0, window=None, scale=None, max_live=None):
    """Tree-attention dispatch: Pallas kernel on TPU (float pools), jnp
    oracle everywhere else (CPU, dry-run, int8 KV pools)."""
    if jax.default_backend() == "tpu" and k_pool.dtype != jnp.int8 \
            and scale is None:
        from repro.kernels import ops
        return ops.tree_attention(q, k_pool, v_pool, block_table, index,
                                  depths, bits, layer=layer, window=window,
                                  max_live=max_live)
    return attn_tree(q, k_pool, v_pool, block_table, index, depths, bits,
                     layer=layer, window=window, scale=scale,
                     max_live=max_live)
