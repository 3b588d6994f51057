"""Pallas TPU kernel: block-table-native paged flash-decode attention.

The TPU drop-in for ``repro.models.attention.attn_paged`` (the jnp oracle —
see ref.py): speculative-decode queries (Q = gamma+1 rows per sequence)
attending over a paged KV block pool without ever materializing the
``[B, max_blocks_per_row * block_size, Kv, D]`` gathered view the old read
path built per layer per round.

Structure (same skeleton as kernels/flash_attention.py):

  * grid ``(B, row tiles, max_blocks_per_row)`` with the KV-block axis
    innermost so the running (max, denom, accum) of every kv head persist
    in VMEM scratch across blocks;
  * one grid step reads one whole ``[BS, Kv * D]`` block of the token-major
    pool (cache/paged_kv.py) and runs all kv heads in its body, cutting
    each head's ``D`` columns out in VMEM (a one-head BlockSpec would be a
    ``D``-wide block of the minor dim, which the TPU takes only when ``D``
    is a multiple of 128; the whole block is legal at every width);
  * GQA folded into the q rows — each kv head attends ``Q * group`` query
    rows against its columns of the block; a prefill chunk's thousands of
    rows are cut into tiles of at most ``ROW_TILE`` (a middle grid axis, of
    length 1 for decode), so the all-heads blocks stay inside VMEM;
  * the pools are the whole layer stack ``[L, NB, BS, Kv * D]``; the layer
    index and the block-table indices are resolved IN-KERNEL via scalar
    prefetch (``PrefetchScalarGridSpec``): the k/v index maps read them, so
    each grid step DMAs exactly one live block of one layer and no layer's
    pool is ever sliced out of the stack;
  * dead steps (``j >= live_blocks[row]``) clamp the index map to the row's
    last live block — Pallas elides the re-fetch of an unchanged block — and
    skip their compute via ``pl.when``, so both traffic and FLOPs are bounded
    by the row's LIVE block count, not the worst-case row capacity.

Interpret mode executes the same body on CPU; tests assert parity against
the oracle across block sizes / GQA / sliding windows / ragged lengths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
ROW_TILE = 256      # q rows (positions x group) a grid step holds per head


def init_scratch(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def attend_block(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, mask, scale):
    """One online-softmax step of every kv head over one pool block: head
    ``h`` attends its q rows ``q_ref[0, h]`` against the block's columns
    ``h*D .. h*D+D``, cut out in VMEM. ``mask`` [R, bs] is shared by the
    heads. Blocks: q [1, Kv, R, D]; k/v [1, 1, bs, Kv*D]; scratch m/l
    [Kv, R, 1], acc [Kv, R, D]."""
    Kv, D = q_ref.shape[1], q_ref.shape[3]
    for h in range(Kv):
        cols = pl.ds(h * D, D)
        q = q_ref[0, h].astype(jnp.float32)                    # [R, D]
        k = k_ref[0, 0, :, cols].astype(jnp.float32)           # [bs, D]
        v = v_ref[0, 0, :, cols].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(mask, s * scale, NEG_INF)

        m_prev = m_ref[h, :, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[h, :, 0] = l_ref[h, :, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[h] = acc_ref[h] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[h, :, 0] = m_new


def emit(o_ref, l_ref, acc_ref):
    denom = jnp.maximum(l_ref[:, :, 0], 1e-30)
    o_ref[0] = (acc_ref[...] / denom[:, :, None]).astype(o_ref.dtype)


def _kernel(tbl_ref, live_ref, idx_ref, layer_ref, q_ref, k_ref, v_ref,
            o_ref, m_ref, l_ref, acc_ref, *, bs: int, gq: int, window,
            scale: float):
    """Grid (row b, row tile t, block j); blocks as in ``attend_block``,
    R = one tile of the padded Q*gq rows."""
    b = pl.program_id(0)
    t = pl.program_id(1)
    j = pl.program_id(2)
    R = q_ref.shape[2]

    pl.when(j == 0)(lambda: init_scratch(m_ref, l_ref, acc_ref))

    @pl.when(j < live_ref[b])
    def _compute():
        # rows are (q position, group); padded tail rows are sliced off by
        # the wrapper, their positions just run past the live length
        r_iota = t * R + jax.lax.broadcasted_iota(jnp.int32, (R, bs), 0)
        q_pos = idx_ref[b] + r_iota // gq
        kv_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (R, bs), 1)
        mask = q_pos >= kv_pos
        if window is not None:
            mask &= jnp.abs(q_pos - kv_pos) < window
        attend_block(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, mask, scale)

    pl.when(j == pl.num_programs(2) - 1)(lambda: emit(o_ref, l_ref, acc_ref))


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_flash_attention(q, k_pool, v_pool, block_table, index, layer=0, *,
                          window=None, interpret=False, max_live=None):
    """q: [B, Q, H, D]; k_pool/v_pool: [L, NB, BS, Kv*D]; block_table:
    [B, MB]; index: [B] committed tokens per row (queries sit at
    index..index+Q-1, already written into layer ``layer`` of the pool).
    H = Kv * gq (GQA-aware). ``max_live`` caps every row's scanned blocks at
    ceil(max_live/BS), matching the oracle's explicit-bound truncation
    semantics. The kernel's result is ``[B, Kv, R, D]``, R = Q*gq padded to
    a multiple of 8 (of ``ROW_TILE`` above it)."""
    B, Q, H, D = q.shape
    BS, Kv = k_pool.shape[2], k_pool.shape[3] // D
    MB = block_table.shape[1]
    gq = H // Kv
    scale = D ** -0.5
    idx = jnp.asarray(index, jnp.int32)
    if idx.ndim == 0:
        idx = jnp.broadcast_to(idx, (B,))
    live = jnp.clip((idx + Q + BS - 1) // BS, 1, MB).astype(jnp.int32)
    if max_live is not None:
        cap = jnp.clip((jnp.asarray(max_live, jnp.int32) + BS - 1) // BS,
                       1, MB).astype(jnp.int32)
        live = jnp.minimum(live, cap)
    lyr = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    # rows = (q position, group); pad to a sublane multiple for the VPU
    # tiles, and to whole row tiles where there is more than one
    qr = q.reshape(B, Q, Kv, gq, D).transpose(0, 2, 1, 3, 4) \
          .reshape(B, Kv, Q * gq, D)
    tile = ROW_TILE if Q * gq > ROW_TILE else 8
    R = -(-(Q * gq) // tile) * tile
    TR = min(R, ROW_TILE)
    if R != Q * gq:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, R - Q * gq), (0, 0)))

    def _kv_map(b, t, j, tbl, live_b, _idx, lyr):
        jj = jnp.minimum(j, jnp.maximum(live_b[b] - 1, 0))
        return (lyr[0], tbl[b, jj], 0, 0)

    def _q_map(b, t, j, *_):
        return (b, 0, t, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, R // TR, MB),
        in_specs=[
            pl.BlockSpec((1, Kv, TR, D), _q_map),
            pl.BlockSpec((1, 1, BS, Kv * D), _kv_map),
            pl.BlockSpec((1, 1, BS, Kv * D), _kv_map),
        ],
        out_specs=pl.BlockSpec((1, Kv, TR, D), _q_map),
        scratch_shapes=[pltpu.VMEM((Kv, TR, 1), jnp.float32),
                        pltpu.VMEM((Kv, TR, 1), jnp.float32),
                        pltpu.VMEM((Kv, TR, D), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=BS, gq=gq, window=window, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kv, R, D), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(block_table.astype(jnp.int32), live, idx, lyr, qr, k_pool, v_pool)
    return out[:, :, :Q * gq].reshape(B, Kv, Q, gq, D) \
              .transpose(0, 2, 1, 3, 4).reshape(B, Q, H, D)
