"""Pallas TPU kernel: block-table-native paged flash-decode attention.

The TPU drop-in for ``repro.models.attention.attn_paged`` (the jnp oracle —
see ref.py): speculative-decode queries (Q = gamma+1 rows per sequence)
attending over a paged KV block pool without ever materializing the
``[B, max_blocks_per_row * block_size, Kv, D]`` gathered view the old read
path built per layer per round.

Structure (same skeleton as kernels/flash_attention.py):

  * grid ``(B, Kv, max_blocks_per_row)`` with the KV-block axis innermost so
    the running (max, denom, accum) persist in VMEM scratch across blocks;
  * GQA folded into the q rows — each (batch, kv-head) program attends
    ``Q * group`` query rows against that head's KV blocks;
  * block-table indices resolved IN-KERNEL via scalar prefetch
    (``PrefetchScalarGridSpec``): the k/v index maps read the prefetched
    block table, so each grid step DMAs exactly one live pool block;
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


def _kernel(tbl_ref, live_ref, idx_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, bs: int, gq: int, window,
            scale: float):
    """Blocks: q/o [1, 1, R, D]; k/v [1, 1, bs, D] (R = padded Q*gq rows)."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    n_j = pl.num_programs(2)
    R = q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < live_ref[b])
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                    # [R, D]
        k = k_ref[0, 0].astype(jnp.float32)                    # [bs, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        # rows are (q position, group); padded tail rows are sliced off by
        # the wrapper, their positions just run past the live length
        r_iota = jax.lax.broadcasted_iota(jnp.int32, (R, bs), 0)
        q_pos = idx_ref[b] + r_iota // gq
        kv_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (R, bs), 1)
        mask = q_pos >= kv_pos
        if window is not None:
            mask &= jnp.abs(q_pos - kv_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[:, 0] = m_new

    @pl.when(j == n_j - 1)
    def _emit():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_flash_attention(q, k_pool, v_pool, block_table, index, *,
                          window=None, interpret=False, max_live=None):
    """q: [B, Q, H, D]; k_pool/v_pool: [NB, Kv, BS, D]; block_table: [B, MB];
    index: [B] committed tokens per row (queries sit at index..index+Q-1,
    already written into the pool). H = Kv * gq (GQA-aware). ``max_live``
    caps every row's scanned blocks at ceil(max_live/BS), matching the
    oracle's explicit-bound truncation semantics."""
    B, Q, H, D = q.shape
    Kv, BS = k_pool.shape[1], k_pool.shape[2]
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

    # rows = (q position, group); pad to a sublane multiple for the VPU tiles
    qr = q.reshape(B, Q, Kv, gq, D).transpose(0, 2, 1, 3, 4) \
          .reshape(B, Kv, Q * gq, D)
    R = -(-(Q * gq) // 8) * 8
    if R != Q * gq:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, R - Q * gq), (0, 0)))

    def _kv_map(b, h, j, tbl, live_b, _idx):
        jj = jnp.minimum(j, jnp.maximum(live_b[b] - 1, 0))
        return (tbl[b, jj], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Kv, MB),
        in_specs=[
            pl.BlockSpec((1, 1, R, D), lambda b, h, j, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, BS, D), _kv_map),
            pl.BlockSpec((1, 1, BS, D), _kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, R, D), lambda b, h, j, *_: (b, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((R, 1), jnp.float32),
                        pltpu.VMEM((R, 1), jnp.float32),
                        pltpu.VMEM((R, D), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=BS, gq=gq, window=window, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kv, R, D), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(block_table.astype(jnp.int32), live, idx, qr, k_pool, v_pool)
    return out[:, :, :Q * gq].reshape(B, Kv, Q, gq, D) \
              .transpose(0, 2, 1, 3, 4).reshape(B, Q, H, D)
