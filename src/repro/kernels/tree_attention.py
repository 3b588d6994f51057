"""Pallas TPU kernel: block-table-native tree-verify attention.

The TPU drop-in for ``repro.models.attention.attn_tree`` (the jnp oracle —
see ref.py): ONE stacked verify pass scores every root-to-leaf path of a
speculation tree.  The ``span = 1 + n_nodes`` query rows per sequence are the
packed ``[t_last, node_1 .. node_N]`` slots, whose KV was just written at
contiguous pool positions ``index .. index+span-1`` (core/tree.py fixes the
slot order; RoPE positions are ``index + depths[slot]``).

Structure is the paged-decode kernel's (kernels/paged_attention.py): grid
``(B, max_blocks_per_row)`` with KV blocks innermost, one whole
``[BS, Kv * D]`` block of the stacked token-major pool per step with all kv
heads in the body, VMEM scratch carrying each head's online-softmax state,
the layer and the block ids resolved in-kernel from prefetched scalars, dead
steps clamped + skipped.  The only new ingredient is the mask:

  * committed prefix (kv_pos < index): ordinary causal (+ window);
  * in-span KV slot t (rel = kv_pos - index in [0, span)): visible iff bit
    ``t`` of the query slot's int32 ancestor bitmask is set — each query
    attends only its own root path, so sibling branches never leak into each
    other's scores;
  * beyond the span: stale slots, never visible.

``depths``/``bits`` ride in as [R, 1] int32 VMEM tensors pre-expanded to the
padded (slot, group) row layout, so the kernel needs no gather. Interpret
mode executes the same body on CPU; tests assert parity against the oracle
across tree shapes / GQA / windows / ragged lengths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention import attend_block, emit, init_scratch


def _kernel(tbl_ref, live_ref, idx_ref, layer_ref, q_ref, k_ref, v_ref,
            dep_ref, bit_ref, o_ref, m_ref, l_ref, acc_ref, *, bs: int,
            span: int, window, scale: float):
    """Grid (row b, block j); blocks as in the paged kernel's
    ``attend_block``, plus dep/bit [R, 1]."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    R = q_ref.shape[2]

    pl.when(j == 0)(lambda: init_scratch(m_ref, l_ref, acc_ref))

    @pl.when(j < live_ref[b])
    def _compute():
        dep = dep_ref[:, 0]                                    # [R]
        bts = bit_ref[:, 0]                                    # [R]
        kv_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (R, bs), 1)
        rel = kv_pos - idx_ref[b]
        q_pos = idx_ref[b] + dep[:, None]                      # [R, 1] -> bc
        prefix = (rel < 0) & (q_pos >= kv_pos)
        if window is not None:
            # the span side of the window rides inside the (pre-windowed)
            # ancestor bitmasks — see the wrapper
            prefix &= (q_pos - kv_pos) < window
        bit = jax.lax.shift_right_logical(
            jnp.broadcast_to(bts[:, None], (R, bs)),
            jnp.clip(rel, 0, 31)) & 1
        inspan = (rel >= 0) & (rel < span) & (bit > 0)
        attend_block(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                     prefix | inspan, scale)

    pl.when(j == pl.num_programs(1) - 1)(lambda: emit(o_ref, l_ref, acc_ref))


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def tree_flash_attention(q, k_pool, v_pool, block_table, index, depths,
                         bits, layer=0, *, window=None, interpret=False,
                         max_live=None):
    """q: [B, span, H, D]; k_pool/v_pool: [L, NB, BS, Kv*D]; block_table:
    [B, MB]; index: [B] committed tokens per row (the root sits at index,
    nodes at index+1..index+span-1, already written into layer ``layer`` of
    the pool);
    depths/bits: int32 [span] per-slot depth and ancestor bitmask
    (core/tree.py). H = Kv * gq (GQA-aware)."""
    B, S, H, D = q.shape                                        # S = span
    BS, Kv = k_pool.shape[2], k_pool.shape[3] // D
    MB = block_table.shape[1]
    gq = H // Kv
    scale = D ** -0.5
    idx = jnp.asarray(index, jnp.int32)
    if idx.ndim == 0:
        idx = jnp.broadcast_to(idx, (B,))
    live = jnp.clip((idx + S + BS - 1) // BS, 1, MB).astype(jnp.int32)
    if max_live is not None:
        cap = jnp.clip((jnp.asarray(max_live, jnp.int32) + BS - 1) // BS,
                       1, MB).astype(jnp.int32)
        live = jnp.minimum(live, cap)
    lyr = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    # rows = (slot, group); pad to a sublane multiple for the VPU tiles.
    # Padded tail rows get bits=0 (attend nothing in-span) and are sliced off.
    qr = q.reshape(B, S, Kv, gq, D).transpose(0, 2, 1, 3, 4) \
          .reshape(B, Kv, S * gq, D)
    R = -(-(S * gq) // 8) * 8
    if R != S * gq:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, R - S * gq), (0, 0)))
    depths = jnp.asarray(depths, jnp.int32)
    bits = jnp.asarray(bits, jnp.int32)
    if window is not None:
        # fold the window's span side into the ancestor masks: slot t stays
        # visible to slot s only if their DEPTH gap (= RoPE position gap)
        # is inside the window, matching the oracle's _tree_mask
        ar = jnp.arange(S, dtype=jnp.int32)
        keep = (((bits[:, None] >> ar[None, :]) & 1) > 0) \
            & (depths[:, None] - depths[None, :] < window)
        bits = jnp.sum(keep.astype(jnp.int32) << ar[None, :], axis=1)
    dep_rows = jnp.repeat(depths, gq)
    bit_rows = jnp.repeat(bits, gq)
    if R != S * gq:
        dep_rows = jnp.pad(dep_rows, (0, R - S * gq))
        bit_rows = jnp.pad(bit_rows, (0, R - S * gq))
    dep_rows = dep_rows[:, None]
    bit_rows = bit_rows[:, None]

    def _kv_map(b, j, tbl, live_b, _idx, lyr):
        jj = jnp.minimum(j, jnp.maximum(live_b[b] - 1, 0))
        return (lyr[0], tbl[b, jj], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, MB),
        in_specs=[
            pl.BlockSpec((1, Kv, R, D), lambda b, j, *_: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, BS, Kv * D), _kv_map),
            pl.BlockSpec((1, 1, BS, Kv * D), _kv_map),
            pl.BlockSpec((R, 1), lambda b, j, *_: (0, 0)),
            pl.BlockSpec((R, 1), lambda b, j, *_: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Kv, R, D), lambda b, j, *_: (b, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((Kv, R, 1), jnp.float32),
                        pltpu.VMEM((Kv, R, 1), jnp.float32),
                        pltpu.VMEM((Kv, R, D), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=BS, span=S, window=window, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kv, R, D), q.dtype),
        interpret=interpret,
        name="tree_attention",
    )(block_table.astype(jnp.int32), live, idx, lyr, qr, k_pool, v_pool,
      dep_rows, bit_rows)
    return out[:, :, :S * gq].reshape(B, Kv, S, gq, D) \
              .transpose(0, 2, 1, 3, 4).reshape(B, S, H, D)
