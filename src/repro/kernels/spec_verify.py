"""Pallas TPU kernel: fused greedy speculative verification.

The hot epilogue of every verification round: argmax over the vocab for the
gamma+1 target positions, compared against the drafted tokens. Naively this
materializes a [B, G+1, V] fp32 logits argmax in HBM (V up to 256k); the fused
kernel streams vocab blocks through VMEM keeping only a [B*(G+1), 1] running
(max, argmax) pair, then the tiny acceptance epilogue runs in jnp.

Grid: (rows/br, V/bv) with V innermost; scratch holds the running max/idx.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _argmax_kernel(lg_ref, o_ref, m_ref, i_ref, *, bv: int, n_v: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        i_ref[...] = jnp.zeros_like(i_ref)

    blk = lg_ref[...].astype(jnp.float32)                      # [br, bv]
    loc_max = jnp.max(blk, axis=1)                             # [br]
    loc_idx = jnp.argmax(blk, axis=1).astype(jnp.int32) + j * bv
    better = loc_max > m_ref[:, 0]
    m_ref[:, 0] = jnp.where(better, loc_max, m_ref[:, 0])
    i_ref[:, 0] = jnp.where(better, loc_idx, i_ref[:, 0])

    @pl.when(j == n_v - 1)
    def _emit():
        o_ref[...] = i_ref[...]


@functools.partial(jax.jit, static_argnames=("br", "bv", "interpret"))
def blockwise_argmax(logits, *, br=8, bv=2048, interpret=False):
    """logits: [R, V] -> argmax int32 [R, 1]. R % br == 0, V % bv == 0."""
    R, V = logits.shape
    assert R % br == 0 and V % bv == 0, (R, V, br, bv)
    n_v = V // bv
    return pl.pallas_call(
        functools.partial(_argmax_kernel, bv=bv, n_v=n_v),
        grid=(R // br, n_v),
        in_specs=[pl.BlockSpec((br, bv), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((br, 1), jnp.float32),
                        pltpu.VMEM((br, 1), jnp.int32)],
        interpret=interpret,
        name="verify_argmax",
    )(logits)


def verify_greedy_fused(draft_tokens, p_logits, *, br=8, bv=2048, interpret=False):
    """Drop-in for repro.core.acceptance.verify_greedy using the fused argmax.

    draft_tokens: [B, G]; p_logits: [B, G+1, V].
    """
    from repro.core.acceptance import VerifyResult
    B, G1, V = p_logits.shape
    G = G1 - 1
    R = B * G1
    pad_r = (-R) % br
    flat = p_logits.reshape(R, V)
    pad_v = (-V) % bv
    if pad_v:
        flat = jnp.pad(flat, ((0, 0), (0, pad_v)), constant_values=-jnp.inf)
    if pad_r:
        flat = jnp.pad(flat, ((0, pad_r), (0, 0)))
    tgt = blockwise_argmax(flat, br=br, bv=bv, interpret=interpret)[:R, 0]
    tgt = tgt.reshape(B, G1)
    match = tgt[:, :G] == draft_tokens
    acc_prefix = jnp.cumprod(match.astype(jnp.int32), axis=1)
    n_accepted = acc_prefix.sum(axis=1)
    extra = jnp.take_along_axis(tgt, n_accepted[:, None], axis=1)[:, 0]
    pos = jnp.arange(G1)[None, :]
    drafts_pad = jnp.pad(draft_tokens, ((0, 0), (0, 1)))
    out = jnp.where(pos < n_accepted[:, None], drafts_pad, 0)
    out = jnp.where(pos == n_accepted[:, None], extra[:, None], out)
    return VerifyResult(n_accepted.astype(jnp.int32), out.astype(jnp.int32),
                        (n_accepted + 1).astype(jnp.int32))
