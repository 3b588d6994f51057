"""Jit'd public wrappers around the Pallas kernels.

Handles padding to block multiples, backend selection (interpret=True when no
TPU is attached — the kernels then execute their bodies on CPU for
correctness), dtype plumbing, and partitioning: XLA cannot partition a
Mosaic kernel, so when the caller traces under a multi-device mesh (a placed
role's submesh, ``api.placement.RolePlacement.jit``) the serving kernels run
under ``shard_map`` — attention split over kv heads when they divide the
mesh, verify on every device. Model code calls these, never pallas_call
directly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import flash_attention as _fa
from repro.kernels import int8_matmul as _imm
from repro.kernels import paged_attention as _pa
from repro.kernels import spec_verify as _sv
from repro.kernels import ssd_scan as _ssd
from repro.kernels import tree_attention as _ta


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _per_device(fn, in_specs, out_specs):
    """``fn`` as is on one device; under the multi-device mesh the caller
    is tracing in, ``fn`` under ``shard_map`` with the given specs."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _head_axes(num_kv_heads):
    """Mesh axes the kv heads are split over (None = every device computes
    all heads, when they do not divide the mesh)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or num_kv_heads % mesh.size:
        return None
    return tuple(mesh.axis_names)


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def quantized_matmul(x, w_q, sw, *, bm=128, bn=128, bk=128, out_dtype=None):
    """bf16/f32 activations x int8 weights: dynamic per-tensor act quant,
    int8 MXU matmul, fused dequant. x: [..., K]; w_q: [K, N]; sw: [N]."""
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    K = x.shape[-1]
    xf = x.reshape(-1, K)
    qmax = 127.0
    sx = jnp.maximum(jnp.max(jnp.abs(xf.astype(jnp.float32))) / qmax, 1e-12)
    x_q = jnp.clip(jnp.round(xf.astype(jnp.float32) / sx), -128, 127).astype(jnp.int8)
    x_q, pm = _pad_to(x_q, 0, bm)
    x_q, pk = _pad_to(x_q, 1, bk)
    w_qp, _ = _pad_to(w_q, 0, bk)
    w_qp, pn = _pad_to(w_qp, 1, bn)
    swp, _ = _pad_to(sw, 0, bn)
    out = _imm.int8_matmul(x_q, w_qp, sx, swp, bm=bm, bn=bn, bk=bk,
                           out_dtype=jnp.dtype(out_dtype), interpret=_interpret())
    M = xf.shape[0]
    N = w_q.shape[1]
    return out[:M, :N].reshape(*lead, N)


def verify_greedy(draft_tokens, p_logits, *, br=8, bv=2048):
    """Fused greedy verification (see repro.core.acceptance for the oracle).
    On a multi-device mesh every device verifies the whole (gathered)
    logits, so the result is replicated."""
    def fn(drafts, logits):
        return _sv.verify_greedy_fused(drafts, logits, br=br, bv=bv,
                                       interpret=_interpret())
    return _per_device(fn, (P(), P()), P())(draft_tokens, p_logits)


def flash_attention(q, k, v, *, bq=256, bs=512, window=None, causal=True):
    """Blockwise attention; pads Sq/Skv to block multiples (mask handles tails)."""
    Sq, Skv = q.shape[1], k.shape[1]
    bq = min(bq, max(8, Sq))
    bs = min(bs, max(8, Skv))
    q, pq = _pad_to(q, 1, bq)
    k, _ = _pad_to(k, 1, bs)
    v, _ = _pad_to(v, 1, bs)
    out = _fa.flash_attention(q, k, v, bq=bq, bs=bs, window=window,
                              causal=causal, interpret=_interpret(),
                              s_valid=Skv)
    return out[:, :Sq]


def paged_attention(q, k_pool, v_pool, block_table, index, *, layer=0,
                    window=None, max_live=None):
    """Block-table-native paged attention (decode/verify path) over layer
    ``layer`` of a stacked ``[L, NB, BS, Kv*D]`` pool. Reads are bounded by
    each row's live block count; the kernel resolves the layer and the pool
    block ids in-kernel from prefetched scalars. int8 KV pools fall back to
    the jnp oracle (the kernel reads float pools only)."""
    if k_pool.dtype == jnp.int8:
        from repro.models.attention import attn_paged
        return attn_paged(q, k_pool, v_pool, block_table, index, layer=layer,
                          window=window, max_live=max_live)

    def fn(q, k, v, tbl, idx, lyr, *ml):
        return _pa.paged_flash_attention(q, k, v, tbl, idx, lyr,
                                         window=window,
                                         interpret=_interpret(),
                                         max_live=ml[0] if ml else None)
    ml = () if max_live is None else (jnp.asarray(max_live, jnp.int32),)
    return _per_device(fn, *_attn_specs(q, k_pool, 3 + len(ml)))(
        q, k_pool, v_pool, block_table, index, jnp.asarray(layer, jnp.int32),
        *ml)


def _attn_specs(q, k_pool, n_replicated):
    """(in_specs, out_specs) of a paged kernel call: q/out [B, Q, H, D] split
    on heads and pools [L, NB, BS, Kv*D] on their minor axis, in whole-head
    ``Kv/n * D`` pieces, then ``n_replicated`` small operands (tables,
    indices, the layer, tree masks, bounds)."""
    ax = _head_axes(k_pool.shape[3] // q.shape[3])
    heads, pool = P(None, None, ax, None), P(None, None, None, ax)
    return (heads, pool, pool) + (P(),) * n_replicated, heads


def tree_attention(q, k_pool, v_pool, block_table, index, depths, bits, *,
                   layer=0, window=None, max_live=None):
    """Block-table-native tree-verify attention: one stacked pass scores all
    root-to-leaf paths of a speculation tree (depths/bits from core/tree.py).
    int8 KV pools fall back to the jnp oracle, mirroring paged_attention."""
    if k_pool.dtype == jnp.int8:
        from repro.models.attention import attn_tree
        return attn_tree(q, k_pool, v_pool, block_table, index, depths, bits,
                         layer=layer, window=window, max_live=max_live)

    def fn(q, k, v, tbl, idx, dep, bts, lyr, *ml):
        return _ta.tree_flash_attention(q, k, v, tbl, idx, dep, bts, lyr,
                                        window=window, interpret=_interpret(),
                                        max_live=ml[0] if ml else None)
    ml = () if max_live is None else (jnp.asarray(max_live, jnp.int32),)
    return _per_device(fn, *_attn_specs(q, k_pool, 5 + len(ml)))(
        q, k_pool, v_pool, block_table, index, jnp.asarray(depths, jnp.int32),
        jnp.asarray(bits, jnp.int32), jnp.asarray(layer, jnp.int32), *ml)


def ssd_scan(x, dA, Bm, Cm, *, chunk=128):
    """Fused chunked SSD scan (mamba2 prefill/train fast path); pads l."""
    l = x.shape[1]
    pad = (-l) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dA = jnp.pad(dA, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    out = _ssd.ssd_scan(x, dA, Bm, Cm, chunk=chunk, interpret=_interpret())
    return out[:, :l]
