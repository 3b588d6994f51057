"""Dense (llama-style) decoder-only transformer: RMSNorm + GQA + RoPE + SwiGLU.

Layers are stacked on a leading axis and executed with lax.scan so the compiled
HLO contains one layer body regardless of depth (critical for the 40x2 dry-run
compile budget). A ring KV cache is threaded through the scan as stacked
xs/ys; a paged pool rides whole in the scan carry, with the layer index
scanned, so each layer writes and reads it in place (``scan_pool``).

API (used by every decoder family):
  init(cfg, rng)                                    -> params
  forward(cfg, params, tokens, cache, ...)          -> logits[, new_cache]
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.cache.ops import PAGED, RING
from repro.models import layers as L
from repro.models.attention import (_tree_mask, attention, attention_paged,
                                    attention_tree, attn_dense)


# ---------------------------------------------------------------------- init
def init_attn(key, cfg):
    d, hd = cfg.d_model, cfg.head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    dt = cfg.weight_dtype
    return {
        "norm": L.init_rmsnorm(d, dt),
        "q": L.init_linear(kq, d, cfg.num_heads * hd, dt),
        "k": L.init_linear(kk, d, cfg.num_kv_heads * hd, dt),
        "v": L.init_linear(kv, d, cfg.num_kv_heads * hd, dt),
        "o": L.init_linear(ko, cfg.num_heads * hd, d, dt),
    }


def init_layer(key, cfg):
    ka, km = jax.random.split(key)
    return {
        "attn": init_attn(ka, cfg),
        "mlp_norm": L.init_rmsnorm(cfg.d_model, cfg.weight_dtype),
        "mlp": L.init_swiglu(km, cfg.d_model, cfg.d_ff, cfg.weight_dtype),
    }


def _stack_layers(key, cfg, init_one, n):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_one(k, cfg))(keys)


def init(cfg, rng):
    ke, kl, kh = jax.random.split(rng, 3)
    params = {
        "embed": L.init_embedding(ke, cfg.vocab_size, cfg.d_model, cfg.weight_dtype,
                                  scale=cfg.embed_init_scale),
        "layers": _stack_layers(kl, cfg, init_layer, cfg.num_layers),
        "final_norm": L.init_rmsnorm(cfg.d_model, cfg.weight_dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_linear(kh, cfg.d_model, cfg.vocab_size, cfg.weight_dtype)
    return params


# ------------------------------------------------------------------- forward
def attn_block(cfg, p, x, q_pos, layer_cache, index, window, use_rope=True,
               block_table=None, max_live=None, tree=None, layer=None):
    """Self-attention sub-block; returns (out, new_layer_cache or None).
    ``block_table`` non-None selects the paged-pool cache path: the pool
    write and the block-table-native read are split, so no gathered
    ``[B, MB*BS, Kv, D]`` view is ever materialized and attention reads are
    bounded by the live block count (``max_live`` threads the round-level
    bound down from the engines; None recomputes it from ``index``).
    ``tree`` = (depths, bits) int32 [Q] marks this as a stacked tree-verify
    pass (core/tree.py): q_pos already carries the depth offsets, the KV
    lands at contiguous slots index..index+Q-1, and visibility follows each
    slot's ancestor bitmask instead of plain causality.

    On the paged path ``layer`` is the index into a stacked
    ``[L, NB, BS, Kv*D]`` pool that is written and read in place; a caller
    holding one layer's ``[NB, BS, Kv*D]`` slice passes ``layer=None`` and
    the slice is used as a stack of one."""
    B, Q, _ = x.shape
    hd = cfg.head_dim
    h = L.rmsnorm(p["norm"], x, cfg.norm_eps)
    q = L.linear(p["q"], h).reshape(B, Q, cfg.num_heads, hd)
    k = L.linear(p["k"], h).reshape(B, Q, cfg.num_kv_heads, hd)
    v = L.linear(p["v"], h).reshape(B, Q, cfg.num_kv_heads, hd)
    if use_rope:
        q = L.apply_rope(q, q_pos, cfg.rope_theta)
        k = L.apply_rope(k, q_pos, cfg.rope_theta)
    if layer_cache is None:
        kv_pos = q_pos
        o = attention(q, k, v, q_pos, kv_pos, window=window)
        new_cache = None
    elif block_table is not None:
        one = layer is None
        pools = ({n: a[None] for n, a in layer_cache.items()} if one
                 else layer_cache)
        lyr = 0 if one else layer
        new_cache = PAGED.write(pools, k, v, block_table, index, lyr)
        if tree is not None:
            o = attention_tree(q, new_cache["k"], new_cache["v"], block_table,
                               index, tree[0], tree[1], layer=lyr,
                               window=window, max_live=max_live)
        else:
            o = attention_paged(q, new_cache["k"], new_cache["v"], block_table,
                                index, layer=lyr, window=window,
                                max_live=max_live)
        if one:
            new_cache = {n: a[0] for n, a in new_cache.items()}
    else:
        k_all, v_all, kv_pos, new_cache = RING.write(layer_cache, k, v, index)
        if tree is not None:
            idx = jnp.asarray(index)
            if idx.ndim == 0:
                idx = jnp.broadcast_to(idx, (B,))
            m = _tree_mask(idx, kv_pos, tree[0], tree[1], window)
            o = attn_dense(q, k_all, v_all, q_pos, kv_pos, window=window,
                           mask=m)
        else:
            o = attention(q, k_all, v_all, q_pos, kv_pos, window=window)
    o = L.linear(p["o"], o.reshape(B, Q, cfg.num_heads * hd))
    return o, new_cache


def dense_layer(cfg, p, x, q_pos, layer_cache, index, block_table=None,
                max_live=None, tree=None, layer=None):
    o, new_cache = attn_block(cfg, p["attn"], x, q_pos, layer_cache, index,
                              cfg.sliding_window, block_table=block_table,
                              max_live=max_live, tree=tree, layer=layer)
    x = x + o
    x = x + L.swiglu(p["mlp"], L.rmsnorm(p["mlp_norm"], x, cfg.norm_eps))
    return x, new_cache


def scan_layers(layer_fn, stacked_params, x, cache, remat=False, cfg=None):
    """Run layer_fn over stacked params via lax.scan, threading per-layer cache."""
    def step(h, xs):
        lp, lc = xs
        h, new_lc = layer_fn(lp, h, lc)
        return h, new_lc
    if remat:
        step = L.remat_wrap(step, cfg)

    if cache is None:
        xs = (stacked_params, None)
        # scan needs a pytree with consistent structure; use a dummy per-layer None
        n = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
        dummy = jnp.zeros((n,), jnp.int32)
        def step_nc(h, xs):
            lp, _ = xs
            h, _ = layer_fn(lp, h, None)
            return h, None
        if remat:
            step_nc = L.remat_wrap(step_nc, cfg)
        h, _ = jax.lax.scan(step_nc, x, (stacked_params, dummy))
        return h, None
    layer_kv = {"k": cache["k"], "v": cache["v"]}
    h, new_kv = jax.lax.scan(step, x, (stacked_params, layer_kv))
    return h, new_kv


def scan_pool(layer_fn, stacked_params, x, pools, remat=False, cfg=None):
    """Run layer_fn(params, h, pools, layer) over stacked params via
    lax.scan with the whole paged pool stack in the carry and the layer index
    as a scanned input: each layer updates the stack in place, so no layer's
    pool is sliced out into xs or written back into a fresh stacked ys."""
    def step(carry, xs):
        lp, l = xs
        h, kv = layer_fn(lp, *carry, l)
        return (h, kv), None
    if remat:
        step = L.remat_wrap(step, cfg)
    n = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    (h, kv), _ = jax.lax.scan(step, (x, pools),
                              (stacked_params, jnp.arange(n, dtype=jnp.int32)))
    return h, kv


def forward(cfg, params, tokens, cache=None, *, input_embeds=None, logits_slice=None,
            max_live=None, tree=None):
    """tokens: [B, Q] int32 (or input_embeds [B, Q, D]).

    cache=None  -> full-sequence causal pass (train / paper-faithful no-cache mode)
    cache=dict  -> extend: write Q new tokens at cache["index"], return new cache
    logits_slice: if "last", only unembed the final position (decode fast-path).
    max_live: paged caches only — live-token bound for the block-scan read
              (ignored on the ring path; None derives it from the index).
    tree: (depths, bits) int32 [Q] — stacked tree-verify pass (core/tree.py):
          RoPE positions become index + depths and attention follows the
          ancestor bitmasks (requires cache).
    """
    x = input_embeds if input_embeds is not None else L.embed(params["embed"], tokens)
    x = x.astype(cfg.act_dtype)
    B, Q = x.shape[0], x.shape[1]
    index = cache["index"] if cache is not None else jnp.zeros((), jnp.int32)
    block_table = cache.get("block_table") if cache is not None else None
    # index: scalar (shared) or [B] (per-row batched speculation)
    offs = jnp.asarray(tree[0], jnp.int32) if tree is not None \
        else jnp.arange(Q, dtype=jnp.int32)
    q_pos = jnp.asarray(index)[..., None] + offs \
        if jnp.asarray(index).ndim else index + offs

    def layer_fn(lp, h, lc, layer=None):
        return dense_layer(cfg, lp, h, q_pos, lc, index, block_table,
                           max_live, tree, layer)

    if block_table is not None:
        x, new_kv = scan_pool(layer_fn, params["layers"], x,
                              {"k": cache["k"], "v": cache["v"]},
                              remat=cfg.remat, cfg=cfg)
    else:
        x, new_kv = scan_layers(layer_fn, params["layers"], x, cache,
                                remat=cfg.remat, cfg=cfg)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if logits_slice == "last":
        x = x[:, -1:]
    if cfg.tie_embeddings:
        logits = L.unembed(params["embed"], x)
    else:
        logits = L.linear(params["lm_head"], x.astype(jnp.float32))
    if cache is None:
        return logits, None
    new_cache = {"k": new_kv["k"], "v": new_kv["v"], "index": index + Q}
    if block_table is not None:
        new_cache["block_table"] = block_table
    return logits, new_cache
