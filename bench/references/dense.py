"""Plain float32 reference of a dense decoder (RMSNorm, GQA, RoPE, SwiGLU).

Written from the published description of the Llama-style block, in
straightforward ``jax.numpy`` at ``highest`` matmul precision, with no
kernel, cache or batching: one full causal pass over one sequence. It
imports nothing of the program under test. The weights it reads are the
benchmark's own, rebuilt from the seed by the family module
(``bench/families/dense.py``), kept in the dtype they are served in and
upcast one layer at a time inside the scan, so that the whole model never
exists in float32.

``control=True`` computes every linear layer (projections, MLP and head) in
float8 e4m3: each weight column and each activation row scaled to the
format's range and rounded to it, the product accumulated in float32. That
is the precision step below the bfloat16 the configurations serve, and the
comparison that decides ``correct`` must tell it apart from the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _matmul(x, w, control):
    """x [S, in] @ w [in, out], both float32."""
    if control:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale


def _rope(x, theta):
    """x [S, H, D]: rotate the two halves of each head by position."""
    S, _, D = x.shape
    freqs = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs       # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, x, p, control):
    f32 = functools.partial(jax.tree_util.tree_map,
                            lambda a: a.astype(jnp.float32))
    p = f32(p)
    S = x.shape[0]
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = _rmsnorm(x, p["attn"]["norm"]["scale"], eps)
    q = _matmul(h, p["attn"]["q"]["w"], control).reshape(S, nq, hd)
    k = _matmul(h, p["attn"]["k"]["w"], control).reshape(S, nkv, hd)
    v = _matmul(h, p["attn"]["v"]["w"], control).reshape(S, nkv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    group = nq // nkv                    # query head i reads kv head i//group
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    x = x + _matmul(a.reshape(S, nq * hd), p["attn"]["o"]["w"], control)
    h = _rmsnorm(x, p["mlp_norm"]["scale"], eps)
    g = _matmul(h, p["mlp"]["gate"]["w"], control)
    u = _matmul(h, p["mlp"]["up"]["w"], control)
    return x + _matmul(jax.nn.silu(g) * u, p["mlp"]["down"]["w"], control)


def logits(cfg: dict, params: dict, tokens, control: bool = False):
    """tokens [S] int32 -> logits [S, vocab] float32 (full causal pass)."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][tokens].astype(jnp.float32)
        x, _ = jax.lax.scan(
            lambda h, p: (_layer(cfg, h, p, control), None), x,
            params["layers"])
        x = _rmsnorm(x, params["final_norm"]["scale"].astype(jnp.float32),
                     cfg["rms_norm_eps"])
        if cfg["tie_word_embeddings"]:
            head = params["embed"]["table"].astype(jnp.float32).T
        else:
            head = params["lm_head"]["w"].astype(jnp.float32)
        return _matmul(x, head, control)


def served_gaps(cfg: dict, params: dict, tokens, start, control=False):
    """For one padded sequence ``tokens`` [S] (prompt, then served tokens,
    then padding, which causality hides from every earlier position):
    ``gap[p]`` = how far the logit of the token at position ``p + 1`` lies
    below the reference's best logit at position ``p``, for the positions
    ``p >= start - 1`` that chose a served token (the caller masks the
    padding). With ``control`` the token compared is the one the float8
    computation puts first, not the served one."""
    ref = logits(cfg, params, tokens)
    if control:
        chosen = jnp.argmax(logits(cfg, params, tokens, control=True), -1)
    else:
        chosen = jnp.roll(tokens, -1)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    pos = jnp.arange(tokens.shape[0])
    return jnp.where(pos >= start - 1, best - got, 0.0)
