"""Seeded weights for a self-drafted dense configuration, built on the device.

The drafter is the target's first ``k`` layers with the target's own
embedding, final norm and head (self-speculation by early exit, as in
LayerSkip, arXiv:2404.16710). With plain random weights such a drafter
agrees with the target only by chance, so the output projections of the
target's layers ``k..L-1`` (``attn.o`` and ``mlp.down``) are scaled by the
configuration's damping factor ``s``: those layers still run in full and
still feed the logits, but they move the residual stream less, and the
drafter's greedy agreement with the target becomes a property of the
configuration instead of luck. Speed does not depend on weight values.

Everything is built in one jitted call per model, layer by layer inside a
``lax.map`` so that no float32 copy of a whole stacked leaf ever exists, and
in the dtype the configuration serves (``torch_dtype``). The layout is the
one ``repro.models.dense`` reads; this module imports nothing of the
program, so the reference can rebuild the same weights from the seed alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key from any whole number ``seed`` and a stream id. A plain
    ``PRNGKey(seed)`` silently truncates seeds past 32 bits when 64-bit
    mode is off; ``SeedSequence`` takes any non-negative integer."""
    words = np.random.SeedSequence(
        [int(seed) & (2 ** 64 - 1), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32))


def dtype_of(cfg: dict):
    return jnp.dtype(cfg["torch_dtype"])


def _normal(key, shape, std, dt):
    return jax.random.normal(key, shape, dt) * jnp.asarray(std, dt)


def _layer(key, i, cfg: dict):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dt = dtype_of(cfg)
    sd = cfg["self_draft"]
    # layers past the drafter's depth write to the residual stream damped
    damp = jnp.where(i >= sd["layers"], sd["damping"], 1.0)
    ks = jax.random.split(jax.random.fold_in(key, i), 7)
    return {
        "attn": {
            "norm": {"scale": jnp.ones((d,), dt)},
            "q": {"w": _normal(ks[0], (d, nq * hd), d ** -0.5, dt)},
            "k": {"w": _normal(ks[1], (d, nkv * hd), d ** -0.5, dt)},
            "v": {"w": _normal(ks[2], (d, nkv * hd), d ** -0.5, dt)},
            "o": {"w": _normal(ks[3], (nq * hd, d),
                               (nq * hd) ** -0.5 * damp, dt)},
        },
        "mlp_norm": {"scale": jnp.ones((d,), dt)},
        "mlp": {
            "gate": {"w": _normal(ks[4], (d, f), d ** -0.5, dt)},
            "up": {"w": _normal(ks[5], (d, f), d ** -0.5, dt)},
            "down": {"w": _normal(ks[6], (f, d), f ** -0.5 * damp, dt)},
        },
    }


def _build(cfg: dict, key) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    dt = dtype_of(cfg)
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    layers = jax.lax.map(lambda i: _layer(k_layers, i, cfg),
                         jnp.arange(cfg["num_hidden_layers"]))
    params = {
        "embed": {"table": _normal(k_embed, (v, d),
                                   cfg["self_draft"]["embed_std"], dt)},
        "layers": layers,
        "final_norm": {"scale": jnp.ones((d,), dt)},
    }
    if not cfg["tie_word_embeddings"]:
        params["lm_head"] = {"w": _normal(k_head, (d, v), d ** -0.5, dt)}
    return params


def target_params(cfg: dict, seed: int) -> dict:
    """The target's weights for ``seed``, built on the default device in one
    jitted call."""
    key = seed_key(seed, 0)
    return jax.jit(lambda k: _build(cfg, k))(key)


def drafter_params(cfg: dict, target: dict) -> dict:
    """The drafter: the target's first ``self_draft.layers`` layers (a copy)
    with the target's own embedding, final norm and head (the same arrays)."""
    k = cfg["self_draft"]["layers"]
    first = jax.jit(lambda ls: jax.tree_util.tree_map(lambda x: x[:k], ls))
    out = dict(target)
    out["layers"] = first(target["layers"])
    return out
