"""The dense family: a Llama-style decoder (RMSNorm, GQA, RoPE, SwiGLU) as
``repro.models.dense`` runs it, every layer attending to its whole context.

A family module is the benchmark's whole knowledge of one family's
program-facing shape; ``bench.families.family(cfg)`` finds it by the
configuration file's ``family`` key (``dense`` where the key is absent).
Every family module gives:

- ``model_config(cfg, layers=None, name=None)``: the program's ModelConfig;
- ``target_params(cfg, seed)``, ``drafter_params(cfg, target)``: the seeded
  weights in the layout the program reads, and the self-drafter;
- ``late_writers(cfg)``: the per-layer leaves (dotted paths under
  ``layers``) that write to the residual stream; the weight builder damps
  them past the drafter's depth and ``faults.skip_late_layers`` zeroes them;
- ``matmul_params(cfg, layers=None)``: parameters in a matrix product per
  token (``bench.counts``);
- ``attention_windows(cfg, layers=None)``: per layer, the window in tokens,
  or ``None`` for full attention (``bench.counts``).

The weights follow ``bench.weights``: built on the device in one jitted call
from the seed, layer by layer inside a ``lax.map``, in the served dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.weights import _normal, dtype_of, seed_key


def model_config(cfg: dict, layers: int = None, name: str = None):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=name or cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"] if layers is None else layers,
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        dtype=cfg["torch_dtype"], param_dtype=cfg["torch_dtype"],
        source=cfg["source"])


def late_writers(cfg: dict) -> list:
    """The attention output and the MLP's down projection."""
    return ["attn.o.w", "mlp.down.w"]


def _layer(key, i, cfg: dict):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dt = dtype_of(cfg)
    sd = cfg["self_draft"]
    late = late_writers(cfg)
    # layers past the drafter's depth write to the residual stream damped
    damp = jnp.where(i >= sd["layers"], sd["damping"], 1.0)

    def std(path, fan_in):
        return fan_in ** -0.5 * damp if path in late else fan_in ** -0.5

    ks = jax.random.split(jax.random.fold_in(key, i), 7)
    return {
        "attn": {
            "norm": {"scale": jnp.ones((d,), dt)},
            "q": {"w": _normal(ks[0], (d, nq * hd), std("attn.q.w", d), dt)},
            "k": {"w": _normal(ks[1], (d, nkv * hd), std("attn.k.w", d), dt)},
            "v": {"w": _normal(ks[2], (d, nkv * hd), std("attn.v.w", d), dt)},
            "o": {"w": _normal(ks[3], (nq * hd, d), std("attn.o.w", nq * hd),
                               dt)},
        },
        "mlp_norm": {"scale": jnp.ones((d,), dt)},
        "mlp": {
            "gate": {"w": _normal(ks[4], (d, f), std("mlp.gate.w", d), dt)},
            "up": {"w": _normal(ks[5], (d, f), std("mlp.up.w", d), dt)},
            "down": {"w": _normal(ks[6], (f, d), std("mlp.down.w", f), dt)},
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


def matmul_params(cfg: dict, layers: int = None) -> int:
    """Every layer's projections and MLP, and the head (the embedding lookup
    is a gather; a tied head counts once, as the head)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    L = cfg["num_hidden_layers"] if layers is None else layers
    per_layer = d * nq * hd * 2 + d * nkv * hd * 2 + 3 * d * f
    return L * per_layer + d * cfg["vocab_size"]


def attention_windows(cfg: dict, layers: int = None) -> list:
    """Every layer attends to its whole context."""
    L = cfg["num_hidden_layers"] if layers is None else layers
    return [None] * L
