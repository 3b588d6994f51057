"""The dense family: a Llama-style decoder (RMSNorm, GQA, RoPE, SwiGLU) as
``repro.models.dense`` runs it, every layer attending to its whole context.

A family module is the benchmark's whole knowledge of one family's
program-facing shape; ``bench.families.family(cfg)`` finds it by the
configuration file's ``family`` key (``dense`` where the key is absent).
Every family module gives:

- ``model_config(cfg, layers=None, name=None)``: the program's ModelConfig;
- ``target_params(cfg, seed)``, ``drafter_params(cfg, target)``: the seeded
  weights in the layout the program reads, and the self-drafter;
- ``late_writers(cfg)``: the residual-writing leaves (dotted paths under
  ``layers``, which may be one stack or a dict of per-kind stacks); the
  weight builder damps their entries past the drafter's depth;
- ``stack_layers(cfg, path)``: the model layer of each entry along the
  leading axis of the ``late_writers`` leaf at ``path``
  (``faults.skip_late_layers`` zeroes those at or past the drafter's depth);
- ``matmul_params(cfg, layers=None)``: parameters in a matrix product per
  token (``bench.counts``);
- ``attention_layers(cfg, layers=None)``: one ``AttentionLayer`` for each
  of the first ``layers`` layers that holds K/V, in order: its window (or
  ``None``), the K/V bytes one cached token holds there, the FLOPs one
  query spends per key, the bytes one query reads and writes; a layer with
  no K/V has no record (``bench.counts``, the roofline reader: one
  paged-attention call a round per record);
- ``other_flops(cfg, start, n)``: FLOPs of ``n`` tokens at positions
  ``start`` .. ``start + n - 1`` outside parameter matmuls and attention,
  such as a state-space scan (``bench.counts.token_flops``).

The weights follow ``bench.weights``: built on the device in one jitted call
from the seed, layer by layer inside a ``lax.map``, in the served dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.families import AttentionLayer
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


def stack_layers(cfg: dict, path: str) -> list:
    """Every leaf is one stack, entry ``i`` model layer ``i``."""
    return list(range(cfg["num_hidden_layers"]))


def attention_layers(cfg: dict, layers: int = None) -> list:
    """Every layer attends to its whole context with the configuration's
    heads: K and V of ``num_key_value_heads`` x ``head_dim``, 2 FLOPs a
    multiply-add for the score and the value of each query head."""
    L = cfg["num_hidden_layers"] if layers is None else layers
    size = dtype_of(cfg).itemsize
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    return [AttentionLayer(None, 2 * nkv * hd * size, 4 * nq * hd,
                           2 * nq * hd * size)] * L


def other_flops(cfg: dict, start: int, n: int) -> int:
    """Nothing outside the matmuls and attention is counted."""
    return 0
