"""Operations and bytes the served model needs, computed from shapes.

Kept with the benchmark so that no later change to the program can change
how its work is counted. ``cfg`` is a configuration file's dict.
"""
from __future__ import annotations


def matmul_params(cfg: dict, layers: int = None) -> int:
    """Parameters that take part in a matrix product per token: every
    layer's projections and MLP, and the head (the embedding lookup is a
    gather; a tied head counts once, as the head)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    L = cfg["num_hidden_layers"] if layers is None else layers
    per_layer = d * nq * hd * 2 + d * nkv * hd * 2 + 3 * d * f
    return L * per_layer + d * cfg["vocab_size"]


def attention_flops(cfg: dict, queries: int, context: int,
                    layers: int = None) -> float:
    """Score and value products of ``queries`` query tokens that each see
    ``context`` keys, over ``layers`` layers: 4 * q * c * heads * head_dim
    per layer."""
    L = cfg["num_hidden_layers"] if layers is None else layers
    return 4.0 * queries * context * cfg["num_attention_heads"] \
        * cfg["head_dim"] * L


def token_flops(cfg: dict, context: int) -> float:
    """Model FLOPs of one token at position ``context`` (it attends to
    ``context + 1`` keys)."""
    return 2.0 * matmul_params(cfg) + attention_flops(cfg, 1, context + 1)


def kv_bytes_per_token(cfg: dict, layers: int = None,
                       itemsize: int = 2) -> int:
    """Key and value bytes one cached token holds over ``layers`` layers."""
    L = cfg["num_hidden_layers"] if layers is None else layers
    return 2 * L * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def paged_attention_least(cfg: dict, queries: int, context: int,
                          layers: int, itemsize: int = 2) -> tuple:
    """(flops, bytes) that one row's paged-attention calls over ``layers``
    layers cannot do without: ``queries`` queries against ``context`` cached
    tokens, each cached key and value read once, the queries read and the
    outputs written once."""
    nq, hd = cfg["num_attention_heads"], cfg["head_dim"]
    flops = attention_flops(cfg, queries, context, layers)
    kv = kv_bytes_per_token(cfg, layers, itemsize) * context
    qo = 2 * queries * nq * hd * itemsize * layers
    return flops, kv + qo
