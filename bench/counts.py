"""Operations and bytes the served model needs, computed from shapes.

Kept with the benchmark so that no later change to the program can change
how its work is counted. ``cfg`` is a configuration file's dict; what
depends on the model family comes from its family module
(``bench.families.family``): the parameters in matrix products, the FLOPs
outside them and attention, and which layers hold K/V, each with its own
record (``AttentionLayer``: window, K/V bytes a cached token holds, FLOPs a
query spends per key, query and output bytes). Attention is summed over
those records alone: a layer with no K/V reads no keys and costs nothing
here, and a layer with a window reads at most that many keys.
"""
from __future__ import annotations

from bench.families import family


def matmul_params(cfg: dict, layers: int = None) -> int:
    """Parameters that take part in a matrix product per token, over the
    first ``layers`` layers and the head (the family's count)."""
    return family(cfg).matmul_params(cfg, layers)


def attention_layers(cfg: dict, layers: int = None) -> list:
    """The family's ``AttentionLayer`` for each of the first ``layers``
    layers that holds K/V."""
    return family(cfg).attention_layers(cfg, layers)


def _key_span(first: int, n: int, window) -> int:
    """Keys read by ``n`` queries that see ``first``, ``first + 1``, ...
    ``first + n - 1`` keys, each capped at ``window`` (None: no cap)."""
    last = first + n - 1
    if window is None or last <= window:
        return n * first + n * (n - 1) // 2
    if first >= window:
        return n * window
    below = window - first + 1              # queries first .. window
    return below * first + below * (below - 1) // 2 + (last - window) * window


def keys_read(cfg: dict, first: int, n: int = 1, layers: int = None) -> int:
    """Keys that ``n`` queries seeing ``first`` .. ``first + n - 1`` keys
    read, summed over the attending layers among the first ``layers``, each
    layer's capped at its window."""
    return sum(_key_span(first, n, a.window)
               for a in attention_layers(cfg, layers))


def _key_flops(cfg: dict, first: int, n: int, layers: int) -> int:
    """Score and value FLOPs of ``n`` queries seeing ``first`` .. ``first +
    n - 1`` keys: each attending layer's keys at its FLOPs per key."""
    return sum(_key_span(first, n, a.window) * a.flops_per_key
               for a in attention_layers(cfg, layers))


def attention_flops(cfg: dict, queries: int, context: int,
                    layers: int = None) -> float:
    """Score and value products of ``queries`` query tokens that each see
    ``context`` keys (or a layer's window, where it is shorter), over the
    attending layers among the first ``layers``."""
    return float(queries * _key_flops(cfg, context, 1, layers))


def causal_attention_flops(cfg: dict, start: int, n: int,
                           layers: int = None) -> float:
    """Score and value products of ``n`` tokens at positions ``start`` ..
    ``start + n - 1``, each seeing the keys up to its own position."""
    return float(_key_flops(cfg, start + 1, n, layers))


def token_flops(cfg: dict, start: int, n: int = 1) -> float:
    """Model FLOPs of ``n`` tokens at positions ``start`` .. ``start + n -
    1`` (the token at ``start`` attends to ``start + 1`` keys): the matmuls,
    attention, and the family's FLOPs outside both."""
    if n <= 0:
        return 0.0
    return 2.0 * matmul_params(cfg) * n \
        + causal_attention_flops(cfg, start, n) \
        + family(cfg).other_flops(cfg, start, n)


def kv_bytes_per_token(cfg: dict, layers: int = None) -> int:
    """Key and value bytes one cached token holds over the attending layers
    among the first ``layers``."""
    return sum(a.kv_bytes for a in attention_layers(cfg, layers))


def paged_attention_least(cfg: dict, queries: int, context: int,
                          layers: int) -> tuple:
    """(flops, bytes) that one row's paged-attention calls over the
    attending layers among the first ``layers`` cannot do without:
    ``queries`` queries against ``context`` cached tokens (a windowed
    layer's last ``window``), each key and value read once, the queries
    read and the outputs written once."""
    flops = attention_flops(cfg, queries, context, layers)
    nbytes = sum(_key_span(context, 1, a.window) * a.kv_bytes
                 + queries * a.query_bytes
                 for a in attention_layers(cfg, layers))
    return flops, nbytes
