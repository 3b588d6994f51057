"""Operations and bytes the served model needs, computed from shapes.

Kept with the benchmark so that no later change to the program can change
how its work is counted. ``cfg`` is a configuration file's dict; what
depends on the model family (the parameters in matrix products, each
layer's attention window) comes from its family module
(``bench.families.family``). A layer with a window reads at most that
many keys.
"""
from __future__ import annotations

from bench.families import family


def matmul_params(cfg: dict, layers: int = None) -> int:
    """Parameters that take part in a matrix product per token, over the
    first ``layers`` layers and the head (the family's count)."""
    return family(cfg).matmul_params(cfg, layers)


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
    read, summed over the first ``layers`` layers, each layer's capped at
    its window."""
    return sum(_key_span(first, n, w)
               for w in family(cfg).attention_windows(cfg, layers))


def attention_flops(cfg: dict, queries: int, context: int,
                    layers: int = None) -> float:
    """Score and value products of ``queries`` query tokens that each see
    ``context`` keys (or a layer's window, where it is shorter), over
    ``layers`` layers: 4 * q * keys * heads * head_dim per layer."""
    return 4.0 * queries * keys_read(cfg, context, 1, layers) \
        * cfg["num_attention_heads"] * cfg["head_dim"]


def causal_attention_flops(cfg: dict, start: int, n: int,
                           layers: int = None) -> float:
    """Score and value products of ``n`` tokens at positions ``start`` ..
    ``start + n - 1``, each seeing the keys up to its own position."""
    return 4.0 * keys_read(cfg, start + 1, n, layers) \
        * cfg["num_attention_heads"] * cfg["head_dim"]


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
    tokens (a windowed layer's last ``window``), each key and value read
    once, the queries read and the outputs written once."""
    nq, hd = cfg["num_attention_heads"], cfg["head_dim"]
    flops = attention_flops(cfg, queries, context, layers)
    kv = kv_bytes_per_token(cfg, 1, itemsize) \
        * keys_read(cfg, context, 1, layers)
    qo = 2 * queries * nq * hd * itemsize * layers
    return flops, kv + qo
