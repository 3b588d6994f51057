"""Model families, found by name: ``bench/families/<family>.py`` is the
benchmark's whole knowledge of one family's program-facing shape."""
from __future__ import annotations

import importlib
from typing import NamedTuple, Optional


class AttentionLayer(NamedTuple):
    """What one layer that holds K/V holds and costs, as its family's
    ``attention_layers`` gives it (``bench.counts`` sums over them)."""
    window: Optional[int]    # keys a query reads at most; None: all
    kv_bytes: int            # bytes one cached token holds in this layer
    flops_per_key: int       # score and value FLOPs of one query per key
    query_bytes: int         # bytes one query reads and its output writes


def family(cfg: dict):
    """The module of the configuration's model family,
    ``bench/families/<family>.py`` (``dense`` where ``family`` is absent):
    every family-specific fact the benchmark uses comes from it."""
    name = cfg.get("family", "dense")
    path = f"bench/families/{name}.py"
    if not name.isidentifier():
        raise LookupError(f"family {name!r}: no module name fits {path}")
    try:
        return importlib.import_module(f"bench.families.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"bench.families.{name}":
            raise
        raise LookupError(f"configuration {cfg.get('name')!r} names family "
                          f"{name!r}, and {path} does not exist") from None
