"""Faults planted under the timed path, to show that ``correct`` catches
them: each takes the served path as ``harness.build`` returns it and the
configuration, and breaks the program's round or weights in place. Used by
``bench/calibrate.py --fault`` on the chip and by the CPU tests; never by a
benchmark run."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from bench.families import family


def token_altered(served, cfg: dict) -> None:
    """The newest committed token of every live row is changed where the
    round produces it."""
    srv = served.srv
    eng = srv._engine(srv._gamma_override)
    inner = eng._round_jit
    vocab = cfg["vocab_size"]

    def broken(pt, pd, st):
        st = inner(pt, pd, st)
        rows = jnp.arange(st.tokens.shape[0])
        idx = jnp.clip(st.length - 1, 0, st.tokens.shape[1] - 1)
        tok = st.tokens[rows, idx]
        new = jnp.where(st.active, (tok + 1) % vocab, tok)
        return st._replace(tokens=st.tokens.at[rows, idx].set(new))
    eng._round_jit = broken


def state_unchanged(served, cfg: dict) -> None:
    """The round returns its state as it came: nothing is committed. (It
    takes a round's time, as a device round would, instead of spinning the
    stepper thread.)"""
    srv = served.srv
    eng = srv._engine(srv._gamma_override)

    def broken(pt, pd, st):
        time.sleep(0.01)
        return jax.tree_util.tree_map(jnp.copy, st)
    eng._round_jit = broken


def skip_late_layers(served, cfg: dict) -> None:
    """The target's layers past the drafter's depth add nothing to the
    residual stream: in each of the family's ``late_writers`` leaves, the
    entries that its ``stack_layers`` puts at model layers at or past that
    depth are zeroed in place, and no other. Every program of the served
    path then serves what the drafter's own layers give."""
    k = cfg["self_draft"]["layers"]
    fam = family(cfg)
    srv = served.srv
    zero = jax.jit(lambda w, idx: w.at[idx].set(0), donate_argnums=0)
    layers = srv.params_t["layers"]
    for path in fam.late_writers(cfg):
        owners = fam.stack_layers(cfg, path)
        late = jnp.asarray([i for i, layer in enumerate(owners) if layer >= k],
                           jnp.int32)

        def zero_late(w):
            if w.shape[0] != len(owners):
                raise ValueError(f"{path}: {w.shape[0]} entries, but "
                                 f"stack_layers names {len(owners)}")
            return zero(w, late)
        layers = _replace(layers, path.split("."), zero_late)
    srv.params_t = dict(srv.params_t, layers=layers)
    served.params[0] = srv.params_t


def _replace(tree: dict, keys: list, fn) -> dict:
    """A copy of the nested dict ``tree`` with ``fn`` applied to the leaf at
    ``keys``; every other leaf is the same array."""
    head, *rest = keys
    return dict(tree, **{head: _replace(tree[head], rest, fn) if rest
                         else fn(tree[head])})


FAULTS = {f.__name__: f for f in (token_altered, state_unchanged,
                                  skip_late_layers)}
