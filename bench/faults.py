"""Faults planted under the timed path, to show that ``correct`` catches
them: each takes the served path as ``harness.build`` returns it and the
configuration, and breaks the program's round or weights in place. Used by
``bench/calibrate.py --fault`` on the chip and by the CPU tests; never by a
benchmark run."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp


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
    residual stream (their ``attn.o`` and ``mlp.down`` zeroed, in place):
    every program of the served path then serves what the drafter's own
    layers give."""
    k = cfg["self_draft"]["layers"]
    srv = served.srv
    zero = jax.jit(lambda w: w.at[k:].set(0), donate_argnums=0)
    layers = dict(srv.params_t["layers"])
    layers["attn"] = dict(layers["attn"])
    layers["attn"]["o"] = {"w": zero(layers["attn"]["o"]["w"])}
    layers["mlp"] = dict(layers["mlp"])
    layers["mlp"]["down"] = {"w": zero(layers["mlp"]["down"]["w"])}
    srv.params_t = dict(srv.params_t, layers=layers)
    served.params[0] = srv.params_t


FAULTS = {f.__name__: f for f in (token_altered, state_unchanged,
                                  skip_late_layers)}
