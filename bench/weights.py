"""Seeded weights for a self-drafted configuration: the helpers every
family's builder (``bench/families/<family>.py``) shares.

The drafter is the target's first ``k`` layers with the target's own
embedding, final norm and head (self-speculation by early exit, as in
LayerSkip, arXiv:2404.16710). With plain random weights such a drafter
agrees with the target only by chance, so the leaves through which the
target's layers ``k..L-1`` write to the residual stream (the family's
``late_writers``) are scaled by the configuration's damping factor ``s``:
those layers still run in full and still feed the logits, but they move the
residual stream less, and the drafter's greedy agreement with the target
becomes a property of the configuration instead of luck. Speed does not
depend on weight values.

A family builds everything in one jitted call per model, layer by layer
inside a ``lax.map`` so that no float32 copy of a whole stacked leaf ever
exists, and in the dtype the configuration serves (``torch_dtype``), in the
layout the program reads. Nothing here imports the program, so the
reference can rebuild the same weights from the seed alone.
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
