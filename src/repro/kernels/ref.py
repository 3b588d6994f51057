"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def int8_matmul_ref(x_q, w_q, sx, sw, out_dtype=jnp.bfloat16):
    """[M,K]i8 @ [K,N]i8 with int32 accumulation, then rescale."""
    acc = jax.lax.dot_general(x_q, w_q, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * sx * sw[None, :]).astype(out_dtype)


def blockwise_argmax_ref(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]


def flash_attention_ref(q, k, v, *, window=None, causal=True):
    """Oracle via the model-level attention (itself equivalence-tested)."""
    from repro.models.attention import attn_dense
    B, Sq = q.shape[0], q.shape[1]
    Skv = k.shape[1]
    q_pos = jnp.arange(Sq, dtype=jnp.int32)
    kv_pos = jnp.arange(Skv, dtype=jnp.int32)
    return attn_dense(q, k, v, q_pos, kv_pos, window=window, causal=causal)


def paged_attention_ref(q, k_pool, v_pool, block_table, index, *, layer=0,
                        window=None):
    """Oracle via the model-level block-scan paged attention (itself
    equivalence-tested against the dense gathered view); pools are the
    stacked ``[L, NB, BS, Kv*D]`` layout the kernel reads."""
    from repro.models.attention import attn_paged
    return attn_paged(q, k_pool, v_pool, block_table, index, layer=layer,
                      window=window)


def tree_attention_ref(q, k_pool, v_pool, block_table, index, depths, bits,
                       *, layer=0, window=None):
    """Oracle via the model-level block-scan tree attention (itself built on
    the equivalence-tested online-softmax step)."""
    from repro.models.attention import attn_tree
    return attn_tree(q, k_pool, v_pool, block_table, index, depths, bits,
                     layer=layer, window=window)


def ssd_scan_ref(x, dA, Bm, Cm, chunk=128):
    """Oracle: the model-level chunked SSD (itself equivalence-tested against
    the sequential recurrence in tests/test_models)."""
    import jax.numpy as jnp
    from repro.models.ssm import ssd_chunked
    b, l, h, p = x.shape
    n = Bm.shape[-1]
    init = jnp.zeros((b, h, p, n), jnp.float32)
    y, _ = ssd_chunked(x.astype(jnp.float32), dA.astype(jnp.float32),
                       Bm.astype(jnp.float32), Cm.astype(jnp.float32),
                       chunk, init)
    return y
