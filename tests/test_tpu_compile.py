"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

Nothing here runs on a chip: each test lowers one kernel at the Llama 3.2
3B / 1B serving widths in bf16 against a *described* v5e topology and asks
the TPU compiler for the executable. That catches what interpret mode
cannot — BlockSpecs the Mosaic lowering refuses, VMEM overruns, kernels
that cannot be partitioned — at no chip time. The topology is described
inside a module fixture (never at import), so pytest-xdist workers collect
the same tests and only the worker that runs this file loads the TPU
compiler.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import llama3_2_1b, llama3_2_3b
from repro.kernels import ops
from repro.kernels.paged_attention import paged_flash_attention
from repro.kernels.spec_verify import verify_greedy_fused
from repro.kernels.tree_attention import tree_flash_attention

B, GAMMA, NB, BS, MB = 4, 4, 256, 8, 16
WIDTHS = {"3b": llama3_2_3b.config(), "1b": llama3_2_1b.config()}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # the TPU compiler is absent or cannot load
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executable can be written to the persistent cache
    # but never read back here; keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _paged_args(cfg, Q, sharding):
    Kv, D = cfg.num_kv_heads, cfg.head_dim
    return (_sds((B, Q, cfg.num_heads, D), jnp.bfloat16, sharding),
            _sds((NB, Kv, BS, D), jnp.bfloat16, sharding),
            _sds((NB, Kv, BS, D), jnp.bfloat16, sharding),
            _sds((B, MB), jnp.int32, sharding),
            _sds((B,), jnp.int32, sharding))


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_attention_compiles(one_chip, arch):
    args = _paged_args(WIDTHS[arch], GAMMA + 1, one_chip)
    text = _compiled_text(
        functools.partial(paged_flash_attention, interpret=False), *args)
    assert "tpu_custom_call" in text and "paged_attention" in text


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_tree_attention_compiles(one_chip, arch):
    span = 1 + 2 * GAMMA                         # a 2-chain tree
    q, k, v, tbl, idx = _paged_args(WIDTHS[arch], span, one_chip)
    dep = _sds((span,), jnp.int32, one_chip)
    text = _compiled_text(
        functools.partial(tree_flash_attention, interpret=False),
        q, k, v, tbl, idx, dep, dep)
    assert "tpu_custom_call" in text and "tree_attention" in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_fused_verify_compiles(one_chip, dtype):
    V = WIDTHS["3b"].vocab_size                  # 1B shares the vocabulary
    drafts = _sds((B, GAMMA), jnp.int32, one_chip)
    logits = _sds((B, GAMMA + 1, V), dtype, one_chip)
    text = _compiled_text(
        functools.partial(verify_greedy_fused, interpret=False),
        drafts, logits)
    assert "tpu_custom_call" in text and "verify_argmax" in text


def test_head_sharded_paged_attention_compiles(topo, monkeypatch):
    """Under a two-chip role mesh (a placed target), ops.paged_attention
    wraps the kernel in shard_map over the kv heads: the program compiles
    with one kernel per device and no all-gather of the pool. (ops picks
    interpret mode from the host backend, which is the CPU here.)"""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = WIDTHS["3b"]
    mesh = Mesh(np.asarray(topo.devices[:2]), ("tx",))
    heads = NamedSharding(mesh, P(None, None, "tx", None))
    pool = NamedSharding(mesh, P(None, "tx", None, None))
    rep = NamedSharding(mesh, P())
    q, k, v, tbl, idx = _paged_args(cfg, GAMMA + 1, rep)
    args = (_sds(q.shape, q.dtype, heads), _sds(k.shape, k.dtype, pool),
            _sds(v.shape, v.dtype, pool), tbl, idx)
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        text = jax.jit(ops.paged_attention).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and "all-gather" not in text
