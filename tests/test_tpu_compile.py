"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

Nothing here runs on a chip: each test lowers one kernel at the Llama 3.2
3B / 1B serving widths in bf16 (or a paged model step at the benchmark's
Granite 3.0 2B and DeepSeek-Coder 33B widths) against a *described* v5e
topology and asks the TPU compiler for the executable. That catches what
interpret mode cannot — BlockSpecs the Mosaic lowering refuses, VMEM
overruns, kernels that cannot be partitioned, copies of the KV pool that
XLA inserts around the layer scan — at no chip time. The topology is described
inside a module fixture (never at import), so pytest-xdist workers collect
the same tests and only the worker that runs this file loads the TPU
compiler.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.cache import paged_kv
from repro.configs import (deepseek_coder_33b, granite_3_2b, llama3_2_1b,
                           llama3_2_3b)
from repro.kernels import ops
from repro.kernels.paged_attention import paged_flash_attention
from repro.kernels.spec_verify import verify_greedy_fused
from repro.kernels.tree_attention import tree_flash_attention
from repro.models import dense

B, GAMMA, NB, BS, MB, L = 4, 4, 256, 8, 16, 2
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
    """q, the stacked [L, NB, BS, Kv*D] pools, table, index, layer."""
    Kv, D = cfg.num_kv_heads, cfg.head_dim
    return (_sds((B, Q, cfg.num_heads, D), jnp.bfloat16, sharding),
            _sds((L, NB, BS, Kv * D), jnp.bfloat16, sharding),
            _sds((L, NB, BS, Kv * D), jnp.bfloat16, sharding),
            _sds((B, MB), jnp.int32, sharding),
            _sds((B,), jnp.int32, sharding),
            _sds((), jnp.int32, sharding))


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_attention_compiles(one_chip, arch):
    args = _paged_args(WIDTHS[arch], GAMMA + 1, one_chip)
    text = _compiled_text(
        functools.partial(paged_flash_attention, interpret=False), *args)
    assert "tpu_custom_call" in text and "paged_attention" in text


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_tree_attention_compiles(one_chip, arch):
    span = 1 + 2 * GAMMA                         # a 2-chain tree
    q, k, v, tbl, idx, lyr = _paged_args(WIDTHS[arch], span, one_chip)
    dep = _sds((span,), jnp.int32, one_chip)
    text = _compiled_text(
        functools.partial(tree_flash_attention, interpret=False),
        q, k, v, tbl, idx, dep, dep, lyr)
    assert "tpu_custom_call" in text and "tree_attention" in text


# the benchmark's cells: (config, layers kept, pool blocks, rows, blocks a row)
STEP_CELLS = {"granite3-2b": (granite_3_2b.config(), 4, 120, 16, 7),
              "dscoder33b": (deepseek_coder_33b.config(), 2, 430, 32, 13)}
POOL_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")
_INSTR = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                    r"([\w\-]+)\(")


def _pool_moves(text, pool_elems, n_layers, n_blocks):
    """Instructions of a compiled module (fusion bodies and loop bodies
    included) that copy, slice or update-slice something the size of one
    layer's KV pool or of the whole stack, whatever its layout."""
    found = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, _, dims, op = m.groups()
        shape = [int(d) for d in dims.split(",") if d]
        moves = op in POOL_MOVES or (
            op == "fusion" and any(o in name for o in POOL_MOVES))
        if (moves and n_blocks in shape
                and int(np.prod(shape)) in (pool_elems, n_layers * pool_elems)):
            found.append(f"{name} {op} {shape}")
    return found


@pytest.mark.parametrize("rows,Q", [("batch", 1 + GAMMA), (1, 256)],
                         ids=["verify", "chunk"])
@pytest.mark.parametrize("cell", sorted(STEP_CELLS))
def test_paged_step_moves_no_kv_pool(one_chip, monkeypatch, cell, rows, Q):
    """The paged model step (a verify of gamma+1 queries a row, or a
    256-token prefill chunk at one row) at the benchmark cells' widths and
    pool sizes, cache donated as the server donates it: the layer scan
    writes and reads the stacked pool in place, so no copy, dynamic-slice
    or dynamic-update-slice in the compiled program has the size of a
    layer's pool or of the stack. (The model picks the kernel by the host
    backend, which is the CPU here: steer it to the chip's branch.)"""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg, n_layers, n_blocks, batch, per_row = STEP_CELLS[cell]
    cfg = cfg.replace(num_layers=n_layers)
    rows = batch if rows == "batch" else rows
    Kv, D, bs = cfg.num_kv_heads, cfg.head_dim, 128
    on_chip = functools.partial(jax.tree.map, lambda a: _sds(a.shape, a.dtype,
                                                             one_chip))
    params = jax.eval_shape(lambda: dense.init(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: paged_kv.init_cache(
        n_layers, rows, n_blocks, bs, per_row, Kv, D))
    step = jax.jit(lambda p, t, c: dense.forward(cfg, p, t, c),
                   donate_argnums=2)
    text = step.lower(on_chip(params), _sds((rows, Q), jnp.int32, one_chip),
                      on_chip(cache)).compile().as_text()
    assert "paged_attention" in text
    moves = _pool_moves(text, n_blocks * bs * Kv * D, n_layers, n_blocks)
    assert not moves, f"KV-pool-sized moves in the layer scan: {moves[:6]}"


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
    wraps the kernel in shard_map over the kv heads, the pools split on
    their minor axis in whole-head pieces: the program compiles with one
    kernel per device and no all-gather of the pool. (ops picks
    interpret mode from the host backend, which is the CPU here.)"""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = WIDTHS["3b"]
    mesh = Mesh(np.asarray(topo.devices[:2]), ("tx",))
    heads = NamedSharding(mesh, P(None, None, "tx", None))
    pool = NamedSharding(mesh, P(None, None, None, "tx"))
    rep = NamedSharding(mesh, P())
    q, k, v, tbl, idx, lyr = _paged_args(cfg, GAMMA + 1, rep)
    args = (_sds(q.shape, q.dtype, heads), _sds(k.shape, k.dtype, pool),
            _sds(v.shape, v.dtype, pool), tbl, idx)
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        text = jax.jit(ops.paged_attention).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and "all-gather" not in text
