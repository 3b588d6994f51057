"""Model families, found by name: the dense family builds what the harness
built before families had modules of their own (the same ModelConfig,
bit-identical weights, the same counts), a windowed family's counts cap each
layer at its window, an unknown family fails at lookup, and a family
registered under a new name runs a whole cell on the CPU, its planted fault
included."""
import dataclasses
import sys
import time
import types
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts, faults, harness
from bench.families import dense
from bench.weights import _normal, dtype_of, seed_key

CONFIGS = ["granite3-2b.sd4", "dscoder33b-l8.sd1"]
DATA = harness.BENCH / "tests" / "data"
TINY = harness.load_json(DATA / "tiny.sd1.json")
ENTRY_POINTS = ("model_config", "target_params", "drafter_params",
                "late_writers", "matmul_params", "attention_windows")


# ------------------------------------------------ the dense family, pinned
def _parent_model_config(name):
    """The ModelConfig fields the harness set for each configuration before
    the dense family had a module."""
    from repro.configs.base import ModelConfig
    if name == "granite3-2b.sd4":
        return ModelConfig(
            name=name, family="dense", num_layers=40, d_model=2048,
            num_heads=32, num_kv_heads=8, head_dim=64, d_ff=8192,
            vocab_size=49155, rope_theta=10000.0, tie_embeddings=True,
            norm_eps=1e-05, dtype="bfloat16", param_dtype="bfloat16",
            source="https://huggingface.co/ibm-granite/granite-3.0-2b-base")
    return ModelConfig(
        name=name, family="dense", num_layers=8, d_model=7168, num_heads=56,
        num_kv_heads=8, head_dim=128, d_ff=19200, vocab_size=32256,
        rope_theta=100000.0, tie_embeddings=False, norm_eps=1e-06,
        dtype="bfloat16", param_dtype="bfloat16",
        source="https://huggingface.co/deepseek-ai/deepseek-coder-33b-base")


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_model_config_is_the_one_the_harness_built(name):
    cfg = harness.config(name)
    assert "family" not in cfg
    assert harness.family(cfg) is dense
    want = _parent_model_config(name)
    assert dense.model_config(cfg) == want
    k = cfg["self_draft"]["layers"]
    assert dense.model_config(cfg, layers=k, name=name + ".draft") == \
        dataclasses.replace(want, name=name + ".draft", num_layers=k)


def _parent_layer(key, i, cfg):
    """The dense weight builder as it stood before families (a frozen copy:
    what the family's builder has to reproduce bit for bit)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dt = dtype_of(cfg)
    sd = cfg["self_draft"]
    damp = jnp.where(i >= sd["layers"], sd["damping"], 1.0)
    ks = jax.random.split(jax.random.fold_in(key, i), 7)
    return {
        "attn": {
            "norm": {"scale": jnp.ones((d,), dt)},
            "q": {"w": _normal(ks[0], (d, nq * hd), d ** -0.5, dt)},
            "k": {"w": _normal(ks[1], (d, nkv * hd), d ** -0.5, dt)},
            "v": {"w": _normal(ks[2], (d, nkv * hd), d ** -0.5, dt)},
            "o": {"w": _normal(ks[3], (nq * hd, d),
                               (nq * hd) ** -0.5 * damp, dt)},
        },
        "mlp_norm": {"scale": jnp.ones((d,), dt)},
        "mlp": {
            "gate": {"w": _normal(ks[4], (d, f), d ** -0.5, dt)},
            "up": {"w": _normal(ks[5], (d, f), d ** -0.5, dt)},
            "down": {"w": _normal(ks[6], (f, d), f ** -0.5 * damp, dt)},
        },
    }


def _parent_build(cfg, key):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    dt = dtype_of(cfg)
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    layers = jax.lax.map(lambda i: _parent_layer(k_layers, i, cfg),
                         jnp.arange(cfg["num_hidden_layers"]))
    params = {
        "embed": {"table": _normal(k_embed, (v, d),
                                   cfg["self_draft"]["embed_std"], dt)},
        "layers": layers,
        "final_norm": {"scale": jnp.ones((d,), dt)},
    }
    if not cfg["tie_word_embeddings"]:
        params["lm_head"] = {"w": _normal(k_head, (d, v), d ** -0.5, dt)}
    return params


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_weights_trace_as_the_parent_built(name):
    """At the configuration's own sizes (traced, not run): the same program
    from the same key, so the same bits for every seed."""
    cfg = harness.config(name)
    key = seed_key(2 ** 33 + 5, 0)
    got = jax.make_jaxpr(lambda k: dense._build(cfg, k))(key)
    want = jax.make_jaxpr(lambda k: _parent_build(cfg, k))(key)
    assert str(got) == str(want)


@pytest.mark.parametrize("seed", [11, 2 ** 33 + 5])
def test_dense_weights_bit_equal_to_the_parent(seed):
    cfg = dict(TINY, num_hidden_layers=4,
               self_draft=dict(TINY["self_draft"], layers=2))
    got = dense.target_params(cfg, seed)
    want = jax.jit(lambda k: _parent_build(cfg, k))(seed_key(seed, 0))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a).view(np.uint16),
                                      np.asarray(b).view(np.uint16))


# what bench/counts.py and bench/metrics/step_mfu.py gave before families
PINNED = {
    "granite3-2b.sd4": dict(
        matmul=2533365760, draft_matmul=343939072,
        token0=5067059200.0, token3000=6050099200.0,
        verify=(4915200000.0, 247398400), draft=(98336768.0, 24616960),
        step_mfu=0.40884990934925847),
    "dscoder33b-l8.sd1": dict(
        matmul=4473749504, draft_matmul=761528320,
        token0=8947728384.0, token3000=9635856384.0,
        verify=(3440640000.0, 99450880), draft=(86044672.0, 12320768),
        step_mfu=0.6787155523013039),
}


def _window_run(cfg):
    """A window of four requests: two finished, one never started, one
    with a single token."""
    recs = [NS(idx=i, prompt_len=p) for i, p in enumerate([300, 2900, 17,
                                                            4000])]
    snap = {0: (1.0, 2.0, 40, True), 1: (1.5, 3.0, 64, True),
            2: (None, None, 0, False), 3: (2.0, 2.5, 1, False)}
    return NS(cfg=cfg, chips=1, peaks={"bf16_flops_per_s": 197e12},
              window=NS(records=recs, snap=snap, t0=0.0, t_end=51.0))


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_counts_are_the_pinned_ones(name):
    cfg, want = harness.config(name), PINNED[name]
    k, L = cfg["self_draft"]["layers"], cfg["num_hidden_layers"]
    assert dense.matmul_params(cfg) == counts.matmul_params(cfg) \
        == want["matmul"]
    assert counts.matmul_params(cfg, k) == want["draft_matmul"]
    assert dense.attention_windows(cfg) == [None] * L
    assert counts.token_flops(cfg, 0) == want["token0"]
    assert counts.token_flops(cfg, 3000) == want["token3000"]
    assert counts.paged_attention_least(cfg, 5, 3000, L) == want["verify"]
    assert counts.paged_attention_least(cfg, 1, 3001, k) == want["draft"]
    assert harness.reader("step_mfu")(_window_run(cfg)) == want["step_mfu"]


# --------------------------------------------------- families found by name
def _wrapping_family(name: str, calls: list, **override):
    """A family module under a new name that does what dense does, noting
    each entry point it is asked for; ``override`` replaces entry points."""
    mod = types.ModuleType(f"bench.families.{name}")
    for fn in ENTRY_POINTS:
        inner = override.get(fn, getattr(dense, fn))

        def entry(*a, _inner=inner, _fn=fn, **kw):
            calls.append(_fn)
            return _inner(*a, **kw)
        setattr(mod, fn, entry)
    return mod


def _windowed(cfg, layers=None):
    """A 1024-token window on three of every four layers."""
    L = cfg["num_hidden_layers"] if layers is None else layers
    return [None if i % 4 == 3 else 1024 for i in range(L)]


def test_a_windowed_family_caps_each_layer_at_its_window(monkeypatch):
    monkeypatch.setitem(sys.modules, "bench.families.windowed",
                        _wrapping_family("windowed", [],
                                         attention_windows=_windowed))
    g = harness.config("granite3-2b.sd4")
    cfg = dict(g, family="windowed")
    per_layer_kv = counts.kv_bytes_per_token(g, layers=1)
    qo = 2 * 5 * 32 * 64 * 2 * 40
    # 30 windowed layers read 1024 of 3000 cached tokens, 10 read all
    keys = 30 * 1024 + 10 * 3000
    f, b = counts.paged_attention_least(cfg, 5, 3000, 40)
    assert b == per_layer_kv * keys + qo
    assert f == 4 * 5 * keys * 32 * 64
    assert counts.paged_attention_least(cfg, 5, 3000, 40)[1] < \
        counts.paged_attention_least(g, 5, 3000, 40)[1]
    # inside the window nothing is capped
    assert counts.paged_attention_least(cfg, 5, 1000, 40) == \
        counts.paged_attention_least(g, 5, 1000, 40)
    assert counts.matmul_params(cfg) == counts.matmul_params(g)


@pytest.mark.parametrize("window", [None, 1, 7, 20, 64])
@pytest.mark.parametrize("first,n", [(1, 1), (1, 30), (5, 12), (19, 3),
                                     (40, 9)])
def test_causal_key_counts_cap_at_the_window(window, first, n, monkeypatch):
    monkeypatch.setitem(sys.modules, "bench.families.one_window",
                        _wrapping_family(
                            "one_window", [],
                            attention_windows=lambda c, layers=None:
                            [window] * (2 if layers is None else layers)))
    cfg = dict(TINY, family="one_window", num_hidden_layers=2)
    keys = sum(c if window is None else min(c, window)
               for c in range(first, first + n))
    assert counts.keys_read(cfg, first, n) == 2 * keys
    assert counts.causal_attention_flops(cfg, first - 1, n) == \
        4.0 * 2 * keys * TINY["num_attention_heads"] * TINY["head_dim"]


@pytest.mark.parametrize("name", ["no_such_family", "not.a.module"])
def test_an_unknown_family_fails_at_lookup(name):
    cfg = dict(TINY, family=name)
    with pytest.raises(LookupError, match=f"bench/families/{name}.py"):
        harness.family(cfg)
    with pytest.raises(LookupError, match=f"bench/families/{name}.py"):
        harness.build(cfg, harness.load_json(DATA / "tiny_open.json"), 1)


BENCH = {"workloads": [{"name": "tiny", "config": "tiny.sd1",
                        "traffic": "tiny_open", "chips": 1}],
         "end_to_end": harness.benchmark()["end_to_end"], "per_layer": []}


def _unreachable(*a, **kw):
    raise AssertionError("reached the dense module without the lookup")


@pytest.mark.parametrize("fault", [None, "skip_late_layers"])
def test_a_family_registered_by_name_runs_a_whole_cell(fault, monkeypatch):
    """The harness reaches a family only through ``harness.family``: a
    module registered under a new name, wrapping dense, serves the tiny
    cell ``correct`` while dense's own entry points raise, and the
    later-layers fault planted through its ``late_writers`` fails."""
    calls = []
    monkeypatch.setitem(sys.modules, "bench.families.wrapped_dense",
                        _wrapping_family("wrapped_dense", calls))
    for fn in ENTRY_POINTS:
        if fn != "late_writers":      # dense's builder reads its own list
            monkeypatch.setattr(dense, fn, _unreachable)
    monkeypatch.setattr(harness, "WARM_TIMEOUT_S", 5.0)
    cfg = dict(TINY, family="wrapped_dense")
    plant = None if fault is None else \
        (lambda s: faults.FAULTS[fault](s, cfg))
    out = harness.run("tiny", 2 ** 34 + 3, 3.0, False, jax.devices(),
                      time.time(), bench=BENCH, cfg=cfg,
                      mx=harness.load_json(DATA / "tiny_open.json"),
                      after_build=plant)
    assert out["correct"] is (fault is None), out["checks"]
    assert calls.count("model_config") == 2        # the target, the drafter
    assert calls.count("target_params") == 2       # served, then re-read
    assert calls.count("drafter_params") == 1
    assert ("late_writers" in calls) is (fault is not None)
