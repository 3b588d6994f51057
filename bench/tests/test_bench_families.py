"""Model families, found by name: the dense family builds what the harness
built before families had modules of their own (the same ModelConfig,
bit-identical weights, the same counts and roofline share), a windowed
family's counts cap each layer at its window, a hybrid-shaped family's
counts, roofline share and planted fault take only its attending layers and
its per-kind stacks, an unknown family fails at lookup, and a family
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
from bench.families import AttentionLayer, dense
from bench.trace_reduce import DeviceTrace
from bench.weights import _normal, dtype_of, seed_key

CONFIGS = ["granite3-2b.sd4", "dscoder33b-l8.sd1"]
DATA = harness.BENCH / "tests" / "data"
TINY = harness.load_json(DATA / "tiny.sd1.json")
ENTRY_POINTS = ("model_config", "target_params", "drafter_params",
                "late_writers", "stack_layers", "matmul_params",
                "attention_layers", "other_flops")


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
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    assert dense.attention_layers(cfg) == [AttentionLayer(
        None, 2 * nkv * hd * 2, 4 * nq * hd, 2 * nq * hd * 2)] * L
    assert dense.stack_layers(cfg, "mlp.down.w") == list(range(L))
    assert dense.other_flops(cfg, 3000, 5) == 0
    assert counts.token_flops(cfg, 0) == want["token0"]
    assert counts.token_flops(cfg, 3000) == want["token3000"]
    assert counts.paged_attention_least(cfg, 5, 3000, L) == want["verify"]
    assert counts.paged_attention_least(cfg, 1, 3001, k) == want["draft"]
    assert harness.reader("step_mfu")(_window_run(cfg)) == want["step_mfu"]


def _round_run(cfg, batch, verify_calls, draft_calls):
    """A traced window of eight rounds of two to four rows (some with a row
    that has no record, one autoregressive, one before the traced span), and
    per round ``draft_calls`` and ``verify_calls`` paged-attention calls at
    the configuration's result shapes, a prefill chunk's call, and an op
    that only reads the kernel's result."""
    gamma = cfg["self_draft"]["gamma"]
    kv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    group = cfg["num_attention_heads"] // kv
    recs = [NS(idx=i, prompt_len=p) for i, p in enumerate([300, 1200, 40,
                                                            2900])]
    events, ops, t = [], [], 0

    def call(i, rows, ns, b=batch):
        nonlocal t
        rows = -(-rows // 8) * 8
        ops.append((f"%paged_attention.{i} = bf16[{b},{kv},{rows},{hd}]"
                    "{3,2,1,0} custom-call(bf16[4]{0} %p.1)", t, t + ns))
        t += ns + 500
    for r in range(8):
        g = 0 if r == 3 else gamma
        n = 2 + r % 3
        events.append(NS(rids=[0, 1, 7, 2][:n], gamma=g, t_wall=10.0 + 0.5 * r,
                         accepted=[r % (g + 1), 2 * r % (g + 1), 1, 0][:n]))
        for j in range(draft_calls if g else 0):
            call(24, group, 9000 + 700 * (j % 5))
        for j in range(verify_calls):
            call(16, (g + 1) * group, 41000 + 900 * (j % 7) + 300 * r)
        call(8, 256, 120000, b=1)
        ops.append(("%fusion.3 = bf16[4]{0} fusion(bf16[4]{0} "
                    "%paged_attention.16)", t, t + 2000))
        t += 3000
    return NS(cfg=cfg, srv_batch=batch,
              peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
              window=NS(records=recs, events=events, trace_span=(10.2, 13.6),
                        trace={"devices": [DeviceTrace("/device:TPU:0",
                                                       ops)]}))


# what bench/metrics/paged_attention_roofline.py gave on _round_run before
# a family named its attending layers
PINNED_ROOFLINE = {"granite3-2b.sd4": (16, 9.963872230184782),
                   "dscoder33b-l8.sd1": (32, 21.42860483496888)}


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_roofline_is_the_pinned_one(name):
    cfg = harness.config(name)
    batch, want = PINNED_ROOFLINE[name]
    L, sd = cfg["num_hidden_layers"], cfg["self_draft"]
    run = _round_run(cfg, batch, L, sd["gamma"] * sd["layers"])
    assert harness.reader("paged_attention_roofline")(run) == want


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
    """Dense's layers, with a 1024-token window on three of every four."""
    return [a._replace(window=None if i % 4 == 3 else 1024)
            for i, a in enumerate(dense.attention_layers(cfg, layers))]


def test_a_windowed_family_caps_each_layer_at_its_window(monkeypatch):
    monkeypatch.setitem(sys.modules, "bench.families.windowed",
                        _wrapping_family("windowed", [],
                                         attention_layers=_windowed))
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
                            attention_layers=lambda c, layers=None:
                            [a._replace(window=window)
                             for a in dense.attention_layers(c, layers)]))
    cfg = dict(TINY, family="one_window", num_hidden_layers=2)
    keys = sum(c if window is None else min(c, window)
               for c in range(first, first + n))
    assert counts.keys_read(cfg, first, n) == 2 * keys
    assert counts.causal_attention_flops(cfg, first - 1, n) == \
        4.0 * 2 * keys * TINY["num_attention_heads"] * TINY["head_dim"]


# ------------------------------- a hybrid-shaped family: 1 layer in 4 attends
def _attends(i):
    return i % 4 == 3


def _hybrid_layers(cfg, layers=None):
    """Dense's record, on layers 3, 7, 11, ... only."""
    L = cfg["num_hidden_layers"] if layers is None else layers
    one = dense.attention_layers(cfg, 1)[0]
    return [one for i in range(L) if _attends(i)]


def _hybrid_stacks(cfg, path):
    """Per-kind stacks: the mixers' stack holds the layers that do not
    attend, the attention stack those that do, the MLPs' stack all."""
    L = range(cfg["num_hidden_layers"])
    kind = path.split(".")[0]
    return {"mixer": [i for i in L if not _attends(i)],
            "attn": [i for i in L if _attends(i)], "mlp": list(L)}[kind]


OTHER = 1000      # FLOPs a token the hybrid-shaped family spends elsewhere


@pytest.fixture
def hybrid(monkeypatch):
    """Granite's widths, 40 layers of which 10 attend, its drafter's 4
    layers holding one of them."""
    monkeypatch.setitem(sys.modules, "bench.families.hybrid_shaped",
                        _wrapping_family(
                            "hybrid_shaped", [],
                            attention_layers=_hybrid_layers,
                            stack_layers=_hybrid_stacks,
                            late_writers=lambda c: ["mixer.out.w",
                                                    "attn.o.w", "mlp.down.w"],
                            other_flops=lambda c, start, n: OTHER * n))
    return dict(harness.config("granite3-2b.sd4"), family="hybrid_shaped")


def test_a_hybrid_family_counts_its_attending_layers_alone(hybrid):
    kv, fpk, qb = 2 * 8 * 64 * 2, 4 * 32 * 64, 2 * 32 * 64 * 2
    assert counts.kv_bytes_per_token(hybrid) == 10 * kv
    assert counts.kv_bytes_per_token(hybrid, layers=4) == kv
    assert counts.paged_attention_least(hybrid, 5, 3000, 40) == \
        (5 * 10 * 3000 * fpk, 10 * (3000 * kv + 5 * qb))
    assert counts.paged_attention_least(hybrid, 1, 3001, 4) == \
        (3001 * fpk, 3001 * kv + qb)
    # the first three layers hold no K/V: no keys, FLOPs or bytes
    assert counts.keys_read(hybrid, 100, 7, layers=3) == 0
    assert counts.attention_flops(hybrid, 5, 100, layers=3) == 0
    assert counts.causal_attention_flops(hybrid, 0, 50, layers=3) == 0
    assert counts.kv_bytes_per_token(hybrid, layers=3) == 0
    assert counts.paged_attention_least(hybrid, 1, 3001, 3) == (0, 0)
    # one token at 0 sees one key in each attending layer
    assert counts.token_flops(hybrid, 0) == \
        2 * counts.matmul_params(hybrid) + 10 * fpk + OTHER


def test_step_mfu_counts_the_family_flops_outside_matmuls(hybrid,
                                                          monkeypatch):
    with_other = harness.reader("step_mfu")(_window_run(hybrid))
    monkeypatch.setattr(sys.modules["bench.families.hybrid_shaped"],
                        "other_flops", lambda c, start, n: 0)
    without = harness.reader("step_mfu")(_window_run(hybrid))
    # prefill and committed tokens of the three requests that started
    tokens = (299 + 40) + (2899 + 64) + (3999 + 1)
    assert with_other - without == \
        pytest.approx(100 * tokens * OTHER / (51.0 * 197e12), rel=1e-9)


def test_a_hybrid_roofline_takes_one_call_a_round_per_attending_layer(
        hybrid):
    """Two speculative rounds of two rows, 1000 and 2000 tokens long, each
    round 10 verify calls (one per attending layer) of 50 us and 4 draft
    calls (the drafter's one attending layer, gamma 4) of 10 us: by hand,
    each round's least time is its bytes at the HBM peak."""
    kv, qb, fpk = 2 * 8 * 64 * 2, 2 * 32 * 64 * 2, 4 * 32 * 64
    ops, t = [], 0
    for _ in range(2):
        for rows, ns, calls in ((8, 10_000, 4), (24, 50_000, 10)):
            for _ in range(calls):
                ops.append((f"%paged_attention.1 = bf16[2,8,{rows},64]"
                            "{3,2,1,0} custom-call()", t, t + ns))
                t += ns + 1000
    run = NS(cfg=hybrid, srv_batch=2,
             peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
             window=NS(records=[NS(idx=0, prompt_len=1000),
                                NS(idx=1, prompt_len=2000)],
                       events=[NS(rids=[0, 1], accepted=[2, 4], gamma=4,
                                  t_wall=11.0),
                               NS(rids=[0, 1], accepted=[0, 1], gamma=4,
                                  t_wall=12.0)],
                       trace_span=(10.0, 13.0),
                       trace={"devices": [DeviceTrace("/device:TPU:0",
                                                      ops)]}))
    least = []
    for lengths in ([1000, 2000], [1003, 2005]):
        nbytes = flops = 0
        for n in lengths:
            nbytes += 10 * (n * kv + 5 * qb)        # verify, 10 layers
            flops += 10 * 5 * n * fpk
            for j in range(4):                      # drafts, 1 layer
                nbytes += (n + j) * kv + qb
                flops += (n + j) * fpk
        assert nbytes / 819e9 > flops / 197e12
        least.append(nbytes / 819e9)
    round_s = (4 * 10_000 + 10 * 50_000) * 1e-9
    assert harness.reader("paged_attention_roofline")(run) == \
        pytest.approx(100 * sum(least) / 2 / round_s, rel=1e-12)


def test_a_latent_record_is_counted_from_the_family(monkeypatch):
    """A latent-attention-shaped layer caches one 576-wide latent a token
    (512 + 64 for position), not K and V of ``num_key_value_heads`` x
    ``head_dim``: its bytes and FLOPs per key are the family's."""
    heads = 32
    latent = AttentionLayer(window=None, kv_bytes=576 * 2,
                            flops_per_key=2 * heads * 576 + 2 * heads * 512,
                            query_bytes=heads * (576 + 512) * 2)
    monkeypatch.setitem(sys.modules, "bench.families.latent",
                        _wrapping_family(
                            "latent", [],
                            attention_layers=lambda c, layers=None:
                            [latent] * (40 if layers is None else layers)))
    cfg = dict(harness.config("granite3-2b.sd4"), family="latent")
    assert counts.kv_bytes_per_token(cfg) == 40 * 1152
    assert counts.paged_attention_least(cfg, 5, 3000, 40) == \
        (5 * 40 * 3000 * (64 * 576 + 64 * 512),
         40 * (3000 * 1152 + 5 * 32 * 1088 * 2))
    assert counts.causal_attention_flops(cfg, 9, 1, layers=1) == \
        10 * (64 * 576 + 64 * 512)


def _stacks(owners: dict) -> dict:
    """Nested dicts of one bf16 leaf per dotted path, its entry i filled
    with i + 1."""
    out = {}
    for path, layers in owners.items():
        *head, leaf = path.split(".")
        node = out
        for key in head:
            node = node.setdefault(key, {})
        n = len(layers)
        node[leaf] = jnp.arange(1, n + 1, dtype=jnp.bfloat16)[:, None, None] \
            * jnp.ones((n, 3, 2), jnp.bfloat16)
    return out


def _leaf(tree: dict, path: str):
    for key in path.split("."):
        tree = tree[key]
    return np.asarray(tree)


@pytest.mark.parametrize("shape", ["dense", "hybrid_shaped"])
def test_skip_late_layers_zeroes_the_entries_of_late_layers(shape, hybrid):
    """On dense's one stack, entries ``[k:]``, as before families named
    their layers; on per-kind stacks, the entries of layers >= k alone."""
    cfg = harness.config("granite3-2b.sd4") if shape == "dense" else hybrid
    fam = harness.family(cfg)
    k = cfg["self_draft"]["layers"]
    owners = {p: fam.stack_layers(cfg, p) for p in fam.late_writers(cfg)}
    before = _stacks(owners)
    served = NS(srv=NS(params_t={"layers": _stacks(owners), "embed": "kept"}),
                params=[None])
    faults.skip_late_layers(served, cfg)
    assert served.params[0] is served.srv.params_t
    assert served.srv.params_t["embed"] == "kept"
    zeroed = 0
    for path, layers_of in owners.items():
        got = _leaf(served.srv.params_t["layers"], path)
        want = _leaf(before, path)
        for i, layer in enumerate(layers_of):
            if layer >= k:
                zeroed += 1
                assert not got[i].any(), (path, i, layer)
            else:
                np.testing.assert_array_equal(got[i].view(np.uint16),
                                              want[i].view(np.uint16))
    if shape == "dense":
        assert zeroed == 2 * (40 - k)
    else:           # mixers of layers 4..38 less 7, 11, ..; attention 7..39
        assert zeroed == 27 + 9 + 36


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
    assert ("stack_layers" in calls) is (fault is not None)
