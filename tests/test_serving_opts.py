"""Serving-optimization correctness: int8 weights, int8 KV, 2D-serving specs,
analytic cost model sanity, HLO collective parsing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import INPUT_SHAPES
from repro.models.model import build_model
from repro.models.specs import ShardingPolicy, cache_specs, param_specs
from repro.quant.int8 import quantize_for_serving


def test_int8_serving_matches_argmax():
    cfg = registry.smoke_config("llama3.2-1b")
    m = build_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    pq = quantize_for_serving(p)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    lg, _, _ = m.apply(p, toks)
    lgq, _, _ = m.apply(pq, toks)
    agree = (jnp.argmax(lg, -1) == jnp.argmax(lgq, -1)).mean()
    assert float(agree) > 0.95


@pytest.mark.parametrize("arch", ["llama3.2-1b", "mixtral-8x7b"])
def test_int8_param_specs_cover_tree(arch):
    cfg = registry.smoke_config(arch)
    m = build_model(cfg)
    shape = jax.eval_shape(lambda: quantize_for_serving(m.init(jax.random.PRNGKey(0))))
    pol = ShardingPolicy(mesh_axis_sizes={"data": 16, "model": 16})
    specs = param_specs(cfg, shape, pol)
    assert (jax.tree_util.tree_structure(shape)
            == jax.tree_util.tree_structure(specs))


def test_int8_kv_cache_generation_agrees():
    from repro.core.engine import autoregressive_generate
    cfg = registry.smoke_config("llama3.2-1b")
    m = build_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 5), 0, cfg.vocab_size)
    ref = autoregressive_generate(m, p, prompt, 10)
    # int8 cache path via model.init_cache dtype
    cache = m.init_cache(1, 20, spec_slack=2, dtype=jnp.int8)
    logits, cache, _ = m.apply(p, prompt, cache)
    toks = [int(jnp.argmax(logits[0, -1]))]
    for _ in range(9):
        lg, cache, _ = m.apply(p, jnp.array([[toks[-1]]]), cache,
                               logits_slice="last")
        toks.append(int(jnp.argmax(lg[0, -1])))
    agree = np.mean(np.asarray(toks) == np.asarray(ref[0, 5:15]))
    assert agree >= 0.9, (toks, ref[0, 5:15])


def test_serve_2d_cache_spec_uses_both_axes():
    cfg = registry.config("llama3-405b")
    m = build_model(cfg)
    pol = ShardingPolicy(mesh_axis_sizes={"data": 16, "model": 16},
                         replicate_batch=True, fsdp=True)
    cshape = m.cache_spec(128, 32768, spec_slack=0)
    specs = cache_specs(cfg, cshape, pol, 128)
    spec_k = specs["k"]
    assert spec_k[1] is None                     # batch replicated
    assert spec_k[2] == ("data", "model")        # W over both axes


def test_analytic_cost_sanity():
    """Analytic FLOPs within 2x of the 6ND rule; decode memory ~ cache+params."""
    from repro.core import analytic_cost
    cfg = registry.config("llama3.2-1b")
    sh = INPUT_SHAPES["train_4k"]
    c = analytic_cost.step_cost(cfg, sh, chips=256)
    six_nd = 6 * cfg.active_param_count() * sh.global_batch * sh.seq_len
    assert six_nd <= c.flops <= 2.5 * six_nd
    shd = INPUT_SHAPES["decode_32k"]
    cd = analytic_cost.step_cost(cfg, shd, chips=256)
    cache = cfg.num_layers * shd.global_batch * shd.seq_len \
        * cfg.num_kv_heads * cfg.head_dim * 2 * 2
    assert cd.hbm_bytes >= cache  # cache read is a lower bound


def test_peaks_are_keyed_by_device_kind():
    """Peaks come from one table keyed by ``device_kind``; a kind missing
    from it is an error, never a silent v5e default."""
    from repro.core import cost_model as cm
    v5e = cm.peaks_for("TPU v5 lite")
    assert v5e is cm.V5E and v5e.peak_flops == 197e12
    assert v5e.hbm_bw == 819e9 and "TPU v5e" in v5e.source
    with pytest.raises(KeyError, match="TPU v6 lite"):
        cm.peaks_for("TPU v6 lite")


@pytest.mark.parametrize("env", [None, "/elsewhere/jax-cache"])
def test_compile_cache_dir(monkeypatch, env):
    """The persistent compile cache follows JAX_COMPILATION_CACHE_DIR when
    set (and is then not set in code); otherwise it is the fixed in-checkout
    ``.jax_cache``. Config writes are captured so the suite's own cache
    stays off."""
    from pathlib import Path
    from repro.launch import cli_args
    writes = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: writes.__setitem__(k, v))
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    got = cli_args.enable_compile_cache()
    if env is None:
        root = Path(cli_args.__file__).resolve().parents[3]
        assert got == str(root / ".jax_cache")
        assert writes == {"jax_compilation_cache_dir": got}
    else:
        assert got == env and writes == {}


def test_hlo_collective_parser():
    from repro.launch.hlo_analysis import collective_bytes
    hlo = """
  %ag = bf16[16,128,4096]{2,1,0} all-gather(%x), replica_groups={}
  %ar = f32[256]{0} all-reduce(%y), to_apply=%add
  %nocoll = f32[8]{0} add(%a, %b)
  %a2a = (f32[4,8]{1,0}, f32[4,8]{1,0}) all-to-all(%p, %q)
"""
    st = collective_bytes(hlo)
    assert st.count_by_kind["all-gather"] == 1
    assert st.bytes_by_kind["all-gather"] == 16 * 128 * 4096 * 2
    assert st.bytes_by_kind["all-reduce"] == 256 * 4
    assert st.bytes_by_kind["all-to-all"] == 2 * 4 * 8 * 4
    assert "nocoll" not in str(st.bytes_by_kind)


def test_scan_trips():
    from repro.core import analytic_cost
    assert analytic_cost.scan_trips(registry.config("llama3-405b"), "decode") == 126
    assert analytic_cost.scan_trips(registry.config("mixtral-8x7b"), "decode") == 32
    l4 = registry.config("llama4-maverick-400b-a17b")
    assert analytic_cost.scan_trips(l4, "decode") == 24   # paired blocks
    rg = registry.config("recurrentgemma-2b")
    assert analytic_cost.scan_trips(rg, "decode") == 8    # (rec,rec,attn) blocks
    assert analytic_cost.scan_trips(registry.config("llama3.2-1b"), "train", 4) == 64
