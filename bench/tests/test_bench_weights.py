"""The self-draft construction: the drafter is the target's first k layers
with the target's own embedding, norm and head."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, weights

CFG = harness.load_json(harness.BENCH / "tests" / "data" / "tiny.sd1.json")


def test_drafter_is_the_targets_first_layers():
    cfg = dict(CFG, num_hidden_layers=4,
               self_draft=dict(CFG["self_draft"], layers=2))
    pt = weights.target_params(cfg, 3)
    pd = weights.drafter_params(cfg, pt)
    for a, b in zip(jax.tree_util.tree_leaves(pd["layers"]),
                    jax.tree_util.tree_leaves(pt["layers"])):
        assert a.shape[0] == 2
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b[:2]))
    assert pd["embed"]["table"] is pt["embed"]["table"]
    assert pd["lm_head"]["w"] is pt["lm_head"]["w"]
    assert pd["final_norm"]["scale"] is pt["final_norm"]["scale"]
    assert pt["layers"]["attn"]["q"]["w"].dtype == jnp.bfloat16


def test_damping_scales_only_the_later_output_projections():
    cfg = dict(CFG, num_hidden_layers=4,
               self_draft=dict(CFG["self_draft"], layers=1, damping=0.01))
    pt = weights.target_params(cfg, 9)
    std = lambda x: float(jnp.std(x.astype(jnp.float32)))  # noqa: E731
    o = pt["layers"]["attn"]["o"]["w"]
    q = pt["layers"]["attn"]["q"]["w"]
    down = pt["layers"]["mlp"]["down"]["w"]
    assert 0.5 < std(o[1]) / std(o[0]) / 0.01 < 2.0
    assert 0.5 < std(down[3]) / std(down[0]) / 0.01 < 2.0
    assert 0.8 < std(q[3]) / std(q[0]) < 1.25


def test_large_seeds_give_distinct_weights():
    a = weights.target_params(CFG, 2 ** 33 + 1)["embed"]["table"]
    b = weights.target_params(CFG, 1)["embed"]["table"]
    c = weights.target_params(CFG, 2 ** 33 + 1)["embed"]["table"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
