"""Placement lowering tests: PlacementPlan -> per-role meshes (api/placement).

Three layers of guarantees:

  * the DEGENERATE lowering (default replicated plans) is a strict no-op —
    placed engines are token-identical to the pre-placement goldens
    (tests/goldens/rounds_parity.json); plans whose submeshes do not fit
    the visible devices raise instead of running degenerate;
  * DISTINCT-submesh plans really execute draft on the drafter mesh and
    verify/commit on the target mesh (sharding inspection) and stay
    token-identical to the replicated goldens — run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the dedicated
    CI step); tests skip when fewer devices are visible;
  * the plan carries placement durably: JSON round-trip of the new
    overlap fields, and the planner's overlapped-round rationale.
"""
import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (DeploymentSpec, ExecutionPlan, Planner, PlacementPlan,
                       Session, SubmeshSpec)
from repro.api import placement as PL
from repro.configs import registry
from repro.core import rounds
from repro.core.batched_engine import BatchedEngineConfig, BatchedSpecEngine
from repro.core.engine import EngineConfig, SpecEngine
from repro.models.model import build_model

GOLD = json.loads((pathlib.Path(__file__).parent
                   / "goldens" / "rounds_parity.json").read_text())
GAMMA = GOLD["meta"]["gamma"]
MAX_NEW = GOLD["meta"]["max_new"]

DEV8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(the dedicated CI placement step)")

HETERO = PlacementPlan(drafter=SubmeshSpec("d2", ("dx",), (2,)),
                       target=SubmeshSpec("t4", ("tx",), (4,)))


@pytest.fixture(scope="module")
def pair():
    cfg_t = registry.smoke_config("llama3.2-1b")
    cfg_d = cfg_t.replace(num_layers=max(1, cfg_t.num_layers - 1),
                          name="draft")
    mt, md = build_model(cfg_t), build_model(cfg_d)
    return (mt, md, mt.init(jax.random.PRNGKey(0)),
            md.init(jax.random.PRNGKey(7)), cfg_t)


def _prompts(cfg, n, length, seed):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (n, length)).astype(np.int32)


# ------------------------------------------------------- degenerate lowering
def test_default_plan_lowers_degenerate():
    pm = PL.lower(PlacementPlan())
    assert not pm.heterogeneous and not pm.disjoint
    x = jnp.arange(4)
    assert pm.to_target(x) is x and pm.to_drafter(x) is x
    assert pm.drafter.put_params(None, {"w": x})["w"] is x


def test_equal_nonreplicated_submeshes_lower_degenerate():
    sub = SubmeshSpec("mx", ("mx",), (4,))
    assert not PL.lower(PlacementPlan(drafter=sub, target=sub)).heterogeneous


def test_degenerate_engine_matches_golden(pair):
    """A SpecEngine handed the degenerate placement takes the unplaced path
    and reproduces the pre-placement goldens bit-for-bit."""
    mt, md, pt, pd, cfg = pair
    ps = jnp.asarray(_prompts(cfg, 2, 6, seed=0))
    eng = SpecEngine(mt, md, EngineConfig(gamma=GAMMA, greedy=True,
                                          use_cache=True, strategy="modular"),
                     placement=PL.DEGENERATE)
    assert eng.placement is None          # degenerate = unplaced path
    toks, stats = eng.generate(pt, pd, ps, MAX_NEW)
    np.testing.assert_array_equal(
        np.asarray(toks), np.asarray(GOLD["single_greedy_cached"]["tokens"]))
    assert stats["rounds"] == GOLD["single_greedy_cached"]["rounds"]


def test_unlowerable_plan_falls_back_degenerate():
    """A plan whose submeshes do not fit the visible devices no longer falls
    back to a degenerate single-mesh run: lowering and Session both raise."""
    big = PlacementPlan(drafter=SubmeshSpec("mx", ("mx",), (4,)),
                        target=SubmeshSpec("mx*my", ("mx", "my"), (16, 16)))
    with pytest.raises(PL.PlacementError):
        PL.lower(big)
    plan = dataclasses.replace(
        Planner(DeploymentSpec(cost_coefficient=0.2,
                               adaptive_gamma=False)).plan(),
        placement=big)
    mt = build_model(registry.smoke_config("llama3.2-1b"))
    with pytest.raises(PL.PlacementError):
        Session(mt, mt, None, None, plan)


def test_explicit_placement_that_does_not_fit_raises():
    """``--placement DxT`` needing more devices than are visible raises: the
    roles never overlap on device 0 and never run as a single mesh."""
    from repro.launch import cli_args
    n = len(jax.devices())
    plan = cli_args.apply_placement_arg(
        Planner(DeploymentSpec(cost_coefficient=0.2,
                               adaptive_gamma=False)).plan(), f"{n}x1")
    with pytest.raises(PL.PlacementError, match=f"{n}\\+1 devices"):
        PL.lower(plan.placement)
    mt = build_model(registry.smoke_config("llama3.2-1b"))
    with pytest.raises(PL.PlacementError):
        Session(mt, mt, None, None, plan)


def test_unsupported_round_configs_reject_placement(pair):
    mt, md, *_ = pair
    spec = rounds.RoundSpec(gamma=2, use_cache=False)
    fake = PL.Placement(drafter=PL.RolePlacement(SubmeshSpec("d", ("d",), (1,)),
                                                 None),
                        target=PL.RolePlacement(SubmeshSpec()))
    with pytest.raises(ValueError, match="cached"):
        rounds.PlacedRound(mt, md, spec, fake)
    with pytest.raises(ValueError, match="linear"):
        rounds.PlacedRound(mt, md, rounds.RoundSpec(
            greedy=True, use_cache=False,
            policy=rounds.MultiDraftPolicy(k=2)), fake)
    # engines downgrade with a recorded reason instead of crashing
    eng = SpecEngine(mt, md, EngineConfig(gamma=2, use_cache=False),
                     placement=PL.lower(HETERO)
                     if len(jax.devices()) >= 6 else PL.DEGENERATE)
    assert eng.placement is None


# ----------------------------------------------------------- plan durability
def test_plan_json_roundtrips_placement_and_overlap_fields():
    pp = PlacementPlan(drafter=SubmeshSpec("d2", ("dx",), (2,)),
                       target=SubmeshSpec("t4", ("tx",), (4,)),
                       explored_variants=4, predicted_speedup=2.5,
                       overlap=True, predicted_round_time=1.48)
    plan = dataclasses.replace(
        Planner(DeploymentSpec(cost_coefficient=0.2,
                               adaptive_gamma=False)).plan(), placement=pp)
    back = ExecutionPlan.from_json(plan.to_json())
    assert back == plan
    assert back.placement.overlap and back.placement.heterogeneous
    assert back.placement.predicted_round_time == pytest.approx(1.48)


def test_planner_records_overlapped_round_term():
    spec = DeploymentSpec(
        alpha=0.9, cost_coefficient=0.1, explore_placement=True,
        adaptive_gamma=False,
        drafter_submeshes=(SubmeshSpec("rep", (), ()),
                           SubmeshSpec("d2", ("dx",), (2,))),
        target_submeshes=(SubmeshSpec("t4", ("tx",), (4,)),),
        submesh_t_draft={"rep": 0.1, "d2": 0.06},
        submesh_t_target={"t4": 1.0})
    plan = Planner(spec).plan()
    assert plan.placement.heterogeneous and plan.placement.overlap
    assert plan.placement.predicted_round_time > 0
    assert any("overlapped-round" in r for r in plan.rationale)
    assert any("measured step times" in r for r in plan.rationale)


# --------------------------------------------------- distinct-submesh (8 dev)
@DEV8
def test_lowering_carves_disjoint_meshes():
    pm = PL.lower(HETERO)
    assert pm.heterogeneous and pm.disjoint
    d, t = set(pm.drafter.devices), set(pm.target.devices)
    assert len(d) == 2 and len(t) == 4 and not (d & t)
    # role policies: submesh axes become the role's tensor axes
    assert pm.drafter.policy.model == "dx"
    assert pm.target.policy.model == "tx"


@DEV8
@pytest.mark.parametrize("overlap", [False, True])
def test_distinct_submesh_tokens_match_golden(pair, overlap):
    """The acceptance check: draft on the drafter mesh, verify on the target
    mesh, tokens identical to the replicated goldens — with and without
    overlapped dispatch."""
    mt, md, pt, pd, cfg = pair
    ps = jnp.asarray(_prompts(cfg, 2, 6, seed=0))
    pm = PL.lower(dataclasses.replace(HETERO, overlap=overlap))
    eng = SpecEngine(mt, md, EngineConfig(gamma=GAMMA, greedy=True,
                                          use_cache=True, strategy="modular"),
                     placement=pm)
    toks, stats = eng.generate(pt, pd, ps, MAX_NEW)
    np.testing.assert_array_equal(
        np.asarray(toks), np.asarray(GOLD["single_greedy_cached"]["tokens"]))
    assert stats["rounds"] == GOLD["single_greedy_cached"]["rounds"]


@DEV8
def test_draft_on_drafter_mesh_verify_on_target_mesh(pair):
    """Sharding inspection of one placed round: every draft-side array lives
    on the drafter submesh, every verify/commit-side array on the target
    submesh, and the handoff package crosses between them."""
    mt, md, pt, pd, cfg = pair
    pm = PL.lower(HETERO)
    eng = SpecEngine(mt, md, EngineConfig(gamma=GAMMA, greedy=True,
                                          use_cache=True, strategy="modular"),
                     placement=pm)
    ps = jnp.asarray(_prompts(cfg, 2, 6, seed=0))
    # two independently-prefilled placed states: the placed jits DONATE the
    # caches (and place_state may alias source shards), so the manual
    # draft-half probe below consumes its state's dcache
    state = rounds.place_state(eng.prefill(pt, pd, ps, 6 + MAX_NEW + GAMMA + 2),
                               pm, mt, md)
    state2 = rounds.place_state(eng.prefill(pt, pd, ps, 6 + MAX_NEW + GAMMA + 2),
                                pm, mt, md)
    d_set, t_set = set(pm.drafter.devices), set(pm.target.devices)

    def devs(tree):
        out = set()
        for leaf in jax.tree_util.tree_leaves(tree):
            out |= set(leaf.devices())
        return out

    assert devs(state.dcache) <= d_set
    assert devs(state.tcache) <= t_set

    pt_placed = pm.target.put_params(mt, pt)
    pd_placed = pm.drafter.put_params(md, pd)
    assert devs(pd_placed) <= d_set and devs(pt_placed) <= t_set

    placed = eng._placed_round
    # draft half runs on the drafter mesh (fed only the [B] last-token +
    # length handoff, never the [B, T] buffer)...
    t_last = rounds._gather_last(state.tokens, state.length)
    t_last_d, length_d = pm.to_drafter((t_last, state.length))
    drafts, q, dcache, _ = placed._draft_jit(
        pd_placed, t_last_d, length_d, state.dcache, None, None)
    assert devs(drafts) <= d_set and devs(dcache) <= d_set
    # ...the committed state of a full round lands on the target mesh, with
    # the rolled-back drafter cache back on the drafter mesh
    new = placed(pt_placed, pd_placed, state2)
    assert devs(new.tokens) <= t_set and devs(new.tcache) <= t_set
    assert devs(new.dcache) <= d_set
    assert int(new.length) > int(state2.length)


@DEV8
def test_per_row_placed_matches_golden(pair):
    mt, md, pt, pd, cfg = pair
    ps = jnp.asarray(_prompts(cfg, 4, 6, seed=1))
    eng = BatchedSpecEngine(mt, md, BatchedEngineConfig(gamma=GAMMA),
                            placement=PL.lower(HETERO))
    toks, lengths, _ = eng.generate(pt, pd, ps, MAX_NEW)
    for b in range(4):
        np.testing.assert_array_equal(
            np.asarray(toks)[b, :6 + MAX_NEW],
            np.asarray(GOLD["per_row_greedy_ring"]["tokens"][b]))


@DEV8
def test_per_row_sampled_placed_equals_unplaced(pair):
    """PRNG-key handoff across submeshes: placed stochastic rounds are
    bit-identical to the unplaced engine at the same seed."""
    mt, _, pt, _, cfg = pair
    ps = jnp.asarray(_prompts(cfg, 3, 6, seed=5))
    mk = lambda pl: BatchedSpecEngine(
        mt, mt, BatchedEngineConfig(gamma=GAMMA, greedy=False,
                                    temperature=1.0), placement=pl)
    t0, l0, _ = mk(None).generate(pt, pt, ps, MAX_NEW,
                                  key=jax.random.PRNGKey(9))
    t1, l1, _ = mk(PL.lower(HETERO)).generate(pt, pt, ps, MAX_NEW,
                                              key=jax.random.PRNGKey(9))
    np.testing.assert_array_equal(np.asarray(t0), np.asarray(t1))
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))


@DEV8
def test_continuous_placed_matches_golden(pair):
    """Placed continuous serving (split per-role prefill, placed bootstrap +
    slot refill) stays token-identical to the goldens."""
    from repro.launch.continuous import ContinuousSpecServer, StreamRequest
    mt, md, pt, pd, cfg = pair
    pr = _prompts(cfg, 5, 6, seed=2)
    srv = ContinuousSpecServer(mt, md, pt, pd, batch=2, prompt_len=6,
                               max_new=MAX_NEW, gamma=GAMMA,
                               placement=PL.lower(HETERO))
    for i in range(5):
        srv.submit(StreamRequest(i, pr[i]))
    done = {r.rid: np.asarray(r.tokens) for r in srv.run()}
    for i in range(5):
        np.testing.assert_array_equal(
            done[i], np.asarray(GOLD["continuous_greedy_ring"]["tokens"][i]))


@DEV8
def test_paged_placed_matches_golden(pair):
    from repro.serving import PagedSpecServer, SchedulerConfig, ServeRequest
    mt, md, pt, pd, cfg = pair
    ragged = [(5, 6), (9, 10), (6, 4), (11, 8)]
    rng = np.random.default_rng(3)
    reqs = [ServeRequest(i, rng.integers(0, cfg.vocab_size, P)
                         .astype(np.int32), new)
            for i, (P, new) in enumerate(ragged)]
    srv = PagedSpecServer(mt, md, pt, pd, SchedulerConfig(max_batch=2),
                          gamma=GAMMA, placement=PL.lower(HETERO))
    for r in reqs:
        srv.submit(r)
    done = {r.rid: np.asarray(r.tokens) for r in srv.run()}
    for i in range(len(ragged)):
        np.testing.assert_array_equal(
            done[i], np.asarray(GOLD["paged_greedy"]["tokens"][i]))


@DEV8
def test_session_threads_placement_to_backend(pair):
    mt, md, pt, pd, cfg = pair
    plan = dataclasses.replace(
        Planner(DeploymentSpec(batch_size=1, prompt_lens=(6,), max_new=8,
                               cost_coefficient=0.2,
                               adaptive_gamma=False)).plan(),
        placement=dataclasses.replace(HETERO, overlap=True))
    sess = Session(mt, md, pt, pd, plan)
    assert sess.placement.heterogeneous and sess.placement.overlap
    toks, stats = sess.generate(jnp.asarray(_prompts(cfg, 1, 6, seed=2)))
    eng = sess.backend._engine(plan.gamma.gamma)
    assert eng.placement is not None
    assert "drafter@d2" in sess.describe()
