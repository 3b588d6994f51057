"""Paged serving driver: ragged variable-length speculative serving.

``python -m repro.launch.serve_paged --arch <id> --smoke`` serves a stream
of synthetic requests with MIXED prompt lengths and per-request decode
budgets — the traffic shape launch/serve.py cannot batch. The driver plans
with ``repro.api.Planner`` (which picks the paged block-pool layout for
ragged continuous traffic) and executes through the ``Session`` facade; the
scheduler's online cost-model gamma/AR decision is the plan's
runtime-feedback hook.
"""
from __future__ import annotations

import argparse
import dataclasses

import numpy as np

from repro.launch import cli_args
from repro.obs import clock
from repro.serving import ServeRequest


def synthetic_requests(rng, n, vocab, prompt_lens=(4, 18), max_news=(4, 24)):
    reqs = []
    for i in range(n):
        P = int(rng.integers(prompt_lens[0], prompt_lens[1] + 1))
        new = int(rng.integers(max_news[0], max_news[1] + 1))
        reqs.append(ServeRequest(i, rng.integers(0, vocab, P), new))
    return reqs


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    cli_args.add_model_args(ap)
    cli_args.add_traffic_args(ap)
    cli_args.add_spec_args(ap, gamma=None)
    cli_args.add_trace_args(ap)
    cli_args.add_robustness_args(ap)
    cli_args.add_prefill_args(ap)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--num-blocks", type=int, default=256)
    ap.add_argument("--max-blocks-per-row", type=int, default=16)
    return ap


def open_session(args, mt, md, pt, pd, reqs):
    """Plan the deployment for ``reqs`` and open its paged Session: the
    planner's decisions, then the CLI's block geometry, gamma pin,
    placement, prefill and overcommit flags on top."""
    from repro.api import DeploymentSpec, Planner, Session

    spec = DeploymentSpec(
        batch_size=args.batch,
        prompt_lens=tuple(r.prompt_len for r in reqs),
        max_new=tuple(r.max_new for r in reqs),
        streaming=True, alpha=args.alpha,
        cost_coefficient=args.cost_coefficient,
        adaptive_gamma=args.gamma is None)
    plan = Planner(spec).plan()
    # CLI block geometry trumps the planner's sizing; --gamma forces a fixed
    # draft length (adaptive_gamma=False above disables the online decision)
    plan = dataclasses.replace(
        plan, batching="continuous",       # paged even if the sample traffic
        cache=dataclasses.replace(plan.cache, kind="paged",  # looked uniform
                                  block_size=args.block_size,
                                  num_blocks=args.num_blocks,
                                  max_blocks_per_row=args.max_blocks_per_row),
        gamma=(plan.gamma if args.gamma is None else
               dataclasses.replace(plan.gamma, gamma=args.gamma)))
    plan = cli_args.apply_placement_arg(plan, args.placement)
    plan = cli_args.apply_prefill_args(plan, args)
    plan = cli_args.apply_overcommit_arg(plan, args.overcommit)
    sess = Session(mt, md, pt, pd, plan, max_batch=args.batch,
                   tracer=cli_args.make_tracer(args))
    if sess.backend_name != "paged":
        raise SystemExit(
            f"--arch {args.arch} (family {mt.family!r}) cannot take the paged "
            f"backend (KV-cache families only) — use repro.launch.serve")
    return sess


def main():
    args = make_parser().parse_args()
    mt, md, pt, pd, cfg_t = cli_args.build_pair(args.arch, args.smoke)
    rng = np.random.default_rng(0)
    reqs = synthetic_requests(rng, args.requests, cfg_t.vocab_size)
    sess = open_session(args, mt, md, pt, pd, reqs)
    if args.placement:
        print(sess.placement.describe())
    fault_plan = cli_args.make_fault_plan(args.faults_seed)
    if fault_plan is not None:
        sess.backend.server.inject_faults(fault_plan)
        print(f"chaos: {fault_plan.describe()}")

    t0 = clock.wall()
    done = sess.serve(reqs)
    dt = clock.wall() - t0
    srv = sess.backend.server
    s = srv.metrics.summary()
    total = s["total_generated_tokens"]
    alpha = s["alpha_hat"]
    print(f"paged-served {len(done)} ragged requests, {total} tokens in "
          f"{dt:.2f}s ({total / dt:.1f} tok/s aggregate, "
          f"mean latency {s['mean_latency_s'] * 1e3:.0f}ms, "
          f"gamma={srv.gamma} [{'forced' if args.gamma is not None else 'cost-model'}], "
          f"rounds={srv.total_rounds}, "
          f"alpha_hat={alpha if alpha is None else round(alpha, 2)})")
    print(f"acceptance histogram (n_accepted per round): "
          f"{s['accept_hist'][:(srv.gamma or 0) + 1].tolist()}")
    cli_args.report_prefill(srv)
    cli_args.report_robustness(srv)
    cli_args.report_telemetry(sess, args)


if __name__ == "__main__":
    cli_args.enable_compile_cache()
    main()
