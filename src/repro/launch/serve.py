"""Serving driver: batched speculative (or plain) decoding with request queue.

``python -m repro.launch.serve --arch <id> --smoke --speculative`` serves a
stream of synthetic requests on CPU with the reduced configs; on hardware the
same loop runs the full configs with the DSE-selected drafter placement.

The driver plans with ``repro.api.Planner`` and executes through the
``Session`` facade. (The legacy fixed-batch ``Server`` wrapper this module
once carried is gone — ``Session.serve`` runs the same grouping loop for
single/per-row plans; migration: docs/API.md.)
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import cli_args
from repro.obs import clock


def main():
    from repro.api import DeploymentSpec, Planner, Session

    ap = argparse.ArgumentParser()
    cli_args.add_model_args(ap)
    cli_args.add_traffic_args(ap)
    cli_args.add_spec_args(ap)
    cli_args.add_trace_args(ap)
    ap.add_argument("--speculative", action="store_true")
    ap.add_argument("--use-cache", action="store_true")
    ap.add_argument("--strategy", default="monolithic")
    ap.add_argument("--batch", type=int, default=8)
    args = ap.parse_args()

    mt, md, pt, pd, cfg_t = cli_args.build_pair(args.arch, args.smoke)
    rng = np.random.default_rng(0)

    spec = DeploymentSpec(batch_size=args.batch,
                          prompt_lens=(args.prompt_len,),
                          max_new=args.max_new, alpha=args.alpha,
                          cost_coefficient=args.cost_coefficient,
                          adaptive_gamma=False, use_cache=args.use_cache,
                          strategy=args.strategy)
    plan = Planner(spec).plan()
    # CLI overrides trump the planner: --gamma forces the draft length and
    # omitting --speculative forces the AR path (gamma 0); with neither,
    # the planner's Eq.-1 decision stands
    if not args.speculative:
        forced = 0
    elif args.gamma is not None:
        forced = args.gamma
    else:
        forced = plan.gamma.gamma
    plan = dataclasses.replace(
        plan, gamma=dataclasses.replace(plan.gamma, gamma=forced))
    plan = cli_args.apply_placement_arg(plan, args.placement)
    sess = Session(mt, md, pt, pd, plan, max_batch=args.batch,
                   tracer=cli_args.make_tracer(args))
    if args.placement:
        print(sess.placement.describe())

    if not args.speculative:
        # plain autoregressive serving baseline (one fixed batch)
        prompts = rng.integers(0, cfg_t.vocab_size,
                               (args.requests, args.prompt_len))
        t0 = clock.wall()
        jax.block_until_ready(
            sess.generate(jnp.asarray(prompts), args.max_new)[0])
        dt = clock.wall() - t0
        print(f"AR served {args.requests} x {args.max_new} tokens in {dt:.2f}s "
              f"({args.requests*args.max_new/dt:.1f} tok/s)")
        return

    reqs = [sess.request(rng.integers(0, cfg_t.vocab_size, args.prompt_len),
                         args.max_new, rid=i) for i in range(args.requests)]
    # serve wave-by-wave so per-request latency (submit -> completion) is real
    t0 = clock.wall()
    done, latencies = [], []
    for i in range(0, len(reqs), args.batch):
        out = sess.serve(reqs[i:i + args.batch])
        latencies += [clock.wall() - t0] * len(out)
        done += out
    dt = clock.wall() - t0
    total = sum(len(r.tokens) - r.prompt_len for r in done)
    alpha = sess.alpha_hat
    print(f"speculative served {len(done)} requests, {total} tokens in "
          f"{dt:.2f}s ({total / dt:.1f} tok/s aggregate, "
          f"mean latency {np.mean(latencies) * 1e3:.0f}ms, "
          f"alpha_hat={float('nan') if alpha is None else alpha:.2f}, "
          f"gamma={forced}, strategy={plan.strategy}, "
          f"cache={args.use_cache}, backend={sess.backend_name})")
    cli_args.report_telemetry(sess, args)


if __name__ == "__main__":
    cli_args.enable_compile_cache()
    main()
