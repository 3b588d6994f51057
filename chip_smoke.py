"""Chip smoke run: the paged speculative server at Llama 3.2 3B / 1B widths.

One process drives the served path once, the way ``repro.launch.serve_paged``
does it (``cli_args.build_pair`` -> ``Planner`` -> ``Session`` ->
``PagedSpecServer``): the Llama 3.2 3B target and its registered Llama 3.2
1B drafter at their published widths in bf16, with seeded random weights,
serve a few ragged requests at a pinned draft length. It then checks what
came out:

  * speculative rounds ran, and nothing degraded, failed or expired;
  * every request got its full ``max_new`` tokens, and the block pool
    audits clean afterwards;
  * the compiled round program holds the Pallas paged-attention and
    fused-verify kernels (``tpu_custom_call``);
  * the greedy tokens equal ``core.engine.autoregressive_generate`` on the
    same prompts. Where two token streams part, the target's logits for the
    two candidates at that position must be its top two and lie within two
    bf16 steps of each other: each program computes both logits from bf16
    activations, each value may sit a step from the other program's, so
    another order of bf16 reductions can flip such a near-tie. Anything
    else fails.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # only the placed path: drafter on two
                                     # chips, target on two, against the
                                     # unplaced one-chip run of the same
                                     # requests

The times it prints are readings of one smoke run, not device metrics.
Without a TPU it exits non-zero and prints no result line. The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH = "llama3.2-3b"
GAMMA = 4
BATCH = 4
N_REQUESTS = 8
PROMPT_LENS = (4, 18)
MAX_NEWS = (16, 24)
KERNELS = ("paged_attention", "verify_argmax")  # pallas_call names
NEAR_TIE_STEPS = 2   # one bf16 step per candidate logit


def log(msg: str = "") -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spends in backend compilation (a persistent-cache load
    included), and the number of persistent-cache hits, since start."""
    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def describe(cfg) -> str:
    return (f"{cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model} "
            f"heads={cfg.num_heads} kv_heads={cfg.num_kv_heads} "
            f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
            f"dtype={cfg.dtype} param_dtype={cfg.param_dtype} "
            f"params={cfg.param_count() / 1e9:.3f}B")


def make_requests(seed: int, vocab: int):
    import numpy as np
    from repro.launch.serve_paged import synthetic_requests
    return synthetic_requests(np.random.default_rng(seed), N_REQUESTS, vocab,
                              prompt_lens=PROMPT_LENS, max_news=MAX_NEWS)


def serve(label, pair, reqs, clock, placement=None):
    """Open the paged Session as ``serve_paged`` does and serve ``reqs``;
    returns (session, server, failures)."""
    from repro.launch import serve_paged
    from repro.obs import clock as wall

    mt, md, pt, pd, _ = pair
    argv = ["--arch", ARCH, "--gamma", str(GAMMA), "--batch", str(BATCH),
            "--requests", str(len(reqs))]
    if placement:
        argv += ["--placement", placement]
    args = serve_paged.make_parser().parse_args(argv)
    sess = serve_paged.open_session(args, mt, md, pt, pd, reqs)
    log(f"[{label}] {sess.placement.describe()}")
    c0, t0 = clock.seconds, wall.wall()
    done = sess.serve(reqs)
    dt = wall.wall() - t0
    srv = sess.backend.server
    s = srv.metrics.summary()
    log(f"[{label}] served {len(done)} requests, "
        f"{s['total_generated_tokens']} tokens, in {dt:.3f} s wall "
        f"({clock.seconds - c0:.3f} s of it compiling); "
        f"rounds={srv.total_rounds} spec_rounds={srv.events.n_spec_rounds} "
        f"gamma={srv.gamma} alpha_hat={s['alpha_hat']}")

    failures = []
    if srv.events.n_spec_rounds == 0:
        failures.append("no speculative round ran")
    for key in ("degradations", "requests_failed", "requests_expired",
                "requests_cancelled", "requests_rejected"):
        if s[key]:
            failures.append(f"{key} = {s[key]}")
    if len(done) != len(reqs):
        failures.append(f"{len(done)} of {len(reqs)} requests completed")
    for r in done:
        got = 0 if r.tokens is None else len(r.tokens) - r.prompt_len
        if got != r.max_new:
            failures.append(f"request {r.rid}: {got} of {r.max_new} tokens")
    try:
        census = srv.alloc.audit()
        log(f"[{label}] block pool audit clean: {census}")
    except AssertionError as e:
        failures.append(f"block pool audit: {e}")
    return sess, srv, failures


def kernel_failures(srv):
    """The served round program, compiled (the persistent cache holds it),
    must call the Pallas paged-attention and fused-verify kernels."""
    eng = srv._engine(GAMMA)
    text = eng._round_jit.lower(srv.params_t, srv.params_d,
                                srv._state).compile().as_text()
    n_calls = text.count("tpu_custom_call")
    found = {k: k in text for k in KERNELS}
    log(f"compiled round program: {n_calls} tpu_custom_call sites; "
        + ", ".join(f"{k}={'present' if v else 'MISSING'}"
                    for k, v in found.items()))
    if n_calls == 0:
        return ["no tpu_custom_call in the compiled round program"]
    return [f"kernel {k} missing from the compiled round program"
            for k, v in found.items() if not v]


def reference_tokens(pair, reqs):
    """Greedy ``autoregressive_generate`` continuation of every prompt
    (one call per prompt length; greedy prefixes do not depend on the
    decode budget, so one budget serves every request of a group)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.engine import autoregressive_generate

    mt, _, pt, _, _ = pair
    n = max(r.max_new for r in reqs)
    groups = defaultdict(list)
    for r in reqs:
        groups[r.prompt_len].append(r)
    out = {}
    for P, group in sorted(groups.items()):
        prompts = jnp.asarray(np.stack([r.prompt for r in group]), jnp.int32)
        toks = np.asarray(autoregressive_generate(mt, pt, prompts, n))
        for r, t in zip(group, toks):
            out[r.rid] = t[:P + r.max_new]
    return out


def bf16_step(x: float) -> float:
    """Spacing of bf16 values (8 significant bits) at magnitude ``x``."""
    import math
    return 2.0 ** (math.floor(math.log2(max(abs(x), 1e-30))) - 7)



def greedy_failures(label, pair, reqs, want):
    """Compare each request's tokens with ``want[rid]``; a parting is a
    failure unless it is a bf16 near-tie of the target's top two logits."""
    import jax.numpy as jnp
    import numpy as np

    mt, _, pt, _, _ = pair
    failures, n_equal, n_tokens = [], 0, 0
    for r in sorted(reqs, key=lambda r: r.rid):
        got = np.asarray(r.tokens)
        ref = np.asarray(want[r.rid])
        n_tokens += r.max_new
        parts = np.nonzero(got != ref)[0]
        if not len(parts):
            n_equal += r.max_new
            continue
        p = int(parts[0])
        n_equal += p - r.prompt_len
        a, b = int(got[p]), int(ref[p])
        logits = np.asarray(
            mt.apply(pt, jnp.asarray(got[None, :p], jnp.int32))[0][0, -1],
            np.float64)
        top2 = [int(i) for i in np.argsort(logits)[-2:][::-1]]
        gap = abs(logits[a] - logits[b])
        step = bf16_step(max(abs(logits[a]), abs(logits[b])))
        tie = sorted(top2) == sorted((a, b)) and gap <= NEAR_TIE_STEPS * step
        log(f"[{label}] request {r.rid} parts at position {p} (generated "
            f"token {p - r.prompt_len}): {a} vs reference {b}; top-2 "
            f"{top2}, logit gap {gap:.6g} vs {NEAR_TIE_STEPS} bf16 steps "
            f"{NEAR_TIE_STEPS * step:.6g} -> "
            f"{'near-tie' if tie else 'MISMATCH'}")
        if not tie:
            failures.append(f"request {r.rid}: token {a} != {b} at position "
                            f"{p}, not a bf16 near-tie")
    log(f"[{label}] greedy match: {n_equal} of {n_tokens} generated tokens "
        f"equal the reference before any near-tie parting")
    return failures


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def one_chip(pair, seed, clock):
    reqs = make_requests(seed, pair[4].vocab_size)
    _, srv, failures = serve("one chip", pair, reqs, clock)
    failures += kernel_failures(srv)
    c0 = clock.seconds
    want = reference_tokens(pair, reqs)
    log(f"reference autoregressive_generate: "
        f"{len({r.prompt_len for r in reqs})} prompt lengths, "
        f"{clock.seconds - c0:.3f} s compiling")
    return failures + greedy_failures("one chip", pair, reqs, want)


def placed(pair, seed, clock):
    vocab = pair[4].vocab_size
    base_reqs = make_requests(seed, vocab)
    _, _, failures = serve("unplaced", pair, base_reqs, clock)
    reqs = make_requests(seed, vocab)
    sess, srv, more = serve("placed 2x2", pair, reqs, clock, placement="2x2")
    failures += more
    if not (sess.placement.heterogeneous and sess.placement.disjoint):
        failures.append("the 2x2 placement did not lower to disjoint meshes")
    want = {r.rid: r.tokens for r in base_reqs}
    return failures + greedy_failures("placed vs unplaced", pair, reqs, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip served path; 4: only the placed "
                         "path against the unplaced run")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the request stream (weights use the "
                         "entry path's own seeds)")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import cli_args
    cache_dir = cli_args.enable_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU visible (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"{len(devices)} visible", file=sys.stderr)
        return 1

    from repro.core import cost_model
    from repro.obs import clock as wall

    clock = CompileClock()
    log(f"device: platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devices)}")
    log(f"peaks: {cost_model.peaks_for(dev.device_kind)}")
    log(f"compile cache: {cache_dir}")
    t0 = wall.wall()
    pair = cli_args.build_pair(ARCH, smoke=False)
    log(f"target  {describe(pair[0].cfg)}")
    log(f"drafter {describe(pair[1].cfg)}")
    log(f"weights: seeded random, built in {wall.wall() - t0:.3f} s; "
        f"peak_bytes_in_use so far {peak_bytes(dev)}")

    phase = one_chip if args.chips == 1 else placed
    failures = phase(pair, args.seed, clock)
    log(f"compile seconds, whole run: {clock.seconds:.3f} "
        f"({clock.hits} persistent-cache hits); wall {wall.wall() - t0:.3f} s")
    for i, d in enumerate(devices[:args.chips]):
        log(f"peak_bytes_in_use[device {i}]: {peak_bytes(d)}")
    if failures:
        for f in failures:
            log(f"FAIL: {f}")
        return 1
    log("all checks passed")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
