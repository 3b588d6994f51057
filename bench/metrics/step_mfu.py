"""Model FLOPs of the window over what the cell's chips could do in it at
their bf16 peak. Model FLOPs: 2 x the target's matmul parameters per prompt
token prefilled and per output token committed, plus attention over each
token's context, capped at each layer's window (``bench.counts``); rejected
drafts and the drafter's own work do not count. Prompt tokens count for the
requests whose first token came in the window; output tokens for those
received in it."""
from bench import counts


def _tokens(cfg, start, n):
    """FLOPs of ``n`` tokens at positions start .. start + n - 1."""
    if n <= 0:
        return 0.0
    return 2.0 * counts.matmul_params(cfg) * n \
        + counts.causal_attention_flops(cfg, start, n)


def read(run):
    w, cfg = run.window, run.cfg
    flops = 0.0
    for r in w.records:
        first, _, n, _ = w.snap[r.idx]
        if first is None:
            continue
        flops += _tokens(cfg, 0, r.prompt_len - 1)        # prefill
        flops += _tokens(cfg, r.prompt_len - 1, n)        # committed
    peak = run.peaks["bf16_flops_per_s"] * run.chips
    return 100.0 * flops / ((w.t_end - w.t0) * peak) if flops else None
