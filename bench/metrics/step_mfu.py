"""Model FLOPs of the window over what the cell's chips could do in it at
their bf16 peak. Model FLOPs: 2 x the target's matmul parameters per prompt
token prefilled and per output token committed, plus attention over each
token's context in each layer that holds K/V, capped at the layer's window,
plus the family's FLOPs outside both (``bench.counts.token_flops``); rejected
drafts and the drafter's own work do not count. Prompt tokens count for the
requests whose first token came in the window; output tokens for those
received in it."""
from bench import counts


def read(run):
    w, cfg = run.window, run.cfg
    flops = 0.0
    for r in w.records:
        first, _, n, _ = w.snap[r.idx]
        if first is None:
            continue
        flops += counts.token_flops(cfg, 0, r.prompt_len - 1)   # prefill
        flops += counts.token_flops(cfg, r.prompt_len - 1, n)  # committed
    peak = run.peaks["bf16_flops_per_s"] * run.chips
    return 100.0 * flops / ((w.t_end - w.t0) * peak) if flops else None
