"""Share of its roofline the paged-attention kernel reaches in the decode
rounds: the least time its work needs on the chip, over the device time of
its calls.

Device time: the ``paged_attention`` events of the traced window whose
result has one row per batch row (the decode rounds' calls; a prefill
chunk's calls have one row), summed and divided by the rounds traced, which
is the number of verify calls (``gamma + 1`` queries per row) over the
target's layers.

Least time of a round: for each live row of committed length ``n``, the
verify's ``gamma + 1`` queries over the target's layers and the ``gamma``
draft steps' single queries over the drafter's layers, each reading every
cached key and value once (``bench.counts.paged_attention_least``); the
larger of its operations at the bf16 peak and its bytes at the HBM peak.
Averaged over the speculative rounds that ended in the traced window. Each
row's committed length is rebuilt from the round events: its prompt, plus
``accepted + 1`` for every round it was live in.
"""
from bench import counts, trace_reduce


def _row_lengths(run):
    """(event, [committed length of each live row before the round])."""
    w = run.window
    length = {r.idx: r.prompt_len for r in w.records}
    out = []
    for ev in w.events:
        rows = [length.get(rid) for rid in ev.rids]
        out.append((ev, rows))
        for rid, acc in zip(ev.rids, ev.accepted):
            if rid in length:
                length[rid] += acc + 1
    return out


def read(run):
    w, cfg = run.window, run.cfg
    if w.trace is None or w.trace_span is None:
        return None
    gamma = cfg["self_draft"]["gamma"]
    k, L = cfg["self_draft"]["layers"], cfg["num_hidden_layers"]
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    verify_rows = -(-(gamma + 1) * group // 8) * 8    # the kernel pads to 8
    lo, hi = w.trace_span
    least = []
    for ev, rows in _row_lengths(run):
        if ev.gamma <= 0 or not lo <= ev.t_wall <= hi:
            continue
        flops = nbytes = 0.0
        for n in rows:
            if n is None:
                continue
            f, b = counts.paged_attention_least(cfg, gamma + 1, n, L)
            flops, nbytes = flops + f, nbytes + b
            for j in range(gamma):
                f, b = counts.paged_attention_least(cfg, 1, n + j, k)
                flops, nbytes = flops + f, nbytes + b
        least.append(max(flops / run.peaks["bf16_flops_per_s"],
                         nbytes / run.peaks["hbm_bytes_per_s"]))
    shares = []
    for dev in w.trace["devices"]:
        busy, verify_calls = 0.0, 0
        for name, s, e in trace_reduce.kernel_ops(dev, "paged_attention"):
            shape = trace_reduce.result_shape(name)
            if not shape or shape[0] != run.srv_batch:
                continue
            busy += (e - s) * 1e-9
            verify_calls += shape[2] == verify_rows
        if least and verify_calls:
            per_round = busy / (verify_calls / L)
            shares.append(sum(least) / len(least) / per_round)
    return 100.0 * sum(shares) / len(shares) if shares else None
