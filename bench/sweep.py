"""Find the knee of an open-loop cell: the cell's mix at several rates.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 1,2,3,4

One process builds the cell's server once, warms it up once, then offers
the mix at each rate for ``--seconds``, cancels what is still in flight and
waits for the server to drain before the next rate. Each rate prints one
JSON line: what was offered, what completed, the tails, how many of the
batch's rows were live, the queue left behind and how late the generator
ran. The knee is the highest rate whose
completed tokens/s keeps up with the offered load and whose queue does not
grow through the window; a cell runs at about four fifths of it. This is a
tool for defining a cell, not a cell: the driver never runs it.
"""
import time

T_PROC = time.time()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


async def _sweep(h, served, cfg, mx, rates, seconds, seed):
    from bench import loadgen
    srv = served.srv
    front = served.sess.serve_async()
    async with front:
        await h.warm_requests(front, mx, cfg["vocab_size"],
                              cfg["self_draft"]["gamma"])
        for i, rate in enumerate(rates):
            mr = dict(mx, rate_rps=rate)
            trace = loadgen.make_trace(mr, seed + i, seconds,
                                       cfg["vocab_size"])
            base = (i + 1) * 1_000_000
            trace = [loadgen.Request(base + r.idx, r.due_s, r.prompt,
                                     r.max_new) for r in trace]
            h.warm_lengths(srv, [len(r.prompt) for r in trace],
                           [len(r.prompt) + r.max_new for r in trace])
            t0 = time.time()
            records, tasks, snap = await h.offer(front, mr, trace, t0,
                                                 seconds, False)
            events = [ev for ev in srv.events.events()
                      if t0 <= ev.t_wall <= t0 + seconds]
            win = h.Window(t0, t0 + seconds, records, snap, events, [], 0, 0,
                           None, None)
            e2e, n_ttft, _ = h.end_to_end(win, 1, 0.0)
            queued = len(srv.sched.queue)
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            for _ in range(200):           # drain before the next rate
                if not srv.sched.queue and all(s is None
                                               for s in srv._slots):
                    break
                await asyncio.sleep(0.05)
            offered = sum(r.max_new for r in trace if r.due_s < seconds)
            live = [ev.n_active for ev in events]
            print(json.dumps({
                "rate_rps": rate, "sent": len(records),
                "finished": sum(1 for s in snap.values() if s[3]),
                "offered_tokens_per_s": offered / seconds,
                "tokens_per_s": e2e["tokens_per_s_per_chip"],
                "ttft_p50_s": loadgen.percentile(
                    [s[0] - r.due for r in records
                     for s in [snap[r.idx]] if s[0] is not None], 50),
                "ttft_p95_s": e2e["ttft_p95_s"], "ttft_over": n_ttft,
                "tpot_p95_ms": e2e["tpot_p95_ms"],
                "rows_live_mean": sum(live) / len(live) if live else None,
                "rows_live_p95": loadgen.percentile(live, 95),
                "rows_live_max": max(live, default=None),
                "round_ms": 1e3 * sum(ev.t_round for ev in events)
                / len(events) if events else None,
                "queued_at_end": queued,
                "late_p95_s": loadgen.percentile(
                    [r.sent - r.due for r in records], 95)}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--mix-override", default="{}",
                    help="JSON merged over the mix's top-level keys, to "
                         "size a mix before it is written down")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness as h
    cell = h.find_cell(h.benchmark(), args.workload)
    h.enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep.py: no TPU visible", file=sys.stderr)
        return 1
    cfg = h.config(cell["config"])
    mx = dict(h.mix(cell["traffic"]), **json.loads(args.mix_override))
    if mx["loop"] != "open":
        print("sweep.py: only an open-loop cell has a knee to find",
              file=sys.stderr)
        return 2
    served = h.build(cfg, mx, args.seed)
    rates = [float(r) for r in args.rates.split(",")]
    asyncio.run(_sweep(h, served, cfg, mx, rates, args.seconds, args.seed))
    return 0


if __name__ == "__main__":
    sys.exit(main())
