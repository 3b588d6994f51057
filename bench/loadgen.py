"""Traffic: one general generator that reads a mix's parameters, and the
clients that replay it against the async front end.

Generation is pure: a mix file (``bench/traffic/<mix>.json``) states the
loop (open or closed), the arrival process and rate, and the length
distributions. Every seed gets the same schedule: (prompt, output) length
pairs taken at ``quantiles`` evenly spaced quantiles of the distributions
and inter-arrival gaps at evenly spaced quantiles of the exponential, in
one order fixed by the mix. The seed draws the prompt tokens (and the
weights): two seeds do the same work at the same times, so a tail read
from one run moves with the system, not with the luck of the draw. (Copied in spirit from ``serving/frontend/traffic.py``, whose
uniform lengths and submit-time timing a benchmark cannot use.)

Replay times every request from when it was DUE, not from when the client
got round to sending it, so a stall that delays the generator is charged
to the requests it delays; how late the generator ran is reported.
"""
from __future__ import annotations

import asyncio
import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclass(frozen=True)
class Request:
    idx: int
    due_s: Optional[float]      # offset from the window's start; None in a
    prompt: np.ndarray          # closed loop, where a client sends its next
    max_new: int                # request when its last one completes


@dataclass
class Record:
    """Client-side timings of one request (host clock, seconds)."""
    idx: int
    prompt_len: int
    max_new: int
    due: float = 0.0
    sent: float = 0.0
    first: Optional[float] = None
    last: Optional[float] = None
    n_tokens: int = 0
    tokens: List[int] = field(default_factory=list)


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the evenly spaced quantiles (i + 0.5) / n of the
    spec's distribution, clipped to [min, max], in ascending order."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        vals = lo + q * (hi + 1 - lo)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(vals), lo, hi).astype(np.int64)


def quantile_gaps(n: int, rate_rps: float) -> np.ndarray:
    """Exponential inter-arrival gaps at the evenly spaced quantiles, scaled
    so that they sum to exactly ``n / rate_rps``."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    return gaps * (n / rate_rps) / gaps.sum()


def n_requests(mix: dict, seconds: float) -> int:
    if mix["loop"] == "open":
        return max(1, int(round(mix["rate_rps"] * seconds)))
    return int(mix["requests"])


def make_trace(mix: dict, seed: int, seconds: float,
               vocab: int) -> List[Request]:
    """The requests of one run. Open loop: ``rate_rps * seconds`` requests
    due over the window, the first at 0. Closed loop: ``requests`` requests
    that the clients take in order."""
    n = n_requests(mix, seconds)
    rng = np.random.default_rng([int(seed) & (2 ** 64 - 1), 1])
    fixed = np.random.default_rng(0)        # the schedule: the same for
                                            # every seed
    # the trace repeats one block of ``quantiles`` (prompt, output) pairs,
    # each copy in its own order: every prefix of whole blocks holds the
    # same lengths on every seed (a closed loop uses only a prefix), and
    # the server meets the same few distinct lengths whatever the rate
    block = int(mix["quantiles"])

    # the (prompt, output) pairs are fixed for the mix: one pairing of the
    # quantiles, the same on every seed; the seed orders them
    prompt_q = quantile_lengths(mix["prompt"], block)
    output_q = quantile_lengths(mix["output"], block)[fixed.permutation(block)]
    full, part = divmod(n, block)
    # a last, partial block takes evenly spaced pairs
    tail = np.unique(np.linspace(0, block - 1, part).round().astype(int))
    order = np.concatenate([fixed.permutation(block) for _ in range(full)]
                           + [fixed.permutation(tail)]).astype(int)
    prompts, outputs = prompt_q[order], output_q[order]
    if mix["loop"] == "open":
        gaps = fixed.permutation(quantile_gaps(n, mix["rate_rps"]))
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    else:
        due = [None] * n
    return [Request(i, None if due[i] is None else float(due[i]),
                    rng.integers(0, vocab, int(prompts[i])).astype(np.int32),
                    int(outputs[i]))
            for i in range(n)]


def trace_bounds(mix: dict) -> tuple:
    """(longest prompt, longest output) any seed of the mix can send."""
    return int(mix["prompt"]["max"]), int(mix["output"]["max"])


async def _consume(front, req: Request, rec: Record, now, keep: bool):
    stream = await front.submit(req.prompt, req.max_new, rid=req.idx)
    async for tok in stream:
        t = now()
        if rec.first is None:
            rec.first = t
        rec.last = t
        rec.n_tokens += 1
        if keep:
            rec.tokens.append(int(tok))


async def open_loop(front, trace: List[Request], t0: float, t_end: float,
                    now, keep_tokens: bool = True) -> List[Record]:
    """Send each request at ``t0 + due_s`` whether or not earlier ones have
    finished; stop sending at ``t_end``. Returns one record per request
    sent; they fill in while the streams run."""
    records: List[Record] = []
    tasks = []
    for req in trace:
        due = t0 + req.due_s
        if due >= t_end:
            break
        delay = due - now()
        if delay > 0:
            await asyncio.sleep(delay)
        rec = Record(req.idx, len(req.prompt), req.max_new, due=due,
                     sent=now())
        records.append(rec)
        tasks.append(asyncio.ensure_future(
            _consume(front, req, rec, now, keep_tokens)))
    await _sleep_until(t_end, now)
    return records, tasks


async def closed_loop(front, trace: List[Request], clients: int, t0: float,
                      t_end: float, now, keep_tokens: bool = True):
    """``clients`` callers, each sending its next request the moment its
    last one completes, until ``t_end``. A request is due when sent."""
    records: List[Record] = []
    it = iter(trace)
    tasks = []

    async def client():
        while now() < t_end:
            req = next(it, None)
            if req is None:
                raise RuntimeError("closed-loop trace ran out; raise the "
                                   "mix's 'requests'")
            t = now()
            rec = Record(req.idx, len(req.prompt), req.max_new, due=t,
                         sent=t)
            records.append(rec)
            await _consume(front, req, rec, now, keep_tokens)

    tasks = [asyncio.ensure_future(client()) for _ in range(clients)]
    await _sleep_until(t_end, now)
    return records, tasks


async def _sleep_until(t_end: float, now):
    delay = t_end - now()
    if delay > 0:
        await asyncio.sleep(delay)


def percentile(values, p: float) -> Optional[float]:
    """Linear-interpolated percentile (numpy's default), None when empty."""
    vals = [v for v in values if v is not None and not math.isnan(v)]
    return float(np.percentile(vals, p)) if vals else None
