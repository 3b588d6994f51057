"""Put the device's idle time down to the program's host spans.

The program writes its host work as ``jax.profiler`` annotations
(``repro.obs.trace``): each ``server.step`` of the paged server with its
phases, and the async front end's drain and fan-out. A traced run's
profile holds them on the ``/host:CPU`` plane, on the clock of the
device's ops. For each device, every idle interval between its first and
last op is cut at the spans' edges, and each piece goes to the bucket of
the spans open at that instant, on any thread:

  frontend  no ``server.step`` open: event loop, fan-out, executor hop, drain
  admit     in ``server.step``, under none of the three below: admission,
            table uploads, the step's own code
  dispatch  under ``step.prefill`` or ``step.round``
  harvest   under ``step.harvest``

The four add up to the idle time between the first and the last op.

A span open when the capture started, or still open when it stopped, is
not in the profile. A recorded span whose parent is missing (a
``round.sync`` whose ``step.round`` began before the capture) stands for
that parent from the capture's edge: up to its own end when it comes
before every recorded parent, from its own start when it comes after.

    python bench/host_spans.py <profile dir or .xplane.pb>

prints the split, the clock check of ``round.sync`` (``sync_lags``) and
the longest gaps with their buckets as one JSON line. Only ``jax.profiler`` is used to read the file.
"""
from __future__ import annotations

import bisect
import math
import os
import sys
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):      # run as a script: bench/ is importable
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace_reduce  # noqa: E402

HOST_PLANE = "/host:CPU"
BUCKETS = ("frontend", "admit", "dispatch", "harvest")
#: each program span below ``server.step``, with the span it opens in
PARENT = {
    "step.admit": "server.step", "step.prefill": "server.step",
    "step.tables": "server.step", "step.round": "server.step",
    "step.harvest": "server.step",
    "prefill_chunk": "step.prefill",
    "round.dispatch": "step.round", "round.sync": "step.round",
    "harvest.pull": "step.harvest",
}
NAMES = frozenset(PARENT) | {"server.step", "frontend.drain",
                             "frontend.fanout"}

Span = Tuple[str, float, float]    # name, start ns, end ns


def load(path_or_data) -> List[Span]:
    """The program's spans on the host plane of a profile (an
    ``.xplane.pb`` path or a ``ProfileData``), sorted by start."""
    from jax.profiler import ProfileData
    data = (ProfileData.from_file(path_or_data)
            if isinstance(path_or_data, (str, os.PathLike)) else path_or_data)
    out = []
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events if ev.name in NAMES]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def restore_parents(spans: List[Span]) -> List[Span]:
    """``spans`` plus the parents the capture's edges cut off, deepest
    level first, so a restored ``step.round`` restores its ``server.step``."""
    spans = list(spans)
    for level in (("prefill_chunk", "round.dispatch", "round.sync",
                   "harvest.pull"),
                  ("step.admit", "step.prefill", "step.tables",
                   "step.round", "step.harvest")):
        for parent in sorted({PARENT[n] for n in level}):
            have = [s for s in spans if s[0] == parent]
            first = min((s[1] for s in have), default=math.inf)
            last = max((s[2] for s in have), default=-math.inf)
            kids = [s for s in spans if s[0] in level
                    and PARENT[s[0]] == parent
                    and not any(p[1] <= s[1] and s[2] <= p[2]
                                for p in have)]
            head = [s[2] for s in kids if s[2] <= first]
            tail = [s[1] for s in kids if have and s[1] >= last]
            if head:
                spans.append((parent, -math.inf, max(head)))
            if tail:
                spans.append((parent, min(tail), math.inf))
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def _bucket(open_: Dict[str, int]) -> str:
    if not open_.get("server.step"):
        return "frontend"
    if open_.get("step.harvest"):
        return "harvest"
    if open_.get("step.prefill") or open_.get("step.round"):
        return "dispatch"
    return "admit"


def segments(spans: List[Span]) -> Tuple[List[float], List[str]]:
    """Edges ``t`` and buckets ``b``: from ``t[i]`` to ``t[i + 1]`` idle
    time goes to ``b[i]``; before ``t[0]`` to ``frontend``."""
    moves: Dict[float, Dict[str, int]] = {}
    for name, s, e in spans:
        for t, d in ((s, 1), (e, -1)):
            at = moves.setdefault(t, {})
            at[name] = at.get(name, 0) + d
    open_: Dict[str, int] = {}
    edges, buckets = [], []
    for t in sorted(moves):
        for name, d in moves[t].items():
            open_[name] = open_.get(name, 0) + d
        edges.append(t)
        buckets.append(_bucket(open_))
    return edges, buckets


def split_interval(edges, buckets, a: float, b: float) -> Dict[str, float]:
    """Length of ``[a, b)`` in each bucket."""
    out: Dict[str, float] = {}
    i = bisect.bisect_right(edges, a) - 1
    t = a
    while t < b:
        nxt = edges[i + 1] if i + 1 < len(edges) else math.inf
        end = min(b, nxt)
        key = buckets[i] if i >= 0 else "frontend"
        out[key] = out.get(key, 0.0) + (end - t)
        t, i = end, i + 1
    return out


def idle_by_bucket(ops, spans: List[Span]) -> Dict[str, float]:
    """Idle nanoseconds per bucket between the first and the last of the
    ``(name, start, end)`` device ops."""
    edges, buckets = segments(spans)
    out = dict.fromkeys(BUCKETS, 0.0)
    busy = trace_reduce.union((s, e) for _, s, e in ops)
    for (_, a), (b, _) in zip(busy, busy[1:]):
        for key, v in split_interval(edges, buckets, a, b).items():
            out[key] += v
    return out


def split(devs, spans: List[Span]) -> Optional[Dict[str, float]]:
    """Idle seconds per bucket, averaged over the devices; None when the
    spans hold no ``server.step``."""
    if not devs or not any(s[0] == "server.step" for s in spans):
        return None
    spans = restore_parents(spans)
    per_dev = [idle_by_bucket(d.ops, spans) for d in devs]
    return {k: sum(p[k] for p in per_dev) * 1e-9 / len(per_dev)
            for k in BUCKETS}


def longest_gaps(dev, spans: List[Span], top: int = 10) -> List[list]:
    """The ``top`` longest idle gaps of a device: seconds after its first
    op, length in seconds, the op that ends it, and the gap's seconds per
    bucket."""
    edges, buckets = segments(restore_parents(spans))
    first = trace_reduce.span_ns(dev)[0]
    starts: Dict[float, str] = {}
    for name, s, _ in dev.ops:
        starts.setdefault(s, trace_reduce.short_name(name))
    busy = trace_reduce.union((s, e) for _, s, e in dev.ops)
    gaps = sorted(((a, b) for (_, a), (b, _) in zip(busy, busy[1:])),
                  key=lambda g: g[0] - g[1])[:top]
    return [[(a - first) * 1e-9, (b - a) * 1e-9, starts.get(b, "?"),
             {k: v * 1e-9 for k, v in
              split_interval(edges, buckets, a, b).items()}]
            for a, b in gaps]


def sync_lags(dev, spans: List[Span]) -> List[float]:
    """Per ``round.sync`` span: its end less the end of the last device op
    that starts before that end, in seconds. A host span on the device's
    clock reads each lag between 0 and the sync's own return cost."""
    ops = sorted(dev.ops, key=lambda o: o[1])
    starts = [o[1] for o in ops]
    out = []
    for name, _, e in spans:
        i = bisect.bisect_left(starts, e) - 1
        if name == "round.sync" and i >= 0:
            out.append((e - ops[i][2]) * 1e-9)
    return out


_SPLITS: Dict[tuple, Optional[Dict[str, float]]] = {}


def share(run, bucket: str) -> Optional[float]:
    """Idle time in ``bucket``, % of the traced window ``idle_share``
    divides by; None without a trace or without the program's spans."""
    from bench import harness
    w = run.window
    if w.trace is None:
        return None
    path = trace_reduce.find_xplane(str(harness.CACHE / "trace"))
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _SPLITS:
        _SPLITS[key] = split(w.trace["devices"], load(path))
    got = _SPLITS[key]
    if got is None:
        return None
    return 100.0 * got[bucket] / w.trace["summary"]["window_s"]


def main(argv=None) -> int:
    import json
    import statistics
    arg = (argv or sys.argv[1:])[0]
    path = arg if arg.endswith(".xplane.pb") else trace_reduce.find_xplane(arg)
    if path is None:
        print(f"host_spans: no profile under {arg}", file=sys.stderr)
        return 1
    devs, spans = trace_reduce.load(path), load(path)
    lags = sync_lags(devs[0], spans) if devs else []
    ok = [0.0 <= x <= 1e-3 for x in lags]
    print(json.dumps({
        "idle_s": split(devs, spans),
        "round_sync": {"n": len(lags),
                       "share_within_0_1ms": sum(ok) / len(ok) if ok else None,
                       "lag_median_s": statistics.median(lags) if lags
                       else None,
                       "lag_min_s": min(lags, default=None),
                       "lag_max_s": max(lags, default=None)},
        "spans": {n: sum(1 for s in spans if s[0] == n) for n in sorted(NAMES)},
        "longest_gaps": longest_gaps(devs[0], spans) if devs else [],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
