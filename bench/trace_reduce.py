"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

Reads the device planes (``/device:TPU:<n>``) and, on each, the ``XLA Ops``
line: one event per operation that ran on the device. From those:

  * busy time: the union of the operations' intervals, per device;
  * the traced window: ``[first op start, last op end]`` widened to the
    host interval the caller traced, when given;
  * time per operation name, summed, the ten largest;
  * the longest idle gaps between operations, each named by the operation
    that ends it (the host span that caused a gap needs the program's own
    annotations, which it does not yet write);
  * the events of a kernel (a Pallas kernel's ``name``), with the result
    shape their HLO text declares.

A TPU's ``XLA Ops`` line nests a loop's body inside its ``while`` op, so
time per operation counts only the ops that contain no other.

Only ``jax.profiler`` is used to read the file.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"


@dataclass
class DeviceTrace:
    """Intervals of one device, in nanoseconds on the trace's clock."""
    name: str
    ops: List[Tuple[str, float, float]] = field(default_factory=list)


def find_xplane(log_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


def load(path_or_data) -> List[DeviceTrace]:
    """Device traces from an ``.xplane.pb`` path or a ``ProfileData``."""
    from jax.profiler import ProfileData
    data = (ProfileData.from_file(path_or_data)
            if isinstance(path_or_data, (str, os.PathLike)) else path_or_data)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        dev = DeviceTrace(plane.name)
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            dev.ops += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events if ev.duration_ns > 0]
        if dev.ops:
            dev.ops.sort(key=lambda e: e[1])
            out.append(dev)
    return out


def leaves(dev: DeviceTrace) -> List[Tuple[str, float, float]]:
    """The ops that contain no other op: on a TPU the ``XLA Ops`` line
    nests a loop's body inside the ``while`` op that runs it."""
    out = []
    ops = sorted(dev.ops, key=lambda e: (e[1], -e[2]))
    for i, (name, s, e) in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt[1] >= e or nxt[2] > e:   # holds no op
            out.append((name, s, e))
    return out


def short_name(name: str) -> str:
    """``%copy.197 = bf16[430,8,128,64]{...} copy(...)`` ->
    ``copy.197 bf16[430,8,128,64] copy``; other names pass unchanged."""
    m = re.match(r"%?(\S+) = (\S+?)(\{[^ ]*\})? ([\w\-]+)\(", name)
    return f"{m.group(1)} {m.group(2)} {m.group(4)}" if m else name


def result_shape(name: str) -> Optional[Tuple[int, ...]]:
    """The result shape an HLO op's text declares, if it has one array."""
    m = re.match(r"%?\S+ = \w+\[([\d,]*)\]", name)
    return tuple(int(x) for x in m.group(1).split(",") if x) if m else None


def union(intervals) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted((s, e) for s, e in intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(dev: DeviceTrace) -> float:
    return sum(e - s for s, e in union((s, e) for _, s, e in dev.ops))


def span_ns(dev: DeviceTrace) -> Tuple[float, float]:
    return dev.ops[0][1], max(e for _, _, e in dev.ops)


def op_times(dev: DeviceTrace) -> Dict[str, float]:
    """Summed time per (short) name of the leaf ops."""
    out: Dict[str, float] = {}
    for name, s, e in leaves(dev):
        key = short_name(name)
        out[key] = out.get(key, 0.0) + (e - s)
    return out


def idle_gaps(dev: DeviceTrace, top: int = 10) -> List[Tuple[str, float]]:
    """The ``top`` longest gaps between busy intervals, in seconds, each
    named after the operation that ends it."""
    starts = {}
    for name, s, _ in dev.ops:
        starts.setdefault(s, short_name(name))
    merged = union((s, e) for _, s, e in dev.ops)
    gaps = [(f"before {starts.get(b[0], '?')}", (b[0] - a[1]) * 1e-9)
            for a, b in zip(merged, merged[1:])]
    return sorted(gaps, key=lambda g: -g[1])[:top]


def kernel_ops(dev: DeviceTrace, kernel: str) -> List[Tuple[str, float,
                                                            float]]:
    """The ops named after ``kernel`` (a Pallas kernel's ``name``): the
    HLO instruction itself, not an op that only reads its result."""
    pat = re.compile(r"%?" + re.escape(kernel) + r"(\.\d+)?( |$)")
    return [op for op in dev.ops if pat.match(op[0])]


def summarize(devs: List[DeviceTrace], window_s: Optional[float] = None,
              top: int = 10) -> dict:
    """Busy and window seconds averaged over the devices, the breakdown of
    the first device (ops and gaps as ``[name, seconds]``)."""
    if not devs:
        raise ValueError("the trace holds no device operations")
    busy = [busy_ns(d) * 1e-9 for d in devs]
    spans = [(span_ns(d)[1] - span_ns(d)[0]) * 1e-9 for d in devs]
    win = max(max(spans), window_s or 0.0)
    ops = sorted(op_times(devs[0]).items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": win,
        "device_ops": [[k, v * 1e-9] for k, v in ops],
        "idle_gaps": [[k, v] for k, v in idle_gaps(devs[0], top)],
    }
