"""One run of one cell: set-up, a measured window, and the output check.

A cell (``BENCHMARK.json`` ``workloads`` entry) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``). The configuration names its model family
(``family``, ``dense`` where absent: ``bench/families/<family>.py``, which
builds the program's ModelConfig and the seeded weights, and says which
layers hold K/V and what each holds and costs per token, which stack entries
belong to which model layer, and the FLOPs outside matmuls and attention:
the counts, the roofline reader and the planted faults read these from it)
and its plain reference (``reference``:
``bench/references/<reference>.py``); per-layer metrics are read by
``bench/metrics/<metric>.py``. Everything is found by name, so a new cell,
mix, metric or model family needs only new files and entries.

The window drives the program's served path as ``repro.launch.serve_paged``
builds it (Planner -> Session -> PagedSpecServer, gamma pinned) behind its
async front end (``AsyncSpecServer.submit``), with requests timed from the
client's side. The program's own tracer stays off: the traced run uses
``jax.profiler`` alone.
"""
from __future__ import annotations

import asyncio
import gc
import importlib
import importlib.util
import json
import math
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench.families import family

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".bench_cache"
WARM_RID = 1 << 30           # request ids of the warm-up requests
WARM_TIMEOUT_S = 120.0       # a warm-up that never ends is cut here, and
                             # the run goes on to show the fault
CHECK_REQUESTS = (4, 8)      # finished requests the reference re-reads:
CHECK_TOKENS = 512           # at least 4, then up to 8 until this many
                             # served tokens


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ lookup
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def mix(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def reference(cfg: dict):
    return importlib.import_module(f"bench.references.{cfg['reference']}")


def reader(metric: str) -> Callable:
    """``read(run)`` of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bench: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import os

    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Seconds JAX spends in backend compilation (persistent-cache loads
    included), the number of compiles, and persistent-cache hits."""
    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


# ------------------------------------------------------------------- build
@dataclass
class Served:
    sess: object
    srv: object
    params: list             # every params tree the server holds


def geometry(mx: dict) -> dict:
    """Block geometry that holds the mix's worst request in a row and the
    whole batch's worst case in the pool (plus one spare row, as the
    planner sizes it), so admission never waits on memory."""
    from bench.loadgen import trace_bounds
    gamma_max = 8                  # the plan's default speculative slack
    max_prompt, max_out = trace_bounds(mx)
    demand = max_prompt + max_out + gamma_max + 1
    bs = int(mx["block_size"])
    per_row = math.ceil(demand / bs)
    return {"block_size": bs, "max_blocks_per_row": per_row,
            "num_blocks": (int(mx["batch"]) + 1) * per_row + 1}


def build(cfg: dict, mx: dict, seed: int) -> Served:
    """Weights from the seed, then the served path as serve_paged opens it."""
    from repro.launch import serve_paged
    from repro.models.model import build_model
    from repro.serving import ServeRequest

    fam = family(cfg)
    k = cfg["self_draft"]["layers"]
    pt = fam.target_params(cfg, seed)
    pd = fam.drafter_params(cfg, pt)
    mt = build_model(fam.model_config(cfg))
    md = build_model(fam.model_config(cfg, layers=k,
                                      name=cfg["name"] + ".draft"))
    geo = geometry(mx)
    argv = ["--arch", cfg["name"], "--gamma", str(cfg["self_draft"]["gamma"]),
            "--batch", str(mx["batch"]),
            "--block-size", str(geo["block_size"]),
            "--num-blocks", str(geo["num_blocks"]),
            "--max-blocks-per-row", str(geo["max_blocks_per_row"]),
            "--prefill-chunk", str(mx["prefill_chunk"])]
    args = serve_paged.make_parser().parse_args(argv)
    max_prompt = int(mx["prompt"]["max"])
    sample = [ServeRequest(0, np.zeros(max_prompt, np.int32),
                           int(mx["output"]["max"]))]
    sess = serve_paged.open_session(args, mt, md, pt, pd, sample)
    return Served(sess, sess.backend.server, [pt, pd])


def warm_lengths(srv, prompt_lens, total_lens) -> None:
    """The server stages a prompt with ``tokens.at[row, :P].set`` and reads
    a finished row with ``tokens[row, :n]``: eager ops that compile once
    per length. Run them here for every length the traffic sends, on an
    array of the same shape, so that none compiles in the window."""
    import jax.numpy as jnp
    tok = jnp.zeros((srv.B, srv.T), jnp.int32)
    for P in sorted(set(int(p) for p in prompt_lens)):
        tok.at[0].set(0).at[0, :P].set(jnp.zeros((P,), jnp.int32))
    for n in sorted(set(int(n) for n in total_lens)):
        np.asarray(tok[0, :n])


async def warm_requests(front, mx: dict, vocab: int, gamma: int) -> None:
    """Two requests through the front end: compiles the chunk program, the
    speculative round and the host paths the window uses."""
    from bench.loadgen import Record, Request, _consume
    C = int(mx["prefill_chunk"])
    rng = np.random.default_rng(0)
    tasks = []
    for i in range(2):
        req = Request(WARM_RID + i, 0.0,
                      rng.integers(0, vocab, C + 8).astype(np.int32),
                      4 * (gamma + 1))
        rec = Record(req.idx, len(req.prompt), req.max_new)
        tasks.append(asyncio.ensure_future(
            _consume(front, req, rec, time.time, False)))
    done, pending = await asyncio.wait(tasks, timeout=WARM_TIMEOUT_S)
    for t in pending:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    if pending:
        log(f"warm-up: {len(pending)} request(s) unfinished after "
            f"{WARM_TIMEOUT_S} s")


def device_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def free(tree) -> None:
    import jax
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "delete") and not leaf.is_deleted():
            leaf.delete()


# ------------------------------------------------------------------ window
gc_pauses: List[float] = []      # seconds of each garbage collection
_gc_start = [0.0]


def _gc_clock(phase, info):
    if phase == "start":
        _gc_start[0] = time.perf_counter()
    else:
        gc_pauses.append(time.perf_counter() - _gc_start[0])


gc.callbacks.append(_gc_clock)


@dataclass
class Window:
    t0: float
    t_end: float
    records: list            # loadgen.Record, one per request sent
    snap: Dict[int, tuple]   # idx -> (first, last, n_tokens, done) at t_end
    events: list             # RoundEvents whose round ended in the window
    requests: list           # the server's RequestRecords of window requests
    failed: int
    compiles: int            # backend compiles inside the window
    trace: Optional[dict]    # reduced device trace (traced runs)
    trace_span: Optional[tuple]  # host interval traced


async def offer(front, mx: dict, trace_reqs, t0: float, seconds: float,
                keep_tokens: bool = True):
    """Offer the mix's requests from ``t0`` for ``seconds``, open or closed
    loop as the mix says. Returns the records of the requests sent, the
    client tasks (still streaming), and each request's state at the close:
    idx -> (first, last, n_tokens, finished in the window)."""
    from bench import loadgen
    t_end = t0 + seconds
    if mx["loop"] == "open":
        records, tasks = await loadgen.open_loop(
            front, trace_reqs, t0, t_end, time.time, keep_tokens)
    else:
        records, tasks = await loadgen.closed_loop(
            front, trace_reqs, int(mx["clients"]), t0, t_end, time.time,
            keep_tokens)
    snap = {r.idx: (r.first, r.last, r.n_tokens,
                    r.n_tokens >= r.max_new and r.last <= t_end)
            for r in records}
    return records, tasks, snap


async def drive(served: Served, cfg: dict, mx: dict, trace_reqs, seconds,
                traced: bool, clock: CompileClock, t_proc: float,
                profile_dir: Optional[Path]):
    """Warm up, then run the window. Returns (setup_s, Window)."""
    import jax

    from bench import loadgen
    srv = served.srv
    front = served.sess.serve_async()
    gamma = cfg["self_draft"]["gamma"]
    async with front:
        await warm_requests(front, mx, cfg["vocab_size"], gamma)
        warm_lengths(srv, [len(r.prompt) for r in trace_reqs],
                     [len(r.prompt) + r.max_new for r in trace_reqs])
        gc.collect()             # set-up's garbage is set-up's to collect
        t0 = time.time()
        setup_s = t0 - t_proc
        c0 = clock.compiles
        gc_pauses.clear()
        t_end = t0 + seconds
        span = None
        if traced:
            span = (t0 + seconds / 3, t0 + seconds / 3 + min(4.0, seconds / 3))

            async def profile():
                # start and stop block for a while: off the event loop, so
                # that the clients keep their schedule
                loop = asyncio.get_running_loop()
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                await loadgen._sleep_until(span[0], time.time)
                await loop.run_in_executor(None, lambda: jax.profiler
                                           .start_trace(str(profile_dir),
                                                        profiler_options=opts))
                t_a = time.time()
                await loadgen._sleep_until(span[1], time.time)
                t_b = time.time()
                await loop.run_in_executor(None, jax.profiler.stop_trace)
                return t_a, t_b
            prof = asyncio.ensure_future(profile())
        records, tasks, snap = await offer(front, mx, trace_reqs, t0,
                                           seconds)
        compiles = clock.compiles - c0
        pauses = list(gc_pauses)
        if traced:
            span = await prof
    await asyncio.gather(*tasks, return_exceptions=True)
    m = srv.metrics
    ids = {r.idx for r in records}
    reqs = [r for r in list(m.completed) + list(m.requests.values())
            + list(m.cancelled) + list(m.failed) if r.rid in ids]
    failed = (sum(1 for r in m.failed if r.rid in ids)
              + sum(1 for rid, _ in m.rejected if rid in ids)
              + sum(1 for r in m.expired if r.rid in ids))
    events = [ev for ev in srv.events.events() if t0 <= ev.t_wall <= t_end]
    trace = None
    if traced:
        from bench import trace_reduce
        path = trace_reduce.find_xplane(str(profile_dir))
        devs = trace_reduce.load(path) if path else []
        if devs:
            trace = {"summary": trace_reduce.summarize(
                devs, window_s=span[1] - span[0]), "devices": devs}
    log(f"garbage collections in the window: {len(pauses)}, "
        f"{sum(pauses):.3f} s, longest {max(pauses, default=0.0):.3f} s")
    return setup_s, Window(t0, t_end, records, snap, events, reqs, failed,
                           compiles, trace, span)


# ------------------------------------------------------------ the e2e side
def end_to_end(win: Window, chips: int, setup_s: float):
    """The end-to-end metrics every cell reports, from the client side, and
    how many requests the TTFT and TPOT tails are taken over."""
    from bench.loadgen import percentile
    seconds = win.t_end - win.t0
    tokens = sum(s[2] for s in win.snap.values())
    ttft = [first - r.due for r in win.records
            for first, *_ in [win.snap[r.idx]] if first is not None]
    tpot = [(last - first) / (n - 1) for r in win.records
            for first, last, n, done in [win.snap[r.idx]]
            if done and n > 1]
    return {
        "tokens_per_s_per_chip": tokens / seconds / chips,
        "ttft_p95_s": percentile(ttft, 95),
        "tpot_p95_ms": percentile([t * 1e3 for t in tpot], 95),
        "setup_s": setup_s,
    }, len(ttft), len(tpot)


# --------------------------------------------------------- the check side
def check_sample(win: Window, seed: int) -> list:
    """Finished requests the reference re-reads: the longest, then others
    drawn from the seed, at least CHECK_REQUESTS[0] of them, and more up
    to CHECK_REQUESTS[1] until CHECK_TOKENS served tokens are in."""
    done = [r for r in win.records if win.snap[r.idx][3]]
    if not done:
        return []
    done.sort(key=lambda r: (-(r.prompt_len + r.n_tokens), r.idx))
    rest = done[1:]
    order = np.random.default_rng([int(seed) & (2 ** 64 - 1), 3]) \
        .permutation(len(rest))
    out, served = [done[0]], done[0].n_tokens
    for i in order:
        if len(out) >= CHECK_REQUESTS[1] or (
                len(out) >= CHECK_REQUESTS[0] and served >= CHECK_TOKENS):
            break
        out.append(rest[i])
        served += rest[i].n_tokens
    return out


def logit_gaps(cfg: dict, seed: int, sample, prompts: dict, pad: int,
               control: bool = False) -> List[float]:
    """Widest gap per sampled request between the reference's best logit and
    the logit of the token served (or, for the control, of the token the
    float8 reference puts first). Rebuilds the weights from the seed."""
    import jax
    import jax.numpy as jnp

    ref = reference(cfg)
    params = family(cfg).target_params(cfg, seed)
    fn = jax.jit(lambda p, t, s: ref.served_gaps(cfg, p, t, s, control))
    out = []
    for rec in sample:
        P, n = rec.prompt_len, rec.n_tokens
        toks = np.zeros(pad, np.int32)
        toks[:P] = prompts[rec.idx]
        toks[P:P + n] = rec.tokens[:n]
        gaps = np.asarray(fn(params, jnp.asarray(toks), P))
        out.append(float(gaps[P - 1:P + n - 1].max()))
    free(params)
    return out


def compare(cfg: dict, gaps: List[float], sample, failed: int) -> dict:
    """Each number that decides ``correct``, beside its limit."""
    return {
        "widest_logit_gap": {"value": max(gaps) if gaps else None,
                             "limit": float(cfg["check"]["widest_logit_gap"])},
        "served_tokens_checked": {"value": sum(r.n_tokens for r in sample),
                                  "limit": 1},
        "failed_requests": {"value": failed, "limit": 0},
    }


def judge(checks: dict) -> bool:
    """The widest gap at most its limit, at least one served token read
    back, and no failed request."""
    gap, n, failed = (checks[k] for k in ("widest_logit_gap",
                                          "served_tokens_checked",
                                          "failed_requests"))
    return (gap["value"] is not None and gap["value"] <= gap["limit"]
            and n["value"] >= n["limit"] and failed["value"] <= failed["limit"])


def check_pad(mx: dict) -> int:
    from bench.loadgen import trace_bounds
    p, o = trace_bounds(mx)
    return int(-(-(p + o) // 128) * 128)


# ------------------------------------------------------------------ one run
def run(cell_name: str, seed: int, seconds: float, traced: bool,
        devices, t_proc: float, bench: Optional[dict] = None,
        cfg: Optional[dict] = None, mx: Optional[dict] = None,
        after_build: Optional[Callable] = None,
        control: bool = False) -> dict:
    """One run of one cell. Returns the result line's dict. ``cfg``/``mx``
    default to the cell's files; ``after_build(served)`` lets a test break
    the served path underneath. ``control`` also reads the float8 control
    on the same sample and judges it as the program (``bench/calibrate.py``;
    never in a benchmark run)."""
    import jax

    from bench import loadgen, peaks
    bench = bench or benchmark()
    cell = find_cell(bench, cell_name)
    cfg = cfg or config(cell["config"])
    mx = mx or mix(cell["traffic"])
    chips = int(cell["chips"])
    used = devices[:chips]
    kind = used[0].device_kind
    clock = CompileClock()
    trace_reqs = loadgen.make_trace(mx, seed, seconds, cfg["vocab_size"])
    prompts = {r.idx: r.prompt for r in trace_reqs}

    served = build(cfg, mx, seed)
    if after_build is not None:
        after_build(served)
    profile_dir = CACHE / "trace"
    if traced:
        shutil.rmtree(profile_dir, ignore_errors=True)
        profile_dir.mkdir(parents=True)
    setup_s, win = asyncio.run(drive(served, cfg, mx, trace_reqs, seconds,
                                     traced, clock, t_proc, profile_dir))
    peak = device_peak(used)
    events = win.events
    n_active = sum(ev.n_active for ev in events if ev.gamma > 0)
    alpha = (sum(sum(ev.accepted) for ev in events if ev.gamma > 0)
             / (cfg["self_draft"]["gamma"] * n_active) if n_active else None)
    summary = served.srv.metrics.summary()
    log(f"setup: {setup_s:.3f} s, of it {clock.seconds:.3f} s compiling "
        f"({clock.compiles} compiles, {clock.hits} persistent-cache hits)")
    log(f"window: {len(win.records)} requests sent, "
        f"{sum(1 for s in win.snap.values() if s[3])} finished, "
        f"{len(events)} rounds, alpha={alpha}, "
        f"degradations={summary.get('degradations')}, "
        f"compiles in window={win.compiles}, "
        f"generator late p95={loadgen.percentile([r.sent - r.due for r in win.records], 95)} s "
        f"max={max([r.sent - r.due for r in win.records], default=None)} s"
        f" at {max(win.records, key=lambda r: r.sent - r.due).due - win.t0 if win.records else None} s"
        f" into the window")
    e2e, n_ttft, n_tpot = end_to_end(win, chips, setup_s)
    log(f"end to end: {json.dumps(e2e)} (TTFT over {n_ttft} requests, "
        f"TPOT over {n_tpot})")
    ctx = RunContext(cfg=cfg, mix=mx, chips=chips,
                     peaks=peaks.peaks_for(kind) if traced else None,
                     window=win, srv_batch=served.srv.B)

    # free the program's state before the reference runs
    free(served.params)
    free(served.srv._state)
    served.srv._state = None
    del served
    gc.collect()

    sample = check_sample(win, seed)
    gaps = (logit_gaps(cfg, seed, sample, prompts, check_pad(mx))
            if sample else [])
    checks = compare(cfg, gaps, sample, win.failed)
    correct = judge(checks)

    metrics = {}
    device = {"platform": used[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": peak}
    breakdown = None
    if not traced:
        for m in metrics_for(bench, cell_name, "end_to_end"):
            v = e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in metrics_for(bench, cell_name, "per_layer"):
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if win.trace is not None:
            s = win.trace["summary"]
            device["busy_s"] = s["busy_s"]
            device["window_s"] = s["window_s"]
            breakdown = {"device_ops": s["device_ops"],
                         "idle_gaps": s["idle_gaps"]}
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    out = {"correct": bool(correct), "attempted": len(win.records),
           "failed": win.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["alpha"] = alpha
    if control:
        # the float8 control in the program's place, judged as the program
        cg = (logit_gaps(cfg, seed, sample, prompts, check_pad(mx),
                         control=True) if sample else [])
        out["control"] = {"correct": judge(compare(cfg, cg, sample,
                                                   win.failed)),
                          "gaps": cg}
        out["program_gaps"] = gaps
    out["checks"] = checks
    return out


@dataclass
class RunContext:
    """What a per-layer metric reader may read."""
    cfg: dict
    mix: dict
    chips: int
    peaks: dict
    window: Window
    srv_batch: int
