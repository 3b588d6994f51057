"""Run one benchmark cell once on the accelerator this process holds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json``. With ``--trace 0``
the result reports the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a ``jax.profiler`` trace and the program's
counters. The last line of standard output is one JSON object; the numbers
that decided ``correct`` end standard error and the result line. Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
import time

T_PROC = time.time()          # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    bench = harness.benchmark()
    cell = harness.find_cell(bench, args.workload)
    cache = harness.enable_compile_cache()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU visible (JAX platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 1
    if len(devices) < int(cell["chips"]):
        print(f"run.py: {args.workload} needs {cell['chips']} chips, "
              f"{len(devices)} visible", file=sys.stderr)
        return 1
    harness.log(f"device: {devices[0].device_kind} x{len(devices)}; "
                f"compile cache {cache}")
    out = harness.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), devices, T_PROC, bench=bench)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
