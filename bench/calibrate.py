"""Readings that set a cell's output-check limit, on the chip.

    python bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1,2,3 [--control] [--fault <name>]

One process runs the cell once per seed, exactly as ``bench/run.py`` does
(same window, same sample of finished requests), and prints per seed the
widest gap by which a served token's logit lies below the float32
reference's best, and ``correct``. With ``--control`` it also puts the
control in the program's place (the reference with float8 e4m3 linear
layers, read at each position of the same prompts and served tokens) and
judges it by the same comparison: its ``correct`` must come out false.
With ``--fault`` the served path is broken underneath first
(``bench/faults.py``), and the run's own ``correct`` must come out false.
The limit goes above the largest program reading and below the smallest
control and fault readings (PERF.md gives them). Not run by the driver.
"""
import time

T_PROC = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import faults, harness
    harness.enable_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate.py: no TPU visible", file=sys.stderr)
        return 1
    cfg = harness.config(harness.find_cell(harness.benchmark(),
                                           args.workload)["config"])
    fault = None
    if args.fault:
        plant = faults.FAULTS[args.fault]
        fault = lambda served: plant(served, cfg)  # noqa: E731
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run(args.workload, seed, args.seconds, False, devices,
                          time.time(), after_build=fault,
                          control=args.control)
        ctrl = out.get("control")
        print(json.dumps({
            "seed": seed, "fault": args.fault, "correct": out["correct"],
            "alpha": out["alpha"],
            "widest_logit_gap": out["checks"]["widest_logit_gap"]["value"],
            "program_gaps": out.get("program_gaps"),
            "control_correct": ctrl and ctrl["correct"],
            "control_widest_logit_gap": ctrl and max(ctrl["gaps"],
                                                     default=None),
            "control_gaps": ctrl and ctrl["gaps"],
            "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
