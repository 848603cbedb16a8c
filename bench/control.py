"""Readings of the control and of the planted faults, at a cell's own size.

    python bench/control.py --workload <cell> --fault control \
        --seeds 11 12 13 --seconds 2

Drives the benchmark's run of the cell (set-up, a short window at the
cell's own load, the comparison with the reference) with the timed path
broken as ``bench/faults.py`` says, once per seed, in one process, and
prints each run's compared numbers beside their limits.  The benchmark's
own runs never do this; ``--fault none`` reads the sound program the same
way.  Needs the chip (exits 1 without a TPU).
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import contextlib

    import jax

    from bench import faults, harness

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU found", file=sys.stderr)
        return 1
    spec = harness.load_spec(ROOT)
    cell = harness.find_cell(spec, args.workload)
    config = harness.load_config(spec, cell["config"], ROOT)
    traffic = harness.load_traffic(cell["traffic"])
    for seed in args.seeds:
        t = time.perf_counter()
        ctx = (contextlib.nullcontext() if args.fault == "none"
               else faults.planted(args.fault, config, traffic))
        with ctx:
            res = harness.execute(args.workload, seed, args.seconds, False)
        print(json.dumps({"workload": args.workload, "fault": args.fault,
                          "seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"], "checks": res["checks"],
                          "metrics": res["metrics"],
                          "wall_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
