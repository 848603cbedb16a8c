"""Rate sweep of the detection service, to find its knee once on the chip.

    python bench/knee.py --workload serve-mix-poisson --seed 1 --seconds 10 \
        --rates 5 10 20 40 80

One process, one service instance warmed as the benchmark warms it, one
window per rate.  For each rate it prints the p50/p95 time-to-detection,
the p95 queue wait (start of the tick that admitted a tenant minus its due
time) and whether the backlog grew (mean queue wait of the last third of
the tenants against that of the first third).  The knee is the highest rate
whose p95 queue wait stays under the lowest rate's p50 time-to-detection
with no growing backlog (the last third of the tenants waits no longer
than twice the first third, or than that p50); the cell runs at 0.8 times
it.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="serve-mix-poisson")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    import numpy as np

    from bench import harness

    if jax.devices()[0].platform != "tpu":
        print("knee: no TPU found", file=sys.stderr)
        return 1
    spec = harness.load_spec(ROOT)
    cell = harness.find_cell(spec, args.workload)
    config = harness.load_config(spec, cell["config"], ROOT)
    traffic = harness.load_traffic(cell["traffic"])
    driver = harness.load_driver(traffic["driver"])
    state = driver.setup(config, traffic, args.seed, jax.devices()[:1])
    rows = []
    for rate in sorted(args.rates):
        state.traffic = dict(traffic, rate=rate)
        state.reports = {}
        rec = driver.window(state, args.seconds)
        waits = rec["queue_wait_s"]          # in order of due time
        third = max(len(waits) // 3, 1)
        first = float(np.mean(waits[:third])) if waits else 0.0
        last = float(np.mean(waits[-third:])) if waits else 0.0
        row = {"rate": rate, "tenants": rec["attempted"],
               "failed": rec["failed"],
               "ttd_p50_s": harness.nearest_rank(rec["ttd_s"], 50),
               "ttd_p95_s": harness.nearest_rank(rec["ttd_s"], 95),
               "queue_wait_p95_s": harness.nearest_rank(waits, 95)
               if waits else None,
               "queue_wait_first_third_s": first,
               "queue_wait_last_third_s": last,
               "ticks": len(rec["ticks"]),
               "tick_ms_mean": 1e3 * float(np.mean(
                   [t1 - t0 for _, t0, t1 in rec["ticks"]]))
               if rec["ticks"] else None,
               "gen_late_p95_ms": 1e3 * harness.nearest_rank(rec["late_s"],
                                                             95)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    unloaded = rows[0]["ttd_p50_s"]
    knee = None
    for row in rows:
        if not (row["failed"] == 0 and row["queue_wait_p95_s"] is not None
                and row["queue_wait_p95_s"] < unloaded
                and row["queue_wait_last_third_s"]
                < max(2.0 * row["queue_wait_first_third_s"], unloaded)):
            break
        knee = row["rate"]
    print(json.dumps({"unloaded_ttd_p50_s": unloaded, "knee_rate": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
