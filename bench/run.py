"""Benchmark command: one run of one cell of ``BENCHMARK.json``.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for.  The process loads the cell's configuration and traffic
mix, builds its inputs from ``--seed``, warms up every program the window
uses (that is set-up), measures for ``--seconds``, compares what the
timed path produced with the plain reference in ``bench/reference``, and
prints one JSON object as the last line of standard output.  With
``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer metrics.  The numbers compared with the reference are
printed beside their limits as the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 1 and prints no result: it never falls back to the CPU.
"""
import time

T0 = time.perf_counter()   # process start: setup_s runs from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def _fail(msg: str, code: int) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        return _fail(f"no program under {ROOT / 'src' / 'repro'}: run from a "
                     "checkout of the repository", 2)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import harness

    try:
        cell = harness.find_cell(harness.load_spec(ROOT), args.workload)
    except harness.BenchError as exc:
        return _fail(str(exc), 2)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    # every program of the window goes to the persistent cache, however
    # quick its compile, so a cell's second run in a checkout compiles
    # nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(f"no TPU found (JAX platform {devices[0].platform!r}); "
                     "the benchmark runs only on the chip", 1)
    if len(devices) < int(cell["chips"]):
        return _fail(f"{args.workload} needs {cell['chips']} chips, found "
                     f"{len(devices)}", 1)
    harness.peaks_for(devices[0].device_kind)   # unknown kind: an error

    result = harness.execute(args.workload, args.seed, args.seconds,
                             bool(args.trace), t0=T0)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
