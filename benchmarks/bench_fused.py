"""Fused sweep+residual head-to-head — the proof for the fused hot path.

Two cells, both measured fused vs. unfused **in the same run**:

1. **Event-level simulator** (the paper-table cell): ``run_cell`` at
   (n=24, p=8, pfait) with ``EngineConfig.fused`` on/off.  Fused means
   ``ConvDiffProblem.update_with_residual`` (one ghost assembly, shared /
   checkerboard-sliced off-diagonal) plus protocol-gated residual skipping.
   Reported: wall-time and sweep-throughput speedup (target ≥1.5×).

2. **Sharded JAX driver**: ``make_sharded_solver`` lowered on a forced
   multi-device host platform with ``SolverConfig.fuse_residual`` on/off;
   HLO-derived ``hbm_bytes_per_device`` per sweep (launch/hlo_analysis).
   Fused means the residual is a by-product of the last inner sweep — no
   residual-only second grid pass (target ~½ traffic for Jacobi, reduced
   for hybrid).

Writes ``BENCH_fused.json`` (repo root by default).

Run:   PYTHONPATH=src:. python benchmarks/bench_fused.py
Smoke: PYTHONPATH=src:. python benchmarks/bench_fused.py --smoke
"""
from __future__ import annotations

import os

# the sharded cell needs >1 device; must be set before any jax import
_DEV = int(os.environ.get("BENCH_DEVICES", "8"))
os.environ.setdefault("XLA_FLAGS",
                      f"--xla_force_host_platform_device_count={_DEV}")
# one BLAS thread per process (see reliability_matrix.py)
for _v in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_v, "1")

import argparse
import json
import time



# ---------------------------------------------------------------------------
# Cell 1: event-level simulator
# ---------------------------------------------------------------------------


def bench_event_sim(n: int, p: int, protocol: str = "pfait", eps: float = 1e-6,
                    seeds=(0, 1, 2, 3), repeats: int = 3, runner=None):
    """Fused/unfused head-to-head via ``fused_event`` campaign cells.

    Timing cells are never cached (``cache=False`` on the kind) but still
    run through the campaign runner — serially, in ONE worker: co-scheduling
    the two legs would let pool contention pollute the wall-clock ratio.
    """
    from benchmarks import campaign
    from benchmarks.campaign import CampaignConfig

    specs = [
        {"kind": "fused_event", "protocol": protocol, "eps": eps, "n": n,
         "p": p, "seeds": list(seeds), "fused": fused, "repeat": rep}
        for fused in (False, True)
        for rep in range(repeats)
    ]
    runner = runner or (lambda s: campaign.map_cells(
        s, CampaignConfig(executor="inline")))
    rows = runner(specs)
    out = {}
    for fused in (False, True):
        cells = [r for s, r in zip(specs, rows) if s["fused"] == fused]
        walls = [c["wall_s"] for c in cells]
        key = "fused" if fused else "unfused"
        out[key] = {
            "wall_s_best": float(min(walls)),
            "wall_s_all": [float(w) for w in walls],
            "sim_iters": int(cells[0]["sim_iters"]),
            "iters_per_s": float(cells[0]["sim_iters"] / min(walls)),
            "r_star_max": max(c["max_r"] for c in cells),
        }
    out["cell"] = {"protocol": protocol, "eps": eps, "n": n, "p": p,
                   "seeds": list(seeds), "repeats": repeats}
    out["wall_speedup"] = out["unfused"]["wall_s_best"] / out["fused"]["wall_s_best"]
    out["throughput_speedup"] = (out["fused"]["iters_per_s"]
                                 / out["unfused"]["iters_per_s"])
    return out


# ---------------------------------------------------------------------------
# Cell 2: sharded JAX driver (HLO-derived HBM traffic per sweep)
# ---------------------------------------------------------------------------


def measure_sharded(n: int, sweep: str, fuse_residual: bool,
                    inner_sweeps: int = 1, use_kernel: bool = False):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import detection
    from repro.launch import hlo_analysis
    from repro.launch.mesh import make_mesh
    from repro.solvers.convdiff import Stencil
    from repro.solvers.fixed_point import SolverConfig, make_sharded_solver
    from repro.solvers.partition import process_grid

    ndev = len(jax.devices())
    px, py = process_grid(ndev)
    mesh = make_mesh((px, py), ("data", "model"))
    st = Stencil.for_contraction(n, 1.0, (1.0, 1.0, 1.0), rho=0.95)
    mon = detection.for_mode("pfait", eps_tilde=1e-6, margin=10.0, staleness=2)
    cfg = SolverConfig(stencil=st, monitor=mon, inner_sweeps=inner_sweeps,
                       max_outer=1000, sweep=sweep, use_kernel=use_kernel,
                       fuse_residual=fuse_residual)
    solve = make_sharded_solver(cfg, mesh)
    spec = P("data", "model", None)
    arr = jax.ShapeDtypeStruct((n, n, n), jnp.float32,
                               sharding=NamedSharding(mesh, spec))
    compiled = jax.jit(solve).lower(arr, arr).compile()
    pstats = hlo_analysis.program_stats(compiled.as_text(), default_group=ndev)
    # normalise per sweep with the analyzer's own loop multiplier (the
    # permute-count heuristic hillclimb uses is jax-version dependent: 4
    # faces lower to 4 or 8 one-directional permutes per outer iteration)
    sweeps = max(pstats.loop_trip_max, 1.0) * inner_sweeps
    return {
        "sweep": sweep,
        "inner_sweeps": inner_sweeps,
        "fuse_residual": fuse_residual,
        "devices": ndev,
        "hbm_bytes_per_device_per_sweep": pstats.hbm_bytes / sweeps,
        "wire_bytes_per_sweep": pstats.total_wire_bytes / sweeps,
    }


def bench_sharded(n: int, inner_sweeps: int = 1, runner=None):
    """HLO-derived traffic cells via the campaign (content-addressed: the
    lowering is deterministic per jax version, so warm re-runs cost zero)."""
    from benchmarks import campaign
    from benchmarks.campaign import CampaignConfig

    specs = [
        {"kind": "fused_sharded", "n": n, "sweep": sweep,
         "fuse_residual": fuse, "inner_sweeps": inner_sweeps}
        for sweep in ("jacobi", "hybrid")
        for fuse in (False, True)
    ]
    runner = runner or (lambda s: campaign.map_cells(
        s, CampaignConfig(executor="inline")))
    results = {(s["sweep"], s["fuse_residual"]): r
               for s, r in zip(specs, runner(specs))}
    rows = []
    for sweep in ("jacobi", "hybrid"):
        pair = {"unfused": results[(sweep, False)],
                "fused": results[(sweep, True)]}
        ratio = (pair["fused"]["hbm_bytes_per_device_per_sweep"]
                 / pair["unfused"]["hbm_bytes_per_device_per_sweep"])
        rows.append({"sweep": sweep, "n": n, "inner_sweeps": inner_sweeps,
                     "unfused": pair["unfused"], "fused": pair["fused"],
                     "hbm_ratio_fused_over_unfused": ratio})
    return rows


# ---------------------------------------------------------------------------


def main():
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + relaxed thresholds (CI)")
    ap.add_argument("--out", default="BENCH_fused.json")
    args = ap.parse_args()

    if args.smoke:
        # best-of-3 over 4 seeds: at smoke scale a single ~0.1 s leg is
        # noise-dominated and the fused/unfused ratio (the regression-gate
        # metric) swings ±2×; three repeats keep the gate's ±30% meaningful
        ev = bench_event_sim(n=16, p=4, seeds=(0, 1, 2, 3), repeats=3)
        sh = bench_sharded(n=16)
        min_speedup = 1.0
    else:
        ev = bench_event_sim(n=24, p=8, seeds=(0, 1, 2, 3), repeats=3)
        sh = bench_sharded(n=64, inner_sweeps=1)
        min_speedup = 1.5

    report = {
        "event_sim": ev,
        "sharded": sh,
        "meta": {"smoke": bool(args.smoke),
                 "timestamp": time.strftime("%Y-%m-%d %H:%M:%S")},
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)

    print(f"event-sim ({ev['cell']['protocol']} n={ev['cell']['n']} "
          f"p={ev['cell']['p']}): wall speedup {ev['wall_speedup']:.2f}x, "
          f"throughput {ev['throughput_speedup']:.2f}x "
          f"(unfused {ev['unfused']['wall_s_best']:.3f}s → "
          f"fused {ev['fused']['wall_s_best']:.3f}s)")
    for row in sh:
        print(f"sharded {row['sweep']:7s}: hbm/sweep "
              f"{row['unfused']['hbm_bytes_per_device_per_sweep']:.3e} → "
              f"{row['fused']['hbm_bytes_per_device_per_sweep']:.3e} "
              f"({row['hbm_ratio_fused_over_unfused']:.2f}x)")

    ok = ev["wall_speedup"] >= min_speedup and all(
        r["hbm_ratio_fused_over_unfused"] < 1.0 for r in sh)
    if not ok:
        raise SystemExit(
            f"targets missed: wall_speedup={ev['wall_speedup']:.2f} "
            f"(need ≥{min_speedup}), hbm ratios="
            f"{[round(r['hbm_ratio_fused_over_unfused'], 3) for r in sh]} "
            f"(need <1.0)")
    print("targets met")


if __name__ == "__main__":
    main()
