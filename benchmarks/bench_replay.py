"""Replay-vs-measured prediction error + the what-if extrapolation table.

Three cell kinds (benchmarks/common.py), all through the unified runtime
API (``repro.runtime.api``) and the trace/replay subsystem
(``repro.core.trace``, ``repro.sim``):

1. **measured** (``replay_measured``, never cached) — run one shard-runtime
   config on real host-emulated shards through ``api.run_shard`` with
   ``record_trace=True``, fit the replay cost model from the calibration
   run's own trace (sim/calibrate.py), self-replay the trace, and score
   the prediction against an independent measured run: predicted wall
   within ±20%, predicted detection step exact or ±1 round.  The CI gate
   exact-matches the two booleans and both detection steps (the programs
   are seeded-deterministic; only the walls themselves are noisy, and they
   are reported but never gated).
2. **what-if** (``replay_whatif``, cached) — a fully deterministic
   extrapolation row: a synthetic geometric-contraction trace replayed at
   64–1024 shards under each reduction topology with canonical cost
   constants from the spec.  Pure numpy, rounded, exact-gateable.
3. **calibrate** (``replay_calibrate``, never cached) — fit an event-sim
   ``DelayModel`` from repeated measured executions of a short
   fixed-iteration shard program, goodness-of-fit reported (the
   measurement → simulator transfer of sim/calibrate.py).

Writes ``BENCH_replay.json`` (repo root) or the smoke variant the
``replay-smoke`` CI job gates against ``benchmarks/baselines/``.

Run:   PYTHONPATH=src:. SHARD_DEVICES=8 python benchmarks/bench_replay.py
Smoke: PYTHONPATH=src:. SHARD_DEVICES=8 python benchmarks/bench_replay.py --smoke
"""
from __future__ import annotations

import os

# the measured cells need >1 device; must be set before any jax import.
# Append to (never clobber) a pre-existing XLA_FLAGS — see
# bench_shard_runtime.py for why setdefault would be wrong.
_DEV = int(os.environ.get("SHARD_DEVICES", "8"))
_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" {_FLAG}={_DEV}").strip()
for _v in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_v, "1")

import argparse
import time
from typing import Dict, Optional

#: acceptance bounds (ISSUE: predicted wall within ±20%, detection step
#: exact or ±1 round)
WALL_TOL = 0.20
DETECT_TOL = 1

#: what-if canonical cost constants (spec-level, so cached cells are pure
#: functions of their spec)
CANON = {"sweep_s": 1e-3, "hop_s": 5e-5, "residual_pass_s": 1e-3,
         "p_ref": 8}


def _ensure_x64():
    import jax

    jax.config.update("jax_enable_x64", True)


def _convdiff_setup(n: int, seed: int = 0, rho: float = 0.9):
    import jax.numpy as jnp

    from repro.solvers.convdiff import Stencil, make_rhs

    st = Stencil.for_contraction(n, 1.0, (1.0, 1.0, 1.0), rho=rho)
    b = jnp.asarray(make_rhs(n, seed=seed))
    return st, b, jnp.zeros_like(b)


def _shard_config(reduction: str, mode: str, eps_tilde: float,
                  staleness: int, max_outer: int, trace_len: int):
    from repro.core import detection
    from repro.runtime import api

    mon = detection.for_mode(mode, eps_tilde=eps_tilde, staleness=staleness,
                             ord=2.0)
    return api.RuntimeConfig(monitor=mon, reduction=reduction,
                             max_outer=max_outer, trace_len=trace_len,
                             record_trace=True)


# ---------------------------------------------------------------------------
# Cell 1: replay vs measured (the tentpole's acceptance)
# ---------------------------------------------------------------------------


def replay_measured(family: str, reduction: str, p: int, n: int,
                    mode: str = "pfait", eps_tilde: float = 1e-6,
                    staleness: int = 2, max_outer: int = 2000,
                    trace_len: int = 2048, repeats: int = 3) -> Dict:
    """Measure, trace, self-replay, score.

    One calibration run fits the cost model from its own trace (wall = the
    min of ``repeats`` timed executions of the compiled program — timing
    noise on a shared host is strictly additive, so min is the robust
    estimator, and a single 5–15 ms execution carries enough scheduler
    jitter to blow the ±20% budget on its own); the prediction is then
    scored against the min steady-state wall of an independently compiled
    second run of the same config.  The detection step is
    seeded-deterministic and must replay exactly.
    """
    _ensure_x64()
    from repro.launch.mesh import make_shard_mesh
    from repro.runtime import api
    from repro.sim.calibrate import fit_cost_model
    from repro.sim.replay import replay

    if family != "convdiff":
        raise ValueError("measured replay cells run the convdiff family")
    cfg = _shard_config(reduction, mode, eps_tilde, staleness, max_outer,
                        trace_len)
    mesh = make_shard_mesh(p)
    st, b, x0 = _convdiff_setup(n)
    reruns = max(int(repeats) - 1, 0)
    calib = api.run_shard(family, cfg, mesh, n, x0, b, stencil=st,
                          timing_runs=reruns)
    if calib.outer_iters > trace_len:
        raise SystemExit(f"trace_len={trace_len} < outer={calib.outer_iters}"
                         " — replay would be truncated")
    calib_walls = [s for name, s in calib.wall_segments
                   if name in ("run", "rerun")]
    calib.trace.meta["wall_s"] = min(calib_walls)
    cost, cost_report = fit_cost_model(calib.trace)
    verdict = replay(calib.trace, cost)

    meas = api.run_shard(family, cfg, mesh, n, x0, b, stencil=st,
                         timing_runs=reruns)
    meas_walls = [s for name, s in meas.wall_segments
                  if name in ("run", "rerun")]
    measured_wall = min(meas_walls)
    if meas.detect_step != calib.detect_step:
        raise SystemExit(f"measured detection step not reproducible: "
                         f"{calib.detect_step} vs {meas.detect_step}")

    wall_err = abs(verdict.predicted_wall_s - measured_wall) / measured_wall
    detect_delta = (None if verdict.predicted_detect_step is None
                    or calib.detect_step is None
                    else abs(verdict.predicted_detect_step
                             - calib.detect_step))
    return {
        "family": family, "reduction": reduction, "p": p, "n": n,
        "mode": mode, "eps_tilde": eps_tilde, "staleness": staleness,
        "converged": bool(calib.converged),
        "recorded_detect_step": calib.detect_step,
        "predicted_detect_step": verdict.predicted_detect_step,
        "detect_step_ok": detect_delta is not None
                          and detect_delta <= DETECT_TOL,
        "detect_step_exact": detect_delta == 0,
        "measured_wall_s": float(measured_wall),
        "predicted_wall_s": float(verdict.predicted_wall_s),
        "wall_err": float(wall_err),
        "wall_within_20pct": bool(wall_err <= WALL_TOL),
        "staleness_steps_at_detect": verdict.staleness_steps,
        "detected_residual": verdict.detected_residual,
        "fresh_residual_at_detect": verdict.fresh_residual,
        "approximate": bool(verdict.approximate),
        "cost_model": cost_report,
    }


# ---------------------------------------------------------------------------
# Cell 2: deterministic what-if extrapolation
# ---------------------------------------------------------------------------


def synthetic_trace(p: int = 8, rho: float = 0.9, r0: float = 1.0,
                    steps: int = 200, eps: float = 1e-7,
                    staleness: int = 2, mode: str = "pfait"):
    """A canonical geometric-contraction trace: residual rho^k·r0, uniform
    workers — the deterministic stand-in the what-if grid replays."""
    from repro.core.trace import Trace

    tr = Trace("synthetic", p, {
        "reduction": "nonblocking", "topology": "flat",
        "monitor": {"mode": mode, "eps": eps, "eps_tilde": eps,
                    "staleness": staleness, "persistence": 4, "ord": 2.0,
                    "check_every": 1},
        "inner_sweeps": [1] * p, "halo_delay": [0] * p,
        "contrib_lag": [0] * p, "synthetic_t": True,
    })
    for k in range(steps):
        tr.add("reduce", float(k + 1), step=k, residual=r0 * rho ** k)
    return tr


def replay_whatif(p: int, topology: str, rho: float = 0.9,
                  steps: int = 200, eps: float = 1e-7,
                  staleness: int = 2, straggler: Optional[float] = None,
                  digits: int = 6) -> Dict:
    """One extrapolation row: pure numpy, rounded, exact-gateable."""
    from repro.sim.replay import CostModel, WhatIf, replay

    tr = synthetic_trace(p=CANON["p_ref"], rho=rho, steps=steps, eps=eps,
                         staleness=staleness)
    cost = CostModel(**CANON)
    stragglers = {0: straggler} if straggler else {}
    v = replay(tr, cost, WhatIf(p=p, topology=topology,
                                stragglers=stragglers))
    return {
        "p": p, "topology": topology, "rho": rho, "eps": eps,
        "straggler": straggler,
        "predicted_wall_s": round(v.predicted_wall_s, digits),
        "predicted_detect_step": v.predicted_detect_step,
        "predicted_outer_iters": v.predicted_outer_iters,
        "staleness_steps_at_detect": v.staleness_steps,
        "converged": bool(v.converged),
    }


# ---------------------------------------------------------------------------
# Cell 3: DelayModel calibration from measured durations
# ---------------------------------------------------------------------------


def replay_calibrate(p: int, n: int, iters: int = 8,
                     samples: int = 24, dist: str = "lognormal") -> Dict:
    """Fit a compute ``DelayModel`` from repeated short program runs.

    The jitted while_loop admits no per-step timestamps, so the sampling
    unit is one fixed-iteration program execution; the per-sweep duration
    sample is its wall divided by the iteration count.
    """
    _ensure_x64()
    import jax

    from repro.core import detection
    from repro.launch.mesh import make_shard_mesh
    from repro.runtime import shard_runtime as sr
    from repro.sim.calibrate import fit_delay_model

    mesh = make_shard_mesh(p)
    # eps=0 never fires: every execution runs exactly ``iters`` outers
    mon = detection.MonitorConfig(mode="pfait", eps=0.0, staleness=2,
                                  ord=2.0)
    cfg = sr.ShardRuntimeConfig(monitor=mon, reduction="nonblocking",
                                max_outer=iters)
    st, b, x0 = _convdiff_setup(n)
    run = jax.jit(sr.make_runtime("convdiff", cfg, mesh, n, stencil=st))
    jax.block_until_ready(run(x0, b))   # compile
    durs = []
    for _ in range(int(samples)):
        t0 = time.perf_counter()
        jax.block_until_ready(run(x0, b))
        durs.append((time.perf_counter() - t0) / iters)
    model, gof = fit_delay_model(durs, dist=dist)
    return {
        "p": p, "n": n, "iters": iters, "samples": samples,
        "fit": gof,
        "per_step_median_s": float(model.base),
        "sigma": float(model.sigma),
    }


# ---------------------------------------------------------------------------
# Campaign assembly
# ---------------------------------------------------------------------------


def _run(specs, runner=None):
    from benchmarks import campaign
    from benchmarks.campaign import CampaignConfig

    runner = runner or (lambda s: campaign.map_cells(
        s, CampaignConfig(executor="inline")))
    return runner(specs)


WHATIF_SHARDS = (64, 128, 256, 512, 1024)
WHATIF_TOPOLOGIES = ("flat-nonblocking", "flat-blocking", "butterfly",
                     "tree")


def main():
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced repeats + measured matrix (CI)")
    ap.add_argument("--out", default="BENCH_replay.json")
    args = ap.parse_args()

    _ensure_x64()
    import jax

    ndev = len(jax.devices())
    if ndev != _DEV:
        raise SystemExit(
            f"expected {_DEV} devices (SHARD_DEVICES), jax sees {ndev} — "
            f"XLA_FLAGS={os.environ.get('XLA_FLAGS')!r} was not honoured "
            "(set before any jax import?)")
    shard_counts = [pp for pp in (2, 4, 8) if pp <= ndev]
    repeats = 3 if args.smoke else 5
    n = 16

    measured_specs = [
        {"kind": "replay_measured", "family": "convdiff", "reduction": red,
         "p": pp, "n": n, "mode": "pfait", "eps_tilde": 1e-6,
         "staleness": 2, "max_outer": 2000, "trace_len": 2048,
         "repeats": repeats}
        for pp in shard_counts
        for red in ("blocking", "nonblocking", "rdoubling")
    ]
    measured = _run(measured_specs)

    whatif_specs = [
        {"kind": "replay_whatif", "p": pp, "topology": topo, "rho": 0.9,
         "steps": 200, "eps": 1e-7, "staleness": 2}
        for pp in WHATIF_SHARDS
        for topo in WHATIF_TOPOLOGIES
        if not (topo == "butterfly" and pp & (pp - 1))
    ] + [
        # a straggler row per shard count: one 4x-slow worker
        {"kind": "replay_whatif", "p": pp, "topology": "flat-nonblocking",
         "rho": 0.9, "steps": 200, "eps": 1e-7, "staleness": 2,
         "straggler": 4.0}
        for pp in (64, 1024)
    ]
    whatif = _run(whatif_specs)

    calib_specs = [{"kind": "replay_calibrate", "p": min(4, ndev), "n": n,
                    "iters": 8, "samples": 12 if args.smoke else 30}]
    calibration = _run(calib_specs)[0]

    report = {
        "measured": measured,
        "whatif": whatif,
        "calibration": calibration,
        "meta": {"smoke": bool(args.smoke), "devices": ndev,
                 "jax": jax.__version__, "wall_tol": WALL_TOL,
                 "detect_tol": DETECT_TOL,
                 "timestamp": time.strftime("%Y-%m-%d %H:%M:%S")},
    }

    from benchmarks.campaign import write_json_atomic

    write_json_atomic(args.out, report)

    # -- summary + in-script acceptance ------------------------------------
    failures = []
    for row in measured:
        print(f"measured {row['reduction']:11s} p={row['p']}: "
              f"detect {row['recorded_detect_step']} -> "
              f"pred {row['predicted_detect_step']} "
              f"(ok={row['detect_step_ok']}), "
              f"wall {row['measured_wall_s']*1e3:.1f}ms -> "
              f"pred {row['predicted_wall_s']*1e3:.1f}ms "
              f"(err={row['wall_err']:.1%})")
        if not row["detect_step_ok"]:
            failures.append(
                f"{row['reduction']} p={row['p']}: detection step "
                f"{row['predicted_detect_step']} != "
                f"{row['recorded_detect_step']} (±{DETECT_TOL})")
        if not row["wall_within_20pct"]:
            failures.append(f"{row['reduction']} p={row['p']}: wall error "
                            f"{row['wall_err']:.1%} > {WALL_TOL:.0%}")
    print(f"whatif: {len(whatif)} rows "
          f"(p up to {max(r['p'] for r in whatif)})")
    print(f"calibration: dist={calibration['fit']['dist']} "
          f"ks={calibration['fit']['ks_statistic']:.3f} "
          f"crit={calibration['fit']['ks_critical']:.3f} "
          f"ok={calibration['fit']['ok']}")
    if failures:
        raise SystemExit("replay acceptance FAILED:\n  " +
                         "\n  ".join(failures))
    print(f"OK -> {args.out}")


if __name__ == "__main__":
    main()
