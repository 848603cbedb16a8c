"""Elastic fault-injection matrix: crash, join and checkpoint-restart
survival of protocol-free detection, at both layers of the repo.

Two cell kinds, both via the campaign cell API (benchmarks/common.py):

1. **event** (``elastic_event``, cached) — the event-level simulator runs
   every termination protocol through dynamic-membership scenarios
   (``core.scenarios.elastic_scenarios``: crashes, late joins,
   checkpoint-restarts, churn) and the PR-2 oracle scores each detection
   against the *active-subsystem* residual (``exact_active_residual``):
   a crashed worker's block is frozen boundary data (Daggitt & Griffin),
   so the survivors' fixed point — not the original full-membership one —
   is the ground truth.  Acceptance: **zero false detections for the
   snapshot-class protocols in every cell**, and every cell terminates.
2. **device** (``elastic_device``, cached per jax version) — the shard
   runtime dies mid-solve: a `FaultPlan` kills real mesh shards, the live
   `HeartbeatMonitor` control loop detects the stall, `plan_restart` +
   `shrink_to_fit` rebuild a smaller mesh, the last committed checkpoint
   restores onto it and iteration resumes under the *unchanged* detection
   monitor (``runtime.elastic.run_elastic``).  Each cell reports detection
   reliability (oracle-scored final exact residual) **and** recovery cost
   (stalled segments, rolled-back iterations, heartbeat latency); the
   ``none`` scenario of each (family, reduction, mode, seed) lane is the
   uninterrupted reference the overhead summary is computed against.

Writes ``BENCH_elastic.json`` (repo root) or the smoke variant the
``elastic-smoke`` CI job gates against ``benchmarks/baselines/``.

Run:   PYTHONPATH=src:. python benchmarks/bench_elastic.py
Smoke: PYTHONPATH=src:. SHARD_DEVICES=4 python benchmarks/bench_elastic.py --smoke
"""
from __future__ import annotations

import os

# the device cells need >1 device; must be set before any jax import (see
# bench_shard_runtime.py for why this appends rather than setdefaults)
_DEV = int(os.environ.get("SHARD_DEVICES", "4"))
_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" {_FLAG}={_DEV}").strip()
for _v in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_v, "1")

import argparse
import dataclasses
import tempfile
import time
from typing import Dict

#: protocols of the event matrix — every detector in core.protocols
EVENT_PROTOCOLS = ("pfait", "rdub", "nfais2", "nfais5", "exact")
#: protocols whose detection carries a certified snapshot claim: these must
#: never fire falsely, crash or no crash (the headline acceptance bar)
SNAPSHOT_PROTOCOLS = ("nfais2", "nfais5", "exact", "rdub")


# ---------------------------------------------------------------------------
# Cell 1: event-level elastic matrix (protocol × scenario × seed)
# ---------------------------------------------------------------------------


def elastic_event(family: str, protocol: str, scenario: str, seed: int,
                  eps: float, max_iters: int, problem: Dict,
                  compute_base: float = 1e-3, residual_stride: int = 25,
                  factor: float = 10.0) -> Dict:
    """One traced engine run through a dynamic-membership scenario,
    oracle-scored against the active-subsystem residual."""
    from benchmarks.common import _finite, make_problem_cached, make_protocol
    from repro.core.async_engine import PLATFORMS
    from repro.core.reliability import detection_report, run_traced
    from repro.core.scenarios import elastic_scenarios

    spec = elastic_scenarios(compute_base)[scenario]
    cfg = dataclasses.replace(
        PLATFORMS[spec.platform](compute_base),
        seed=seed, max_iters=max_iters,
        fifo=(protocol == "exact"), scenario=spec.scenario,
    )
    res, rec = run_traced(
        lambda: make_problem_cached(family, seed=seed, **problem),
        cfg,
        lambda pr: make_protocol(protocol, eps, pr.ord),
        residual_stride=residual_stride,
        record_sends=False,
    )
    rep = detection_report(rec, eps, factor=factor)
    return {
        "status": "ok",
        "family": family, "protocol": protocol, "scenario": scenario,
        "seed": seed,
        "terminated": res.terminated,
        "membership_changes": int(rep.membership_changes),
        "detected_residual": _finite(rep.detected_residual),
        "true_at_detect": _finite(rep.true_at_detect),
        "active_residual": _finite(rep.active_residual),
        "certified_residual": _finite(rep.certified_residual),
        "claim": rep.claim,
        "overshoot": _finite(rep.overshoot),
        "false_detection": rep.false_detection,
        "latency_overhead": _finite(rep.latency_overhead),
        "k_max": res.k_max,
        "r_star": _finite(res.r_star),
    }


# ---------------------------------------------------------------------------
# Cell 2: device-level elastic runs (reduction × mode × fault plan × seed)
# ---------------------------------------------------------------------------


def device_plans(p0: int) -> Dict[str, "object"]:
    """Named fault plans of the device matrix, scaled to ``p0`` shards.
    Segments are the control-loop quantum of ``run_elastic``; the plans
    strike early enough that every solve is still far from converged."""
    from repro.runtime.elastic import FaultPlan

    last = p0 - 1
    return {
        # uninterrupted reference lane (recovery overhead baseline)
        "none": FaultPlan(),
        # kill one shard mid-solve: stall -> heartbeat -> shrink -> restore
        "crash": FaultPlan(crash_at={1: 3}),
        # standby shard arrives: hot scale-up from live state, no rollback
        "join": FaultPlan(join_at={p0: 2}),
        # crash, then the repaired worker returns: mesh p0 -> p' -> p0
        "crash_rejoin": FaultPlan(crash_at={1: 3}, join_at={1: 8}),
        # persistent straggler: flagged by the quantile policy, never killed
        "slow": FaultPlan(slow={last: 3.0}),
    }


def elastic_device(family: str, reduction: str, mode: str, scenario: str,
                   seed: int, n: int, p0: int, eps_tilde: float,
                   margin: float = 10.0, staleness: int = 2,
                   persistence: int = 4, segment_len: int = 10,
                   ckpt_every: int = 2, max_segments: int = 60,
                   factor: float = 10.0) -> Dict:
    """One elastic shard-runtime run through a named fault plan.  Detection
    is scored like the reliability oracle (final exact residual within
    ``factor × ε̃``); recovery cost comes from the driver's report."""
    from benchmarks.bench_shard_runtime import (
        _convdiff_exact_residual,
        _convdiff_setup,
        _ensure_x64,
        _monitor,
        _pagerank_setup,
    )

    _ensure_x64()
    import numpy as np

    from repro.runtime import elastic
    from repro.runtime.shard_runtime import ShardRuntimeConfig

    ord_ = 2.0 if family == "convdiff" else 1.0
    mon = _monitor(mode, eps_tilde, margin, staleness, persistence, ord_)
    cfg = ShardRuntimeConfig(
        monitor=mon, reduction=reduction,
        # scalar per-shard fields: the shard count changes mid-run
        inner_sweeps=2, halo_delay=1,
        contrib_lag=1 if reduction == "nonblocking" else 0,
    )
    plan = device_plans(p0)[scenario]
    st = damping = None
    if family == "convdiff":
        st, b, x0 = _convdiff_setup(n, seed=seed)
        arg = b
    else:
        prob, arg, x0 = _pagerank_setup(n, p0, seed=seed)
        damping = prob.d
    with tempfile.TemporaryDirectory(prefix="elastic_ckpt_") as ckpt_dir:
        rep = elastic.run_elastic(
            family, cfg, n, np.asarray(x0), np.asarray(arg), plan, ckpt_dir,
            stencil=st, damping=(damping if damping is not None else 0.85),
            p0=p0, segment_len=segment_len, ckpt_every=ckpt_every,
            max_segments=max_segments)
    if family == "convdiff":
        r_star = _convdiff_exact_residual(st, rep.x, b, ord_)
    else:
        xs = np.asarray(rep.x, dtype=np.float64)
        rv = prob.d * (np.asarray(arg, np.float64) @ xs) + prob.v - xs
        r_star = float(np.sum(np.abs(rv) ** ord_) ** (1.0 / ord_))
    return {
        "family": family, "reduction": reduction, "mode": mode,
        "scenario": scenario, "seed": seed, "n": n, "p0": p0,
        "eps_tilde": eps_tilde, "eps": mon.eps,
        "terminated": bool(rep.converged),
        "detected_residual": (float(rep.detected_residual)
                              if rep.converged else None),
        "r_star": r_star,
        "false_detection": bool(rep.converged
                                and r_star > factor * eps_tilde),
        "outer_iters": int(rep.outer_iters),
        "segments_run": int(rep.segments_run),
        "restarts": int(rep.restarts),
        "stall_segments": int(rep.stall_segments),
        "lost_iters": int(rep.lost_iters),
        "detect_latency": [float(v) for v in rep.detect_latency],
        "checkpoint_saves": int(rep.checkpoint_saves),
        "mesh_history": [[int(s), int(p)] for s, p in rep.mesh_history],
        "members_final": [int(w) for w in rep.members_final],
        "stragglers_flagged": [int(w) for w in rep.stragglers_flagged],
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


def _overhead(rows) -> Dict:
    """Recovery cost of each fault lane vs its uninterrupted reference:
    extra outer iterations to convergence (work overhead) and segments
    lost to stalls + rollback (availability overhead)."""
    ref = {(r["family"], r["reduction"], r["mode"], r["seed"]):
           r for r in rows if r["scenario"] == "none"}
    out = {}
    for r in rows:
        if r["scenario"] == "none" or not r["terminated"]:
            continue
        base = ref.get((r["family"], r["reduction"], r["mode"], r["seed"]))
        if base is None or not base["terminated"]:
            continue
        key = f"{r['family']}/{r['reduction']}/{r['mode']}/{r['scenario']}/s{r['seed']}"
        out[key] = {
            "extra_outer_iters": r["outer_iters"] - base["outer_iters"],
            "lost_iters": r["lost_iters"],
            "stall_segments": r["stall_segments"],
            "extra_segments": r["segments_run"] - base["segments_run"],
        }
    return out


def main():
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes + reduced matrix (CI)")
    ap.add_argument("--out", default="BENCH_elastic.json")
    args = ap.parse_args()

    import jax

    jax.config.update("jax_enable_x64", True)
    p0 = len(jax.devices())
    if p0 != _DEV:
        raise SystemExit(
            f"expected {_DEV} devices (SHARD_DEVICES), jax sees {p0} — "
            f"XLA_FLAGS={os.environ.get('XLA_FLAGS')!r} was not honoured "
            "(set before any jax import?)")

    if args.smoke:
        event_scenarios = ("crash_early", "crash_restart", "join_late")
        event_seeds = (1,)
        device_families = ("convdiff",)
        device_scenarios = ("none", "crash", "crash_rejoin")
        device_reductions = ("nonblocking",)
        device_modes = ("pfait", "nfais2")
        device_seeds = (0,)
    else:
        event_scenarios = ("crash_early", "crash_late", "crash_two",
                           "join_late", "crash_restart", "churn")
        event_seeds = (0, 1, 2, 3)
        device_families = ("convdiff", "pagerank")
        device_scenarios = ("none", "crash", "join", "crash_rejoin", "slow")
        device_reductions = ("nonblocking", "rdoubling")
        device_modes = ("pfait", "nfais2")
        device_seeds = (0, 1)

    event_specs = [
        {"kind": "elastic_event", "family": "convdiff", "protocol": proto,
         "scenario": scen, "seed": seed, "eps": 1e-6, "max_iters": 6000,
         "problem": {"n": 12, "p": 4, "rho": 0.9}}
        for proto in EVENT_PROTOCOLS
        for scen in event_scenarios
        for seed in event_seeds
    ]
    event_rows = _run(event_specs)

    n_cd, n_pr = 24, 240
    device_specs = [
        {"kind": "elastic_device", "family": fam, "reduction": red,
         "mode": mode, "scenario": scen, "seed": seed,
         "n": (n_cd if fam == "convdiff" else n_pr), "p0": p0,
         "eps_tilde": 1e-6 if fam == "convdiff" else 1e-8,
         "margin": 10.0, "staleness": 2, "persistence": 4,
         "segment_len": 10, "ckpt_every": 2, "max_segments": 60}
        for fam in device_families
        for red in device_reductions
        for mode in device_modes
        for scen in device_scenarios
        for seed in device_seeds
    ]
    device_rows = _run(device_specs)
    overhead = _overhead(device_rows)

    report = {
        "event": event_rows,
        "device": device_rows,
        "recovery_overhead": overhead,
        "meta": {"smoke": bool(args.smoke), "devices": p0,
                 "jax": jax.__version__,
                 "timestamp": time.strftime("%Y-%m-%d %H:%M:%S")},
    }
    from benchmarks.campaign import write_json_atomic

    write_json_atomic(args.out, report)

    # -- summary + in-script acceptance ------------------------------------
    failures = []
    ev_undet = [r for r in event_rows if not r["terminated"]]
    ev_false = [r for r in event_rows if r["false_detection"]]
    ev_false_snap = [r for r in ev_false
                     if r["protocol"] in SNAPSHOT_PROTOCOLS]
    mem = sum(r["membership_changes"] for r in event_rows)
    print(f"event: {len(event_rows)} cells "
          f"({len(EVENT_PROTOCOLS)} protocols x {len(event_scenarios)} "
          f"scenarios x {len(event_seeds)} seeds), "
          f"{mem} membership changes scored, "
          f"{len(ev_false)} false ({len(ev_false_snap)} snapshot-class), "
          f"{len(ev_undet)} undetected")
    if ev_undet:
        failures.append(f"{len(ev_undet)} event cells undetected")
    if ev_false_snap:
        failures.append(
            f"{len(ev_false_snap)} snapshot-class false detections")
    dv_undet = [r for r in device_rows if not r["terminated"]]
    dv_false = [r for r in device_rows if r["false_detection"]]
    crashes = [r for r in device_rows
               if r["scenario"] in ("crash", "crash_rejoin")]
    no_restart = [r for r in crashes if r["restarts"] < 1]
    print(f"device: {len(device_rows)} cells, {len(dv_false)} false, "
          f"{len(dv_undet)} undetected; "
          f"{sum(r['restarts'] for r in device_rows)} restarts, "
          f"{sum(r['stall_segments'] for r in device_rows)} stall segments, "
          f"{sum(r['lost_iters'] for r in device_rows)} iters rolled back")
    for key, ov in sorted(overhead.items()):
        print(f"  overhead {key}: +{ov['extra_outer_iters']} outer, "
              f"{ov['stall_segments']} stalled, "
              f"{ov['lost_iters']} rolled back")
    if dv_undet:
        failures.append(f"{len(dv_undet)} device cells undetected")
    if dv_false:
        failures.append(f"{len(dv_false)} device false detections")
    if no_restart:
        failures.append(
            f"{len(no_restart)} crash cells never exercised restart")
    print(f"wrote {args.out}")
    if failures:
        raise SystemExit("elastic acceptance failed: " + "; ".join(failures))
    print("acceptance ok")


if __name__ == "__main__":
    main()
