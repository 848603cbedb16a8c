"""ML-workload detection matrix: async SGD certified protocol-free.

Two cell kinds, both via the campaign cell API (benchmarks/common.py):

1. **event** (``ml_event``, cached) — the event-level simulator runs the
   ML fixed-point family (``solvers/mlfixed.py``: ridge least squares and
   ℓ2-regularised logistic regression as contraction maps) through every
   termination protocol, and the reliability oracle scores each detection
   against the exact update-difference residual.  Acceptance: **zero
   false detections in every cell** — the same bar the PDE families meet.
2. **train** (``ml_train``, cached per jax version) — a real async
   data-parallel training run on mesh shards (``runtime/train_async.py``):
   heterogeneous local SGD with stale parameter averages, convergence
   certified by the protocol-free non-blocking residual instead of a
   synchronized eval.  Each cell reports the detection round, the
   synchronized-eval oracle's round on the host reference trajectory, and
   decade-consistency (``core.termination.detection_consistent``); the
   ``blocking`` reduction lane is the synchronized-eval cost baseline the
   wall-clock comparison in EXPERIMENTS.md §ML-workloads is built from.

Writes ``BENCH_ml.json`` (repo root) or the smoke variant the ``ml-smoke``
CI job gates against ``benchmarks/baselines/``.

Run:   PYTHONPATH=src:. python benchmarks/bench_ml.py
Smoke: PYTHONPATH=src:. SHARD_DEVICES=4 python benchmarks/bench_ml.py --smoke
"""
from __future__ import annotations

import os

# the train cells need >1 device; must be set before any jax import (see
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
import time
from typing import Dict

#: the acceptance matrix of ISSUE 7: every event-sim protocol on the family
EVENT_PROTOCOLS = ("pfait", "nfais2", "nfais5", "exact")
TRAIN_REDUCTIONS = ("blocking", "nonblocking", "rdoubling")


# ---------------------------------------------------------------------------
# Cell 1: event-level protocol matrix (task × protocol × seed)
# ---------------------------------------------------------------------------


def ml_event(task: str, protocol: str, seed: int, eps: float,
             max_iters: int, problem: Dict, platform: str = "stable",
             compute_base: float = 1e-3, residual_stride: int = 25,
             factor: float = 10.0) -> Dict:
    """One traced event-sim run of the ML family, oracle-scored."""
    from benchmarks.common import _finite, make_problem_cached, make_protocol
    from repro.core.async_engine import PLATFORMS
    from repro.core.reliability import detection_report, run_traced

    cfg = dataclasses.replace(
        PLATFORMS[platform](compute_base),
        seed=seed, max_iters=max_iters, fifo=(protocol == "exact"),
    )
    res, rec = run_traced(
        lambda: make_problem_cached("mlfixed", seed=seed, task=task,
                                    **problem),
        cfg,
        lambda pr: make_protocol(protocol, eps, pr.ord),
        residual_stride=residual_stride,
        record_sends=False,
    )
    rep = detection_report(rec, eps, factor=factor)
    return {
        "status": "ok",
        "task": task, "protocol": protocol, "seed": seed,
        "terminated": res.terminated,
        "detected_residual": _finite(rep.detected_residual),
        "true_at_detect": _finite(rep.true_at_detect),
        "certified_residual": _finite(rep.certified_residual),
        "claim": rep.claim,
        "overshoot": _finite(rep.overshoot),
        "false_detection": rep.false_detection,
        "latency_overhead": _finite(rep.latency_overhead),
        "k_max": res.k_max,
        "r_star": _finite(res.r_star),
    }


# ---------------------------------------------------------------------------
# Cell 2: real async-SGD runs (task × reduction × mode × seed)
# ---------------------------------------------------------------------------


def ml_train(task: str, reduction: str, mode: str, seed: int,
             eps_tilde: float, n: int = 16, p: int = 4, m_rows: int = 64,
             inner_steps=2, view_delay=0, contrib_lag=0,
             num_batches: int = 2, margin: float = 10.0, staleness: int = 2,
             persistence: int = 4, max_rounds: int = 20000,
             factor: float = 10.0) -> Dict:
    """One async data-parallel SGD run on real shards, scored against the
    synchronized-eval oracle on the host reference trajectory."""
    import jax

    jax.config.update("jax_enable_x64", True)
    import numpy as np

    from repro.core import detection
    from repro.core.termination import detection_consistent, oracle_detect_step
    from repro.launch.mesh import make_shard_mesh
    from repro.runtime import train_async as ta
    from repro.solvers.mlfixed import MLFixedPointProblem

    prob = MLFixedPointProblem(n=n, p=p, m_rows=m_rows, task=task, seed=seed)
    gamma = ta.safe_gamma(prob, p, num_batches=num_batches)
    mon = detection.for_mode(mode, eps_tilde=eps_tilde, margin=margin,
                             staleness=staleness, persistence=persistence)
    if reduction == "blocking":
        inner_steps, view_delay, contrib_lag = 2, 0, 0
    cfg = ta.TrainAsyncConfig(
        monitor=mon, reduction=reduction, inner_steps=inner_steps,
        view_delay=view_delay, contrib_lag=contrib_lag,
        num_batches=num_batches, gamma=gamma, max_rounds=max_rounds)
    mesh = make_shard_mesh(p)
    run = jax.jit(ta.make_train_runtime(prob, cfg, mesh))
    X0 = ta.init_replicas(prob, p)
    A, y = prob.A, prob.y
    r = run(X0, A, y)          # compile + run once (rounds vary per cell)
    jax.block_until_ready(r.x)
    t0 = time.time()
    r = run(X0, A, y)
    jax.block_until_ready(r.x)
    wall = time.time() - t0

    converged = bool(r.converged)
    detected = int(r.rounds) if converged else None
    exact = ta.exact_train_residual(prob, np.asarray(r.x), cfg.inner_steps,
                                    gamma, num_batches=num_batches)
    # synchronized-eval oracle: the same map run synchronously on the host
    horizon = (detected or max_rounds) + 16
    _, ref = ta.reference_trace(prob, p, cfg.inner_steps, num_batches,
                                gamma, rounds=min(horizon, max_rounds + 16))
    oracle = oracle_detect_step(ref, eps_tilde)
    consistent = (converged
                  and detection_consistent(detected, ref, eps_tilde,
                                           factor=factor))
    return {
        "task": task, "reduction": reduction, "mode": mode, "seed": seed,
        "n": n, "p": p, "m_rows": m_rows, "num_batches": num_batches,
        "eps_tilde": eps_tilde, "eps": mon.eps,
        "terminated": converged,
        "detected_round": detected,
        "oracle_round": oracle,
        "oracle_consistent": bool(consistent),
        "false_detection": bool(converged and exact > factor * eps_tilde),
        "detected_residual": float(r.residual) if converged else None,
        "exact_residual": float(exact),
        "final_loss": float(r.loss),
        "local_steps": [int(s) for s in np.asarray(r.local_steps)],
        "verifications": int(r.verifications),
        "wall_s": wall,
        "rounds": int(r.rounds),
    }


# ---------------------------------------------------------------------------
# Campaign assembly
# ---------------------------------------------------------------------------


def _run(specs):
    from benchmarks import campaign
    from benchmarks.campaign import CampaignConfig

    return campaign.map_cells(specs, CampaignConfig(executor="inline"))


def _wall_comparison(rows) -> Dict:
    """Detection-vs-synchronized-eval cost: each non-blocking lane vs the
    blocking lane of the same (task, mode, seed) — blocking pays an extra
    evaluation pass of the worker map every round (the synchronized
    eval); the protocol-free lanes get the residual for free."""
    ref = {(r["task"], r["mode"], r["seed"]): r
           for r in rows if r["reduction"] == "blocking"}
    out = {}
    for r in rows:
        if r["reduction"] == "blocking" or not r["terminated"]:
            continue
        base = ref.get((r["task"], r["mode"], r["seed"]))
        if base is None or not base["terminated"]:
            continue
        key = f"{r['task']}/{r['mode']}/{r['reduction']}/s{r['seed']}"
        out[key] = {
            "rounds": r["rounds"],
            "blocking_rounds": base["rounds"],
            "wall_s": r["wall_s"],
            "blocking_wall_s": base["wall_s"],
            "wall_ratio": (r["wall_s"] / base["wall_s"]
                           if base["wall_s"] > 0 else None),
            "detect_gap_rounds": (r["detected_round"]
                                  - base["detected_round"]),
        }
    return out


def main():
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes + reduced matrix (CI)")
    ap.add_argument("--out", default="BENCH_ml.json")
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
        event_tasks = ("lstsq", "logistic")
        event_seeds = (0,)
        train_tasks = ("lstsq",)
        train_modes = ("pfait", "nfais2")
        train_seeds = (3,)
    else:
        event_tasks = ("lstsq", "logistic")
        event_seeds = (0, 1, 2, 3)
        train_tasks = ("lstsq", "logistic")
        train_modes = ("pfait", "nfais2")
        train_seeds = (3, 4)

    event_specs = [
        {"kind": "ml_event", "task": task, "protocol": proto, "seed": seed,
         "eps": 1e-8, "max_iters": 20000,
         "problem": {"n": 16, "p": 4, "m_rows": 64}}
        for task in event_tasks
        for proto in EVENT_PROTOCOLS
        for seed in event_seeds
    ]
    event_rows = _run(event_specs)

    train_specs = [
        {"kind": "ml_train", "task": task, "reduction": red, "mode": mode,
         "seed": seed, "eps_tilde": 1e-6, "n": 16, "p": p0, "m_rows": 64,
         "inner_steps": [2, 4, 2, 4], "view_delay": [0, 1, 2, 1],
         "contrib_lag": [0, 1, 0, 2], "num_batches": 2,
         "margin": 10.0, "staleness": 2, "max_rounds": 20000}
        for task in train_tasks
        for red in TRAIN_REDUCTIONS
        for mode in train_modes
        for seed in train_seeds
    ]
    train_rows = _run(train_specs)
    walls = _wall_comparison(train_rows)

    report = {
        "event": event_rows,
        "train": train_rows,
        "wall_comparison": walls,
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
    print(f"event: {len(event_rows)} cells ({len(event_tasks)} tasks x "
          f"{len(EVENT_PROTOCOLS)} protocols x {len(event_seeds)} seeds), "
          f"{len(ev_false)} false, {len(ev_undet)} undetected")
    if ev_undet:
        failures.append(f"{len(ev_undet)} event cells undetected")
    if ev_false:
        failures.append(f"{len(ev_false)} event false detections")

    tr_undet = [r for r in train_rows if not r["terminated"]]
    tr_false = [r for r in train_rows if r["false_detection"]]
    tr_incons = [r for r in train_rows
                 if r["terminated"] and not r["oracle_consistent"]]
    print(f"train: {len(train_rows)} cells, {len(tr_false)} false, "
          f"{len(tr_undet)} undetected, "
          f"{len(tr_incons)} oracle-inconsistent")
    for key, w in sorted(walls.items()):
        print(f"  wall {key}: {w['rounds']} rounds {w['wall_s']:.3f}s vs "
              f"blocking {w['blocking_rounds']} rounds "
              f"{w['blocking_wall_s']:.3f}s")
    if tr_undet:
        failures.append(f"{len(tr_undet)} train cells undetected")
    if tr_false:
        failures.append(f"{len(tr_false)} train false detections")
    if tr_incons:
        failures.append(
            f"{len(tr_incons)} train detections outside the oracle decade")
    print(f"wrote {args.out}")
    if failures:
        raise SystemExit("ml acceptance failed: " + "; ".join(failures))
    print("acceptance ok")


if __name__ == "__main__":
    main()
