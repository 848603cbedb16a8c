"""On-device shard-runtime head-to-head: blocking vs non-blocking reduction
vs recursive doubling, on real (host-emulated) JAX shards.

Four cell kinds, all via the campaign cell API (benchmarks/common.py):

1. **parity** (``shard_parity``, cached) — the synchronous anchor: in
   blocking staleness-0 mode the runtime's residual trajectory must match
   the global synchronous reference to float tolerance, and (convdiff) the
   detection point must match the sharded reference driver
   (solvers/fixed_point.py).  If this fails nothing else means anything.
2. **detection** (``shard_detect``, cached) — the paper's reliability
   claim on device: non-blocking / recursive-doubling reductions under
   stale halos, k-lagged lanes and heterogeneous sweep rates must detect
   without lying (final exact residual within a decade of ε̃).
3. **wall-time** (``shard_timed``, never cached) — the paper's performance
   claim: blocking detection pays an extra residual pass + an immediately
   consumed reduction every check; non-blocking detection is free.  Fixed
   iteration count, all modes measured round-robin in one cell, the gated
   saving is the median of per-round ratios (common-mode load cancels).
4. **HLO traffic** (``shard_hbm``, cached per jax version) — the
   deterministic shadow of (3): HBM bytes per device per outer iteration,
   exact-matched by the CI gate (wall-clock on shared runners is floored,
   bytes are not).

Writes ``BENCH_shard.json`` (repo root) or the smoke variant the
``shard-runtime`` CI job gates against ``benchmarks/baselines/``.

Run:   PYTHONPATH=src:. python benchmarks/bench_shard_runtime.py
Smoke: PYTHONPATH=src:. SHARD_DEVICES=4 python benchmarks/bench_shard_runtime.py --smoke
"""
from __future__ import annotations

import os

# the runtime needs >1 device; must be set before any jax import.  Append
# to (never clobber, never be clobbered by) a pre-existing XLA_FLAGS — a
# setdefault would silently leave the bench on 1 device and produce a
# structurally-valid-but-meaningless report (main() re-asserts the count).
_DEV = int(os.environ.get("SHARD_DEVICES", "4"))
_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" {_FLAG}={_DEV}").strip()
# one BLAS thread per process (see reliability_matrix.py)
for _v in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_v, "1")

import argparse
import statistics
import time
from typing import Dict, Sequence, Tuple


def _ensure_x64():
    import jax

    jax.config.update("jax_enable_x64", True)


# ---------------------------------------------------------------------------
# Shared builders
# ---------------------------------------------------------------------------


#: per-shard asynchrony presets (pure functions of p, JSON-addressable by
#: name): "uniform" is the synchronous reference shape, "stale" adds
#: delayed neighbour views + lagged reduction lanes, "het" additionally
#: lets shards advance at different sweep rates.
def het_preset(name: str, p: int) -> Dict[str, Tuple[int, ...]]:
    if name == "uniform":
        return {"inner_sweeps": (1,) * p, "halo_delay": (0,) * p,
                "contrib_lag": (0,) * p}
    if name == "stale":
        return {"inner_sweeps": (1,) * p,
                "halo_delay": tuple(i % 3 for i in range(p)),
                "contrib_lag": tuple((i + 1) % 2 for i in range(p))}
    if name == "het":
        return {"inner_sweeps": tuple(1 + (i % 3) for i in range(p)),
                "halo_delay": tuple(i % 3 for i in range(p)),
                "contrib_lag": tuple(i % 2 for i in range(p))}
    raise KeyError(name)


def _monitor(mode: str, eps_tilde: float, margin: float, staleness: int,
             persistence: int, ord_: float):
    from repro.core import detection

    return detection.for_mode(mode, eps_tilde=eps_tilde, margin=margin,
                              staleness=staleness, persistence=persistence,
                              ord=ord_)


def _convdiff_setup(n: int, seed: int = 0, rho: float = 0.9):
    import jax.numpy as jnp

    from repro.solvers.convdiff import Stencil, make_rhs

    st = Stencil.for_contraction(n, 1.0, (1.0, 1.0, 1.0), rho=rho)
    b = jnp.asarray(make_rhs(n, seed=seed))
    return st, b, jnp.zeros_like(b)


def _convdiff_exact_residual(st, x, b, ord_: float) -> float:
    """Ground-truth r(x̄) in f64 (no f32 contribution floor)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.solvers import jacobi
    from repro.solvers.fixed_point import _zero_ghosts, ghosted

    r = np.asarray(jacobi.residual_block(st, ghosted(x, _zero_ghosts(x)), b),
                   dtype=np.float64)
    if np.isinf(ord_):
        return float(np.max(np.abs(r)))
    return float(jnp.linalg.norm(r.ravel(), ord=ord_))


def _pagerank_setup(n: int, p: int, seed: int):
    import jax.numpy as jnp

    from repro.solvers.pagerank import PageRankProblem

    prob = PageRankProblem(n=n, p=p, seed=seed)
    return prob, jnp.asarray(prob.to_dense()), jnp.full((n,), 1.0 / n)


def _runtime(family: str, cfg, mesh, n: int, st=None, damping: float = 0.85):
    from repro.runtime.shard_runtime import (
        make_convdiff_runtime,
        make_pagerank_runtime,
    )

    if family == "convdiff":
        return make_convdiff_runtime(cfg, mesh, st, n)
    if family == "pagerank":
        return make_pagerank_runtime(cfg, mesh, n, damping)
    raise KeyError(family)


# ---------------------------------------------------------------------------
# Cell 1: synchronous parity (trajectory + reference-driver detection point)
# ---------------------------------------------------------------------------


def shard_parity(family: str, n: int, p: int, eps: float,
                 max_outer: int = 500, trace_len: int = 256,
                 rtol: float = 5e-5) -> Dict:
    _ensure_x64()
    import jax
    import numpy as np

    from repro.core import detection
    from repro.launch.mesh import make_shard_mesh
    from repro.runtime import shard_runtime as sr

    mesh = make_shard_mesh(p)
    ord_ = 2.0 if family == "convdiff" else 1.0
    mon = detection.MonitorConfig(mode="sync", eps=eps, staleness=0, ord=ord_)
    cfg = sr.ShardRuntimeConfig(monitor=mon, reduction="blocking",
                                max_outer=max_outer, trace_len=trace_len)
    if family == "convdiff":
        st, b, x0 = _convdiff_setup(n)
        run = jax.jit(_runtime(family, cfg, mesh, n, st=st))
        r = run(x0, b)
        T = min(int(r.outer_iters), trace_len)
        ref = np.asarray(sr.convdiff_reference_trace(st, b, T, ord=ord_))
    else:
        prob, P_dense, x0 = _pagerank_setup(n, p, seed=0)
        run = jax.jit(_runtime(family, cfg, mesh, n, damping=prob.d))
        r = run(x0, P_dense)
        T = min(int(r.outer_iters), trace_len)
        ref = np.asarray(sr.pagerank_reference_trace(
            P_dense, n, T, damping=prob.d, ord=ord_))
    trace = np.asarray(r.trace)[:T]
    rel = float(np.max(np.abs(trace - ref) / np.maximum(ref, 1e-30)))
    out = {
        "family": family, "n": n, "p": p, "eps": eps,
        "outer_iters": int(r.outer_iters),
        "converged": bool(r.converged),
        "detected_residual": float(r.residual),
        "trace_compared": T,
        "max_rel_trajectory_err": rel,
        "trajectory_ok": bool(r.converged) and rel < rtol,
    }
    if family == "convdiff":
        out.update(_driver_reference(n, p, eps, max_outer, st, b, x0, r, rtol))
    return out


def _driver_reference(n, p, eps, max_outer, st, b, x0, r, rtol) -> Dict:
    """Detection-point parity against the sharded reference driver."""
    import jax

    from repro.core import detection
    from repro.launch.mesh import make_mesh
    from repro.solvers.fixed_point import SolverConfig, make_sharded_solver
    from repro.solvers.partition import process_grid

    px, py = process_grid(p)
    mesh2d = make_mesh((px, py), ("data", "model"))
    mon = detection.MonitorConfig(mode="sync", eps=eps, staleness=0, ord=2.0)
    dcfg = SolverConfig(stencil=st, monitor=mon, inner_sweeps=1,
                        max_outer=max_outer, sweep="jacobi",
                        fuse_residual=False)
    ref = jax.jit(make_sharded_solver(dcfg, mesh2d))(x0, b)
    same_outer = int(ref.outer_iters) == int(r.outer_iters)
    rel = abs(float(ref.residual) - float(r.residual)) / max(
        float(ref.residual), 1e-30)
    return {
        "driver_outer_iters": int(ref.outer_iters),
        "driver_detected_residual": float(ref.residual),
        "driver_residual_rel_err": rel,
        "driver_match": same_outer and rel < rtol,
    }


# ---------------------------------------------------------------------------
# Cell 2: asynchronous detection reliability
# ---------------------------------------------------------------------------


def shard_detect(family: str, reduction: str, mode: str, preset: str,
                 n: int, p: int, seed: int, eps_tilde: float,
                 margin: float = 10.0, staleness: int = 2,
                 persistence: int = 4, max_outer: int = 2000,
                 factor: float = 10.0) -> Dict:
    """One asynchronous run, scored like the reliability oracle: a detection
    is *false* when the final exact residual exceeds ``factor × ε̃``."""
    _ensure_x64()
    import jax

    from repro.launch.mesh import make_shard_mesh
    from repro.runtime import shard_runtime as sr

    mesh = make_shard_mesh(p)
    ord_ = 2.0 if family == "convdiff" else 1.0
    mon = _monitor(mode, eps_tilde, margin, staleness, persistence, ord_)
    cfg = sr.ShardRuntimeConfig(monitor=mon, reduction=reduction,
                                max_outer=max_outer, **het_preset(preset, p))
    if family == "convdiff":
        st, b, x0 = _convdiff_setup(n, seed=seed)
        r = jax.jit(_runtime(family, cfg, mesh, n, st=st))(x0, b)
        r_star = _convdiff_exact_residual(st, r.x, b, ord_)
    else:
        prob, P_dense, x0 = _pagerank_setup(n, p, seed=seed)
        r = jax.jit(_runtime(family, cfg, mesh, n, damping=prob.d))(
            x0, P_dense)
        import numpy as np

        xs = np.asarray(r.x, dtype=np.float64)
        rv = prob.d * (np.asarray(P_dense, np.float64) @ xs) + prob.v - xs
        r_star = float(np.sum(np.abs(rv) ** ord_) ** (1.0 / ord_))
    terminated = bool(r.converged)
    return {
        "family": family, "reduction": reduction, "mode": mode,
        "preset": preset, "seed": seed, "eps_tilde": eps_tilde,
        "eps": mon.eps, "staleness": staleness,
        "terminated": terminated,
        "outer_iters": int(r.outer_iters),
        "local_sweeps": [int(s) for s in r.local_sweeps],
        "detected_residual": float(r.residual) if terminated else None,
        "r_star": r_star,
        "verifications": int(r.verifications),
        "false_detection": bool(terminated and r_star > factor * eps_tilde),
    }


# ---------------------------------------------------------------------------
# Cell 3: wall-time (fixed iterations, detection never fires)
# ---------------------------------------------------------------------------


def shard_timed(reductions: Sequence[str], n: int, p: int, iters: int,
                staleness: int = 2, repeats: int = 5) -> Dict:
    """All modes in ONE cell, measured round-robin: shared-runner load
    drifts on the scale of seconds, so interleaving the modes decorrelates
    the drift from the blocking/non-blocking ratio (the gated metric) in a
    way per-mode cells cannot."""
    _ensure_x64()
    import jax

    from repro.core import detection
    from repro.launch.mesh import make_shard_mesh
    from repro.runtime import shard_runtime as sr

    mesh = make_shard_mesh(p)
    st, b, x0 = _convdiff_setup(n)
    runs = {}
    for reduction in reductions:
        mode = "sync" if reduction == "blocking" else "pfait"
        K = staleness if reduction == "nonblocking" else 0
        mon = detection.MonitorConfig(mode=mode, eps=1e-300, staleness=K,
                                      ord=2.0)
        cfg = sr.ShardRuntimeConfig(monitor=mon, reduction=reduction,
                                    max_outer=iters)
        run = jax.jit(_runtime("convdiff", cfg, mesh, n, st=st))
        r = run(x0, b)
        jax.block_until_ready(r.x)  # compile + warm
        if int(r.outer_iters) != iters:
            raise RuntimeError(
                f"timed cell detected early: {reduction} n={n} "
                f"outer={int(r.outer_iters)} != {iters}")
        runs[reduction] = (run, K)
    walls = {reduction: [] for reduction in reductions}
    for _ in range(repeats):
        for reduction in reductions:
            run, _K = runs[reduction]
            t0 = time.perf_counter()
            r = run(x0, b)
            jax.block_until_ready(r.x)
            walls[reduction].append(time.perf_counter() - t0)
    # the gated ratio is the MEDIAN of per-round ratios: within one round
    # both modes see ~the same machine load, so common-mode drift cancels;
    # independent best-of would pair one mode's lucky run with the other's
    # unlucky one
    ref = reductions[0]
    savings = {
        reduction: float(statistics.median(
            [rw / w for rw, w in zip(walls[ref], walls[reduction])]))
        for reduction in reductions
    }
    return {
        "n": n, "p": p, "iters": iters, "reference": ref,
        "modes": {
            reduction: {
                "reduction": reduction, "staleness": runs[reduction][1],
                "wall_s_best": min(w),
                "wall_s_all": w,
                "us_per_iter": 1e6 * min(w) / iters,
                "saving_vs_" + ref: savings[reduction],
            }
            for reduction, w in walls.items()
        },
    }



# ---------------------------------------------------------------------------
# Cell 4: HLO-derived HBM traffic per outer iteration (deterministic)
# ---------------------------------------------------------------------------


def shard_hbm(reduction: str, n: int, p: int, staleness: int = 2,
              max_outer: int = 500) -> Dict:
    _ensure_x64()
    import jax
    import jax.numpy as jnp

    from repro.core import detection
    from repro.launch import hlo_analysis
    from repro.launch.mesh import make_shard_mesh
    from repro.runtime import shard_runtime as sr

    mesh = make_shard_mesh(p)
    mode = "sync" if reduction == "blocking" else "pfait"
    K = staleness if reduction == "nonblocking" else 0
    mon = detection.MonitorConfig(mode=mode, eps=1e-7, staleness=K, ord=2.0)
    cfg = sr.ShardRuntimeConfig(monitor=mon, reduction=reduction,
                                max_outer=max_outer)
    st, b, x0 = _convdiff_setup(n)
    run = _runtime("convdiff", cfg, mesh, n, st=st)
    compiled = jax.jit(run).lower(
        jnp.asarray(x0), jnp.asarray(b)).compile()
    ps = hlo_analysis.program_stats(compiled.as_text(), default_group=p)
    iters = max(ps.loop_trip_max, 1.0)
    return {
        "reduction": reduction, "n": n, "p": p, "staleness": K,
        "hbm_bytes_per_device_per_iter": ps.hbm_bytes / iters,
        "wire_bytes_per_iter": ps.total_wire_bytes / iters,
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


def main():
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes + reduced matrix (CI)")
    ap.add_argument("--parity-only", action="store_true",
                    help="run only the synchronous parity cells (sanity "
                         "lane on alternative device counts)")
    ap.add_argument("--out", default="BENCH_shard.json")
    args = ap.parse_args()

    _ensure_x64()
    import jax

    p = len(jax.devices())
    if p != _DEV:
        raise SystemExit(
            f"expected {_DEV} devices (SHARD_DEVICES), jax sees {p} — "
            f"XLA_FLAGS={os.environ.get('XLA_FLAGS')!r} was not honoured "
            "(set before any jax import?)")
    if args.smoke or args.parity_only:
        n_cd, n_pr = 16, 256
        timed_n, timed_iters, repeats = 48, 120, 9
        seeds = (0,)
        detect_modes = ("pfait", "nfais2")
        min_saving = None
    else:
        n_cd, n_pr = 32, 512
        timed_n, timed_iters, repeats = 64, 100, 7
        seeds = (0, 1, 2)
        detect_modes = ("pfait", "nfais2", "nfais5")
        min_saving = 1.0
    if n_cd % p or n_pr % p:
        raise SystemExit(f"device count {p} must divide n={n_cd}/{n_pr}")

    parity_specs = [
        {"kind": "shard_parity", "family": "convdiff", "n": n_cd, "p": p,
         "eps": 1e-7, "max_outer": 500, "trace_len": 192},
        {"kind": "shard_parity", "family": "pagerank", "n": n_pr, "p": p,
         "eps": 1e-9, "max_outer": 500, "trace_len": 192},
    ]
    parity_rows = _run(parity_specs)
    parity = {row["family"]: row for row in parity_rows}
    report = {
        "parity": parity,
        "meta": {"smoke": bool(args.smoke),
                 "parity_only": bool(args.parity_only),
                 "devices": p, "jax": jax.__version__,
                 "timestamp": time.strftime("%Y-%m-%d %H:%M:%S")},
    }

    parity_ok = all(
        row["trajectory_ok"] and row.get("driver_match", True)
        for row in parity_rows)

    if not args.parity_only:
        detect_specs = [
            {"kind": "shard_detect", "family": fam, "reduction": red,
             "mode": mode, "preset": preset, "n": (n_cd if fam == "convdiff"
                                                   else n_pr),
             "p": p, "seed": seed,
             "eps_tilde": 1e-6 if fam == "convdiff" else 1e-8,
             "margin": 10.0, "staleness": 2, "persistence": 4,
             "max_outer": 3000}
            for fam in ("convdiff", "pagerank")
            for red in ("nonblocking", "rdoubling")
            for mode in detect_modes
            for preset in (("stale",) if args.smoke else ("stale", "het"))
            for seed in seeds
        ]
        detect_rows = _run(detect_specs)

        timed_specs = [
            {"kind": "shard_timed",
             "reductions": ["blocking", "nonblocking", "rdoubling"],
             "n": timed_n, "p": p, "iters": timed_iters, "staleness": 2,
             "repeats": repeats},
        ]
        timed_rows = _run(timed_specs)[0]["modes"]

        hbm_specs = [
            {"kind": "shard_hbm", "reduction": red, "n": timed_n, "p": p,
             "staleness": 2}
            for red in ("blocking", "nonblocking", "rdoubling")
        ]
        hbm_rows = {r["reduction"]: r for r in _run(hbm_specs)}

        wall = {
            red: timed_rows[red] for red in timed_rows
        }
        wall["saving_nonblocking_vs_blocking"] = (
            timed_rows["nonblocking"]["saving_vs_blocking"])
        wall["saving_rdoubling_vs_blocking"] = (
            timed_rows["rdoubling"]["saving_vs_blocking"])
        hbm = dict(hbm_rows)
        hbm["ratio_nonblocking_over_blocking"] = (
            hbm_rows["nonblocking"]["hbm_bytes_per_device_per_iter"]
            / hbm_rows["blocking"]["hbm_bytes_per_device_per_iter"])
        report.update({
            "detect": detect_rows,
            "walltime": wall,
            "hbm": hbm,
        })

    from benchmarks.campaign import write_json_atomic

    write_json_atomic(args.out, report)

    # -- summary + in-script acceptance ------------------------------------
    for fam, row in parity.items():
        extra = ("" if "driver_match" not in row else
                 f", driver_match={row['driver_match']}")
        print(f"parity {fam:9s}: outer={row['outer_iters']} "
              f"traj_err={row['max_rel_trajectory_err']:.2e} "
              f"ok={row['trajectory_ok']}{extra}")
    failures = [] if parity_ok else ["synchronous parity failed"]
    if not args.parity_only:
        false_cells = [r for r in detect_rows if r["false_detection"]]
        undetected = [r for r in detect_rows if not r["terminated"]]
        print(f"detect: {len(detect_rows)} cells, "
              f"{len(false_cells)} false, {len(undetected)} undetected")
        sv = wall["saving_nonblocking_vs_blocking"]
        print(f"wall (n={timed_n}, {timed_iters} iters): "
              + ", ".join(f"{red} {timed_rows[red]['us_per_iter']:.0f}us/it"
                          for red in ("blocking", "nonblocking", "rdoubling"))
              + f" -> non-blocking saving {sv:.2f}x")
        print(f"hbm/iter: "
              + ", ".join(f"{red} {hbm_rows[red]['hbm_bytes_per_device_per_iter']:.3e}"
                          for red in ("blocking", "nonblocking", "rdoubling"))
              + f" (nb/blocking {hbm['ratio_nonblocking_over_blocking']:.3f})")
        if false_cells:
            failures.append(f"{len(false_cells)} false detections")
        if undetected:
            failures.append(f"{len(undetected)} undetected cells")
        if hbm["ratio_nonblocking_over_blocking"] >= 1.0:
            failures.append("non-blocking did not reduce HBM traffic")
        if min_saving is not None and sv < min_saving:
            failures.append(
                f"wall saving {sv:.2f}x below target {min_saving}x")
    print(f"wrote {args.out}")
    if failures:
        raise SystemExit("shard-runtime acceptance failed: "
                         + "; ".join(failures))
    print("acceptance ok")


if __name__ == "__main__":
    main()
