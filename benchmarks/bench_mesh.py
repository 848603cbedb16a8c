"""Mesh-partitioned shard-runtime head-to-head: 1-D pencils vs 2-D block
meshes, with and without comm/compute-overlapped halo exchange, on real
(host-emulated) JAX shards.

Four cell kinds, all via the campaign cell API (benchmarks/common.py):

1. **parity** (``mesh_parity``, cached) — the synchronous anchor per mesh
   shape: blocking staleness-0 on the block-decomposed mesh runtime must
   match the global synchronous reference trajectory, AND the overlap path
   must be *bitwise* the non-overlap path (the face slabs are swept from
   the same stencil inputs in the same op order, so overlap is free — any
   ULP drift means the slab math diverged from the full sweep).
2. **detection** (``mesh_detect``, cached) — the paper's reliability claim
   across mesh shapes: stale halos, lagged lanes and heterogeneous sweep
   rates on (4,)/(2,2)/(1,4) meshes must detect without lying (final
   exact residual within a decade of ε̃).
3. **wall-time** (``mesh_timed``, never cached) — the tentpole perf claim
   at the acceptance size (n=64, p=4): the 2-D block mesh beats the
   non-overlapped 1-D pencil runtime on wall/iter (gated floor).  All
   variants measured round-robin in one cell; the gated saving is the
   median of per-round ratios (common-mode load cancels).  The overlap
   variant's wall is *reported and regression-tracked* but carries no
   absolute floor on this platform: host-emulated devices share one CPU
   and execute collectives serially, so there is no halo latency for the
   slab pre-ship to hide — its ~12% redundant face compute is visible as
   pure overhead here, while on a real accelerator mesh the same schedule
   puts the exchange behind the interior sweep.
4. **HLO traffic** (``mesh_hbm``, cached per jax version) — the
   deterministic shadow of (3), where the overlap win *is* measurable on
   any platform: shipping faces computed before the fused sweep removes
   the separate post-sweep face-extraction pass, so the overlap variant
   must have the LOWEST HBM bytes per device per outer iteration (gated),
   and every variant stays within the fused single-pass budget (the
   detection residual rides the sweep — no extra HBM pass).  At p=4 the
   (2,2) mesh's wire volume equals the pencil's (4 half-faces = 2 full
   faces), so the wire ratio is gated at ≤ 1.0; the strict surface win
   appears at p ≥ 8, where pencil faces stay n² while block faces shrink.

Writes ``BENCH_mesh.json`` (repo root) or the smoke variant the
``mesh-runtime`` CI job gates against ``benchmarks/baselines/``.

Run:   PYTHONPATH=src:. python benchmarks/bench_mesh.py
Smoke: PYTHONPATH=src:. SHARD_DEVICES=4 python benchmarks/bench_mesh.py --smoke
"""
from __future__ import annotations

import os

# must be set before any jax import (see bench_shard_runtime.py)
_DEV = int(os.environ.get("SHARD_DEVICES", "4"))
_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" {_FLAG}={_DEV}").strip()
for _v in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_v, "1")

import argparse
import statistics
import time
from typing import Dict, Sequence, Tuple


def _ensure_x64():
    import jax

    jax.config.update("jax_enable_x64", True)


#: the timed/HBM variants: (name, mesh_shape, overlap).  "1d" is the
#: historical pencil path (lowering-identical to the pre-mesh runtime);
#: "2d" the block mesh without overlap; "2d_overlap" the tentpole.
VARIANTS: Tuple[Tuple[str, Tuple[int, ...], bool], ...] = (
    ("1d", (4,), False),
    ("2d", (2, 2), False),
    ("2d_overlap", (2, 2), True),
)


def _convdiff_setup(n: int, seed: int = 0, rho: float = 0.9):
    import jax.numpy as jnp

    from repro.solvers.convdiff import Stencil, make_rhs

    st = Stencil.for_contraction(n, 1.0, (1.0, 1.0, 1.0), rho=rho)
    b = jnp.asarray(make_rhs(n, seed=seed))
    return st, b, jnp.zeros_like(b)


def _exact_residual(st, x, b, ord_: float) -> float:
    import numpy as np

    from repro.solvers import jacobi
    from repro.solvers.fixed_point import _zero_ghosts, ghosted

    r = np.asarray(jacobi.residual_block(st, ghosted(x, _zero_ghosts(x)), b),
                   dtype=np.float64)
    if np.isinf(ord_):
        return float(np.max(np.abs(r)))
    return float(np.linalg.norm(r.ravel(), ord=ord_))


def het_knobs(p: int) -> Dict[str, Tuple[int, ...]]:
    """Heterogeneous per-shard asynchrony (pure function of p)."""
    return {"inner_sweeps": tuple(1 + (i % 3) for i in range(p)),
            "halo_delay": tuple(i % 3 for i in range(p)),
            "contrib_lag": tuple(i % 2 for i in range(p))}


# ---------------------------------------------------------------------------
# Cell 1: synchronous parity + overlap bitwise equivalence, per mesh shape
# ---------------------------------------------------------------------------


def mesh_parity(mesh_shape: Sequence[int], n: int, eps: float,
                max_outer: int = 500, trace_len: int = 256,
                rtol: float = 5e-5) -> Dict:
    _ensure_x64()
    import jax
    import numpy as np

    from repro.core import detection
    from repro.launch.mesh import make_shard_mesh
    from repro.runtime import shard_runtime as sr

    shape = tuple(int(s) for s in mesh_shape)
    mesh = make_shard_mesh(shape)
    st, b, x0 = _convdiff_setup(n)
    mon = detection.MonitorConfig(mode="sync", eps=eps, staleness=0, ord=2.0)
    cfg = sr.ShardRuntimeConfig(monitor=mon, reduction="blocking",
                                max_outer=max_outer, trace_len=trace_len,
                                mesh_shape=shape)
    r = jax.jit(sr.make_convdiff_runtime(cfg, mesh, st, n))(x0, b)
    T = min(int(r.outer_iters), trace_len)
    ref = np.asarray(sr.convdiff_reference_trace(st, b, T))
    trace = np.asarray(r.trace)[:T]
    rel = float(np.max(np.abs(trace - ref) / np.maximum(ref, 1e-30)))
    out = {
        "mesh_shape": list(shape), "n": n, "eps": eps,
        "outer_iters": int(r.outer_iters),
        "converged": bool(r.converged),
        "detected_residual": float(r.residual),
        "trace_compared": T,
        "max_rel_trajectory_err": rel,
        "trajectory_ok": bool(r.converged) and rel < rtol,
    }
    # overlap is a pure reordering: the async trajectory must be BITWISE
    # the non-overlap one under heterogeneous knobs (jacobi sweeps only)
    p = int(np.prod(shape))
    monp = detection.MonitorConfig(mode="pfait", eps=eps, staleness=2,
                                   persistence=4, ord=2.0)
    base = dict(monitor=monp, reduction="nonblocking", max_outer=4 * max_outer,
                trace_len=64, mesh_shape=shape, **het_knobs(p))
    r0 = jax.jit(sr.make_convdiff_runtime(
        sr.ShardRuntimeConfig(overlap=False, **base), mesh, st, n))(x0, b)
    r1 = jax.jit(sr.make_convdiff_runtime(
        sr.ShardRuntimeConfig(overlap=True, **base), mesh, st, n))(x0, b)
    out["overlap_bitwise_ok"] = bool(
        bool(r0.converged) and bool(r1.converged)
        and int(r0.outer_iters) == int(r1.outer_iters)
        and np.array_equal(np.asarray(r0.x), np.asarray(r1.x))
        and np.array_equal(np.asarray(r0.trace), np.asarray(r1.trace)))
    return out


# ---------------------------------------------------------------------------
# Cell 2: asynchronous detection reliability across mesh shapes
# ---------------------------------------------------------------------------


def mesh_detect(mesh_shape: Sequence[int], reduction: str, mode: str,
                n: int, seed: int, eps_tilde: float, margin: float = 10.0,
                staleness: int = 2, persistence: int = 4,
                max_outer: int = 3000, factor: float = 10.0) -> Dict:
    """One asynchronous mesh run, scored like the reliability oracle: a
    detection is *false* when the final exact residual exceeds
    ``factor × ε̃``."""
    _ensure_x64()
    import jax
    import numpy as np

    from repro.core import detection
    from repro.launch.mesh import make_shard_mesh
    from repro.runtime import shard_runtime as sr

    shape = tuple(int(s) for s in mesh_shape)
    mesh = make_shard_mesh(shape)
    p = int(np.prod(shape))
    mon = detection.for_mode(mode, eps_tilde=eps_tilde, margin=margin,
                             staleness=staleness, persistence=persistence,
                             ord=2.0)
    cfg = sr.ShardRuntimeConfig(monitor=mon, reduction=reduction,
                                max_outer=max_outer, mesh_shape=shape,
                                overlap=(len(shape) > 1), **het_knobs(p))
    st, b, x0 = _convdiff_setup(n, seed=seed)
    r = jax.jit(sr.make_convdiff_runtime(cfg, mesh, st, n))(x0, b)
    r_star = _exact_residual(st, r.x, b, 2.0)
    terminated = bool(r.converged)
    return {
        "mesh_shape": list(shape), "reduction": reduction, "mode": mode,
        "seed": seed, "eps_tilde": eps_tilde, "staleness": staleness,
        "overlap": len(shape) > 1,
        "terminated": terminated,
        "outer_iters": int(r.outer_iters),
        "detected_residual": float(r.residual) if terminated else None,
        "r_star": r_star,
        "false_detection": bool(terminated and r_star > factor * eps_tilde),
    }


# ---------------------------------------------------------------------------
# Cell 3: wall-time (fixed iterations, detection never fires)
# ---------------------------------------------------------------------------


def mesh_timed(n: int, iters: int, staleness: int = 2,
               repeats: int = 5) -> Dict:
    """All variants in ONE cell, measured round-robin (see
    bench_shard_runtime.shard_timed for why): the gated metric is the
    median per-round wall ratio of the 1-D pencil over the comm-overlapped
    2-D mesh."""
    _ensure_x64()
    import jax

    from repro.core import detection
    from repro.launch.mesh import make_shard_mesh
    from repro.runtime import shard_runtime as sr

    st, b, x0 = _convdiff_setup(n)
    mon = detection.MonitorConfig(mode="pfait", eps=1e-300,
                                  staleness=staleness, ord=2.0)
    runs = {}
    for name, shape, overlap in VARIANTS:
        mesh = make_shard_mesh(shape)
        cfg = sr.ShardRuntimeConfig(monitor=mon, reduction="nonblocking",
                                    max_outer=iters, mesh_shape=shape,
                                    halo_delay=1, overlap=overlap)
        run = jax.jit(sr.make_convdiff_runtime(cfg, mesh, st, n))
        r = run(x0, b)
        jax.block_until_ready(r.x)  # compile + warm
        if int(r.outer_iters) != iters:
            raise RuntimeError(
                f"timed cell detected early: {name} n={n} "
                f"outer={int(r.outer_iters)} != {iters}")
        runs[name] = run
    walls = {name: [] for name, _, _ in VARIANTS}
    for _ in range(repeats):
        for name, _, _ in VARIANTS:
            t0 = time.perf_counter()
            r = runs[name](x0, b)
            jax.block_until_ready(r.x)
            walls[name].append(time.perf_counter() - t0)
    savings = {
        name: float(statistics.median(
            [r1d / w for r1d, w in zip(walls["1d"], walls[name])]))
        for name in walls
    }
    return {
        "n": n, "p": _DEV, "iters": iters, "reference": "1d",
        "modes": {
            name: {
                "mesh_shape": list(shape), "overlap": overlap,
                "wall_s_best": min(walls[name]),
                "wall_s_all": walls[name],
                "us_per_iter": 1e6 * min(walls[name]) / iters,
                "saving_vs_1d": savings[name],
            }
            for name, shape, overlap in VARIANTS
        },
    }


# ---------------------------------------------------------------------------
# Cell 4: HLO-derived traffic per outer iteration (deterministic)
# ---------------------------------------------------------------------------


def mesh_hbm(variant: str, n: int, staleness: int = 2,
             max_outer: int = 500) -> Dict:
    _ensure_x64()
    import jax
    import jax.numpy as jnp

    from repro.core import detection
    from repro.launch import hlo_analysis
    from repro.launch.mesh import make_shard_mesh
    from repro.runtime import shard_runtime as sr

    shape, overlap = {name: (s, ov) for name, s, ov in VARIANTS}[variant]
    mesh = make_shard_mesh(shape)
    mon = detection.MonitorConfig(mode="pfait", eps=1e-7,
                                  staleness=staleness, ord=2.0)
    cfg = sr.ShardRuntimeConfig(monitor=mon, reduction="nonblocking",
                                max_outer=max_outer, mesh_shape=shape,
                                halo_delay=1, overlap=overlap)
    st, b, x0 = _convdiff_setup(n)
    run = sr.make_convdiff_runtime(cfg, mesh, st, n)
    compiled = jax.jit(run).lower(jnp.asarray(x0), jnp.asarray(b)).compile()
    ps = hlo_analysis.program_stats(compiled.as_text(), default_group=_DEV)
    iters = max(ps.loop_trip_max, 1.0)
    return {
        "variant": variant, "mesh_shape": list(shape), "overlap": overlap,
        "n": n, "staleness": staleness,
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
    ap.add_argument("--out", default="BENCH_mesh.json")
    args = ap.parse_args()

    _ensure_x64()
    import jax

    p = len(jax.devices())
    if p != _DEV:
        raise SystemExit(
            f"expected {_DEV} devices (SHARD_DEVICES), jax sees {p} — "
            f"XLA_FLAGS={os.environ.get('XLA_FLAGS')!r} was not honoured "
            "(set before any jax import?)")
    if p != 4:
        raise SystemExit("the mesh bench matrix is written for p=4 "
                         f"((4,)/(2,2)/(1,4) shapes); got {p} devices")
    # the ISSUE acceptance size is n=64 p=4 — the timed cell keeps it even
    # in smoke (fewer iters/repeats); the detect/parity matrix shrinks
    if args.smoke:
        n_cells, timed_iters, repeats = 16, 60, 5
        seeds = (0,)
        detect_modes = ("pfait", "nfais2")
        min_saving = None
    else:
        n_cells, timed_iters, repeats = 32, 100, 7
        seeds = (0, 1)
        detect_modes = ("pfait", "nfais2", "nfais5")
        min_saving = 1.0
    timed_n = 64

    parity_specs = [
        {"kind": "mesh_parity", "mesh_shape": list(shape), "n": n_cells,
         "eps": 1e-7, "max_outer": 500, "trace_len": 192}
        for shape in [(2, 2), (1, 4)]
    ]
    parity_rows = _run(parity_specs)
    parity = {"x".join(map(str, row["mesh_shape"])): row
              for row in parity_rows}

    detect_specs = [
        {"kind": "mesh_detect", "mesh_shape": list(shape),
         "reduction": red, "mode": mode, "n": n_cells, "seed": seed,
         "eps_tilde": 1e-6, "margin": 10.0, "staleness": 2,
         "persistence": 4, "max_outer": 3000}
        for shape in [(4,), (2, 2), (1, 4)]
        for red in ("nonblocking", "rdoubling")
        for mode in detect_modes
        for seed in seeds
    ]
    detect_rows = _run(detect_specs)

    timed_rows = _run([
        {"kind": "mesh_timed", "n": timed_n, "iters": timed_iters,
         "staleness": 2, "repeats": repeats},
    ])[0]["modes"]

    hbm_rows = {r["variant"]: r for r in _run([
        {"kind": "mesh_hbm", "variant": name, "n": timed_n, "staleness": 2}
        for name, _, _ in VARIANTS
    ])}

    wall = dict(timed_rows)
    wall["saving_overlap2d_vs_1d"] = timed_rows["2d_overlap"]["saving_vs_1d"]
    wall["saving_2d_vs_1d"] = timed_rows["2d"]["saving_vs_1d"]
    hbm = dict(hbm_rows)
    hbm["wire_ratio_2d_over_1d"] = (
        hbm_rows["2d"]["wire_bytes_per_iter"]
        / max(hbm_rows["1d"]["wire_bytes_per_iter"], 1.0))

    report = {
        "parity": parity,
        "detect": detect_rows,
        "walltime": wall,
        "hbm": hbm,
        "meta": {"smoke": bool(args.smoke), "devices": p,
                 "jax": jax.__version__,
                 "timestamp": time.strftime("%Y-%m-%d %H:%M:%S")},
    }

    from benchmarks.campaign import write_json_atomic

    write_json_atomic(args.out, report)

    # -- summary + in-script acceptance ------------------------------------
    failures = []
    for name, row in parity.items():
        print(f"parity {name}: outer={row['outer_iters']} "
              f"traj_err={row['max_rel_trajectory_err']:.2e} "
              f"ok={row['trajectory_ok']} "
              f"overlap_bitwise={row['overlap_bitwise_ok']}")
        if not (row["trajectory_ok"] and row["overlap_bitwise_ok"]):
            failures.append(f"parity failed on mesh {name}")
    false_cells = [r for r in detect_rows if r["false_detection"]]
    undetected = [r for r in detect_rows if not r["terminated"]]
    print(f"detect: {len(detect_rows)} cells, {len(false_cells)} false, "
          f"{len(undetected)} undetected")
    sv2d = wall["saving_2d_vs_1d"]
    svov = wall["saving_overlap2d_vs_1d"]
    print(f"wall (n={timed_n}, {timed_iters} iters): "
          + ", ".join(f"{name} {timed_rows[name]['us_per_iter']:.0f}us/it"
                      for name, _, _ in VARIANTS)
          + f" -> 2d saving {sv2d:.2f}x, overlap-2d {svov:.2f}x vs 1d")
    print("hbm/iter: "
          + ", ".join(f"{name} "
                      f"{hbm_rows[name]['hbm_bytes_per_device_per_iter']:.3e}"
                      for name, _, _ in VARIANTS)
          + f" (wire 2d/1d {hbm['wire_ratio_2d_over_1d']:.3f})")
    if false_cells:
        failures.append(f"{len(false_cells)} false detections")
    if undetected:
        failures.append(f"{len(undetected)} undetected cells")
    # at p=4 the (2,2) block mesh's 4 half-faces equal the pencil's 2 full
    # faces, so equality is the break-even point; strictly more wire than
    # the 1-D baseline would mean the partitioner regressed
    if hbm["wire_ratio_2d_over_1d"] > 1.0:
        failures.append("2-D mesh wire traffic exceeds 1-D pencil")
    # deterministic overlap win: pre-shipping faces computed ahead of the
    # fused sweep drops the separate post-sweep face-extraction pass, so
    # overlap must be the cheapest variant in HBM/iter on any platform
    ov_hbm = hbm_rows["2d_overlap"]["hbm_bytes_per_device_per_iter"]
    if any(ov_hbm > hbm_rows[v]["hbm_bytes_per_device_per_iter"]
           for v in ("1d", "2d")):
        failures.append(
            f"overlap HBM/iter {ov_hbm:.3e} is not the lowest variant")
    if min_saving is not None and sv2d < min_saving:
        failures.append(
            f"2-D wall saving {sv2d:.2f}x vs 1-D below target {min_saving}x")
    print(f"wrote {args.out}")
    if failures:
        raise SystemExit("mesh-runtime acceptance failed: "
                         + "; ".join(failures))
    print("acceptance ok")


if __name__ == "__main__":
    main()
