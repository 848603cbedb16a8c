"""Chip smoke test: the main path of this repository, end to end, on a TPU.

Run from the repository root on a machine with a TPU:

    python3 chip_smoke.py               # one chip: phases (a)-(d)
    python3 chip_smoke.py --four-chips  # the four-chip sharded path only

One chip runs four phases through the entry points a user calls, in f32:

(a) kernels  — the four jacobi3d Pallas entries and the residual_norm
    kernel at n=256 against their jnp oracles (max-abs error, f32
    tolerance);
(b) convdiff — ``runtime.api.run_shard`` at n=256 on the 1-D mesh (jnp
    sweeps + the residual_norm kernel) and on the (1,1) block mesh (the
    jacobi3d halo kernels), {blocking+sync, nonblocking+pfait,
    nonblocking+nfais2} x {jacobi, hybrid}: every run must detect, the
    exact residual of the returned x (recomputed in f64 on the host) must
    be <= eps_tilde, and blocking Jacobi must follow the synchronous
    reference trajectory;
(c) pagerank — ``run_shard("pagerank")`` at n=8192 with a dense operator,
    nonblocking + pfait, certified the same way;
(d) service  — ``launch.serve.serve_detection`` with 24 tenants over the
    three families: 0 false detections, 0 timeouts.

``--four-chips`` runs only the path that exists across chips: convdiff
n=256 on the (4,) ring and the (2,2) block mesh (blocking parity against
the one-device reference, nonblocking and rdoubling detection, the
comm-overlapped exchange bitwise equal to the plain one) and PageRank on
(4,), printing which device holds each shard.

Every phase raises on the first failed check.  The last line of stdout is
``{"ok": true, "device": {...}}``; without a TPU, or outside a checkout of
the repository, the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
INF = float("inf")

#: convdiff target precision (l-inf): the f32 residual floor of the
#: for_contraction(rho=0.9) stencil is ~1.1e-6, n-independent, so PFAIT's
#: tightened eps = eps_tilde/10 = 1e-5 sits ~10x above it
CONVDIFF_EPS = 1e-4
#: pagerank target precision (l1): eps = 1e-6 stays ~10x above the f32 floor
PAGERANK_EPS = 1e-5
#: blocking-parity tolerance between two f32 trajectories of the same math
PARITY_RTOL, PARITY_ATOL = 1e-3, 4e-6


class SmokeFailure(RuntimeError):
    """A check of the smoke test failed."""


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Independent host references (f64 numpy, none of the code under test)
# ---------------------------------------------------------------------------


def convdiff_residual_f64(st, x, b) -> float:
    """max|b - A x| over the n^3 grid with zero Dirichlet ghosts, in f64."""
    import numpy as np

    g = np.pad(np.asarray(x, np.float64), 1)
    c = g[1:-1, 1:-1, 1:-1]
    ax = (st.diag * c
          + st.xm * g[:-2, 1:-1, 1:-1] + st.xp * g[2:, 1:-1, 1:-1]
          + st.ym * g[1:-1, :-2, 1:-1] + st.yp * g[1:-1, 2:, 1:-1]
          + st.zm * g[1:-1, 1:-1, :-2] + st.zp * g[1:-1, 1:-1, 2:])
    return float(np.max(np.abs(np.asarray(b, np.float64) - ax)))


def pagerank_residual_f64(P64, d: float, x) -> float:
    """||d P x + (1-d)/n - x||_1 in f64."""
    import numpy as np

    xs = np.asarray(x, np.float64)
    return float(np.sum(np.abs(d * (P64 @ xs) + (1.0 - d) / xs.size - xs)))


# ---------------------------------------------------------------------------
# (a) kernels vs oracles
# ---------------------------------------------------------------------------


def _max_err(a, b) -> float:
    import numpy as np

    return float(np.max(np.abs(np.asarray(a, np.float64)
                                - np.asarray(b, np.float64))))


def _check_close(name: str, got, want, rtol: float) -> None:
    import numpy as np

    err = _max_err(got, want)
    scale = max(float(np.max(np.abs(np.asarray(want, np.float64)))), 1e-30)
    log(f"[a] {name}: max_abs_err={err!r} (scale {scale!r})")
    require(err <= rtol * scale, f"{name}: max-abs error {err} > {rtol} x {scale}")


def phase_kernels(n: int = 256) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.jacobi3d import jacobi3d, ops, ref
    from repro.kernels.residual_norm import ref as rn_ref
    from repro.kernels.residual_norm.residual_norm import diff_norm_partials
    from repro.solvers import gauss_seidel
    from repro.solvers.convdiff import Stencil

    st = Stencil.for_contraction(n, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    coefs = jnp.asarray([st.diag, st.xm, st.xp, st.ym, st.yp, st.zm, st.zp],
                        jnp.float32)
    keys = jax.random.split(jax.random.key(0), 9)
    x = jax.random.normal(keys[0], (n, n, n), jnp.float32)
    b = jax.random.normal(keys[1], (n, n, n), jnp.float32)
    halos = tuple(jax.random.normal(keys[2 + q], (n, n), jnp.float32)
                  for q in range(6))
    g1 = ref.ghosted6_ref(x, halos)
    rtol = 1e-5  # a few ulps of the largest entry (f32 eps ~ 1.2e-7)

    new, parts = jacobi3d.fused_sweep_residual_halo(x, halos, b, coefs)
    new_r, c_r = jax.jit(ref.fused_sweep_residual_halo_ref)(x, halos, b,
                                                            coefs)
    _check_close("fused_sweep_residual_halo new", new, new_r, rtol)
    _check_close("fused_sweep_residual_halo max|r|",
                 ref.reduce_partials(parts), c_r, rtol)

    _, parts = jacobi3d.fused_sweep_residual_halo(x, halos, b, coefs,
                                                  op="residual", linf=False)
    _, c_r = jax.jit(ref.fused_sweep_residual_halo_ref,
                     static_argnames=("op", "linf"))(
        x, halos, b, coefs, op="residual", linf=False)
    _check_close("fused_sweep_residual_halo residual sum r^2",
                 ref.reduce_partials(parts, False), c_r, 1e-4)

    new, parts = jacobi3d.fused_sweep_residual(g1, b, coefs)
    new_r, c_r = jax.jit(ref.fused_sweep_residual_ref)(g1, b, coefs)
    _check_close("fused_sweep_residual new", new, new_r, rtol)
    _check_close("fused_sweep_residual max|r|", ref.reduce_partials(parts),
                 c_r, rtol)

    oxyz = 5
    gs = jax.jit(gauss_seidel.redblack_gs_sweep_residual,
                 static_argnums=(0,))
    new, parts = jacobi3d.fused_rbgs_sweep_residual_halo(
        x, halos, b, coefs, jnp.int32(oxyz), linf=False)
    new_r, r_r = gs(st, g1, b, oxyz, 0, 0)
    _check_close("fused_rbgs_sweep_residual_halo new", new, new_r, rtol)
    _check_close("fused_rbgs_sweep_residual_halo sum r^2",
                 ref.reduce_partials(parts, False),
                 ref.contribution(r_r, False), 1e-4)

    ghosts4 = halos[:4]
    new, parts = jacobi3d.fused_rbgs_sweep_residual(
        ops.ghost_pad2(x, ghosts4), jnp.pad(b, ((1, 1), (1, 1), (0, 0))),
        coefs, jnp.int32(3))
    new_r, r_r = gs(st, ops.ghost_pad1(x, ghosts4), b, 1, 2)
    _check_close("fused_rbgs_sweep_residual new", new, new_r, rtol)
    _check_close("fused_rbgs_sweep_residual max|r|",
                 ref.reduce_partials(parts), ref.contribution(r_r), rtol)

    y = x + 1e-3 * b
    for linf in (True, False):
        got = diff_norm_partials(y, x, linf=linf)
        want = jax.jit(rn_ref.diff_norm_partials_ref,
                       static_argnames=("linf",))(y, x, linf=linf)
        _check_close(f"diff_norm_partials {'linf' if linf else 'l2'}",
                     got, want, rtol)


# ---------------------------------------------------------------------------
# Solves through runtime.api.run_shard
# ---------------------------------------------------------------------------


def _convdiff_problem(n: int):
    import numpy as np

    from repro.solvers.convdiff import Stencil, make_rhs

    st = Stencil.for_contraction(n, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    b = make_rhs(n, seed=0).astype(np.float32)
    return st, b


def _run_convdiff(label, mesh, st, b, reduction, mode, sweep, *,
                  max_outer, **cfg_kw):
    """One certified convdiff solve; returns the report."""
    import numpy as np

    from repro.core import detection
    from repro.runtime.api import RuntimeConfig, run_shard

    n = b.shape[0]
    mon = detection.for_mode(mode, eps_tilde=CONVDIFF_EPS, ord=INF,
                             staleness=0 if mode == "sync" else 2)
    cfg = RuntimeConfig(monitor=mon, reduction=reduction, sweep=sweep,
                        max_outer=max_outer, trace_len=max_outer, **cfg_kw)
    rep = run_shard("convdiff", cfg, mesh, n, np.zeros_like(b), b, stencil=st)
    seg = dict(rep.wall_segments)
    r = convdiff_residual_f64(st, rep.x, b)
    log(f"[convdiff] {label} {reduction}+{mode} {sweep}: "
        f"detect_step={rep.detect_step} build_s={seg['build']!r} "
        f"run_s={seg['run']!r} exact_residual={r!r} (eps_tilde "
        f"{CONVDIFF_EPS})")
    require(rep.converged, f"{label} {reduction}+{mode} {sweep}: no detection "
            f"within {max_outer} steps")
    require(r <= CONVDIFF_EPS, f"{label} {reduction}+{mode} {sweep}: exact "
            f"residual {r} > eps_tilde {CONVDIFF_EPS}")
    return rep


class _ReferenceTrace:
    """``convdiff_reference_trace`` on one device, extended on demand."""

    def __init__(self, st, b):
        self.st, self.b, self.trace = st, b, None

    def __call__(self, steps: int):
        import jax.numpy as jnp
        import numpy as np

        from repro.runtime.shard_runtime import convdiff_reference_trace

        if self.trace is None or len(self.trace) < steps:
            length = -(-steps // 256) * 256   # few distinct scan lengths
            self.trace = np.asarray(convdiff_reference_trace(
                self.st, jnp.asarray(self.b), length, ord=INF))
        return self.trace[:steps]


def _check_parity(label, rep, reference) -> None:
    import numpy as np

    hist = rep.residual_history
    want = reference(len(hist))
    err = np.abs(hist - want)
    worst = float(np.max(err / (PARITY_RTOL * want + PARITY_ATOL)))
    log(f"[parity] {label}: {len(hist)} steps vs the one-device reference, "
        f"max_abs_err={float(np.max(err))!r}, worst err/tol={worst!r}")
    require(worst <= 1.0, f"{label}: blocking trajectory leaves the "
            f"reference (err/tol {worst})")


def phase_convdiff(n: int = 256, max_outer: int = 2000) -> None:
    from repro.launch.mesh import make_shard_mesh

    st, b = _convdiff_problem(n)
    reference = _ReferenceTrace(st, b)
    meshes = (("1-D (1,)", make_shard_mesh(1), {}),
              ("block (1,1)", make_shard_mesh((1, 1)),
               {"mesh_shape": (1, 1)}))
    for label, mesh, kw in meshes:
        for reduction, mode in (("blocking", "sync"),
                                ("nonblocking", "pfait"),
                                ("nonblocking", "nfais2")):
            for sweep in ("jacobi", "hybrid"):
                rep = _run_convdiff(label, mesh, st, b, reduction, mode,
                                    sweep, max_outer=max_outer, **kw)
                if reduction == "blocking" and sweep == "jacobi":
                    _check_parity(f"{label} blocking jacobi", rep, reference)


def _run_pagerank(label, mesh, n: int, max_outer: int = 2000):
    import numpy as np

    from repro.core import detection
    from repro.runtime.api import RuntimeConfig, run_shard
    from repro.solvers.pagerank import PageRankProblem

    prob = PageRankProblem(n=n, p=4, seed=0)
    P64 = prob.to_dense()
    P = P64.astype(np.float32)
    mon = detection.for_mode("pfait", eps_tilde=PAGERANK_EPS, ord=1.0,
                             staleness=2)
    cfg = RuntimeConfig(monitor=mon, reduction="nonblocking",
                        max_outer=max_outer, trace_len=max_outer)
    x0 = np.full((n,), 1.0 / n, np.float32)
    rep = run_shard("pagerank", cfg, mesh, n, x0, P, damping=prob.d)
    seg = dict(rep.wall_segments)
    r = pagerank_residual_f64(P64, prob.d, rep.x)
    log(f"[pagerank] {label} n={n} dense P {P.nbytes} bytes, "
        f"nonblocking+pfait: detect_step={rep.detect_step} "
        f"build_s={seg['build']!r} run_s={seg['run']!r} "
        f"exact_residual={r!r} (eps_tilde {PAGERANK_EPS})")
    require(rep.converged, f"pagerank {label}: no detection")
    require(r <= PAGERANK_EPS, f"pagerank {label}: exact residual {r} > "
            f"eps_tilde {PAGERANK_EPS}")
    return rep


def phase_pagerank(n: int = 8192) -> None:
    from repro.launch.mesh import make_shard_mesh

    _run_pagerank("1-D (1,)", make_shard_mesh(1), n)


# ---------------------------------------------------------------------------
# (d) the multi-tenant detection service
# ---------------------------------------------------------------------------

#: (family, problem kwargs, eps_tilde grid): grids sit >= 10x above each
#: family's f32 residual floor after PFAIT's margin of 10
SERVICE_MIX = (
    ("convdiff", {"n": 32, "p": 4, "rho": 0.9}, (1e-3, 1e-4)),
    ("pagerank", {"n": 1024, "p": 4}, (1e-4, 1e-5)),
    ("mlfixed", {"n": 64, "p": 4, "m_rows": 192, "cond": 10.0},
     (1e-3, 1e-4)),
)
SERVICE_MODES = ("pfait", "nfais5", "nfais2", "sync")


def service_requests(tenants: int, mix=SERVICE_MIX):
    """A seeded tenant mix: families round-robin, modes and eps_tilde
    cycled, four arrivals per tick."""
    from repro.launch.serve import TenantSpec

    reqs = []
    for i in range(tenants):
        family, problem, grid = mix[i % len(mix)]
        spec = TenantSpec(
            tenant=f"t{i:02d}", family=family, problem=problem, seed=i,
            eps_tilde=grid[(i // len(mix)) % len(grid)],
            mode=SERVICE_MODES[(i // len(mix)) % len(SERVICE_MODES)],
            staleness=i % 4)
        reqs.append((spec, i // 4))
    return reqs


def phase_service(tenants: int = 24, mix=SERVICE_MIX) -> None:
    from repro.launch.serve import ServeConfig, serve_detection

    t0 = time.perf_counter()
    rep = serve_detection(service_requests(tenants, mix),
                          ServeConfig(lanes=8, chunk=16, max_steps=4096))
    wall = time.perf_counter() - t0
    log(f"[service] tenants={tenants} served={rep.served} "
        f"timeouts={rep.timeouts} false={rep.false_detections} "
        f"rejected={rep.rejected} compiles={rep.compile_count} "
        f"warm_hits={rep.warm_hits} ticks={rep.ticks} "
        f"serve_wall_s={rep.wall_s!r} total_wall_s={wall!r}")
    require(rep.served == tenants, f"service served {rep.served}/{tenants}")
    require(rep.timeouts == 0, f"service timeouts: {rep.timeouts}")
    require(rep.false_detections == 0,
            f"service false detections: {rep.false_detections}")


# ---------------------------------------------------------------------------
# --four-chips
# ---------------------------------------------------------------------------


def _placement(label, mesh, x) -> None:
    """Print which device holds each shard; all shards on distinct chips."""
    shards = sorted((tuple(sl.start or 0 for sl in s.index), s.device)
                    for s in x.addressable_shards)
    where = ", ".join(f"{start}->dev{d.id}{tuple(getattr(d, 'coords', ()))}"
                      for start, d in shards)
    log(f"[placement] {label}: {where}")
    require(len({d.id for _, d in shards}) == mesh.devices.size,
            f"{label}: shards share a device")


def _linked(a, b) -> bool:
    return sum(abs(p - q) for p, q in zip(a.coords, b.coords)) == 1


def _check_ring(mesh) -> None:
    ring = list(mesh.devices.flat)
    hops = [_linked(ring[k], ring[(k + 1) % len(ring)])
            for k in range(len(ring))]
    log(f"[mesh] (4,) ring {[d.id for d in ring]}: every hop linked = "
        f"{all(hops)}")
    require(all(hops), "the (4,) ring has a hop between unlinked chips")


def phase_four_chips(n: int = 256, pagerank_n: int = 8192,
                     max_outer: int = 2000) -> None:
    import numpy as np

    from repro.launch.mesh import make_shard_mesh

    st, b = _convdiff_problem(n)
    reference = _ReferenceTrace(st, b)
    ring = make_shard_mesh(4)
    block = make_shard_mesh((2, 2))
    if hasattr(ring.devices.flat[0], "coords"):
        _check_ring(ring)
    for label, mesh, kw in (("ring (4,)", ring, {}),
                            ("block (2,2)", block, {"mesh_shape": (2, 2)})):
        rep = _run_convdiff(label, mesh, st, b, "blocking", "sync", "jacobi",
                            max_outer=max_outer, **kw)
        _check_parity(f"{label} blocking jacobi", rep, reference)
        _placement(label, mesh, rep.x)
        plain = _run_convdiff(label, mesh, st, b, "nonblocking", "pfait",
                              "jacobi", max_outer=max_outer, **kw)
        _run_convdiff(label, mesh, st, b, "rdoubling", "pfait", "jacobi",
                      max_outer=max_outer, **kw)
    over = _run_convdiff("block (2,2) overlap", block, st, b, "nonblocking",
                         "pfait", "jacobi", max_outer=max_outer, overlap=True,
                         mesh_shape=(2, 2))
    same = (np.array_equal(np.asarray(over.x), np.asarray(plain.x))
            and over.outer_iters == plain.outer_iters
            and np.array_equal(over.residual_history, plain.residual_history))
    log(f"[overlap] block (2,2): overlap vs plain bitwise equal = {same} "
        f"(outer_iters {over.outer_iters} vs {plain.outer_iters})")
    require(same, "overlap=True run differs from the plain exchange")
    rep = _run_pagerank("ring (4,)", ring, pagerank_n, max_outer)
    _placement("pagerank ring (4,)", ring, rep.x)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded path")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: run from a checkout of the repository "
              f"(no src/repro next to {Path(__file__).name})", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); this test runs only on the chip",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: --four-chips needs 4 TPU devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    log(f"[device] {devices[0].device_kind} x{len(devices)}, "
        f"jax {jax.__version__}, compile cache {cache}")
    t0 = time.perf_counter()
    if args.four_chips:
        phases = (("four-chips", phase_four_chips),)
    else:
        phases = (("a kernels", phase_kernels), ("b convdiff", phase_convdiff),
                  ("c pagerank", phase_pagerank), ("d service", phase_service))
    for name, phase in phases:
        t = time.perf_counter()
        phase()
        log(f"[phase] {name} passed in {time.perf_counter() - t!r} s")
    log(f"[total] {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
