"""Fused sweep+residual hot path: parity + structure regression tests.

Covers the three layers of the fusion:
  * numpy event-sim problem  — ``update_with_residual`` ≡ (update, local_residual)
  * jnp/Pallas driver ops    — ``sweep_with_contribution`` ≡ sweep + residual pass
  * solver drivers           — one fused grid pass per outer iteration, no
                               residual-only second pass (PASS_COUNTS + HLO bytes)
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import detection
from repro.kernels.jacobi3d import ops as jac_ops
from repro.kernels.jacobi3d.jacobi3d import (
    _slab_planes,
    fused_rbgs_sweep_residual,
    fused_rbgs_sweep_residual_halo,
)
from repro.kernels.jacobi3d.ref import contribution, ghosted6_ref, reduce_partials
from repro.solvers import gauss_seidel, jacobi
from repro.solvers.convdiff import ConvDiffProblem, Stencil, make_rhs
from repro.solvers.fixed_point import SolverConfig, make_sharded_solver, solve_single

RNG = np.random.default_rng(0)


def _random_state(prob):
    xs = [prob.init_local(i) + RNG.standard_normal(prob.part.block)
          for i in range(prob.p)]
    deps = [{j: prob.interface(j, xs[j], i) for j in prob.neighbors(i)}
            for i in range(prob.p)]
    return xs, deps


# ---------------------------------------------------------------------------
# Event-sim problem parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sweep", ["hybrid", "jacobi"])
@pytest.mark.parametrize("ordv", [float("inf"), 2.0])
def test_update_with_residual_matches_pair(sweep, ordv):
    prob = ConvDiffProblem(n=12, p=4, rho=0.9, seed=1, ord=ordv, sweep=sweep)
    xs, deps = _random_state(prob)
    for i in range(prob.p):
        x_ref = prob.update(i, xs[i], deps[i])
        r_ref = prob.local_residual(i, xs[i], deps[i])
        x_new, r_i = prob.update_with_residual(i, xs[i], deps[i])
        np.testing.assert_allclose(x_new, x_ref, atol=1e-13)
        assert r_i == pytest.approx(r_ref, rel=1e-12)
        # the residual-skipping (checkerboard-sliced) path must produce the
        # identical sweep
        x_new2, r2 = prob.update_with_residual(i, xs[i], deps[i],
                                               need_residual=False)
        assert r2 is None
        np.testing.assert_allclose(x_new2, x_ref, atol=1e-13)


def test_local_residual_fast_matches():
    prob = ConvDiffProblem(n=12, p=4, rho=0.9, seed=2)
    xs, deps = _random_state(prob)
    for i in range(prob.p):
        assert prob.local_residual_fast(i, xs[i], deps[i]) == pytest.approx(
            prob.local_residual(i, xs[i], deps[i]), rel=1e-12)


# ---------------------------------------------------------------------------
# Driver ops parity (ref mode — off-TPU dispatch)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sweep", ["hybrid", "jacobi"])
@pytest.mark.parametrize("ordv", [float("inf"), 2.0])
def test_sweep_with_contribution_matches_separate_passes(sweep, ordv):
    st = Stencil.for_contraction(8, 1.0, (1.0, 1.0, 1.0), 0.9)
    bx, by, bz = 8, 8, 8
    x = jnp.asarray(RNG.standard_normal((bx, by, bz)))
    b = jnp.asarray(RNG.standard_normal((bx, by, bz)))
    ghosts = (jnp.asarray(RNG.standard_normal((by, bz))),
              jnp.asarray(RNG.standard_normal((by, bz))),
              jnp.asarray(RNG.standard_normal((bx, bz))),
              jnp.asarray(RNG.standard_normal((bx, bz))))
    new_f, contrib = jac_ops.sweep_with_contribution(
        st, x, ghosts, b, sweep=sweep, ox=3, oy=5, ord=ordv)
    new_s = jac_ops.sweep(st, x, ghosts, b, sweep=sweep, ox=3, oy=5)
    # the fused contribution measures the *input* state's residual
    contrib_s = jac_ops.residual_contribution(
        st, jac_ops.ghost_pad1(x, ghosts), b, ord=ordv)
    np.testing.assert_allclose(np.asarray(new_f), np.asarray(new_s), atol=1e-12)
    assert float(contrib) == pytest.approx(float(contrib_s), rel=1e-5)


@pytest.mark.parametrize("ox,oy", [(0, 0), (3, 5), (6, 2)])
@pytest.mark.parametrize("linf", [True, False])
def test_rbgs_kernel_interpret_matches_oracle(ox, oy, linf):
    """Pallas single-pass hybrid kernel (interpret=True) vs the pure-jnp
    oracle — two x-slabs exercise the cross-slab colour dependency."""
    _check_rbgs_kernel((16, 8, 8), ox, oy, linf)


@pytest.mark.parametrize("block", [(5, 256, 256), (24, 8, 8)])
@pytest.mark.parametrize("ox,oy", [(0, 0), (3, 5), (6, 2)])
@pytest.mark.parametrize("linf", [True, False])
def test_rbgs_kernel_window_warmup_and_drain(block, ox, oy, linf):
    """The streamed kernel's window fills and drains at the block's x
    faces: one-plane slabs (a 512 KiB f64 plane), five of them, and three
    8-plane slabs, the middle one with neighbours on both sides."""
    _check_rbgs_kernel(block, ox, oy, linf)


@pytest.mark.parametrize("donate", [True, False])
def test_rbgs_kernel_writes_over_its_input(donate):
    """The new block aliases ``x``: with ``x`` donated the kernel writes
    it in place, without, the caller's ``x`` survives the call; both give
    the oracle's sweep.  Three 8-plane slabs, so the output trails the
    input across slab boundaries."""
    st = Stencil.for_contraction(8, 1.0, (1.0, 1.0, 1.0), 0.9)
    bx, by, bz = 24, 8, 8
    x_np = RNG.standard_normal((bx, by, bz))
    b = jnp.asarray(RNG.standard_normal((bx, by, bz)))
    halos = tuple(jnp.asarray(RNG.standard_normal(s)) for s in
                  ((by, bz), (by, bz), (bx, bz), (bx, bz), (bx, by), (bx, by)))
    x = jnp.asarray(x_np)
    new_ref, r_ref = gauss_seidel.redblack_gs_sweep_residual(
        st, ghosted6_ref(x, halos), b, 3, 0, 0)
    sweep = jax.jit(functools.partial(fused_rbgs_sweep_residual_halo,
                                      interpret=True),
                    donate_argnums=(0,) if donate else ())
    new_k, parts_k = sweep(x, halos, b, jac_ops._coefs(st).astype(b.dtype),
                           jnp.int32(3))
    np.testing.assert_allclose(np.asarray(new_k), np.asarray(new_ref),
                               atol=1e-12)
    np.testing.assert_allclose(float(reduce_partials(parts_k)),
                               float(contribution(r_ref)),
                               rtol=1e-5, atol=1e-9)
    if not donate:
        np.testing.assert_array_equal(np.asarray(x), x_np)


def _check_rbgs_kernel(block, ox, oy, linf):
    st = Stencil.for_contraction(8, 1.0, (1.0, 1.0, 1.0), 0.9)
    bx, by, bz = block
    x = jnp.asarray(RNG.standard_normal((bx, by, bz)))
    b = jnp.asarray(RNG.standard_normal((bx, by, bz)))
    ghosts = tuple(jnp.asarray(RNG.standard_normal(s))
                   for s in ((by, bz), (by, bz), (bx, bz), (bx, bz)))
    g1 = jac_ops.ghost_pad1(x, ghosts)
    new_ref, r_ref = gauss_seidel.redblack_gs_sweep_residual(st, g1, b, ox, oy)
    new_k, parts_k = fused_rbgs_sweep_residual(
        jac_ops.ghost_pad2(x, ghosts), jnp.pad(b, ((1, 1), (1, 1), (0, 0))),
        jac_ops._coefs(st).astype(b.dtype), jnp.int32(ox + oy),
        linf=linf, interpret=True)
    assert parts_k.shape == (bx // _slab_planes(bx, by, bz, x.dtype.itemsize),)
    np.testing.assert_allclose(np.asarray(new_k), np.asarray(new_ref), atol=1e-12)
    np.testing.assert_allclose(float(reduce_partials(parts_k, linf)),
                               float(contribution(r_ref, linf)),
                               rtol=1e-5, atol=1e-9)
    # the fused partials reduce the residual of the input state
    r_in = jacobi.residual_block(st, g1, b)
    np.testing.assert_allclose(np.asarray(r_ref), np.asarray(r_in), atol=1e-12)


# ---------------------------------------------------------------------------
# Solver structure regression: no residual-only second pass
# ---------------------------------------------------------------------------


def _solver_cfg(n, inner_sweeps, fuse, sweep="hybrid"):
    st = Stencil.for_contraction(n, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    mon = detection.for_mode("pfait", eps_tilde=1e-8, margin=10.0,
                             staleness=2, ord=float("inf"))
    return SolverConfig(stencil=st, monitor=mon, inner_sweeps=inner_sweeps,
                        max_outer=500, sweep=sweep, use_kernel=True,
                        fuse_residual=fuse)


@pytest.mark.parametrize("inner_sweeps", [1, 3])
def test_sharded_solver_single_fused_pass_per_outer(inner_sweeps):
    """With use_kernel + fuse_residual, each outer iteration lowers to
    exactly one fused sweep+residual kernel invocation (the last inner
    sweep) and no residual-only pass — counted at trace time."""
    from repro.launch.mesh import make_mesh

    n = 8
    mesh = make_mesh((1, 1), ("data", "model"))
    b = jax.ShapeDtypeStruct((n, n, n), jnp.float32)
    cfg = _solver_cfg(n, inner_sweeps, fuse=True)
    jac_ops.reset_pass_counts()
    jax.jit(make_sharded_solver(cfg, mesh)).lower(b, b)
    counts = dict(jac_ops.PASS_COUNTS)
    assert counts["residual"] == 0, counts  # no residual-only second pass
    assert counts["fused"] > 0, counts
    # per outer iteration: inner_sweeps−1 plain sweeps + 1 fused pass,
    # regardless of how many times jax traced the loop body
    assert counts["sweep"] == (inner_sweeps - 1) * counts["fused"], counts


def test_sharded_solver_unfused_baseline_has_residual_pass():
    from repro.launch.mesh import make_mesh

    n = 8
    mesh = make_mesh((1, 1), ("data", "model"))
    b = jax.ShapeDtypeStruct((n, n, n), jnp.float32)
    cfg = _solver_cfg(n, 1, fuse=False)
    jac_ops.reset_pass_counts()
    jax.jit(make_sharded_solver(cfg, mesh)).lower(b, b)
    counts = dict(jac_ops.PASS_COUNTS)
    assert counts["fused"] == 0, counts
    assert counts["residual"] == counts["sweep"] > 0, counts


def test_solve_single_fused_pass_counts():
    n = 8
    cfg = _solver_cfg(n, 2, fuse=True)
    jac_ops.reset_pass_counts()
    jax.jit(lambda b: solve_single(cfg, b)).lower(
        jax.ShapeDtypeStruct((n, n, n), jnp.float32))
    counts = dict(jac_ops.PASS_COUNTS)
    assert counts["residual"] == 0 and counts["fused"] > 0
    assert counts["sweep"] == counts["fused"]  # inner_sweeps−1 == 1


def test_fused_sharded_solver_reduces_hbo_bytes():
    """HLO-derived HBM traffic per sweep drops when the residual is fused
    (jacobi flavour: the residual-only pass is a full second grid pass)."""
    from repro.launch import hlo_analysis
    from repro.launch.mesh import make_mesh

    n = 16
    mesh = make_mesh((1, 1), ("data", "model"))
    b = jax.ShapeDtypeStruct((n, n, n), jnp.float32)
    bytes_per = {}
    for fuse in (False, True):
        st = Stencil.for_contraction(n, 1.0, (1.0, 1.0, 1.0), rho=0.9)
        mon = detection.for_mode("pfait", eps_tilde=1e-8, margin=10.0,
                                 staleness=2)
        cfg = SolverConfig(stencil=st, monitor=mon, inner_sweeps=1,
                           max_outer=500, sweep="jacobi", fuse_residual=fuse)
        text = jax.jit(make_sharded_solver(cfg, mesh)).lower(b, b).compile().as_text()
        stats = hlo_analysis.program_stats(text, default_group=1)
        bytes_per[fuse] = stats.hbm_bytes / max(stats.loop_trip_max, 1.0)
    assert bytes_per[True] < bytes_per[False], bytes_per


# ---------------------------------------------------------------------------
# Fused solves still converge to the right answer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sweep", ["hybrid", "jacobi"])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_solve_single_fused_reaches_threshold(sweep, use_kernel):
    n = 12
    st = Stencil.for_contraction(n, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    b = jnp.asarray(make_rhs(n, 0))
    mon = detection.for_mode("pfait", eps_tilde=1e-8, margin=10.0,
                             staleness=3, ord=float("inf"))
    cfg = SolverConfig(stencil=st, monitor=mon, inner_sweeps=1,
                       max_outer=20_000, sweep=sweep, use_kernel=use_kernel,
                       fuse_residual=True)
    r = solve_single(cfg, b)
    assert bool(r.converged)
    from repro.solvers.fixed_point import _zero_ghosts, ghosted
    g = ghosted(r.x, _zero_ghosts(r.x))
    assert float(jnp.max(jnp.abs(jacobi.residual_block(st, g, b)))) < 1e-8


# ---------------------------------------------------------------------------
# Engine-level equivalence
# ---------------------------------------------------------------------------


def test_engine_fused_matches_unfused_pfait():
    """PFAIT never consumes per-iteration residuals, so the fused engine run
    is numerically the same trajectory (modulo contraction-order rounding)."""
    from repro.core.async_engine import AsyncEngine, stable_platform
    from repro.core.protocols import PFAIT

    res = {}
    for fused in (False, True):
        prob = ConvDiffProblem(n=12, p=4, rho=0.9, seed=3)
        cfg = dataclasses.replace(stable_platform(), seed=3, max_iters=30_000,
                                  fused=fused)
        res[fused] = AsyncEngine(prob, cfg, PFAIT(1e-6, ord=prob.ord)).run()
    assert res[True].terminated and res[False].terminated
    assert res[True].r_star == pytest.approx(res[False].r_star, rel=1e-6)
    assert res[True].k_max == res[False].k_max
    assert res[True].wtime == pytest.approx(res[False].wtime, rel=1e-9)


@pytest.mark.parametrize("proto", ["nfais2", "nfais5", "exact"])
def test_engine_fused_snapshot_protocols_terminate_correctly(proto):
    from benchmarks.common import run_cell

    cell = run_cell(proto, 1e-5, n=12, p=4, seeds=(0, 1), fused=True)
    assert cell["max_r"] < 1e-4  # detection guarantee holds on the fused path
