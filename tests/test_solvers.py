"""Convection–diffusion solver substrate: numpy sim + JAX distributed."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import detection
from repro.solvers.convdiff import ConvDiffProblem, Stencil, make_rhs
from repro.solvers.fixed_point import (
    SolverConfig,
    _zero_ghosts,
    ghosted,
    make_sharded_solver,
    solve_single,
)
from repro.solvers import jacobi


def test_stencil_contraction_rate():
    st = Stencil.for_contraction(16, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    h = 1.0 / 17
    d = 1.0 / h**2
    assert (6 * d) / st.diag == pytest.approx(0.9)


def test_sim_problem_converges_to_reference():
    prob = ConvDiffProblem(n=10, p=4, rho=0.85, seed=0)
    ref = prob.solve_reference(tol=1e-13)
    # drive every subdomain synchronously (round-robin sweeps, fresh deps)
    xs = [prob.init_local(i) for i in range(prob.p)]
    for _ in range(400):
        deps = [
            {j: prob.interface(j, xs[j], i) for j in prob.neighbors(i)}
            for i in range(prob.p)
        ]
        xs = [prob.update(i, xs[i], deps[i]) for i in range(prob.p)]
    np.testing.assert_allclose(prob.assemble(xs), ref, atol=1e-8)


def test_sim_local_residuals_consistent_with_global():
    prob = ConvDiffProblem(n=10, p=4, rho=0.85, seed=1)
    xs = [prob.init_local(i) + np.random.default_rng(i).standard_normal(prob.part.block)
          for i in range(prob.p)]
    deps = [
        {j: prob.interface(j, xs[j], i) for j in prob.neighbors(i)}
        for i in range(prob.p)
    ]
    local_max = max(prob.local_residual(i, xs[i], deps[i]) for i in range(prob.p))
    assert local_max == pytest.approx(prob.exact_residual(xs), rel=1e-12)


@pytest.mark.parametrize("sweep", ["jacobi", "hybrid"])
def test_solve_single_reaches_threshold(sweep):
    n = 12
    st = Stencil.for_contraction(n, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    b = jnp.asarray(make_rhs(n, 0))
    mon = detection.for_mode("pfait", eps_tilde=1e-8, margin=10.0,
                             staleness=3, ord=float("inf"))
    cfg = SolverConfig(stencil=st, monitor=mon, inner_sweeps=1,
                       max_outer=20_000, sweep=sweep)
    r = solve_single(cfg, b)
    assert bool(r.converged)
    g = ghosted(r.x, _zero_ghosts(r.x))
    exact = float(jnp.max(jnp.abs(jacobi.residual_block(st, g, b))))
    assert exact < 1e-8


def test_hybrid_gs_converges_faster_than_jacobi():
    n = 12
    st = Stencil.for_contraction(n, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    b = jnp.asarray(make_rhs(n, 0))
    mon = detection.for_mode("sync", eps_tilde=1e-8, ord=float("inf"))
    out = {}
    for sweep in ["jacobi", "hybrid"]:
        cfg = SolverConfig(stencil=st, monitor=mon, max_outer=20_000, sweep=sweep)
        out[sweep] = int(solve_single(cfg, b).outer_iters)
    assert out["hybrid"] < out["jacobi"]


@pytest.mark.slow
def test_sharded_solver_single_device_mesh_matches_single():
    from repro.launch.mesh import make_mesh

    n = 12
    mesh = make_mesh((1, 1), ("data", "model"))
    st = Stencil.for_contraction(n, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    b = jnp.asarray(make_rhs(n, 0))
    mon = detection.for_mode("pfait", eps_tilde=1e-8, margin=10.0,
                             staleness=2, ord=float("inf"))
    cfg = SolverConfig(stencil=st, monitor=mon, inner_sweeps=2, max_outer=20_000)
    solve = make_sharded_solver(cfg, mesh)  # mesh passed explicitly
    r_mesh = solve(jnp.zeros_like(b), b)
    r_single = solve_single(cfg, b)
    assert bool(r_mesh.converged)
    np.testing.assert_allclose(np.asarray(r_mesh.x), np.asarray(r_single.x), atol=1e-12)
    assert int(r_mesh.outer_iters) == int(r_single.outer_iters)


def test_inner_sweeps_reduce_outer_iterations():
    """Communication-avoiding asynchrony: more local sweeps per exchange →
    fewer outer iterations (halo exchanges + reductions)."""
    n = 12
    st = Stencil.for_contraction(n, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    b = jnp.asarray(make_rhs(n, 0))
    mon = detection.for_mode("sync", eps_tilde=1e-8, ord=float("inf"))
    outer = {}
    for s in [1, 4]:
        cfg = SolverConfig(stencil=st, monitor=mon, inner_sweeps=s, max_outer=20_000)
        outer[s] = int(solve_single(cfg, b).outer_iters)
    assert outer[4] < outer[1]
