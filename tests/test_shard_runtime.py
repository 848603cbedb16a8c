"""Shard-runtime tests: config validation, single-shard parity against the
reference drivers, detection-mode semantics, and (subprocess) the real
multi-device paths the in-process session cannot host.

The pytest session runs on ONE device (tests/conftest.py), so in-process
tests use a 1-shard mesh — which still exercises the full ring/monitor
machinery (ppermute on a single rank delivers the boundary zeros).  The
genuinely multi-device behaviours (halo exchange between ranks, butterfly
partners, psum lanes) run in a forced-4-device subprocess, marked
``slow``; the shard-runtime CI lane covers them at full size.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import detection
from repro.launch.mesh import make_shard_mesh, shard_axis_of
from repro.runtime import shard_runtime as sr
from repro.solvers.convdiff import Stencil, make_rhs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mon(mode="sync", eps=1e-7, staleness=0, ord=2.0, persistence=4):
    return detection.MonitorConfig(mode=mode, eps=eps, staleness=staleness,
                                   ord=ord, persistence=persistence)


# ---------------------------------------------------------------------------
# Config / mesh validation
# ---------------------------------------------------------------------------


def test_config_rejects_unknown_reduction():
    with pytest.raises(ValueError, match="reduction"):
        sr.ShardRuntimeConfig(monitor=_mon(), reduction="psum")


def test_config_rejects_unknown_sweep():
    with pytest.raises(ValueError, match="sweep"):
        sr.ShardRuntimeConfig(monitor=_mon(), sweep="sor")


def test_blocking_mode_forbids_staleness_knobs():
    mesh = make_shard_mesh(1)
    st = Stencil.for_contraction(8, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    cfg = sr.ShardRuntimeConfig(monitor=_mon(), reduction="blocking",
                                halo_delay=1)
    with pytest.raises(ValueError, match="blocking"):
        sr.make_convdiff_runtime(cfg, mesh, st, 8)


def test_per_shard_params_validated():
    mesh = make_shard_mesh(1)
    st = Stencil.for_contraction(8, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    cfg = sr.ShardRuntimeConfig(monitor=_mon(), inner_sweeps=(1, 2))
    with pytest.raises(ValueError, match="inner_sweeps"):
        sr.make_convdiff_runtime(cfg, mesh, st, 8)
    cfg0 = sr.ShardRuntimeConfig(monitor=_mon(), inner_sweeps=0)
    with pytest.raises(ValueError, match="inner_sweeps"):
        sr.make_convdiff_runtime(cfg0, mesh, st, 8)


def test_effective_monitor_forces_staleness():
    mon = _mon(mode="pfait", staleness=3)
    blocking = sr.ShardRuntimeConfig(monitor=mon, reduction="blocking")
    assert blocking.effective_monitor().staleness == 0
    rd = sr.ShardRuntimeConfig(monitor=mon, reduction="rdoubling")
    assert rd.effective_monitor().staleness == 0
    nb = sr.ShardRuntimeConfig(monitor=mon, reduction="nonblocking")
    assert nb.effective_monitor().staleness == 3


def test_rdoubling_requires_power_of_two_shards():
    with pytest.raises(ValueError, match="power-of-two"):
        sr._butterfly_rounds(3)
    assert sr._butterfly_rounds(1) == 0
    assert sr._butterfly_rounds(8) == 3


def test_make_shard_mesh_validates():
    with pytest.raises(ValueError, match="exceeds"):
        make_shard_mesh(len(jax.devices()) + 1)
    with pytest.raises(ValueError, match=">= 1"):
        make_shard_mesh(0)
    mesh = make_shard_mesh(1)
    assert shard_axis_of(mesh) == "shard"


def test_shard_axis_of_rejects_2d_mesh():
    from repro.launch.mesh import make_mesh

    with pytest.raises(ValueError, match="1-D"):
        shard_axis_of(make_mesh((1, 1), ("data", "model")))


def test_convdiff_runtime_requires_divisible_n():
    # a 2-shard mesh shape is enough to hit the (pre-shard_map) validation
    # without owning 2 devices
    import types

    mesh = types.SimpleNamespace(shape={"shard": 2})
    st = Stencil.for_contraction(9, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    cfg = sr.ShardRuntimeConfig(monitor=_mon())
    with pytest.raises(ValueError, match="divisible"):
        sr.make_convdiff_runtime(cfg, mesh, st, 9)
    with pytest.raises(ValueError, match="divisible"):
        sr.make_pagerank_runtime(cfg, mesh, 9)


# ---------------------------------------------------------------------------
# Single-shard parity (full machinery, one rank)
# ---------------------------------------------------------------------------


N = 10


def _setup(n=N, seed=0, rho=0.9):
    st = Stencil.for_contraction(n, 1.0, (1.0, 1.0, 1.0), rho=rho)
    b = jnp.asarray(make_rhs(n, seed=seed))
    return st, b, jnp.zeros_like(b)


def test_blocking_trajectory_matches_reference():
    st, b, x0 = _setup()
    mesh = make_shard_mesh(1)
    cfg = sr.ShardRuntimeConfig(monitor=_mon(eps=1e-7), reduction="blocking",
                                max_outer=400, trace_len=256)
    r = jax.jit(sr.make_convdiff_runtime(cfg, mesh, st, N))(x0, b)
    assert bool(r.converged)
    T = min(int(r.outer_iters), 256)
    ref = np.asarray(sr.convdiff_reference_trace(st, b, T))
    trace = np.asarray(r.trace)[:T]
    np.testing.assert_allclose(trace, ref, rtol=5e-5)


def test_blocking_matches_solve_single_detection_point():
    st, b, x0 = _setup()
    from repro.solvers.fixed_point import SolverConfig, solve_single

    mesh = make_shard_mesh(1)
    mon = _mon(eps=1e-7)
    cfg = sr.ShardRuntimeConfig(monitor=mon, reduction="blocking",
                                max_outer=400)
    r = jax.jit(sr.make_convdiff_runtime(cfg, mesh, st, N))(x0, b)
    ref = solve_single(
        SolverConfig(stencil=st, monitor=mon, inner_sweeps=1, max_outer=400,
                     sweep="jacobi", fuse_residual=False), b)
    assert int(r.outer_iters) == int(ref.outer_iters)
    assert float(r.residual) == pytest.approx(float(ref.residual), rel=1e-5)
    np.testing.assert_allclose(np.asarray(r.x), np.asarray(ref.x),
                               rtol=1e-10, atol=1e-12)


def test_nonblocking_staleness_delays_detection():
    st, b, x0 = _setup()
    mesh = make_shard_mesh(1)
    outers = {}
    for K in (0, 4):
        mon = _mon(mode="pfait", eps=1e-7, staleness=K)
        cfg = sr.ShardRuntimeConfig(monitor=mon, reduction="nonblocking",
                                    max_outer=600)
        r = jax.jit(sr.make_convdiff_runtime(cfg, mesh, st, N))(x0, b)
        assert bool(r.converged)
        outers[K] = int(r.outer_iters)
    # a K-stale ring consumes the value launched K checks earlier: detection
    # fires exactly K checks later on a monotone trajectory
    assert outers[4] == outers[0] + 4


def test_inner_sweeps_accelerate_outer_convergence():
    st, b, x0 = _setup()
    mesh = make_shard_mesh(1)
    outers = {}
    for s in (1, 3):
        cfg = sr.ShardRuntimeConfig(monitor=_mon(eps=1e-7),
                                    reduction="blocking", inner_sweeps=s,
                                    max_outer=400)
        r = jax.jit(sr.make_convdiff_runtime(cfg, mesh, st, N))(x0, b)
        outers[s] = int(r.outer_iters)
        assert int(r.local_sweeps[0]) == s * outers[s]
    assert outers[3] < outers[1]


def test_rdoubling_single_shard_detects():
    st, b, x0 = _setup()
    mesh = make_shard_mesh(1)
    cfg = sr.ShardRuntimeConfig(monitor=_mon(mode="pfait", eps=1e-7),
                                reduction="rdoubling", max_outer=400)
    r = jax.jit(sr.make_convdiff_runtime(cfg, mesh, st, N))(x0, b)
    assert bool(r.converged)
    assert float(r.residual) < 1e-7


def test_nfais2_verification_counts():
    st, b, x0 = _setup()
    mesh = make_shard_mesh(1)
    mon = detection.for_mode("nfais2", eps_tilde=1e-6, staleness=2,
                             persistence=2)
    cfg = sr.ShardRuntimeConfig(monitor=mon, reduction="nonblocking",
                                max_outer=600)
    r = jax.jit(sr.make_convdiff_runtime(cfg, mesh, st, N))(x0, b)
    assert bool(r.converged)
    assert int(r.verifications) >= 1


def test_max_outer_exhaustion_reports_unconverged():
    st, b, x0 = _setup()
    mesh = make_shard_mesh(1)
    cfg = sr.ShardRuntimeConfig(monitor=_mon(eps=1e-30),
                                reduction="blocking", max_outer=7)
    r = jax.jit(sr.make_convdiff_runtime(cfg, mesh, st, N))(x0, b)
    assert not bool(r.converged)
    assert int(r.outer_iters) == 7
    assert not np.isfinite(float(r.residual))


def test_pagerank_runtime_single_shard():
    from repro.solvers.pagerank import PageRankProblem

    n = 64
    prob = PageRankProblem(n=n, p=4, seed=0)
    P_dense = jnp.asarray(prob.to_dense())
    x0 = jnp.full((n,), 1.0 / n)
    mesh = make_shard_mesh(1)
    mon = _mon(mode="pfait", eps=1e-9, ord=1.0)
    cfg = sr.ShardRuntimeConfig(monitor=mon, reduction="nonblocking",
                                max_outer=500, trace_len=64)
    r = jax.jit(sr.make_pagerank_runtime(cfg, mesh, n, prob.d))(x0, P_dense)
    assert bool(r.converged)
    # final exact residual (f64) must be at/under the detected one's decade
    xs = np.asarray(r.x, np.float64)
    rv = prob.d * (np.asarray(P_dense, np.float64) @ xs) + prob.v - xs
    assert float(np.sum(np.abs(rv))) < 1e-8


def test_pagerank_trace_matches_reference():
    from repro.solvers.pagerank import PageRankProblem

    n = 64
    prob = PageRankProblem(n=n, p=4, seed=1)
    P_dense = jnp.asarray(prob.to_dense())
    x0 = jnp.full((n,), 1.0 / n)
    mesh = make_shard_mesh(1)
    cfg = sr.ShardRuntimeConfig(monitor=_mon(eps=1e-10, ord=1.0),
                                reduction="blocking", max_outer=300,
                                trace_len=128)
    r = jax.jit(sr.make_pagerank_runtime(cfg, mesh, n, prob.d))(x0, P_dense)
    T = min(int(r.outer_iters), 128)
    ref = np.asarray(sr.pagerank_reference_trace(P_dense, n, T,
                                                 damping=prob.d, ord=1.0))
    np.testing.assert_allclose(np.asarray(r.trace)[:T], ref, rtol=5e-5)


# ---------------------------------------------------------------------------
# Ring-buffer semantics (pure helpers)
# ---------------------------------------------------------------------------


def test_ring_write_read_roundtrip():
    ring = sr._ring_fill(jnp.zeros((2,)), 3)
    for k in range(5):
        ring = sr._ring_write(ring, jnp.full((2,), float(k)), k)
    # slot k mod 3 holds the value written at the latest such k
    assert float(sr._ring_read(ring, 4)[0]) == 4.0
    assert float(sr._ring_read(ring, 3)[0]) == 3.0
    assert float(sr._ring_read(ring, 2)[0]) == 2.0
    # negative steps clamp to slot 0
    assert float(sr._ring_read(ring, -2)[0]) == 3.0  # slot 0 last wrote k=3


def test_ring_fill_broadcasts_initial_view():
    ring = sr._ring_fill({"a": jnp.arange(4.0)}, 5)
    assert ring["a"].shape == (5, 4)
    for s in range(5):
        np.testing.assert_array_equal(np.asarray(ring["a"][s]),
                                      np.arange(4.0))


# ---------------------------------------------------------------------------
# Multi-device behaviour (forced 4-device subprocess)
# ---------------------------------------------------------------------------


_SUBPROCESS_PROGRAM = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np
    from repro.core import detection
    from repro.launch.mesh import make_shard_mesh
    from repro.runtime import shard_runtime as sr
    from repro.solvers.convdiff import Stencil, make_rhs

    n = 12
    mesh = make_shard_mesh(4)
    st = Stencil.for_contraction(n, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    b = jnp.asarray(make_rhs(n, seed=0))
    x0 = jnp.zeros_like(b)

    # 1. blocking parity across 4 real shards
    mon = detection.MonitorConfig(mode="sync", eps=1e-7, staleness=0)
    cfg = sr.ShardRuntimeConfig(monitor=mon, reduction="blocking",
                                max_outer=400, trace_len=256)
    r = jax.jit(sr.make_convdiff_runtime(cfg, mesh, st, n))(x0, b)
    assert bool(r.converged)
    T = min(int(r.outer_iters), 256)
    ref = np.asarray(sr.convdiff_reference_trace(st, b, T))
    np.testing.assert_allclose(np.asarray(r.trace)[:T], ref, rtol=5e-5)

    # 2. asynchronous modes detect truthfully under staleness
    from repro.solvers import jacobi
    from repro.solvers.fixed_point import _zero_ghosts, ghosted
    for red, mode in (("nonblocking", "pfait"), ("nonblocking", "nfais2"),
                      ("rdoubling", "pfait")):
        m = detection.for_mode(mode, eps_tilde=1e-6, margin=10.0,
                               staleness=2, persistence=4)
        c = sr.ShardRuntimeConfig(
            monitor=m, reduction=red, max_outer=2000,
            inner_sweeps=(1, 2, 1, 3), halo_delay=(0, 1, 2, 1),
            contrib_lag=(0, 1, 0, 1))
        rr = jax.jit(sr.make_convdiff_runtime(c, mesh, st, n))(x0, b)
        assert bool(rr.converged), (red, mode)
        res = np.asarray(jacobi.residual_block(
            st, ghosted(rr.x, _zero_ghosts(rr.x)), b), np.float64)
        r_star = float(np.linalg.norm(res.ravel()))
        assert r_star < 10.0 * 1e-6, (red, mode, r_star)
        sweeps = np.asarray(rr.local_sweeps)
        k = int(rr.outer_iters)
        assert list(sweeps) == [k, 2 * k, k, 3 * k]
    print("MULTIDEVICE_OK")
""")


@pytest.mark.slow
def test_multidevice_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_PROGRAM], env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MULTIDEVICE_OK" in out.stdout
