"""Mesh-partitioned runtime tests: the pluggable 2-D/3-D partitioner, the
halo-consuming fused kernels, 1-shard mesh parity against the reference
driver, mesh-aware config validation, per-face trace schema, and
(subprocess) real 4-device 2-D behaviour.

The pytest session runs on ONE device (tests/conftest.py), so in-process
mesh tests use 1-shard meshes of every dimensionality — which still route
through the block-decomposed mesh runtime (``MeshPartition``, per-face
ghost assembly, the overlap face-slab path) with boundary zeros on every
face.  Genuinely multi-device 2-D behaviour (per-axis ppermute rings,
overlap bitwise parity under heterogeneous knobs, the detect matrix
across mesh shapes) runs in a forced-4-device subprocess, marked
``slow``; the mesh-runtime CI lane covers it at full size.
"""
import os
import subprocess
import sys
import textwrap
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import detection
from repro.launch.mesh import make_shard_mesh, shard_axes_of
from repro.runtime import shard_runtime as sr
from repro.solvers.convdiff import Stencil, make_rhs
from repro.solvers.partition import FACES, MeshPartition

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RNG = np.random.default_rng(0)


def _mon(mode="pfait", eps=1e-7, staleness=0, ord=float("inf"),
         persistence=4):
    return detection.MonitorConfig(mode=mode, eps=eps, staleness=staleness,
                                   ord=ord, persistence=persistence)


# ---------------------------------------------------------------------------
# MeshPartition: tiling, topology, ring geometry
# ---------------------------------------------------------------------------


SHAPES = [(1,), (4,), (2, 2), (4, 2), (1, 2), (2, 2, 2), (2, 1, 2)]


@pytest.mark.parametrize("shape", SHAPES)
def test_partition_tiles_exactly(shape):
    """Every cell of the global cube is owned by exactly one shard."""
    n = 8
    part = MeshPartition(n, shape)
    covered = np.zeros((n, n, n), np.int32)
    for i in range(part.p):
        sl = tuple(slice(o, o + e) for o, e in part.block_spec(i))
        covered[sl] += 1
    assert (covered == 1).all()


@pytest.mark.parametrize("shape", SHAPES)
def test_partition_rank_coords_roundtrip(shape):
    part = MeshPartition(8, shape)
    assert part.p == int(np.prod(shape))
    for i in range(part.p):
        assert part.rank(*part.coords(i)) == i


@pytest.mark.parametrize("shape", SHAPES)
def test_partition_neighbours_symmetric_with_opposed_faces(shape):
    part = MeshPartition(8, shape)
    for i in range(part.p):
        for j in part.neighbors(i):
            assert i in part.neighbors(j), (shape, i, j)
            fi, fj = part.face(i, j), part.face(j, i)
            # the faces across one link are the two sides of the same axis
            assert fi[0] == fj[0] and fi != fj, (fi, fj)


def test_partition_face_labels_and_shapes():
    part = MeshPartition(8, (2, 2))
    assert FACES[0] == ("x-", "x+")
    # rank 0 = coords (0, 0): neighbours are x+ (rank 2) and y+ (rank 1)
    assert set(part.neighbors(0)) == {1, 2}
    assert part.face(0, 2) == "x+" and part.face(0, 1) == "y+"
    shapes = part.face_shapes()
    # a (2,2) mesh of n=8 has 4x8 blocks: x-faces are (4, 8), y-faces (4, 8)
    assert shapes["x+"] == (4, 8) and shapes["y+"] == (4, 8)


def test_partition_ring_slots_and_buffer_elems():
    part = MeshPartition(8, (2, 2))
    # double buffering floor: even delay 0 needs 2 slots (write k+1, read k)
    assert part.ring_slots(0) == 2
    assert part.ring_slots(3) == 4
    with pytest.raises(ValueError, match=">= 0"):
        part.ring_slots(-1)
    # 2 slots x 4 exchanged faces (x-,x+,y-,y+) of 4x8 elements each
    assert part.buffer_elems(0) == 2 * 4 * (4 * 8)


def test_partition_validates():
    with pytest.raises(ValueError, match="1-D, 2-D, or 3-D"):
        MeshPartition(8, (2, 2, 2, 2))
    with pytest.raises(ValueError, match=">= 1"):
        MeshPartition(8, (2, 0))
    with pytest.raises(ValueError, match="divisible"):
        MeshPartition(9, (2,))
    with pytest.raises(ValueError, match="out of range"):
        MeshPartition(8, (2,)).coords(5)


def test_make_shard_mesh_accepts_tuples():
    mesh = make_shard_mesh((1, 1))
    assert shard_axes_of(mesh) == ("shard_x", "shard_y")
    mesh1 = make_shard_mesh((1,))
    assert shard_axes_of(mesh1) == ("shard",)
    with pytest.raises(ValueError, match="exceeds"):
        make_shard_mesh((len(jax.devices()) + 1, 1))


# ---------------------------------------------------------------------------
# Halo-consuming fused kernels vs the ghosted oracle (interpret mode)
# ---------------------------------------------------------------------------


def _halo_setup(bx=8, by=8, bz=8, dtype=jnp.float64):
    st = Stencil.for_contraction(bx, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    coefs = jnp.asarray([st.diag, st.xm, st.xp, st.ym, st.yp, st.zm, st.zp],
                        dtype)
    x = jnp.asarray(RNG.standard_normal((bx, by, bz)), dtype)
    b = jnp.asarray(RNG.standard_normal((bx, by, bz)), dtype)
    halos = tuple(jnp.asarray(RNG.standard_normal(s), dtype) for s in
                  [(by, bz), (by, bz), (bx, bz), (bx, bz), (bx, by),
                   (bx, by)])
    return st, coefs, x, b, halos


#: block shapes: one x-slab, two slabs, and three one-plane slabs (a
#: 512 KiB f64 plane) whose x neighbours reach the face halos
HALO_BLOCKS = [(8, 8, 8), (16, 8, 8), (3, 256, 256)]


@pytest.mark.parametrize("block", HALO_BLOCKS)
@pytest.mark.parametrize("op", ["sweep", "residual"])
def test_halo_kernel_matches_oracle(block, op):
    from repro.kernels.jacobi3d.jacobi3d import fused_sweep_residual_halo
    from repro.kernels.jacobi3d.ref import (
        fused_sweep_residual_halo_ref,
        reduce_partials,
    )

    _, coefs, x, b, halos = _halo_setup(*block)
    new_k, parts_k = fused_sweep_residual_halo(
        x, halos, b, coefs, op=op, linf=True, interpret=True)
    new_r, c_r = fused_sweep_residual_halo_ref(x, halos, b, coefs, op=op,
                                               linf=True)
    np.testing.assert_allclose(np.asarray(new_k), np.asarray(new_r),
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(float(reduce_partials(parts_k)), float(c_r),
                               rtol=1e-12, atol=1e-12)


#: the red-black kernel streams the block through a window that lags the
#: slab it reads by two: one-plane slabs whose lag reaches both x faces,
#: and three 8-plane slabs, the middle one with neighbours on both sides
RBGS_BLOCKS = HALO_BLOCKS + [(5, 256, 256), (24, 8, 8)]


@pytest.mark.parametrize("block", RBGS_BLOCKS)
@pytest.mark.parametrize("oxyz", [0, 1, 5])
def test_rbgs_halo_kernel_matches_oracle(oxyz, block):
    from repro.kernels.jacobi3d.jacobi3d import fused_rbgs_sweep_residual_halo
    from repro.kernels.jacobi3d.ref import (
        contribution,
        ghosted6_ref,
        reduce_partials,
    )
    from repro.solvers import gauss_seidel

    st, coefs, x, b, halos = _halo_setup(*block)
    new_k, parts_k = fused_rbgs_sweep_residual_halo(
        x, halos, b, coefs, jnp.int32(oxyz), linf=True, interpret=True)
    g = ghosted6_ref(x, halos)
    new_r, rr = gauss_seidel.redblack_gs_sweep_residual(st, g, b, oxyz, 0, 0)
    np.testing.assert_allclose(np.asarray(new_k), np.asarray(new_r),
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(float(reduce_partials(parts_k)),
                               float(contribution(rr)), rtol=1e-12,
                               atol=1e-12)


def test_ops_halo_entries_match_ghosted_solvers_bitwise():
    """The jnp dispatch path of the halo ops must be the exact expression
    trees of ghosted6 + solvers — this is the bitwise-parity basis the
    mesh runtime's equivalence to ``solve_single`` rests on."""
    from repro.kernels.jacobi3d import ops as jac_ops
    from repro.solvers import gauss_seidel, jacobi
    from repro.solvers.fixed_point import ghosted6

    st, _, x, b, halos = _halo_setup()
    new = jac_ops.sweep_halo(st, x, halos, b)
    ref = jacobi.jacobi_sweep(st, ghosted6(x, halos), b)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(ref))

    new2, c = jac_ops.sweep_with_contribution_halo(st, x, halos, b,
                                                   ord=float("inf"))
    ref2, rr = jacobi.jacobi_sweep_residual(st, ghosted6(x, halos), b)
    np.testing.assert_array_equal(np.asarray(new2), np.asarray(ref2))
    # partials accumulate in f32 (the kernel layout); the cast is monotone,
    # so the contribution is exactly the f32 cast of the oracle's max
    assert float(c) == float(jnp.max(jnp.abs(rr)).astype(jnp.float32))

    c2 = jac_ops.residual_contribution_halo(st, x, halos, b,
                                            ord=float("inf"))
    assert float(c2) == float(jnp.max(jnp.abs(jacobi.residual_block(
        st, ghosted6(x, halos), b))).astype(jnp.float32))

    newh = jac_ops.sweep_halo(st, x, halos, b, sweep="hybrid",
                              ox=3, oy=1, oz=2)
    refh = gauss_seidel.redblack_gs_sweep(st, ghosted6(x, halos), b, 3, 1, 2)
    np.testing.assert_array_equal(np.asarray(newh), np.asarray(refh))


# ---------------------------------------------------------------------------
# Mesh-aware config validation
# ---------------------------------------------------------------------------


def test_config_validates_mesh_shape():
    with pytest.raises(ValueError, match="mesh_shape"):
        sr.ShardRuntimeConfig(monitor=_mon(), mesh_shape=(2, 2, 2, 2))
    with pytest.raises(ValueError, match="mesh_shape"):
        sr.ShardRuntimeConfig(monitor=_mon(), mesh_shape=(2, 0))
    cfg = sr.ShardRuntimeConfig(monitor=_mon(), mesh_shape=[2, 2])
    assert cfg.mesh_shape == (2, 2)   # normalised to an int tuple


def test_overlap_requires_jacobi_nonblocking():
    with pytest.raises(ValueError, match="red-black"):
        sr.ShardRuntimeConfig(monitor=_mon(), sweep="hybrid", overlap=True)
    with pytest.raises(ValueError, match="blocking"):
        sr.ShardRuntimeConfig(monitor=_mon(), reduction="blocking",
                              overlap=True)


def test_per_shard_error_names_mesh_shape():
    """A wrong-length per-shard sequence on a 2-D mesh names the mesh shape
    and the row-major total, not just a bare length."""
    mesh = types.SimpleNamespace(shape={"shard_x": 2, "shard_y": 2},
                                 axis_names=("shard_x", "shard_y"))
    st = Stencil.for_contraction(8, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    cfg = sr.ShardRuntimeConfig(monitor=_mon(), inner_sweeps=(1, 2),
                                mesh_shape=(2, 2))
    with pytest.raises(ValueError, match=r"mesh shape \(2, 2\)"):
        sr.make_convdiff_runtime(cfg, mesh, st, 8)


def test_mesh_shape_must_match_mesh():
    mesh = types.SimpleNamespace(shape={"shard_x": 2, "shard_y": 2},
                                 axis_names=("shard_x", "shard_y"))
    st = Stencil.for_contraction(8, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    cfg = sr.ShardRuntimeConfig(monitor=_mon(), mesh_shape=(2, 1))
    with pytest.raises(ValueError, match="does not match"):
        sr.make_convdiff_runtime(cfg, mesh, st, 8)


def test_overlap_needs_block_extent_two():
    # a 2-wide axis at n=2 leaves 1-plane blocks: no interior to overlap
    mesh = types.SimpleNamespace(shape={"shard_x": 2, "shard_y": 1},
                                 axis_names=("shard_x", "shard_y"))
    st = Stencil.for_contraction(2, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    cfg = sr.ShardRuntimeConfig(monitor=_mon(), overlap=True,
                                mesh_shape=(2, 1))
    with pytest.raises(ValueError, match="block extent"):
        sr.make_convdiff_runtime(cfg, mesh, st, 2)


def test_pagerank_rejects_multi_axis_and_overlap():
    mesh = types.SimpleNamespace(shape={"shard_x": 2, "shard_y": 2},
                                 axis_names=("shard_x", "shard_y"))
    cfg = sr.ShardRuntimeConfig(monitor=_mon())
    with pytest.raises(ValueError, match="1-D"):
        sr.make_pagerank_runtime(cfg, mesh, 8)
    mesh1 = make_shard_mesh(1)
    cfg_ov = sr.ShardRuntimeConfig(monitor=_mon(), overlap=True)
    with pytest.raises(ValueError, match="convdiff-only"):
        sr.make_pagerank_runtime(cfg_ov, mesh1, 8)


def test_mesh_state_spec_per_family():
    from jax.sharding import PartitionSpec as P

    mesh1 = make_shard_mesh(1)
    assert sr.mesh_state_spec("convdiff", mesh1) == P("shard", None, None)
    assert sr.mesh_state_spec("pagerank", mesh1) == P("shard")
    mesh2 = make_shard_mesh((1, 1))
    assert sr.mesh_state_spec("convdiff", mesh2) == P("shard_x", "shard_y",
                                                      None)
    with pytest.raises(ValueError, match="1-D"):
        sr.mesh_state_spec("pagerank", mesh2)


# ---------------------------------------------------------------------------
# 1-shard mesh parity: every dimensionality reproduces solve_single bitwise
# ---------------------------------------------------------------------------


N = 8


def _setup(n=N, seed=0, rho=0.9):
    st = Stencil.for_contraction(n, 1.0, (1.0, 1.0, 1.0), rho=rho)
    b = jnp.asarray(make_rhs(n, seed=seed))
    return st, b, jnp.zeros_like(b)


def _reference(st, b, sweep="jacobi", mon=None):
    from repro.solvers.fixed_point import SolverConfig, solve_single

    # default fuse_residual: the fused sweep+residual expression tree is
    # exactly what the mesh runtime's halo ops build — bitwise comparable
    mon = mon or _mon()
    return solve_single(
        SolverConfig(stencil=st, monitor=mon, inner_sweeps=1, max_outer=400,
                     sweep=sweep), b)


@pytest.mark.parametrize("shape", [(1,), (1, 1), (1, 1, 1)])
def test_one_shard_mesh_bitwise_matches_solve_single(shape):
    """The mesh runtime on a 1-shard mesh of any dimensionality — with the
    overlap path forced on — is bitwise the reference driver: identical
    iteration count, identical solution array."""
    st, b, x0 = _setup()
    ref = _reference(st, b)
    mesh = make_shard_mesh(shape)
    cfg = sr.ShardRuntimeConfig(monitor=_mon(), reduction="nonblocking",
                                max_outer=400, mesh_shape=shape,
                                overlap=True)
    r = jax.jit(sr.make_convdiff_runtime(cfg, mesh, st, N))(x0, b)
    assert bool(r.converged)
    assert int(r.outer_iters) == int(ref.outer_iters)
    np.testing.assert_array_equal(np.asarray(r.x), np.asarray(ref.x))


def test_one_shard_mesh_hybrid_bitwise_matches_solve_single():
    st, b, x0 = _setup()
    ref = _reference(st, b, sweep="hybrid")
    mesh = make_shard_mesh((1, 1))
    cfg = sr.ShardRuntimeConfig(monitor=_mon(), reduction="nonblocking",
                                max_outer=400, sweep="hybrid",
                                mesh_shape=(1, 1))
    r = jax.jit(sr.make_convdiff_runtime(cfg, mesh, st, N))(x0, b)
    assert bool(r.converged)
    assert int(r.outer_iters) == int(ref.outer_iters)
    np.testing.assert_array_equal(np.asarray(r.x), np.asarray(ref.x))


def test_unified_api_runs_mesh_shape():
    """run_shard accepts a 2-D mesh + mesh_shape/overlap through
    RuntimeConfig and returns a truthful report."""
    from repro.runtime import api

    st, b, _ = _setup()
    cfg = api.RuntimeConfig(monitor=_mon(), reduction="nonblocking",
                            max_outer=400, mesh_shape=(1, 1), overlap=True,
                            record_trace=True)
    rep = api.run_shard("convdiff", cfg, make_shard_mesh((1, 1)), N,
                        np.zeros_like(np.asarray(b)), np.asarray(b),
                        stencil=st)
    assert rep.converged
    assert rep.trace.meta["mesh_shape"] == [1, 1]


# ---------------------------------------------------------------------------
# Trace schema: mesh shape + per-face halo events
# ---------------------------------------------------------------------------


def _fake_result(outer=3):
    return types.SimpleNamespace(
        outer_iters=outer, converged=True, residual=0.25,
        trace=np.asarray([1.0, 0.5, 0.25]))


def test_trace_records_mesh_shape_and_per_face_halos():
    from repro.core.trace import trace_from_shard_run

    cfg = sr.ShardRuntimeConfig(monitor=_mon(), trace_len=3,
                                mesh_shape=(2, 2))
    tr = trace_from_shard_run(_fake_result(), cfg, 4, wall_s=1.0)
    tr.validate()
    assert tr.meta["mesh_shape"] == [2, 2]
    halos = [e for e in tr.events if e["kind"] == "halo"]
    # every worker of a (2,2) mesh exchanges exactly 2 faces per step
    per_step_w0 = [e for e in halos if e["w"] == 0 and e["step"] == 0]
    assert len(per_step_w0) == 2
    assert {e["face"] for e in per_step_w0} == {"x+", "y+"}
    assert {e["peer"] for e in per_step_w0} == {1, 2}


def test_trace_1d_keeps_single_halo_event():
    from repro.core.trace import trace_from_shard_run

    cfg = sr.ShardRuntimeConfig(monitor=_mon(), trace_len=3)
    tr = trace_from_shard_run(_fake_result(), cfg, 4, wall_s=1.0)
    tr.validate()
    assert tr.meta["mesh_shape"] == [4]
    halos = [e for e in tr.events
             if e["kind"] == "halo" and e["w"] == 0 and e["step"] == 0]
    assert len(halos) == 1 and "face" not in halos[0]


# ---------------------------------------------------------------------------
# Multi-device 2-D behaviour (forced 4-device subprocess)
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

    n = 16
    st = Stencil.for_contraction(n, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    b = jnp.asarray(make_rhs(n, seed=0))
    x0 = jnp.zeros_like(b)

    # 1. blocking (2,2) parity vs the synchronous reference trace
    mesh22 = make_shard_mesh((2, 2))
    mon = detection.MonitorConfig(mode="sync", eps=1e-7, staleness=0)
    cfg = sr.ShardRuntimeConfig(monitor=mon, reduction="blocking",
                                max_outer=400, trace_len=256,
                                mesh_shape=(2, 2))
    r = jax.jit(sr.make_convdiff_runtime(cfg, mesh22, st, n))(x0, b)
    assert bool(r.converged)
    T = min(int(r.outer_iters), 256)
    ref = np.asarray(sr.convdiff_reference_trace(st, b, T))
    np.testing.assert_allclose(np.asarray(r.trace)[:T], ref, rtol=5e-5)

    # 2. overlap vs non-overlap: bitwise-identical trajectory under
    #    heterogeneous per-shard knobs
    monp = detection.MonitorConfig(mode="pfait", eps=1e-7, staleness=2,
                                   persistence=4)
    base = dict(monitor=monp, reduction="nonblocking", max_outer=2000,
                inner_sweeps=(1, 2, 1, 3), halo_delay=(0, 1, 2, 1),
                contrib_lag=(0, 1, 0, 1), trace_len=64, mesh_shape=(2, 2))
    r0 = jax.jit(sr.make_convdiff_runtime(
        sr.ShardRuntimeConfig(overlap=False, **base), mesh22, st, n))(x0, b)
    r1 = jax.jit(sr.make_convdiff_runtime(
        sr.ShardRuntimeConfig(overlap=True, **base), mesh22, st, n))(x0, b)
    assert bool(r0.converged) and bool(r1.converged)
    assert int(r0.outer_iters) == int(r1.outer_iters)
    np.testing.assert_array_equal(np.asarray(r0.x), np.asarray(r1.x))
    np.testing.assert_array_equal(np.asarray(r0.trace), np.asarray(r1.trace))
    sweeps = np.asarray(r1.local_sweeps); k = int(r1.outer_iters)
    assert list(sweeps) == [k, 2*k, k, 3*k], sweeps

    # 3. truthful detection across mesh shapes x reductions
    from repro.solvers import jacobi
    from repro.solvers.fixed_point import _zero_ghosts, ghosted
    for shape in [(4,), (2, 2), (1, 4)]:
        mesh = make_shard_mesh(shape)
        for red, mode in (("nonblocking", "pfait"),
                          ("nonblocking", "nfais2"),
                          ("rdoubling", "pfait")):
            m = detection.for_mode(mode, eps_tilde=1e-6, margin=10.0,
                                   staleness=2, persistence=4)
            c = sr.ShardRuntimeConfig(
                monitor=m, reduction=red, max_outer=2000, mesh_shape=shape,
                inner_sweeps=(1, 2, 1, 3), halo_delay=(0, 1, 2, 1),
                contrib_lag=(0, 1, 0, 1), overlap=(len(shape) > 1))
            rr = jax.jit(sr.make_convdiff_runtime(c, mesh, st, n))(x0, b)
            assert bool(rr.converged), (shape, red, mode)
            res = np.asarray(jacobi.residual_block(
                st, ghosted(rr.x, _zero_ghosts(rr.x)), b), np.float64)
            r_star = float(np.linalg.norm(res.ravel()))
            assert r_star < 10.0 * 1e-6, (shape, red, mode, r_star)

    # 4. red-black hybrid on (2,2) converges truthfully
    mh = detection.for_mode("pfait", eps_tilde=1e-6, margin=10.0,
                            staleness=1, persistence=4)
    ch = sr.ShardRuntimeConfig(monitor=mh, reduction="nonblocking",
                               sweep="hybrid", max_outer=2000,
                               mesh_shape=(2, 2), halo_delay=(0, 1, 0, 1))
    rh = jax.jit(sr.make_convdiff_runtime(ch, mesh22, st, n))(x0, b)
    assert bool(rh.converged)
    res = np.asarray(jacobi.residual_block(
        st, ghosted(rh.x, _zero_ghosts(rh.x)), b), np.float64)
    assert float(np.linalg.norm(res.ravel())) < 1e-5
    print("MULTIDEVICE_MESH_OK")
""")


@pytest.mark.slow
def test_multidevice_mesh_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_PROGRAM], env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MULTIDEVICE_MESH_OK" in out.stdout
