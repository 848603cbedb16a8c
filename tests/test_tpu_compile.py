"""The main-path Pallas kernels compile for a TPU v5e at the real block size.

Interpret mode cannot see what the chip's compiler refuses (tile-illegal
blocks, vector loads from HBM refs, VMEM overuse), so these tests compile
each kernel entry ahead of time for a described ``v5e:2x2`` topology — no
chip attached — at n=256 f32 (the red-black kernel also at the
benchmark's n=512) and check that the program holds the Mosaic
kernel (``tpu_custom_call``).  Whole (1,1)-mesh solves compiled the
same way show where the kernels sit and what the while loop copies.  The
topology is described only inside the fixture: only the worker that runs
these tests loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.jacobi3d import jacobi3d
from repro.kernels.residual_norm.residual_norm import diff_norm_partials

N = 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests.
    # The chip runs with x64 off (f32 state, i32 indices), so compile so.
    from jax.experimental.compilation_cache import compilation_cache

    prev = (jax.config.jax_enable_compilation_cache,
            jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev[0])
    jax.config.update("jax_enable_x64", prev[1])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _halos(sharding, n=N):
    return tuple(_spec((n, n), sharding) for _ in range(6))


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("op", ["sweep", "residual"])
def test_jacobi_halo_compiles(one_chip, op):
    s = one_chip
    _assert_kernel(jacobi3d.fused_sweep_residual_halo.lower(
        _spec((N, N, N), s), _halos(s), _spec((N, N, N), s),
        _spec((7,), s), op=op))


#: 256: four planes per slab; 512: the benchmark's block, one plane per
#: slab, so the rolling window's VMEM scratch is sized for 1 MiB planes
RBGS_N = [256, 512]


@pytest.mark.parametrize("n", RBGS_N)
def test_rbgs_halo_compiles(one_chip, n):
    s = one_chip
    _assert_kernel(jacobi3d.fused_rbgs_sweep_residual_halo.lower(
        _spec((n, n, n), s), _halos(s, n), _spec((n, n, n), s),
        _spec((7,), s), _spec((), s, jnp.int32), linf=False))


def test_jacobi_ghosted_compiles(one_chip):
    s = one_chip
    _assert_kernel(jacobi3d.fused_sweep_residual.lower(
        _spec((N + 2, N + 2, N + 2), s), _spec((N, N, N), s),
        _spec((7,), s)))


@pytest.mark.parametrize("n", RBGS_N)
def test_rbgs_ghosted_compiles(one_chip, n):
    s = one_chip
    _assert_kernel(jacobi3d.fused_rbgs_sweep_residual.lower(
        _spec((n + 4, n + 4, n + 2), s), _spec((n + 2, n + 2, n), s),
        _spec((7,), s), _spec((), s, jnp.int32)))


@pytest.mark.parametrize("linf", [True, False])
def test_diff_norm_partials_compiles(one_chip, linf):
    s = one_chip
    _assert_kernel(diff_norm_partials.lower(
        _spec((N, N, N), s), _spec((N, N, N), s), linf=linf))


def _mesh_solve_text(one_chip, monkeypatch, n, reduction, mode):
    """Optimised HLO of the hybrid convdiff solve on a (1,1) mesh, built
    as the benchmark's cells build it, with the kernel path forced on (the
    test process's backend is the CPU)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from repro.core import detection
    from repro.kernels.jacobi3d import ops as jac_ops
    from repro.launch.mesh import shard_axis_names
    from repro.runtime import shard_runtime as sr
    from repro.solvers.convdiff import Stencil

    monkeypatch.setattr(jac_ops, "_use_kernel", lambda interpret: True)
    (device,) = one_chip.device_set
    mesh = Mesh(np.array([device]).reshape(1, 1),
                shard_axis_names("shard", 2))
    st = Stencil.for_contraction(n, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    mon = detection.for_mode(mode, eps_tilde=1e-4, margin=10.0,
                             ord=float("inf"),
                             staleness=2 if reduction == "nonblocking" else 0)
    cfg = sr.ShardRuntimeConfig(monitor=mon, reduction=reduction,
                                sweep="hybrid", max_outer=2000,
                                mesh_shape=(1, 1))
    spec = _spec((n, n, n), NamedSharding(mesh, sr.mesh_state_spec(
        "convdiff", mesh)))
    return jax.jit(sr.make_convdiff_runtime(cfg, mesh, st, n)).lower(
        spec, spec).compile().as_text()


SOLVE_MODES = [("blocking", "sync"), ("nonblocking", "pfait")]


@pytest.mark.parametrize("reduction,mode", SOLVE_MODES)
def test_solve_kernels_sit_in_their_scopes(one_chip, monkeypatch, reduction,
                                           mode):
    """The whole (1,1)-mesh solve compiled for the chip: the red-black
    kernel under ``repro.sweep``, the blocking mode's residual-only kernel
    under ``repro.reduce``, and the zero faces the compiler materialises
    for the kernels under ``repro.halo`` (a profiler trace reads the
    scopes from each op's ``op_name``)."""
    text = _mesh_solve_text(one_chip, monkeypatch, N, reduction, mode)

    def innermost(pattern):
        """Innermost ``repro.`` scope of each instruction named so."""
        out = set()
        for m in re.finditer(r"^\s*(?:ROOT )?%" + pattern + r"\S* = .*"
                             r'op_name="([^"]*)"', text, re.MULTILINE):
            out.add(re.findall(r"repro\.(\w+)", m.group(1))[-1])
        return out

    assert innermost("fused_rbgs_sweep_residual_halo") == {"sweep"}
    want = {"reduce"} if reduction == "blocking" else set()
    assert innermost("fused_sweep_residual_halo") == want
    faces = re.findall(r"^\s*%broadcast\S* = f32\[(?:1,\d+,\d+|\d+,1,\d+|"
                       r'\d+,\d+,1)\].*op_name="([^"]*)"', text, re.MULTILINE)
    assert faces and {re.findall(r"repro\.(\w+)", f)[-1]
                      for f in faces} == {"halo"}


@pytest.mark.parametrize("reduction,mode", SOLVE_MODES)
def test_solve_loop_copies_no_block(one_chip, monkeypatch, reduction, mode):
    """The benchmark's 512³ solve compiled for the chip: the red-black
    kernel writes its new block over its input
    (``output_to_operand_aliasing``), so the while loop carries ``x``
    without copying it; the one block-sized copy left is the pre-loop
    copy of the undonated ``x0``."""
    n = 512
    text = _mesh_solve_text(one_chip, monkeypatch, n, reduction, mode)
    rbgs = re.findall(r"^\s*%fused_rbgs_sweep_residual_halo\S* = .*$", text,
                      re.MULTILINE)
    assert rbgs and all("output_to_operand_aliasing={{0}: (2, {})}" in line
                        for line in rbgs)
    copies = []     # (computation, instruction) of each block-sized copy
    computation = None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%(\S+) .*\{$", line)
        if head:
            computation = "ENTRY" if head.group(1) else head.group(2)
        elif re.search(rf"= f32\[{n},{n},{n}\]\S* copy\(", line):
            copies.append((computation, line.split("=")[0].strip()))
    assert len(copies) == 1 and copies[0][0] == "ENTRY", copies
