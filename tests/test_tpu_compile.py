"""The main-path Pallas kernels compile for a TPU v5e at the real block size.

Interpret mode cannot see what the chip's compiler refuses (tile-illegal
blocks, vector loads from HBM refs, VMEM overuse), so these tests compile
each kernel entry ahead of time for a described ``v5e:2x2`` topology — no
chip attached — at n=256 f32 (the red-black kernel also at the
benchmark's n=512) and check that the program holds the Mosaic
kernel (``tpu_custom_call``).  The topology is described only inside the
fixture: only the worker that runs these tests loads the TPU compiler.
"""
import os

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


@pytest.mark.parametrize("reduction,mode", [("blocking", "sync"),
                                            ("nonblocking", "pfait")])
def test_solve_kernels_sit_in_their_scopes(one_chip, monkeypatch, reduction,
                                           mode):
    """The whole (1,1)-mesh solve compiled for the chip: the red-black
    kernel under ``repro.sweep``, the blocking mode's residual-only kernel
    under ``repro.reduce``, and the zero faces the compiler materialises
    for the kernels under ``repro.halo`` (a profiler trace reads the
    scopes from each op's ``op_name``)."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from repro.core import detection
    from repro.runtime import shard_runtime as sr
    from repro.solvers.convdiff import Stencil

    # the kernel entries pick the Pallas path by the default backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    (device,) = one_chip.device_set
    mesh = Mesh(np.array([device]).reshape(1, 1), ("shard_x", "shard_y"))
    st = Stencil.for_contraction(N, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    mon = detection.for_mode(mode, eps_tilde=1e-4, margin=10.0,
                             staleness=2 if reduction == "nonblocking" else 0)
    cfg = sr.ShardRuntimeConfig(monitor=mon, reduction=reduction,
                                sweep="hybrid", max_outer=50,
                                mesh_shape=(1, 1))
    spec = _spec((N, N, N), NamedSharding(mesh, sr.mesh_state_spec(
        "convdiff", mesh)))
    text = jax.jit(sr.make_convdiff_runtime(cfg, mesh, st, N)).lower(
        spec, spec).compile().as_text()

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
