"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps, interpret=True."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention_flat
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.jacobi3d.jacobi3d import fused_sweep_residual
from repro.kernels.jacobi3d.ref import fused_sweep_residual_ref, reduce_partials
from repro.kernels.residual_norm.ops import diff_norm
from repro.kernels.residual_norm.ref import diff_norm_partials_ref
from repro.kernels.residual_norm.residual_norm import diff_norm_partials
from repro.solvers.convdiff import Stencil

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# jacobi3d
# ---------------------------------------------------------------------------

JACOBI_CASES = [
    # (bx, by, bz, dtype): one x-slab, several slabs, and one-plane slabs
    # (a 1 MiB plane) — the slab size follows from the plane size
    (8, 8, 8, jnp.float32),
    (16, 8, 128, jnp.float32),
    (24, 16, 128, jnp.float32),
    (3, 512, 512, jnp.float32),
    (16, 8, 8, jnp.float64),
]


@pytest.mark.parametrize("bx,by,bz,dtype", JACOBI_CASES)
@pytest.mark.parametrize("op", ["sweep", "residual"])
@pytest.mark.parametrize("linf", [True, False])
def test_jacobi3d_matches_oracle(bx, by, bz, dtype, op, linf):
    st = Stencil.for_contraction(bx, 1.0, (1.0, 1.0, 1.0), 0.9)
    coefs = jnp.asarray([st.diag, st.xm, st.xp, st.ym, st.yp, st.zm, st.zp], dtype)
    g = jnp.asarray(RNG.standard_normal((bx + 2, by + 2, bz + 2)), dtype)
    b = jnp.asarray(RNG.standard_normal((bx, by, bz)), dtype)
    new_k, res_k = fused_sweep_residual(g, b, coefs, op=op, linf=linf,
                                        interpret=True)
    new_r, res_r = fused_sweep_residual_ref(g, b, coefs, op=op, linf=linf)
    tol = 1e-5 if dtype == jnp.float32 else 1e-12
    np.testing.assert_allclose(np.asarray(new_k), np.asarray(new_r), rtol=tol, atol=tol)
    np.testing.assert_allclose(float(reduce_partials(res_k, linf)),
                               float(res_r), rtol=1e-4, atol=tol)


def test_jacobi3d_sweep_equals_solver_sweep():
    """Kernel sweep == solvers.jacobi.jacobi_sweep (the production oracle)."""
    from repro.solvers import jacobi

    st = Stencil.for_contraction(8, 1.0, (1.0, 1.0, 1.0), 0.9)
    coefs = jnp.asarray([st.diag, st.xm, st.xp, st.ym, st.yp, st.zm, st.zp])
    g = jnp.asarray(RNG.standard_normal((10, 10, 10)))
    b = jnp.asarray(RNG.standard_normal((8, 8, 8)))
    new_k, _ = fused_sweep_residual(g, b, coefs, interpret=True)
    np.testing.assert_allclose(np.asarray(new_k),
                               np.asarray(jacobi.jacobi_sweep(st, g, b)),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (BH, BN, Sq, H, causal, window, dtype)
    (8, 4, 256, 64, True, 0, jnp.float32),
    (4, 4, 256, 128, False, 0, jnp.float32),
    (6, 2, 384, 64, True, 128, jnp.float32),
    (4, 2, 128, 64, True, 64, jnp.float32),
    (4, 2, 256, 64, True, 0, jnp.bfloat16),
]


@pytest.mark.parametrize("BH,BN,Sq,H,causal,window,dtype", FLASH_CASES)
def test_flash_attention_matches_oracle(BH, BN, Sq, H, causal, window, dtype):
    q = jnp.asarray(RNG.standard_normal((BH, Sq, H)), dtype)
    k = jnp.asarray(RNG.standard_normal((BN, Sq, H)), dtype)
    v = jnp.asarray(RNG.standard_normal((BN, Sq, H)), dtype)
    out_k = flash_attention_flat(q, k, v, causal=causal, window=window, interpret=True)
    out_r = flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), atol=tol, rtol=tol)


def test_flash_matches_model_blocked_attention():
    """Kernel == models.attention.attention_fwd (grouped GQA layout)."""
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.models.attention import attention_fwd

    B, S, N, P, H = 2, 128, 2, 3, 32
    q = jnp.asarray(RNG.standard_normal((B, S, N, P, H)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, N, H)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, N, H)), jnp.float32)
    out_k = flash_attention(q, k, v, causal=True, interpret=True)
    out_b = attention_fwd(q, k, v, causal=True, block_kv=64)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_b),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# residual_norm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(1000,), (128, 130), (7, 33, 65)])
@pytest.mark.parametrize("linf", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64, jnp.bfloat16])
def test_residual_norm_matches_oracle(shape, linf, dtype):
    a = jnp.asarray(RNG.standard_normal(shape), dtype)
    b = jnp.asarray(RNG.standard_normal(shape), dtype)
    pk = diff_norm_partials(a, b, block=256, linf=linf, interpret=True)
    pr = diff_norm_partials_ref(a, b, block=256, linf=linf)
    np.testing.assert_allclose(np.asarray(pk), np.asarray(pr), rtol=1e-5, atol=1e-5)


def test_diff_norm_wrapper():
    a = jnp.asarray(RNG.standard_normal((64, 64)), jnp.float32)
    b = jnp.asarray(RNG.standard_normal((64, 64)), jnp.float32)
    np.testing.assert_allclose(
        float(diff_norm(a, b, ord=2, interpret=True)),
        float(jnp.linalg.norm((a - b).ravel())), rtol=1e-5,
    )
    np.testing.assert_allclose(
        float(diff_norm(a, b, ord=float("inf"), interpret=True)),
        float(jnp.max(jnp.abs(a - b))), rtol=1e-6,
    )
