"""Plain reference of the 3-D convection-diffusion system (arXiv:2206.15418
section 4.1), written from the paper's equations and sharing no code with
the program.

Backward Euler and centred finite differences on the n^3 interior of
[0,1]^3 with homogeneous Dirichlet boundaries give ``A x = b`` with the
7-point stencil

    diag  = 1/dt + 6 nu/h^2
    x-/x+ = -nu/h^2 -/+ a_x/(2h)      (likewise y, z)

with h = 1/(n+1).  The time step is chosen so that the Jacobi contraction
6 (nu/h^2) / diag equals ``rho``.  ``b`` is a smooth source plus 5% noise.
"""
from __future__ import annotations

import math
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Sequence, Tuple

import numpy as np

#: the seven coefficients in the order (diag, x-, x+, y-, y+, z-, z+)
Coefs = Tuple[float, float, float, float, float, float, float]


def coefficients(n: int, nu: float, a: Sequence[float], rho: float) -> Coefs:
    h = 1.0 / (n + 1)
    d = nu / (h * h)
    inv_dt = 6.0 * d * (1.0 - rho) / rho
    cx, cy, cz = (ai / (2.0 * h) for ai in a)
    return (inv_dt + 6.0 * d, -d - cx, -d + cx, -d - cy, -d + cy,
            -d - cz, -d + cz)


def rhs_device(key, n: int, dtype=None):
    """``b`` on the device: smooth modes plus 0.05 standard-normal noise,
    made by ``jax.random`` from ``key`` (call under ``jax.jit`` with the
    sharding wanted as ``out_shardings``)."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.float32 if dtype is None else dtype
    s = (jnp.arange(n, dtype=jnp.float32) + 1.0) / (n + 1.0)
    X = s[:, None, None]
    Y = s[None, :, None]
    Z = s[None, None, :]
    pi = math.pi
    smooth = (jnp.sin(pi * X) * jnp.sin(pi * Y) * jnp.sin(pi * Z)
              + 0.3 * jnp.sin(2.0 * pi * X) * jnp.cos(pi * Z))
    noise = jax.random.normal(key, (n, n, n), jnp.float32)
    return (smooth + 0.05 * noise).astype(dtype)


def rhs_host(n: int, seed: int) -> np.ndarray:
    """``b`` as the detection service's convdiff tenants build it (numpy
    ``default_rng(seed)``, float64): the same smooth modes and noise."""
    rng = np.random.default_rng(seed)
    xs = np.linspace(0, 1, n + 2)[1:-1]
    X, Y, Z = np.meshgrid(xs, xs, xs, indexing="ij")
    b = (np.sin(np.pi * X) * np.sin(np.pi * Y) * np.sin(np.pi * Z)
         + 0.3 * np.sin(2 * np.pi * X) * np.cos(np.pi * Z))
    return b + 0.05 * rng.standard_normal((n, n, n))


def apply_a(x: np.ndarray, c: Coefs) -> np.ndarray:
    """``A x`` in float64 over a whole (small) grid, zero ghosts."""
    g = np.pad(np.asarray(x, np.float64), 1)
    return (c[0] * g[1:-1, 1:-1, 1:-1]
            + c[1] * g[:-2, 1:-1, 1:-1] + c[2] * g[2:, 1:-1, 1:-1]
            + c[3] * g[1:-1, :-2, 1:-1] + c[4] * g[1:-1, 2:, 1:-1]
            + c[5] * g[1:-1, 1:-1, :-2] + c[6] * g[1:-1, 1:-1, 2:])


def _slab_residual(x: np.ndarray, b: np.ndarray, c: Coefs, i0: int,
                   i1: int) -> float:
    g = np.zeros((i1 - i0 + 2,) + tuple(s + 2 for s in x.shape[1:]))
    g[1:-1, 1:-1, 1:-1] = x[i0:i1]
    if i0 > 0:
        g[0, 1:-1, 1:-1] = x[i0 - 1]
    if i1 < x.shape[0]:
        g[-1, 1:-1, 1:-1] = x[i1]
    ax =(c[0] * g[1:-1, 1:-1, 1:-1]
          + c[1] * g[:-2, 1:-1, 1:-1] + c[2] * g[2:, 1:-1, 1:-1]
          + c[3] * g[1:-1, :-2, 1:-1] + c[4] * g[1:-1, 2:, 1:-1]
          + c[5] * g[1:-1, 1:-1, :-2] + c[6] * g[1:-1, 1:-1, 2:])
    r = np.asarray(b[i0:i1], np.float64) - ax
    return float(np.max(np.abs(r)))


def residual_inf(x: np.ndarray, b: np.ndarray, c: Coefs,
                 slab: int = 8, threads: int = 0) -> float:
    """``max |b - A x|`` in float64 over the whole grid, in x-slabs of
    ``slab`` planes on a thread pool (numpy releases the interpreter lock),
    so a 1024^3 grid needs a few slabs of memory, not a float64 copy."""
    n0 = x.shape[0]
    bounds = [(i, min(i + slab, n0)) for i in range(0, n0, slab)]
    threads = threads or min(8, os.cpu_count() or 1)
    with ThreadPoolExecutor(max_workers=threads) as pool:
        parts = list(pool.map(lambda ij: _slab_residual(x, b, c, *ij),
                              bounds))
    return max(parts)


def parity(n: int):
    """Red-black colour of each cell, ``(i + j + k) mod 2``, on the device."""
    import jax.numpy as jnp

    i = jnp.arange(n)
    return ((i[:, None, None] + i[None, :, None] + i[None, None, :]) % 2) == 1


def solve(b, c: Coefs, sweep: str, eps: float, max_outer: int, dtype):
    """Plain relaxation from x0 = 0 in ``dtype`` (jnp, any sharding of
    ``b``): Jacobi, or red-black Gauss-Seidel (``sweep="hybrid"``; on one
    block there are no interfaces, so the hybrid scheme is plain RB-GS),
    stopping when the inf-norm residual drops below ``eps`` or after
    ``max_outer`` sweeps.  Returns ``(x, outer_iters, converged)``."""
    import jax
    import jax.numpy as jnp

    b = b.astype(dtype)
    co = [jnp.asarray(v, dtype) for v in c]
    red = parity(b.shape[0]) if sweep == "hybrid" else None

    def off(x):
        g = jnp.pad(x, 1)
        return (co[1] * g[:-2, 1:-1, 1:-1] + co[2] * g[2:, 1:-1, 1:-1]
                + co[3] * g[1:-1, :-2, 1:-1] + co[4] * g[1:-1, 2:, 1:-1]
                + co[5] * g[1:-1, 1:-1, :-2] + co[6] * g[1:-1, 1:-1, 2:])

    def step(x):
        if red is None:
            return (b - off(x)) / co[0]
        even = jnp.where(red, x, (b - off(x)) / co[0])
        return jnp.where(red, (b - off(even)) / co[0], even)

    def resid(x):
        return jnp.max(jnp.abs(b - co[0] * x - off(x))).astype(jnp.float32)

    def cond(s):
        _, r, k = s
        return (r >= eps) & (k < max_outer)

    def body(s):
        x, _, k = s
        x = step(x)
        return x, resid(x), k + 1

    x0 = jnp.zeros_like(b)
    x, r, k = jax.lax.while_loop(cond, body, (x0, resid(x0), jnp.int32(0)))
    return x, k, r < eps


def config_coefficients(config: Dict) -> Coefs:
    return coefficients(int(config["n"]), float(config["nu"]),
                        config["a"], float(config["rho"]))
