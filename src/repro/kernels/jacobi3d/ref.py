"""Pure-jnp oracle for the jacobi3d kernel."""
from __future__ import annotations

import jax.numpy as jnp


def contribution(r, linf: bool = True):
    """Pre-σ local contribution of a residual block, in f32: ``max|r|``
    (l∞) or ``Σr²`` (l2) — what the kernel's per-slab partials reduce to."""
    if linf:
        return jnp.max(jnp.abs(r)).astype(jnp.float32)
    return jnp.sum((r * r).astype(jnp.float32))


def reduce_partials(parts, linf: bool = True):
    """Combine a kernel's per-slab partials into the block contribution."""
    return jnp.max(parts) if linf else jnp.sum(parts)


def ghosted6_ref(x, halos):
    """(bx+2, by+2, bz+2) ghosted block from six face planes (the
    halo-consuming kernels' window semantics, assembled whole)."""
    gxm, gxp, gym, gyp, gzm, gzp = halos
    bx, by, bz = x.shape
    g = jnp.zeros((bx + 2, by + 2, bz + 2), x.dtype)
    g = g.at[1:-1, 1:-1, 1:-1].set(x)
    g = g.at[0, 1:-1, 1:-1].set(gxm)
    g = g.at[-1, 1:-1, 1:-1].set(gxp)
    g = g.at[1:-1, 0, 1:-1].set(gym)
    g = g.at[1:-1, -1, 1:-1].set(gyp)
    g = g.at[1:-1, 1:-1, 0].set(gzm)
    g = g.at[1:-1, 1:-1, -1].set(gzp)
    return g


def fused_sweep_residual_halo_ref(x, halos, b, coefs, op: str = "sweep",
                                  linf: bool = True):
    """Oracle for ``fused_sweep_residual_halo`` (assemble-then-sweep)."""
    return fused_sweep_residual_ref(ghosted6_ref(x, halos), b, coefs, op=op,
                                    linf=linf)


def fused_sweep_residual_ref(g, b, coefs, op: str = "sweep",
                             linf: bool = True):
    """``(new_block, contribution)`` of a Jacobi sweep (or residual-only
    pass) over a ±1 ghosted block."""
    diag, xm, xp, ym, yp, zm, zp = [coefs[i] for i in range(7)]
    off = (
        xm * g[:-2, 1:-1, 1:-1]
        + xp * g[2:, 1:-1, 1:-1]
        + ym * g[1:-1, :-2, 1:-1]
        + yp * g[1:-1, 2:, 1:-1]
        + zm * g[1:-1, 1:-1, :-2]
        + zp * g[1:-1, 1:-1, 2:]
    )
    r = b - (diag * g[1:-1, 1:-1, 1:-1] + off)
    new = (b - off) / diag if op == "sweep" else g[1:-1, 1:-1, 1:-1]
    return new, contribution(r, linf)
