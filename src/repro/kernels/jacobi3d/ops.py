"""jit'd dispatch wrapper for the jacobi3d kernel.

``sweep``/``sweep_with_contribution``/``residual_contribution`` are the entry
points used by ``solvers.fixed_point`` when ``SolverConfig.use_kernel`` is
set, and their ``*_halo`` twins the block-mesh shard runtime's.  On a TPU
they run the Pallas kernels; elsewhere the pure-jnp path (identical math,
XLA-fused) so the distributed driver runs everywhere.  ``interpret=True``
runs the kernels through the Pallas interpreter for validation.

The kernels take the block and its face planes as they are — a caller
pays no ghost assembly on the TPU path.  ``sweep_with_contribution`` is the
fused hot path: one grid pass yields both the swept block and the
detection layer's local contribution (the residual of the *input* state,
see kernels/jacobi3d/jacobi3d.py).

``PASS_COUNTS`` counts trace-time invocations per entry kind so tests can
assert the solver drivers lower to the expected number of grid passes (in
particular: no residual-only second pass on the fused path).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.jacobi3d.jacobi3d import (
    fused_rbgs_sweep_residual_halo,
    fused_sweep_residual,
    fused_sweep_residual_halo,
)
from repro.kernels.jacobi3d.ref import contribution, reduce_partials
from repro.solvers import gauss_seidel, jacobi
from repro.solvers.convdiff import Stencil

# trace-time grid-pass instrumentation (see module docstring)
PASS_COUNTS: Dict[str, int] = {"sweep": 0, "fused": 0, "residual": 0}


def reset_pass_counts() -> None:
    for k in PASS_COUNTS:
        PASS_COUNTS[k] = 0


def _coefs(st: Stencil) -> jnp.ndarray:
    return jnp.asarray([st.diag, st.xm, st.xp, st.ym, st.yp, st.zm, st.zp])


def _use_kernel(interpret: Optional[bool]) -> bool:
    return jax.default_backend() == "tpu" or bool(interpret)


# ---------------------------------------------------------------------------
# Ghost assembly (z ghosts = Dirichlet BC = 0)
# ---------------------------------------------------------------------------


def ghost_pad1(x: jax.Array, ghosts) -> jax.Array:
    """(bx+2, by+2, bz+2) ghosted block from interior + 4 (x,y) face planes
    (the driver's canonical assembly — one definition, shared)."""
    from repro.solvers.fixed_point import ghosted  # function-level: no cycle

    return ghosted(x, ghosts)


def ghost_pad2(x: jax.Array, ghosts) -> jax.Array:
    """(bx+4, by+4, bz+2) twice-padded block (the layout of
    ``fused_rbgs_sweep_residual``): ghosts sit one ring in; the outermost
    ring is never read."""
    gxm, gxp, gym, gyp = ghosts
    bx, by, bz = x.shape
    g = jnp.zeros((bx + 4, by + 4, bz + 2), x.dtype)
    g = g.at[2:-2, 2:-2, 1:-1].set(x)
    g = g.at[1, 2:-2, 1:-1].set(gxm)
    g = g.at[-2, 2:-2, 1:-1].set(gxp)
    g = g.at[2:-2, 1, 1:-1].set(gym)
    g = g.at[2:-2, -2, 1:-1].set(gyp)
    return g


def _with_zero_z(x: jax.Array, ghosts):
    """The four (x, y) ghost planes plus the z Dirichlet planes."""
    zero = jnp.zeros(x.shape[:2], x.dtype)
    return tuple(ghosts) + (zero, zero)


# ---------------------------------------------------------------------------
# Fused sweep + residual contribution
# ---------------------------------------------------------------------------


def _sweep_halo_impl(st, x, halos, b, sweep, ox, oy, oz, linf, interpret):
    """One relaxation sweep fused with the input-state residual
    contribution, from an unghosted block + six face planes.  Off-TPU the
    jnp path assembles ``ghosted6`` and runs the same solver math the
    single-device reference uses (bitwise parity of the 1-shard mesh); the
    kernels skip the assembly."""
    if not _use_kernel(interpret):
        from repro.solvers.fixed_point import ghosted6  # function-level: no cycle

        g = ghosted6(x, halos)
        if sweep == "jacobi":
            new, r = jacobi.jacobi_sweep_residual(st, g, b)
        else:
            new, r = gauss_seidel.redblack_gs_sweep_residual(st, g, b, ox, oy,
                                                             oz)
        return new, contribution(r, linf)
    if sweep == "jacobi":
        new, parts = fused_sweep_residual_halo(
            x, halos, b, _coefs(st), op="sweep", linf=linf,
            interpret=bool(interpret))
    else:
        oxyz = (jnp.asarray(ox, jnp.int32) + jnp.asarray(oy, jnp.int32)
                + jnp.asarray(oz, jnp.int32))
        new, parts = fused_rbgs_sweep_residual_halo(
            x, halos, b, _coefs(st), oxyz, linf=linf,
            interpret=bool(interpret))
    return new, reduce_partials(parts, linf)


def sweep(st: Stencil, x: jax.Array, ghosts, b: jax.Array,
          sweep: str = "jacobi", ox=0, oy=0,
          interpret: Optional[bool] = None) -> jax.Array:
    """Sweep-only entry (inner sweeps that don't feed detection).  The unused
    residual partials are dead code XLA eliminates."""
    PASS_COUNTS["sweep"] += 1
    new, _ = _sweep_halo_impl(st, x, _with_zero_z(x, ghosts), b, sweep, ox,
                              oy, 0, True, interpret)
    return new


def sweep_with_contribution(st: Stencil, x: jax.Array, ghosts, b: jax.Array,
                            sweep: str = "jacobi", ox=0, oy=0,
                            ord: float = float("inf"),
                            interpret: Optional[bool] = None):
    """Fused hot path: ``(new_block, contrib)`` in one pass.

    ``contrib`` is the pre-σ local contribution (max|r| for l∞, Σr² for l2)
    of the *input* state's residual — one sweep staler than a dedicated
    post-sweep pass, which the detection layer tolerates by design."""
    PASS_COUNTS["fused"] += 1
    return _sweep_halo_impl(st, x, _with_zero_z(x, ghosts), b, sweep, ox, oy,
                            0, np.isinf(ord), interpret)


def sweep_halo(st: Stencil, x: jax.Array, halos, b: jax.Array,
               sweep: str = "jacobi", ox=0, oy=0, oz=0,
               interpret: Optional[bool] = None) -> jax.Array:
    """Halo-buffer sweep-only entry (dead partials XLA eliminates)."""
    PASS_COUNTS["sweep"] += 1
    new, _ = _sweep_halo_impl(st, x, halos, b, sweep, ox, oy, oz, True,
                              interpret)
    return new


def sweep_with_contribution_halo(st: Stencil, x: jax.Array, halos,
                                 b: jax.Array, sweep: str = "jacobi",
                                 ox=0, oy=0, oz=0, ord: float = float("inf"),
                                 interpret: Optional[bool] = None):
    """Fused halo-buffer hot path: ``(new_block, contrib)`` in one pass."""
    PASS_COUNTS["fused"] += 1
    return _sweep_halo_impl(st, x, halos, b, sweep, ox, oy, oz,
                            np.isinf(ord), interpret)


def residual_contribution_halo(st: Stencil, x: jax.Array, halos,
                               b: jax.Array, ord: float = float("inf"),
                               interpret: Optional[bool] = None):
    """Residual-only pass from an unghosted block + six face planes
    (blocking mode's barrier pass and NFAIS2's exact verification)."""
    PASS_COUNTS["residual"] += 1
    linf = np.isinf(ord)
    if not _use_kernel(interpret):
        from repro.solvers.fixed_point import ghosted6

        return contribution(jacobi.residual_block(st, ghosted6(x, halos), b),
                            linf)
    _, parts = fused_sweep_residual_halo(x, halos, b, _coefs(st),
                                         op="residual", linf=linf,
                                         interpret=bool(interpret))
    return reduce_partials(parts, linf)


def residual_contribution(st: Stencil, g: jax.Array, b: jax.Array,
                          ord: float = float("inf"),
                          interpret: Optional[bool] = None):
    """Residual-only pass over a ±1 ghosted block (unfused baseline path and
    NFAIS2's exact verification)."""
    PASS_COUNTS["residual"] += 1
    linf = np.isinf(ord)
    if not _use_kernel(interpret):
        return contribution(jacobi.residual_block(st, g, b), linf)
    _, parts = fused_sweep_residual(g, b, _coefs(st), op="residual",
                                    linf=linf, interpret=bool(interpret))
    return reduce_partials(parts, linf)
