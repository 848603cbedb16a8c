"""Distributed fixed-point driver — the TPU-native production path.

The paper's runtime, mapped to an SPMD pod:

* the (x, y) process grid of the paper becomes the ``(data, model)`` device
  mesh (one subdomain per chip, full z-pencil local — paper §4.1);
* interface messages become ``lax.ppermute`` halo exchanges;
* asynchronous iterations become *communication-avoiding bounded-delay*
  iterations: ``inner_sweeps`` local sweeps between halo exchanges
  (``inner_sweeps = 1`` ≡ synchronous; ``> 1`` ≡ model (2) with
  ``τ ≥ k − inner_sweeps``);
* the paper's non-blocking residual reduction becomes the K-stale pipelined
  reduction of ``core.detection`` — the loop predicate reads the global
  residual launched K outer iterations earlier, so the scalar all-reduce
  overlaps sweep compute instead of fencing it;
* the residual itself is a *by-product of the sweep* (``fuse_residual``,
  default on): the last inner sweep of each outer iteration returns its
  local contribution fused, so one outer iteration performs exactly one
  ghost assembly + one grid pass — no residual-only second pass.  The
  contribution therefore measures the state *before* that sweep with
  *pre-exchange* ghosts (one sweep + one exchange staler than the seed's
  post-exchange evaluation) — precisely the kind of staleness the paper's
  protocol-free detection absorbs; NFAIS2's exact verification still
  recomputes a fresh post-exchange residual under its ``lax.cond``.
  ``fuse_residual=False`` restores the unfused two-pass baseline (used by
  benchmarks/bench_fused.py for the head-to-head).

``solve_sharded``/``make_sharded_solver`` build the shard_map program;
``solve_single`` is the 1-device reference used by tests.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import detection
from repro.core import residual as res
from repro.solvers import gauss_seidel, jacobi
from repro.solvers.convdiff import Stencil


class SolveResult(NamedTuple):
    x: jax.Array                 # solution (global layout as input)
    residual: jax.Array          # residual that fired detection (stale)
    outer_iters: jax.Array       # outer iterations executed
    converged: jax.Array


@dataclass(frozen=True)
class SolverConfig:
    stencil: Stencil
    monitor: detection.MonitorConfig
    inner_sweeps: int = 1        # bounded-delay asynchrony (s)
    max_outer: int = 10_000
    sweep: str = "hybrid"        # "hybrid" (RB-GS interior) | "jacobi"
    use_kernel: bool = False     # dispatch sweeps to the Pallas jacobi3d kernel
    fuse_residual: bool = True   # residual as sweep by-product (no 2nd pass)


# ---------------------------------------------------------------------------
# Halo exchange
# ---------------------------------------------------------------------------


def _shift(x: jax.Array, axis_name: str, up: bool, axis_size: int) -> jax.Array:
    """ppermute a face to the next (+1) or previous (−1) rank along an axis;
    edge ranks receive zeros (homogeneous Dirichlet BC)."""
    if up:
        perm = [(i, i + 1) for i in range(axis_size - 1)]
    else:
        perm = [(i + 1, i) for i in range(axis_size - 1)]
    return jax.lax.ppermute(x, axis_name, perm)


def halo_exchange(x: jax.Array, ax_x: str, ax_y: str, nx: int, ny: int):
    """Exchange the 4 (x,y) faces of a (bx, by, bz) block. Returns ghosts
    (xm, xp, ym, yp), each a face plane from the corresponding neighbour."""
    gxm = _shift(x[-1, :, :], ax_x, up=True, axis_size=nx)   # from rank-1's x+ face
    gxp = _shift(x[0, :, :], ax_x, up=False, axis_size=nx)   # from rank+1's x- face
    gym = _shift(x[:, -1, :], ax_y, up=True, axis_size=ny)
    gyp = _shift(x[:, 0, :], ax_y, up=False, axis_size=ny)
    return gxm, gxp, gym, gyp


def ghosted(x: jax.Array, ghosts) -> jax.Array:
    """Assemble the (bx+2, by+2, bz+2) ghosted block (z ghosts = BC = 0)."""
    gxm, gxp, gym, gyp = ghosts
    bx, by, bz = x.shape
    g = jnp.zeros((bx + 2, by + 2, bz + 2), x.dtype)
    g = g.at[1:-1, 1:-1, 1:-1].set(x)
    g = g.at[0, 1:-1, 1:-1].set(gxm)
    g = g.at[-1, 1:-1, 1:-1].set(gxp)
    g = g.at[1:-1, 0, 1:-1].set(gym)
    g = g.at[1:-1, -1, 1:-1].set(gyp)
    return g


def ghosted6(x: jax.Array, ghosts) -> jax.Array:
    """Assemble the (bx+2, by+2, bz+2) ghosted block from six face planes
    ``(gxm, gxp, gym, gyp, gzm, gzp)`` — the multi-axis mesh runtime's
    assembly, where any of x/y/z may be partitioned.  Unpartitioned or
    boundary faces pass the zero Dirichlet plane; corners/edges stay zero
    (the 7-point stencil never reads them)."""
    gxm, gxp, gym, gyp, gzm, gzp = ghosts
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


def _zero_ghosts(x: jax.Array):
    bx, by, bz = x.shape
    z = jnp.zeros
    return (
        z((by, bz), x.dtype), z((by, bz), x.dtype),
        z((bx, bz), x.dtype), z((bx, bz), x.dtype),
    )


# ---------------------------------------------------------------------------
# Sweeps
# ---------------------------------------------------------------------------


def _sweep_block(cfg: SolverConfig, x: jax.Array, ghosts, b: jax.Array, ox, oy) -> jax.Array:
    """One sweep, contribution discarded (inner sweeps that don't feed
    detection — the fused partials are dead code XLA eliminates)."""
    if cfg.use_kernel:
        from repro.kernels.jacobi3d import ops as jac_ops

        return jac_ops.sweep(cfg.stencil, x, ghosts, b, sweep=cfg.sweep,
                             ox=ox, oy=oy)
    g = ghosted(x, ghosts)
    if cfg.sweep == "jacobi":
        return jacobi.jacobi_sweep(cfg.stencil, g, b)
    return gauss_seidel.redblack_gs_sweep(cfg.stencil, g, b, ox, oy)


def _sweep_with_contribution(cfg: SolverConfig, x: jax.Array, ghosts,
                             b: jax.Array, ox, oy):
    """The fused hot path: ``(new_x, contrib)`` from one ghost assembly and
    one grid pass.  ``contrib`` is the pre-σ residual contribution of the
    *input* state (see module docstring for the staleness semantics)."""
    if cfg.use_kernel:
        from repro.kernels.jacobi3d import ops as jac_ops

        return jac_ops.sweep_with_contribution(
            cfg.stencil, x, ghosts, b, sweep=cfg.sweep, ox=ox, oy=oy,
            ord=cfg.monitor.ord)
    g = ghosted(x, ghosts)
    if cfg.sweep == "jacobi":
        new, r = jacobi.jacobi_sweep_residual(cfg.stencil, g, b)
    else:
        new, r = gauss_seidel.redblack_gs_sweep_residual(cfg.stencil, g, b, ox, oy)
    return new, res.local_contribution(r, cfg.monitor.ord)


def _local_contribution(cfg: SolverConfig, g: jax.Array, b: jax.Array) -> jax.Array:
    """Residual-only pass (unfused baseline + NFAIS2 exact verification)."""
    if cfg.use_kernel:
        from repro.kernels.jacobi3d import ops as jac_ops

        return jac_ops.residual_contribution(cfg.stencil, g, b, ord=cfg.monitor.ord)
    r = jacobi.residual_block(cfg.stencil, g, b)
    return res.local_contribution(r, cfg.monitor.ord)


def _outer_iteration(cfg: SolverConfig, x, ghosts, b, ox, oy):
    """Shared outer-iteration kernel for both drivers: ``inner_sweeps``
    sweeps, the last one fused with the detection contribution, then a
    residual-only pass only when ``fuse_residual`` is off."""
    if cfg.fuse_residual:
        for s in range(cfg.inner_sweeps - 1):
            x = _sweep_block(cfg, x, ghosts, b, ox, oy)
        x, contrib = _sweep_with_contribution(cfg, x, ghosts, b, ox, oy)
        return x, contrib
    for _ in range(cfg.inner_sweeps):
        x = _sweep_block(cfg, x, ghosts, b, ox, oy)
    return x, None




# ---------------------------------------------------------------------------
# Distributed solve (shard_map over the production mesh)
# ---------------------------------------------------------------------------


def make_sharded_solver(cfg: SolverConfig, mesh: Mesh, ax_x: str = "data", ax_y: str = "model"):
    """Build a jit-able ``solve(x0, b) -> SolveResult`` over ``mesh``.

    ``x0, b`` are global (n, n, n) arrays sharded P(ax_x, ax_y, None). On a
    multi-pod mesh pass composite axes, e.g. ax_x=("pod", "data")."""
    ax_x_t = ax_x if isinstance(ax_x, tuple) else (ax_x,)
    ax_y_t = ax_y if isinstance(ax_y, tuple) else (ax_y,)
    nx = int(np.prod([mesh.shape[a] for a in ax_x_t]))
    ny = int(np.prod([mesh.shape[a] for a in ax_y_t]))
    axis_names = ax_x_t + ax_y_t
    mon_cfg = cfg.monitor

    def local_solve(x0, b):
        def body_fn(state):
            x, ghosts, mon, k = state
            bx, by, _ = x.shape
            ox = _linear_index(ax_x_t) * bx
            oy = _linear_index(ax_y_t) * by
            x, contrib = _outer_iteration(cfg, x, ghosts, b, ox, oy)
            ghosts = halo_exchange(x, ax_x_t, ax_y_t, nx, ny)
            if contrib is None:  # unfused baseline: post-exchange second pass
                contrib = _local_contribution(cfg, ghosted(x, ghosts), b)
                def exact_fn(c=contrib):
                    return res.psum_sigma(c, axis_names, mon_cfg.ord)
            else:
                # fused contrib is one sweep stale; NFAIS2's exact
                # verification must measure the fresh post-exchange state
                # (paid lazily under its lax.cond).
                def exact_fn(x=x, ghosts=ghosts):
                    return res.psum_sigma(
                        _local_contribution(cfg, ghosted(x, ghosts), b),
                        axis_names, mon_cfg.ord)
            mon = detection.step(mon_cfg, mon, contrib, axis_names=axis_names,
                                 exact_residual_fn=exact_fn)
            return x, ghosts, mon, k + 1

        def cond_fn(state):
            _, _, mon, k = state
            return (~mon.converged) & (k < cfg.max_outer)

        ghosts = halo_exchange(x0, ax_x_t, ax_y_t, nx, ny)
        mon = detection.init_state(mon_cfg)
        x, _, mon, k = jax.lax.while_loop(
            cond_fn, body_fn, (x0, ghosts, mon, jnp.zeros((), jnp.int32))
        )
        return SolveResult(
            x=x, residual=mon.detected_residual, outer_iters=k, converged=mon.converged
        )

    spec = P(ax_x, ax_y, None)
    return jax.shard_map(
        local_solve,
        mesh=mesh,
        in_specs=(spec, spec),
        out_specs=SolveResult(x=spec, residual=P(), outer_iters=P(), converged=P()),
        check_vma=False,
    )


def _linear_index(axis_names: Tuple[str, ...]):
    """Linear rank along possibly-composite mesh axes."""
    idx = jnp.zeros((), jnp.int32)
    for a in axis_names:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


# ---------------------------------------------------------------------------
# Single-device reference (tests / examples)
# ---------------------------------------------------------------------------


def solve_single(cfg: SolverConfig, b: jax.Array, x0: Optional[jax.Array] = None) -> SolveResult:
    """p = 1 solve (no mesh): ghosts are the physical boundary (zeros)."""
    if x0 is None:
        x0 = jnp.zeros_like(b)
    mon_cfg = cfg.monitor

    def body_fn(state):
        x, mon, k = state
        x, contrib = _outer_iteration(cfg, x, _zero_ghosts(x), b, 0, 0)
        if contrib is None:  # unfused baseline: residual-only second pass
            contrib = _local_contribution(cfg, ghosted(x, _zero_ghosts(x)), b)
            def exact_fn(c=contrib):
                return res.sigma(c, mon_cfg.ord)
        else:
            def exact_fn(x=x):
                return res.sigma(
                    _local_contribution(cfg, ghosted(x, _zero_ghosts(x)), b),
                    mon_cfg.ord)
        mon = detection.step(mon_cfg, mon, contrib, axis_names=None,
                             exact_residual_fn=exact_fn)
        return x, mon, k + 1

    def cond_fn(state):
        _, mon, k = state
        return (~mon.converged) & (k < cfg.max_outer)

    mon = detection.init_state(mon_cfg)
    x, mon, k = jax.lax.while_loop(cond_fn, body_fn, (x0, mon, jnp.zeros((), jnp.int32)))
    return SolveResult(x=x, residual=mon.detected_residual, outer_iters=k, converged=mon.converged)
