"""Device-resident asynchronous shard runtime — the paper's execution model
on real JAX shards.

Everything before this layer *simulates* the paper's claim: the event
engine (core/async_engine.py) replays asynchronous iterations in virtual
time, and the sharded driver (solvers/fixed_point.py) runs lockstep SPMD
with a pipelined reduction.  This module closes the gap: a shard_map
program where each mesh shard owns a block of the ConvDiff/PageRank state
and the *ingredients of asynchrony are explicit, per-shard quantities*:

* **heterogeneous progress** — shard i performs ``inner_sweeps[i]`` local
  sweeps per exchange (its own iteration count; the bounded-delay model (2)
  of the paper with per-process rates),
* **stale halos** — every exchange lands in a ring of delayed neighbour
  buffers; shard i *consumes* the view from ``halo_delay[i]`` exchanges ago
  (bounded staleness τ ≤ max delay),
* **k-lagged reduction lanes** — in non-blocking mode shard i's reduction
  contribution is its local residual from ``contrib_lag[i]`` checks ago:
  contributions enter the collective at staggered ages, exactly the
  inconsistency of the paper's free-running ``MPI_Iallreduce``.

The global residual is produced three ways, all routed through the same
``core.detection`` monitor (so the existing monitors and the reliability
oracle score them unchanged — the monitor receives a pre-σ reduced scalar
via ``axis_names=None``):

* ``blocking``    — barrier semantics: an *extra* residual-only pass over
  the fresh post-exchange state (detection work on the critical path), the
  psum consumed the same step, monitor staleness forced to 0.  With
  ``halo_delay = 0`` and uniform sweeps this is the synchronous reference:
  its residual trajectory matches the sharded driver to float tolerance.
* ``nonblocking`` — the paper: the contribution is the *free by-product* of
  the last inner sweep (zero extra passes), lanes are k-lagged, and the
  monitor consumes the reduction launched K checks earlier
  (``MonitorConfig.staleness``), leaving detection off the critical path.
* ``rdoubling``   — protocol-based on-device baseline (modified recursive
  doubling, Zou & Magoulès 2019; event-level twin in
  ``core.protocols.RecursiveDoublingProtocol``): one butterfly round per
  outer step over XOR partners via ``ppermute``; a global value completes
  every log2(p) steps and is consumed with that staleness.

``benchmarks/bench_shard_runtime.py`` measures the three against each other
(wall-time + HLO traffic) and the ``shard-runtime`` CI lane gates the
result; ``tests/test_shard_runtime.py`` holds the parity proofs.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Callable, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import detection
from repro.core import residual as res
from repro.core.reduction import REDUCTIONS, get_reduction
from repro.core.trace import device_scope
from repro.kernels.jacobi3d import ops as jac_ops
from repro.kernels.residual_norm import ops as rn_ops
from repro.solvers import gauss_seidel, jacobi
from repro.solvers.convdiff import Stencil
from repro.solvers.fixed_point import _shift, ghosted
from repro.solvers.partition import MeshPartition

P = jax.sharding.PartitionSpec
# replication checking off: per-shard state (rings, lanes) varies by design
_shard_map = functools.partial(jax.shard_map, check_vma=False)

# REDUCTIONS is re-exported above from repro.core.reduction — the registry is
# the single source of truth; historical importers of
# ``shard_runtime.REDUCTIONS`` keep working.


def _per_shard(v: Union[int, Sequence[int]], p: int, name: str,
               mesh_shape: Optional[Tuple[int, ...]] = None) -> np.ndarray:
    """Broadcast/validate a per-shard config field: a scalar broadcasts over
    all ``p`` shards (row-major over the mesh axes); a sequence must match
    the *total* shard count of the mesh, whatever its dimensionality."""
    arr = np.full(p, v, dtype=np.int32) if np.isscalar(v) else \
        np.asarray(v, dtype=np.int32)
    if arr.shape != (p,):
        where = (f" — mesh shape {tuple(mesh_shape)} has {p} shards total, "
                 "row-major" if mesh_shape is not None else "")
        raise ValueError(
            f"{name} must be a scalar or length-{p}{where}, got {arr.shape}")
    if (arr < 0).any():
        raise ValueError(f"{name} must be >= 0, got {arr.tolist()}")
    return arr


@dataclass(frozen=True)
class ShardRuntimeConfig:
    """Configuration of the asynchronous shard loop (per-shard fields accept
    a scalar or a length-p sequence)."""

    monitor: detection.MonitorConfig
    reduction: str = "nonblocking"   # blocking | nonblocking | rdoubling
    inner_sweeps: Union[int, Sequence[int]] = 1   # per-shard sweeps/exchange
    halo_delay: Union[int, Sequence[int]] = 0     # per-shard neighbour-view age
    contrib_lag: Union[int, Sequence[int]] = 0    # per-shard reduction-lane age
    max_outer: int = 10_000
    trace_len: int = 0               # >0: record the launched-residual series
    sweep: str = "jacobi"            # convdiff only: "jacobi" | "hybrid"
    axis: str = "shard"
    mesh_shape: Optional[Tuple[int, ...]] = None  # (px[,py[,pz]]); None = 1-D
    overlap: bool = False            # comm/compute-overlapped halo exchange

    def __post_init__(self):
        get_reduction(self.reduction)  # registry validation at construction
        if self.sweep not in ("jacobi", "hybrid"):
            raise ValueError(f"sweep {self.sweep!r} not in ('jacobi', 'hybrid')")
        if self.mesh_shape is not None:
            shape = tuple(int(s) for s in self.mesh_shape)
            if not 1 <= len(shape) <= 3 or any(s < 1 for s in shape):
                raise ValueError(
                    f"mesh_shape {self.mesh_shape!r} must be a tuple of 1-3 "
                    "positive ints (px,), (px, py) or (px, py, pz)")
            object.__setattr__(self, "mesh_shape", shape)
        if self.overlap:
            if self.sweep != "jacobi":
                raise ValueError(
                    "overlap=True requires sweep='jacobi': the red-black "
                    "ordering serializes face updates behind the colour "
                    "pass, so there is no independent slab to ship early")
            if self.reduction == "blocking":
                raise ValueError(
                    "overlap=True is incompatible with the blocking barrier "
                    "reference (its exact pass already serializes the step)")

    def effective_monitor(self) -> detection.MonitorConfig:
        """Monitor as the runtime runs it: blocking consumes its reduction
        immediately and recursive doubling carries its own log2(p)-step
        pipeline, so both force the monitor's K to 0; non-blocking keeps the
        configured staleness (the in-flight window)."""
        if get_reduction(self.reduction).forces_zero_staleness \
                and self.monitor.staleness:
            return dataclasses.replace(self.monitor, staleness=0)
        return self.monitor


class ShardRunResult(NamedTuple):
    x: jax.Array              # solution, global layout as input
    residual: jax.Array       # the (possibly stale) residual that fired
    outer_iters: jax.Array    # exchanges performed
    converged: jax.Array
    local_sweeps: jax.Array   # [p] per-shard sweep counts (heterogeneous)
    verifications: jax.Array  # NFAIS2 blocking verifications paid
    trace: jax.Array          # [trace_len] launched global residual per step


class _ShardProblem(NamedTuple):
    """Local view of one shard's problem inside the shard_map body."""

    exchange: Callable      # x_block -> ghosts pytree (the per-step collective)
    sweep: Callable         # (x_block, ghosts) -> x_block'
    sweep_contrib: Callable  # (x_block, ghosts) -> (x_block', pre-σ contrib)
    exact_contrib: Callable  # (x_block, ghosts) -> pre-σ contrib of x_block
    # comm-overlapped final step: (x, ghosts) -> (x', contrib, fresh ghosts).
    # The fresh faces are recomputed as thin slabs *before* the full-block
    # fused sweep, so the ppermute exchange is independent of it and XLA can
    # run the collective while the interior sweeps (None: no overlap).
    fused_step: Optional[Callable] = None


# ---------------------------------------------------------------------------
# Ring buffers (delayed neighbour views / k-lagged lanes)
# ---------------------------------------------------------------------------


def _ring_write(ring, value, step: jax.Array):
    """Write ``value`` at slot ``step mod L`` of every leaf (L = leading dim)."""
    return jax.tree_util.tree_map(
        lambda r, v: jax.lax.dynamic_update_index_in_dim(
            r, v.astype(r.dtype), jnp.mod(step, r.shape[0]), 0),
        ring, value)


def _ring_read(ring, step: jax.Array):
    """Read slot ``max(step, 0) mod L`` of every leaf."""
    idx = jnp.maximum(step, 0)
    return jax.tree_util.tree_map(
        lambda r: jax.lax.dynamic_index_in_dim(
            r, jnp.mod(idx, r.shape[0]), 0, keepdims=False),
        ring)


def _ring_fill(value, length: int):
    """A ring pre-filled with ``value`` in every slot (valid initial views
    for any delay)."""
    return jax.tree_util.tree_map(
        lambda v: jnp.broadcast_to(v[None], (length,) + v.shape), value)


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------


def _preduce(contribution: jax.Array, axis: str, ord: float) -> jax.Array:
    """Pre-σ global reduction of local contributions (psum / pmax) — σ is
    applied by ``detection.step`` itself under its ``axis_names=None``
    convention, so the monitor code path is byte-identical to the
    simulator's."""
    if np.isinf(ord):
        return jax.lax.pmax(contribution, axis)
    return jax.lax.psum(contribution, axis)


def _butterfly_rounds(p: int) -> int:
    if p & (p - 1):
        raise ValueError(f"rdoubling requires a power-of-two shard count, got {p}")
    return max(p.bit_length() - 1, 0)


def _butterfly_step(lane, partial, visible, k, p: int, axis: str, ord: float):
    """One round of the modified recursive-doubling reduction: round
    ``k mod log2(p)`` exchanges partials with the XOR partner; a completed
    global value becomes visible every log2(p) steps (the protocol's
    built-in staleness)."""
    rounds = _butterfly_rounds(p)
    if rounds == 0:  # single shard: the lane is the global value
        return lane, lane
    r = jnp.mod(k, rounds)
    base = jnp.where(r == 0, lane, partial)   # fresh epoch samples the lane

    def make_round(rr: int):
        perm = [(i, i ^ (1 << rr)) for i in range(p)]
        return lambda v: jax.lax.ppermute(v, axis, perm)

    recv = jax.lax.switch(r, [make_round(rr) for rr in range(rounds)], base)
    total = jnp.maximum(base, recv) if np.isinf(ord) else base + recv
    visible = jnp.where(r == rounds - 1, total, visible)
    return total, visible


# ---------------------------------------------------------------------------
# Generic asynchronous shard loop
# ---------------------------------------------------------------------------


def _make_loop(cfg: ShardRuntimeConfig, prob: _ShardProblem, p: int,
               rank_fn: Callable[[], jax.Array],
               axes: Optional[Tuple[str, ...]] = None,
               mesh_shape: Optional[Tuple[int, ...]] = None):
    mon_cfg = cfg.effective_monitor()
    ord_ = mon_cfg.ord
    inner = _per_shard(cfg.inner_sweeps, p, "inner_sweeps", mesh_shape)
    if (inner < 1).any():
        raise ValueError("inner_sweeps must be >= 1 per shard")
    delay = _per_shard(cfg.halo_delay, p, "halo_delay", mesh_shape)
    lag = _per_shard(cfg.contrib_lag, p, "contrib_lag", mesh_shape)
    if cfg.reduction == "blocking" and (delay.any() or lag.any()):
        raise ValueError("blocking mode is the synchronous barrier reference: "
                         "halo_delay and contrib_lag must be 0")
    if cfg.reduction == "rdoubling":
        _butterfly_rounds(p)  # validate early, outside the traced body
    Lg = int(delay.max()) + 1
    if prob.fused_step is not None:
        # double-buffered halo ring: the exchange writes slot k+1 while the
        # fused sweep still reads slot k-delay — distinct slots, so the
        # collective never aliases the buffer the kernel is consuming
        Lg = max(Lg, 2)
    Lc = int(lag.max()) + 1
    tlen = max(int(cfg.trace_len), 1)
    # collectives take a single axis name (historical 1-D mesh) or the tuple
    # of all shard axes (multi-axis mesh: reduce over the whole shard space)
    axis = cfg.axis if axes is None else axes

    def loop(x0, *problem_args):
        rank = rank_fn()
        my_inner = jnp.asarray(inner)[rank]
        my_delay = jnp.asarray(delay)[rank]
        my_lag = jnp.asarray(lag)[rank]

        def body(state):
            # each phase runs under the device scope of its event kind
            # (``repro.sweep`` …); a scope nested inside another wins
            x, gring, cring, partial, visible, mon, trace, k = state
            with device_scope("halo"):
                ghosts = _ring_read(gring, k - my_delay)

            def plain(_, xx):
                return prob.sweep(xx, ghosts, *problem_args)

            fresh = None
            with device_scope("sweep"):
                if cfg.reduction == "blocking":
                    x = jax.lax.fori_loop(0, my_inner, plain, x)
                    contrib = None
                elif prob.fused_step is not None:
                    # comm-overlapped step: thin face slabs are swept first
                    # and shipped, then the full block sweeps against the
                    # *landed* ghosts — the collective and the interior
                    # pass commute
                    x = jax.lax.fori_loop(0, my_inner - 1, plain, x)
                    x, contrib, fresh = prob.fused_step(x, ghosts,
                                                        *problem_args)
                else:
                    x = jax.lax.fori_loop(0, my_inner - 1, plain, x)
                    x, contrib = prob.sweep_contrib(x, ghosts, *problem_args)

            with device_scope("halo"):
                if fresh is None:
                    fresh = prob.exchange(x)
                gring = _ring_write(gring, fresh, k + 1)
            with device_scope("reduce"):
                if contrib is None:
                    # barrier mode: detection pays a residual-only pass over
                    # the fresh post-exchange state, every check
                    contrib = prob.exact_contrib(x, fresh, *problem_args)
                cring = _ring_write(cring, contrib, k)
                lane = _ring_read(cring, k - my_lag)

                if cfg.reduction == "rdoubling":
                    partial, visible = _butterfly_step(
                        lane, partial, visible, k, p, axis, ord_)
                    g_pre = visible
                else:
                    g_pre = _preduce(lane, axis, ord_)

                trace = trace.at[jnp.minimum(k, tlen - 1)].set(
                    jnp.where(k < tlen,
                              res.sigma(g_pre, ord_).astype(jnp.float32),
                              trace[jnp.minimum(k, tlen - 1)]))

            def exact_fn(x=x, fresh=fresh):
                # NFAIS2's verification: a *blocking* exact reduction of the
                # fresh state, paid lazily under the monitor's lax.cond
                return res.psum_sigma(
                    prob.exact_contrib(x, fresh, *problem_args), axis, ord_)

            with device_scope("detect"):
                mon = detection.step(mon_cfg, mon, g_pre, axis_names=None,
                                     exact_residual_fn=exact_fn)
            return x, gring, cring, partial, visible, mon, trace, k + 1

        def cond(state):
            mon, k = state[5], state[7]
            return (~mon.converged) & (k < cfg.max_outer)

        with device_scope("halo"):
            ghosts0 = prob.exchange(x0)
        state0 = (
            x0,
            _ring_fill(ghosts0, Lg),
            jnp.full((Lc,), jnp.inf, jnp.float32),
            jnp.full((), jnp.inf, jnp.float32),   # butterfly partial
            jnp.full((), jnp.inf, jnp.float32),   # butterfly visible
            detection.init_state(mon_cfg),
            jnp.full((tlen,), jnp.inf, jnp.float32),
            jnp.zeros((), jnp.int32),
        )
        x, _, _, _, _, mon, trace, k = jax.lax.while_loop(cond, body, state0)
        return ShardRunResult(
            x=x,
            residual=mon.detected_residual,
            outer_iters=k,
            converged=mon.converged,
            local_sweeps=(k * my_inner)[None],
            verifications=mon.verifications,
            trace=trace,
        )

    return loop


def _result_specs(cfg: ShardRuntimeConfig, x_spec,
                  axes: Optional[Tuple[str, ...]] = None) -> ShardRunResult:
    # local_sweeps is [p] with one entry per shard: on a multi-axis mesh the
    # per-shard scalars concatenate row-major over the tuple of shard axes
    sweeps_spec = P(cfg.axis) if axes is None else P(axes)
    return ShardRunResult(
        x=x_spec, residual=P(), outer_iters=P(), converged=P(),
        local_sweeps=sweeps_spec, verifications=P(), trace=P(),
    )


# ---------------------------------------------------------------------------
# ConvDiff shards (1-D pencils or 2-D/3-D blocks, stale-halo exchange)
# ---------------------------------------------------------------------------


def _make_convdiff_mesh_runtime(cfg: ShardRuntimeConfig, mesh, stencil:
                                Stencil, n: int):
    """Multi-axis (or comm-overlapped) convdiff runtime.

    The grid tiles by ``solvers.partition.MeshPartition`` over the mesh's
    shard axes; each shard owns an ``n/px × n/py × n/pz`` block and
    exchanges one face plane per partitioned direction per outer step
    (faces on unpartitioned directions are the physical boundary, ghost
    value 0).  Sweeps route through the halo-consuming jacobi3d entries
    (``ops.sweep_halo``/``sweep_with_contribution_halo``) which keep the
    single-HBM-pass fused sweep+residual, so ``core.detection`` and every
    reduction consume the same free by-product as the 1-D path.

    With ``cfg.overlap`` the final sweep of each outer step is the
    comm-overlapped ``fused_step``: the *new* face values are recomputed
    early as thickness-1 slabs (bitwise-identical to the faces the full
    sweep produces — same inputs, same operation order), the ``ppermute``
    is issued on those slabs against ring slot k+1, and the full fused
    sweep+residual then runs against the landed slot k-delay ghosts with
    no data dependence on the in-flight collective.
    """
    axes = tuple(mesh.axis_names)
    shape = tuple(int(mesh.shape[a]) for a in axes)
    part = MeshPartition(n, shape)
    p = part.p
    ndim = part.ndim
    block = tuple(n // s for s in part.full_shape)   # (bx, by, bz)
    parted = tuple(d for d in range(ndim) if shape[d] > 1)
    plane = {0: (block[1], block[2]), 1: (block[0], block[2]),
             2: (block[0], block[1])}
    st = stencil
    ord_ = cfg.monitor.ord
    if cfg.overlap:
        for d in parted:
            if block[d] < 2:
                raise ValueError(
                    "overlap=True needs block extent >= 2 on every "
                    f"partitioned axis: mesh {shape} at n={n} gives "
                    f"block {block}")

    def _face(x, d, last):
        return jax.lax.index_in_dim(x, x.shape[d] - 1 if last else 0, d,
                                    keepdims=False)

    @device_scope("halo")
    def _ship(faces):
        """ppermute each partitioned direction's (minus, plus) face pair to
        the respective neighbours; edge shards receive zeros (Dirichlet)."""
        out = []
        for d in parted:
            fm, fp = faces[d]
            gm = _shift(fp, axes[d], up=True, axis_size=shape[d])
            gp = _shift(fm, axes[d], up=False, axis_size=shape[d])
            out.append((gm, gp))
        return tuple(out)

    def exchange(x):
        return _ship({d: (_face(x, d, False), _face(x, d, True))
                      for d in parted})

    @device_scope("halo")
    def _halos6(x, faces):
        """Six face planes for the halo-consuming sweeps: exchanged ghosts
        on partitioned directions, zeros (physical BC) elsewhere."""
        h, fi = [], 0
        for d in range(3):
            if d in parted:
                gm, gp = faces[fi]
                fi += 1
            else:
                gm = gp = jnp.zeros(plane[d], x.dtype)
            h.extend((gm, gp))
        return tuple(h)

    def _offsets():
        return tuple(
            jax.lax.axis_index(axes[d]) * block[d] if d < ndim else 0
            for d in range(3))

    def sweep(x, faces, b):
        h = _halos6(x, faces)
        if cfg.sweep == "jacobi":
            return jac_ops.sweep_halo(st, x, h, b)
        ox, oy, oz = _offsets()
        return jac_ops.sweep_halo(st, x, h, b, sweep="hybrid",
                                  ox=ox, oy=oy, oz=oz)

    def sweep_contrib(x, faces, b):
        h = _halos6(x, faces)
        ox, oy, oz = _offsets() if cfg.sweep == "hybrid" else (0, 0, 0)
        return jac_ops.sweep_with_contribution_halo(
            st, x, h, b, sweep=cfg.sweep, ox=ox, oy=oy, oz=oz, ord=ord_)

    def exact_contrib(x, faces, b):
        return jac_ops.residual_contribution_halo(st, x, _halos6(x, faces),
                                                  b, ord=ord_)

    @device_scope("halo")
    def _face_sweep(x, h6, b, d, last):
        """The new values of one face of the block, as the full Jacobi sweep
        will produce them, from a thickness-1 slab: the same sweep entry on
        the same stencil inputs, so the result is bitwise-identical to the
        corresponding face of ``sweep(x, ...)`` — cheap enough to compute
        *before* the full sweep and hand to the exchange."""
        idx = x.shape[d] - 1 if last else 0
        slab = jax.lax.slice_in_dim(x, idx, idx + 1, axis=d)
        b_slab = jax.lax.slice_in_dim(b, idx, idx + 1, axis=d)
        sg = []
        for e in range(3):
            if e == d:
                # along the face normal: one side is the landed ghost, the
                # other the adjacent in-block plane (block extent >= 2)
                gm = h6[2 * d] if not last else \
                    jax.lax.index_in_dim(x, idx - 1, d, keepdims=False)
                gp = jax.lax.index_in_dim(x, idx + 1, d, keepdims=False) \
                    if not last else h6[2 * d + 1]
            else:
                # transverse: the block's e-ghost planes restricted to the
                # slab's row (axis d sits at position d or d-1 of the plane)
                pos = d if d < e else d - 1
                gm = jax.lax.slice_in_dim(h6[2 * e], idx, idx + 1, axis=pos)
                gp = jax.lax.slice_in_dim(h6[2 * e + 1], idx, idx + 1,
                                          axis=pos)
            sg.extend((gm, gp))
        new_slab = jac_ops.sweep_halo(st, slab, tuple(sg), b_slab)
        return jnp.squeeze(new_slab, axis=d)

    def fused_step(x, faces, b):
        h = _halos6(x, faces)
        fresh = _ship({d: (_face_sweep(x, h, b, d, False),
                           _face_sweep(x, h, b, d, True)) for d in parted})
        new, contrib = jac_ops.sweep_with_contribution_halo(
            st, x, h, b, sweep="jacobi", ord=ord_)
        return new, contrib, fresh

    def rank_fn():
        r = jnp.zeros((), jnp.int32)
        for d in range(ndim):
            r = r * shape[d] + jax.lax.axis_index(axes[d])
        return r

    prob = _ShardProblem(exchange, sweep, sweep_contrib, exact_contrib,
                         fused_step if cfg.overlap else None)
    loop = _make_loop(cfg, prob, p, rank_fn, axes=axes, mesh_shape=shape)
    spec = P(*axes, *([None] * (3 - ndim)))
    return _shard_map(loop, mesh=mesh, in_specs=(spec, spec),
                      out_specs=_result_specs(cfg, spec, axes=axes))


def make_convdiff_runtime(cfg: ShardRuntimeConfig, mesh, stencil: Stencil,
                          n: int):
    """Build ``run(x0, b) -> ShardRunResult`` over a shard mesh.

    ``x0, b`` are global (n, n, n) arrays sharded over the mesh's shard
    axes.  On the historical 1-D mesh each shard owns an x-pencil of
    ``n // p`` planes and exchanges its two x-faces per outer step (y/z
    faces are the physical boundary); that path is kept byte-identical in
    lowering (the HBM-exact CI gate pins it).  A multi-axis mesh — or
    ``cfg.overlap`` — routes to the block-decomposed mesh runtime.
    """
    axes = tuple(getattr(mesh, "axis_names", (cfg.axis,)))
    if cfg.mesh_shape is not None:
        mshape = tuple(int(mesh.shape[a]) for a in axes)
        if cfg.mesh_shape != mshape:
            raise ValueError(
                f"cfg.mesh_shape {cfg.mesh_shape} does not match the mesh's "
                f"shard axes {dict(zip(axes, mshape))}")
    if len(axes) > 1 or cfg.overlap:
        return _make_convdiff_mesh_runtime(cfg, mesh, stencil, n)
    axis = cfg.axis
    p = mesh.shape[axis]
    if n % p:
        raise ValueError(f"n={n} not divisible by shard count p={p}")
    bx = n // p
    st = stencil
    ord_ = cfg.monitor.ord

    def exchange(x):
        gxm = _shift(x[-1, :, :], axis, up=True, axis_size=p)
        gxp = _shift(x[0, :, :], axis, up=False, axis_size=p)
        return gxm, gxp

    @device_scope("halo")
    def _ghosted(x, ghosts):
        gxm, gxp = ghosts
        zero = jnp.zeros((x.shape[0], x.shape[2]), x.dtype)
        return ghosted(x, (gxm, gxp, zero, zero))  # y ghosts = BC = 0

    def _offsets():
        return jax.lax.axis_index(axis) * bx, 0

    def sweep(x, ghosts, b):
        g = _ghosted(x, ghosts)
        if cfg.sweep == "jacobi":
            return jacobi.jacobi_sweep(st, g, b)
        ox, oy = _offsets()
        return gauss_seidel.redblack_gs_sweep(st, g, b, ox, oy)

    def sweep_contrib(x, ghosts, b):
        g = _ghosted(x, ghosts)
        if cfg.sweep == "jacobi":
            new = jacobi.jacobi_sweep(st, g, b)
            # Jacobi residual is the update difference scaled by the
            # diagonal: fused diff-norm via the residual_norm kernel ops
            return new, rn_ops.update_contribution(new, x, ord=ord_,
                                                   scale=st.diag)
        ox, oy = _offsets()
        new, r = gauss_seidel.redblack_gs_sweep_residual(st, g, b, ox, oy)
        return new, res.local_contribution(r, ord_)

    def exact_contrib(x, ghosts, b):
        return res.local_contribution(
            jacobi.residual_block(st, _ghosted(x, ghosts), b), ord_)

    prob = _ShardProblem(exchange, sweep, sweep_contrib, exact_contrib)
    loop = _make_loop(cfg, prob, p, lambda: jax.lax.axis_index(axis))
    spec = P(axis, None, None)
    return _shard_map(loop, mesh=mesh, in_specs=(spec, spec),
                      out_specs=_result_specs(cfg, spec))


# ---------------------------------------------------------------------------
# PageRank shards (row blocks, stale all-gathered state views)
# ---------------------------------------------------------------------------


def make_pagerank_runtime(cfg: ShardRuntimeConfig, mesh, n: int,
                          damping: float = 0.85):
    """Build ``run(x0, P_dense) -> ShardRunResult`` over a 1-D shard mesh.

    ``x0`` is the global (n,) state sharded ``P(axis)``; ``P_dense`` the
    (n, n) column-stochastic operator sharded by rows ``P(axis, None)``.
    The "halo" is the full state view assembled by all-gather; staleness
    delays the *consumed* view, while a shard's own block is always
    current (the asynchronous-iterations convention).
    """
    if len(getattr(mesh, "axis_names", (cfg.axis,))) != 1:
        raise ValueError(
            "pagerank shards are 1-D row blocks; got mesh axes "
            f"{tuple(mesh.axis_names)} — multi-axis meshes are convdiff-only")
    if cfg.overlap:
        raise ValueError("overlap=True is convdiff-only (pagerank has no "
                         "halo ring: its exchange is an all-gather)")
    axis = cfg.axis
    p = mesh.shape[axis]
    if n % p:
        raise ValueError(f"n={n} not divisible by shard count p={p}")
    nb = n // p
    d = float(damping)
    v = (1.0 - d) / n
    ord_ = cfg.monitor.ord

    def exchange(x):
        return jax.lax.all_gather(x, axis, tiled=True)

    def _own_current(x, view):
        start = jax.lax.axis_index(axis) * nb
        return jax.lax.dynamic_update_slice(view, x.astype(view.dtype),
                                            (start,))

    def sweep(x, view, P_rows):
        # full-precision f32 products: a TPU's default f32 matmul rounds to
        # bf16 and would converge to another operator's fixed point
        return d * jnp.matmul(P_rows, _own_current(x, view),
                              precision="highest") + v

    def sweep_contrib(x, view, P_rows):
        new = sweep(x, view, P_rows)
        # D-iteration residual = the update difference (scale 1)
        return new, rn_ops.update_contribution(new, x, ord=ord_)

    def exact_contrib(x, view, P_rows):
        return res.local_contribution(sweep(x, view, P_rows) - x, ord_)

    prob = _ShardProblem(exchange, sweep, sweep_contrib, exact_contrib)
    loop = _make_loop(cfg, prob, p, lambda: jax.lax.axis_index(axis))
    return _shard_map(loop, mesh=mesh, in_specs=(P(axis), P(axis, None)),
                      out_specs=_result_specs(cfg, P(axis)))


# ---------------------------------------------------------------------------
# Family dispatch (benchmarks + the elastic restart driver)
# ---------------------------------------------------------------------------


FAMILIES = ("convdiff", "pagerank")


def make_runtime(family: str, cfg: ShardRuntimeConfig, mesh, n: int, *,
                 stencil: Optional[Stencil] = None, damping: float = 0.85):
    """``run(x0, problem_arg) -> ShardRunResult`` for a problem family.

    .. deprecated:: Prefer ``repro.runtime.api.run_shard`` (unified
       ``RuntimeConfig``/``RunReport`` surface).  This builder remains the
       compatibility shim the unified API routes through — signature and
       return type are frozen.

    One entry point for every caller that must rebuild the runtime against
    a *changing* mesh (the elastic driver re-invokes it after each
    remesh — per-shard config fields must then be scalars, since a
    length-p sequence is pinned to the old shard count)."""
    if family == "convdiff":
        if stencil is None:
            raise ValueError("convdiff runtime requires stencil=")
        return make_convdiff_runtime(cfg, mesh, stencil, n)
    if family == "pagerank":
        return make_pagerank_runtime(cfg, mesh, n, damping)
    raise KeyError(f"family {family!r} not in {FAMILIES}")


def state_spec(family: str, axis: str = "shard") -> P:
    """PartitionSpec of the solution array on a 1-D shard mesh."""
    if family == "convdiff":
        return P(axis, None, None)
    if family == "pagerank":
        return P(axis)
    raise KeyError(f"family {family!r} not in {FAMILIES}")


def mesh_state_spec(family: str, mesh) -> P:
    """PartitionSpec of the solution array on any shard mesh (1-D, 2-D or
    3-D): one spec dim per shard axis, trailing dims replicated."""
    axes = tuple(mesh.axis_names)
    if family == "convdiff":
        return P(*axes, *([None] * (3 - len(axes))))
    if family == "pagerank":
        if len(axes) != 1:
            raise ValueError(f"pagerank shards are 1-D; got axes {axes}")
        return P(axes[0])
    raise KeyError(f"family {family!r} not in {FAMILIES}")


# ---------------------------------------------------------------------------
# Synchronous references (parity oracles — tests/benchmarks)
# ---------------------------------------------------------------------------


def convdiff_reference_trace(stencil: Stencil, b: jax.Array, steps: int,
                             ord: float = 2.0,
                             x0: Optional[jax.Array] = None) -> jax.Array:
    """Global synchronous Jacobi trajectory: entry k is the exact residual
    after k+1 sweeps — what the blocking runtime must reproduce."""
    x = jnp.zeros_like(b) if x0 is None else x0

    def step(x, _):
        zero = (jnp.zeros((b.shape[1], b.shape[2]), b.dtype),) * 2
        zy = (jnp.zeros((x.shape[0], b.shape[2]), b.dtype),) * 2
        g = ghosted(x, zero + zy)
        x = jacobi.jacobi_sweep(stencil, g, b)
        g = ghosted(x, zero + zy)
        r = res.local_contribution(
            jacobi.residual_block(stencil, g, b), ord)
        return x, res.sigma(r, ord).astype(jnp.float32)

    _, trace = jax.lax.scan(step, x, None, length=steps)
    return trace


def pagerank_reference_trace(P_dense: jax.Array, n: int, steps: int,
                             damping: float = 0.85,
                             ord: float = 1.0) -> jax.Array:
    """Global synchronous D-iteration trajectory (post-step residuals)."""
    d = float(damping)
    v = (1.0 - d) / n
    x = jnp.full((n,), 1.0 / n, P_dense.dtype)

    def f(x):
        return d * jnp.matmul(P_dense, x, precision="highest") + v

    def step(x, _):
        x = f(x)
        r = res.local_contribution(f(x) - x, ord)
        return x, res.sigma(r, ord).astype(jnp.float32)

    _, trace = jax.lax.scan(step, x, None, length=steps)
    return trace
