"""Fused 7-point convection–diffusion sweep + local residual norm — Pallas TPU.

The paper's hot loop.  GPU implementations make two passes over the grid
(relaxation sweep, then residual norm for the detection layer); here one
grid pass produces BOTH the swept block and the block's residual-norm
partials — the stencil is memory-bound, so fusing the detection pass saves
a second read of the field.  Two sweep flavours are fused:

* ``fused_sweep_residual*``       — Jacobi sweep (±1 plane window);
* ``fused_rbgs_sweep_residual*``  — the paper's hybrid red-black GS sweep
  (±2 plane window: each slab recomputes the colour-0 updates of its two
  neighbouring planes locally, so the two-colour dependency never crosses
  grid steps and the sweep stays a single grid pass).

Both report the residual of the *input* state (``b − A x_in``), i.e. the
detection contribution is one sweep staler than a dedicated post-sweep pass
— exactly the trade the paper's protocol-free detection is built to absorb.

Layout.  A block is ``(bx, by, bz)`` with z on the 128-wide lanes and y on
the sublanes.  The grid walks x-slabs of ``tx`` whole ``(by, bz)`` planes,
so every y/z neighbour of a slab cell is in the slab: the ±1 shifts along
sublanes/lanes are ``pltpu.roll`` rotations whose wrapped row/column is
replaced by the face halo.  The x neighbours outside the slab arrive as
extra single-plane blocks (index clamped into the block; the x∓ face halo
substitutes at the block edge), so every input is a tile-legal block that
Pallas pipelines HBM→VMEM itself.  ``tx`` follows from the plane size (see
``_slab_planes``), which keeps VMEM bounded for blocks up to 512² planes.
The seven stencil coefficients and the checkerboard phase live in SMEM;
each grid step writes its residual partial into its own lane-dense
``(8, 128)`` output tile.

The ghosted-layout entries (``fused_sweep_residual`` on a ±1 ghosted
block, ``fused_rbgs_sweep_residual`` on a ±2 one) unpack the ghost layers
into face planes and run the same kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.trace import device_scope

#: VMEM bytes one x-slab may take; with its halo planes, double buffering
#: and the stencil temporaries a kernel stays inside ``_VMEM_LIMIT``
_SLAB_BYTES = 1 << 20
#: most planes per slab: the per-plane loop is unrolled in the kernel body
_MAX_PLANES = 8
#: scoped VMEM per kernel: the RB-GS kernel on 512² planes needs more than
#: the 16 MiB default and fits 32 MiB (v5e compile); v5e has 128 MiB
_VMEM_LIMIT = 48 << 20
#: partials tile: one lane-dense f32 (8, 128) block per grid step
_PART = (8, 128)


def _slab_planes(bx: int, by: int, bz: int, itemsize: int) -> int:
    """Planes per grid step: the largest divisor of ``bx`` whose slab fits
    ``_SLAB_BYTES`` (at least one plane, at most ``_MAX_PLANES``)."""
    cap = max(1, min(_MAX_PLANES, _SLAB_BYTES // (by * bz * itemsize)))
    return max(d for d in range(1, min(cap, bx) + 1) if bx % d == 0)


def _roll(v, shift: int, axis: int):
    """``jnp.roll`` along a tiled axis (non-negative shift; no-op on a
    length-1 axis).  The shift is an i32, the rotate's operand type, also
    when x64 is enabled."""
    shift %= v.shape[axis]
    return pltpu.roll(v, np.int32(shift), axis) if shift else v


def _plane_off(c, xm, xp, ym, yp, zm, zp, k):
    """Off-diagonal apply on one x-plane ``c`` (by, bz): ``xm``/``xp`` are
    the neighbouring planes, ``ym``/``yp`` the (1, bz) y-halo rows and
    ``zm``/``zp`` the (by, 1) z-halo columns of this plane.  Terms are
    summed in the order of ``solvers.jacobi.offdiag_apply``."""
    by, bz = c.shape
    row = jax.lax.broadcasted_iota(jnp.int32, c.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
    vym = jnp.where(row == 0, ym, _roll(c, 1, 0))
    vyp = jnp.where(row == by - 1, yp, _roll(c, by - 1, 0))
    vzm = jnp.where(col == 0, zm, _roll(c, 1, 1))
    vzp = jnp.where(col == bz - 1, zp, _roll(c, bz - 1, 1))
    return (k[1] * xm + k[2] * xp + k[3] * vym + k[4] * vyp
            + k[5] * vzm + k[6] * vzp)


def _coefs(c_ref, dtype):
    return [c_ref[q].astype(dtype) for q in range(7)]


def _accumulate(acc, r, linf: bool):
    """Elementwise running max|r| / Σr² (f32) over the slab's planes."""
    v = jnp.abs(r).astype(jnp.float32) if linf else (r * r).astype(jnp.float32)
    if acc is None:
        return v
    return jnp.maximum(acc, v) if linf else acc + v


def _write_partial(res_ref, acc, linf: bool):
    part = jnp.max(acc) if linf else jnp.sum(acc)
    res_ref[...] = jnp.full(res_ref.shape, part, jnp.float32)


def _jacobi_kernel(c_ref, x_ref, xm_ref, xp_ref, gxm_ref, gxp_ref, gym_ref,
                   gyp_ref, gzm_ref, gzp_ref, b_ref, *out_refs, sweep: bool,
                   linf: bool, nx: int):
    """Jacobi sweep (``sweep``) or residual-only pass over one x-slab."""
    i = pl.program_id(0)
    tx = x_ref.shape[0]
    dtype = x_ref.dtype
    k = _coefs(c_ref, dtype)
    first = jnp.where(i == 0, gxm_ref[0], xm_ref[0])
    last = jnp.where(i == nx - 1, gxp_ref[0], xp_ref[0])
    acc = None
    for t in range(tx):
        c = x_ref[t]
        off = _plane_off(c, first if t == 0 else x_ref[t - 1],
                         last if t == tx - 1 else x_ref[t + 1],
                         gym_ref[t], gyp_ref[t], gzm_ref[t], gzp_ref[t], k)
        b = b_ref[t]
        acc = _accumulate(acc, b - (k[0] * c + off), linf)
        if sweep:
            out_refs[0][t] = (b - off) / k[0]
    _write_partial(out_refs[-1], acc, linf)


def _rbgs_kernel(c_ref, ph_ref, x_ref, xm2_ref, xm1_ref, xp1_ref, xp2_ref,
                 gxm_ref, gxp_ref, gym_ref, gym_m_ref, gym_p_ref, gyp_ref,
                 gyp_m_ref, gyp_p_ref, gzm_ref, gzm_m_ref, gzm_p_ref, gzp_ref,
                 gzp_m_ref, gzp_p_ref, b_ref, bm_ref, bp_ref, new_ref, res_ref,
                 *, linf: bool, bx: int):
    """Hybrid red-black GS sweep fused with the pre-sweep residual over one
    x-slab.  Planes ``x0-2 … x0+tx+1`` are in view: the slab's neighbouring
    planes ``x0-1``/``x0+tx`` get their colour-0 update recomputed here
    (ghost planes stay frozen), so colour 1 on the slab sees same-sweep
    colour-0 values without waiting on another grid step."""
    i = pl.program_id(0)
    tx = x_ref.shape[0]
    x0 = i * tx
    dtype = x_ref.dtype
    k = _coefs(c_ref, dtype)
    diag = k[0]
    _, by, bz = x_ref.shape
    yz = (jax.lax.broadcasted_iota(jnp.int32, (by, bz), 0)
          + jax.lax.broadcasted_iota(jnp.int32, (by, bz), 1) + ph_ref[0])

    def even(gx):
        """Colour-0 mask of the plane at global row ``gx``."""
        return jnp.bitwise_and(yz + gx, 1) == 0

    def outside(gx, ref):
        """Plane ``gx`` outside the slab: the block's own plane, the x∓
        face halo one step past the edge (anything further is dead)."""
        ghost = jnp.where(gx < 0, gxm_ref[0], gxp_ref[0])
        return jnp.where((gx >= 0) & (gx < bx), ref[0], ghost)

    # input state of planes x0-2 … x0+tx+1
    planes = ([outside(x0 - 2, xm2_ref), outside(x0 - 1, xm1_ref)]
              + [x_ref[t] for t in range(tx)]
              + [outside(x0 + tx, xp1_ref), outside(x0 + tx + 1, xp2_ref)])
    yhalo = ([(gym_m_ref[0], gyp_m_ref[0], gzm_m_ref[0], gzp_m_ref[0])]
             + [(gym_ref[t], gyp_ref[t], gzm_ref[t], gzp_ref[t])
                for t in range(tx)]
             + [(gym_p_ref[0], gyp_p_ref[0], gzm_p_ref[0], gzp_p_ref[0])])
    bs = [bm_ref[0]] + [b_ref[t] for t in range(tx)] + [bp_ref[0]]

    # colour 0 on planes x0-1 … x0+tx (index s = plane - x0 + 1), with the
    # slab's residual from the same off-diagonal apply
    upd0 = []
    acc = None
    for s in range(tx + 2):
        c = planes[s + 1]
        off = _plane_off(c, planes[s], planes[s + 2], *yhalo[s], k)
        u = jnp.where(even(x0 + s - 1), (bs[s] - off) / diag, c)
        if s == 0 or s == tx + 1:
            gx = x0 + s - 1
            u = jnp.where((gx >= 0) & (gx < bx), u, c)   # ghosts stay frozen
        else:
            acc = _accumulate(acc, bs[s] - (diag * c + off), linf)
        upd0.append(u)

    # colour 1 on the slab, against same-sweep colour-0 values
    for t in range(tx):
        s = t + 1
        off1 = _plane_off(upd0[s], upd0[s - 1], upd0[s + 1], *yhalo[s], k)
        new_ref[t] = jnp.where(even(x0 + t), upd0[s], (bs[s] - off1) / diag)
    _write_partial(res_ref, acc, linf)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------


def _face_specs(bx, by, bz, tx):
    """Blocks of the six face planes, reshaped by ``_face_arrays``."""
    return [
        pl.BlockSpec((1, by, bz), lambda i: (0, 0, 0)),       # gxm
        pl.BlockSpec((1, by, bz), lambda i: (0, 0, 0)),       # gxp
        pl.BlockSpec((tx, 1, bz), lambda i: (i, 0, 0)),       # gym
        pl.BlockSpec((tx, 1, bz), lambda i: (i, 0, 0)),       # gyp
        pl.BlockSpec((tx, by, 1), lambda i: (i, 0, 0)),       # gzm
        pl.BlockSpec((tx, by, 1), lambda i: (i, 0, 0)),       # gzp
    ]


@device_scope("halo")
def _face_arrays(halos, x):
    """The six face planes in block dtype, shaped so every slab takes a
    tile-legal block: x faces ``(1, by, bz)``, y faces ``(bx, 1, bz)``,
    z faces ``(bx, by, 1)``.  Halo assembly: where a face is the constant
    boundary, the compiler materialises it here, every call."""
    bx, by, bz = x.shape
    gxm, gxp, gym, gyp, gzm, gzp = (h.astype(x.dtype) for h in halos)
    return (gxm.reshape(1, by, bz), gxp.reshape(1, by, bz),
            gym.reshape(bx, 1, bz), gyp.reshape(bx, 1, bz),
            gzm.reshape(bx, by, 1), gzp.reshape(bx, by, 1))


def _plane_spec(by, bz, index):
    """One x-plane of a block, at a clamped plane index."""
    return pl.BlockSpec((1, by, bz), lambda i: (index(i), 0, 0))


def _partials(res):
    return res[::_PART[0], 0]


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel",),
                                vmem_limit_bytes=_VMEM_LIMIT)


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


@functools.partial(jax.jit, static_argnames=("op", "linf", "interpret"))
def fused_sweep_residual_halo(
    x: jax.Array,              # [bx, by, bz] unghosted block
    halos,                     # 6 face planes (gxm, gxp, gym, gyp, gzm, gzp)
    b: jax.Array,              # [bx, by, bz]
    stencil_coefs: jax.Array,  # [7] (diag, xm, xp, ym, yp, zm, zp)
    op: str = "sweep",
    linf: bool = True,
    interpret: bool = False,
):
    """Jacobi sweep (``op="sweep"``) or residual-only pass (``"residual"``,
    the block comes back unchanged) + input-state residual partials, from
    an unghosted block and explicit halo planes for every face — no
    host-side ghost assembly.

    Returns ``(new_block [bx,by,bz], residual partials [nx])`` (one partial
    per x-slab: max|r| for l∞, Σr² for l2, in f32)."""
    bx, by, bz = x.shape
    tx = _slab_planes(bx, by, bz, x.dtype.itemsize)
    nx = bx // tx
    sweep = op == "sweep"
    slab = pl.BlockSpec((tx, by, bz), lambda i: (i, 0, 0))
    part = pl.BlockSpec(_PART, lambda i: (i, 0))
    part_shape = jax.ShapeDtypeStruct((nx * _PART[0], _PART[1]), jnp.float32)
    outs = pl.pallas_call(
        functools.partial(_jacobi_kernel, sweep=sweep, linf=linf, nx=nx),
        grid=(nx,),
        in_specs=[
            _SMEM,
            slab,
            _plane_spec(by, bz, lambda i: jnp.maximum(i * tx - 1, 0)),
            _plane_spec(by, bz, lambda i: jnp.minimum((i + 1) * tx, bx - 1)),
            *_face_specs(bx, by, bz, tx),
            slab,
        ],
        out_specs=[slab, part] if sweep else [part],
        out_shape=([jax.ShapeDtypeStruct(x.shape, x.dtype)] if sweep else [])
        + [part_shape],
        compiler_params=_params(),
        interpret=interpret,
    )(stencil_coefs.astype(x.dtype), x, x, x, *_face_arrays(halos, x),
      b.astype(x.dtype))
    return (outs[0] if sweep else x), _partials(outs[-1])


@functools.partial(jax.jit, static_argnames=("linf", "interpret"))
def fused_rbgs_sweep_residual_halo(
    x: jax.Array,              # [bx, by, bz] unghosted block
    halos,                     # 6 face planes (gxm, gxp, gym, gyp, gzm, gzp)
    b: jax.Array,              # [bx, by, bz]
    stencil_coefs: jax.Array,  # [7] (diag, xm, xp, ym, yp, zm, zp)
    oxyz: jax.Array,           # i32 scalar: ox + oy + oz (checkerboard phase)
    linf: bool = True,
    interpret: bool = False,
):
    """Hybrid RB-GS sweep + pre-sweep residual partials from an unghosted
    block and explicit halo planes, in one grid pass.

    Returns ``(new_block [bx,by,bz], residual partials [nx])`` where the
    partials reduce ``b − A x_in`` (the *input* state's residual — the free
    by-product of the relaxation)."""
    bx, by, bz = x.shape
    tx = _slab_planes(bx, by, bz, x.dtype.itemsize)
    nx = bx // tx

    def plane(offset):
        return _plane_spec(
            by, bz, lambda i: jnp.clip(i * tx + offset, 0, bx - 1))

    def row(shape, offset):
        return pl.BlockSpec(
            shape, lambda i: (jnp.clip(i * tx + offset, 0, bx - 1), 0, 0))

    faces = _face_arrays(halos, x)
    fx, fy, fz = faces[:2], faces[2:4], faces[4:]
    specs = _face_specs(bx, by, bz, tx)
    slab = pl.BlockSpec((tx, by, bz), lambda i: (i, 0, 0))
    yrow, zcol = (1, 1, bz), (1, by, 1)
    new, res = pl.pallas_call(
        functools.partial(_rbgs_kernel, linf=linf, bx=bx),
        grid=(nx,),
        in_specs=[
            _SMEM, _SMEM,
            slab, plane(-2), plane(-1), plane(tx), plane(tx + 1),
            *specs[:2],
            specs[2], row(yrow, -1), row(yrow, tx),
            specs[3], row(yrow, -1), row(yrow, tx),
            specs[4], row(zcol, -1), row(zcol, tx),
            specs[5], row(zcol, -1), row(zcol, tx),
            slab, plane(-1), plane(tx),
        ],
        out_specs=[slab, pl.BlockSpec(_PART, lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((nx * _PART[0], _PART[1]), jnp.float32),
        ],
        compiler_params=_params(),
        interpret=interpret,
    )(stencil_coefs.astype(x.dtype),
      jnp.asarray(oxyz, jnp.int32).reshape((1,)),
      x, x, x, x, x, *fx,
      fy[0], fy[0], fy[0], fy[1], fy[1], fy[1],
      fz[0], fz[0], fz[0], fz[1], fz[1], fz[1],
      *(b.astype(x.dtype),) * 3)
    return new, _partials(res)


# ---------------------------------------------------------------------------
# Ghosted-layout entries
# ---------------------------------------------------------------------------


def _unghost(g, pad_xy: int):
    """Interior + six face planes of a block ghosted by ``pad_xy`` layers in
    x/y (ghosts on the innermost layer) and one layer in z."""
    p = pad_xy
    x = g[p:-p, p:-p, 1:-1]
    q = p - 1
    halos = (g[q, p:-p, 1:-1], g[-p, p:-p, 1:-1],
             g[p:-p, q, 1:-1], g[p:-p, -p, 1:-1],
             g[p:-p, p:-p, 0], g[p:-p, p:-p, -1])
    return x, halos


@functools.partial(jax.jit, static_argnames=("op", "linf", "interpret"))
def fused_sweep_residual(
    g: jax.Array,              # [(bx+2), (by+2), (bz+2)] ghosted block
    b: jax.Array,              # [bx, by, bz]
    stencil_coefs: jax.Array,  # [7] (diag, xm, xp, ym, yp, zm, zp)
    op: str = "sweep",
    linf: bool = True,
    interpret: bool = False,
):
    """Jacobi sweep / residual pass over a ±1 ghosted block (corners
    unused).  Returns ``(new_block [bx,by,bz], residual partials [nx])``."""
    x, halos = _unghost(g, 1)
    return fused_sweep_residual_halo(x, halos, b, stencil_coefs, op=op,
                                     linf=linf, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("linf", "interpret"))
def fused_rbgs_sweep_residual(
    g2: jax.Array,             # [(bx+4), (by+4), (bz+2)] twice-padded block
    b2: jax.Array,             # [bx+2, by+2, bz] rhs, zero-padded ±1 in x/y
    stencil_coefs: jax.Array,  # [7] (diag, xm, xp, ym, yp, zm, zp)
    oxy: jax.Array,            # i32 scalar: ox + oy (global checkerboard phase)
    linf: bool = True,
    interpret: bool = False,
):
    """Hybrid RB-GS sweep + pre-sweep residual partials over the twice-padded
    layout of ``ops.ghost_pad2`` (ghosts one ring in; the outermost ring and
    the rhs padding are never read).  Returns ``(new_block, partials [nx])``."""
    x, halos = _unghost(g2, 2)
    return fused_rbgs_sweep_residual_halo(x, halos, b2[1:-1, 1:-1, :],
                                          stencil_coefs, oxy, linf=linf,
                                          interpret=interpret)
