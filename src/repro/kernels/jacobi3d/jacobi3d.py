"""Fused 7-point convection–diffusion sweep + local residual norm — Pallas TPU.

The paper's hot loop.  GPU implementations make two passes over the grid
(relaxation sweep, then residual norm for the detection layer); here one
grid pass produces BOTH the swept block and the block's residual-norm
partials — the stencil is memory-bound, so fusing the detection pass saves
a second read of the field.  Two sweep flavours are fused:

* ``fused_sweep_residual*``       — Jacobi sweep (±1 plane window);
* ``fused_rbgs_sweep_residual*``  — the paper's hybrid red-black GS sweep,
  streamed along x through a rolling window of VMEM scratch: each plane of
  ``x`` and ``b`` leaves HBM once, its colour-0 update is computed once
  and kept until colour 1 of both its neighbours has used it, and the
  output trails the input by two slabs, so the two-colour dependency
  crosses grid steps inside VMEM and the sweep stays a single grid pass.

Both report the residual of the *input* state (``b − A x_in``), i.e. the
detection contribution is one sweep staler than a dedicated post-sweep pass
— exactly the trade the paper's protocol-free detection is built to absorb.

Layout.  A block is ``(bx, by, bz)`` with z on the 128-wide lanes and y on
the sublanes.  The grid walks x-slabs of ``tx`` whole ``(by, bz)`` planes,
so every y/z neighbour of a slab cell is in the slab: the ±1 shifts along
sublanes/lanes are ``pltpu.roll`` rotations whose wrapped row/column is
replaced by the face halo.  The x∓ face halo substitutes for the plane
past the block edge.  The Jacobi kernel gets the x neighbours outside its
slab as extra single-plane blocks (index clamped into the block); the
RB-GS kernel's grid runs in order (``"arbitrary"``) and keeps them in its
window.  Every input is a tile-legal block that Pallas pipelines HBM→VMEM
itself.  ``tx`` follows from the plane size (see ``_slab_planes``), which
keeps VMEM bounded for blocks up to 512² planes.
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
#: scoped VMEM per kernel: the RB-GS kernel's window (three slabs of x and
#: of colour-0 planes, two of b and face rows) and pipeline buffers on 512²
#: planes need more than the 16 MiB default and fit 20 MiB; on 512×1024
#: planes they fit 40 MiB (v5e compiles); v5e has 128 MiB
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


def _rbgs_kernel(c_ref, ph_ref, x_ref, gxm_ref, gxp_ref, gym_ref, gyp_ref,
                 gzm_ref, gzp_ref, b_ref, new_ref, res_ref, xw, uw, bw, yw,
                 zw, *, linf: bool, nx: int):
    """Hybrid red-black GS sweep fused with the pre-sweep residual, streamed
    along x through a rolling window of VMEM scratch.

    Grid step ``j`` brings slab ``j`` (``tx`` planes of ``x``, ``b`` and
    their y/z face rows); step ``j`` computes colour 0 and the residual of
    slab ``j-1``, whose x neighbours are then all in view, and colour 1 of
    slab ``j-2``, whose neighbours' colour-0 values then exist.  The
    window: ``xw`` holds the ``x`` slabs ``j-2 … j`` (3 slots), ``uw`` the
    colour-0 slabs ``j-3 … j-1`` (3 slots), ``bw``/``yw``/``zw`` the ``b``
    and face rows of slabs ``j-2, j-1`` (2 slots; slab ``j`` replaces
    ``j-2`` after its last use).  The x∓ face halo fills the slot a plane
    past the block edge would take (ghost planes stay frozen).

    The new block is written in place over ``x`` (the wrapper aliases
    them), and no slab is read after its new values land:

    * slab ``j`` of ``x`` is fetched once, for step ``lead(j) = min(j,
      nx-1)``; the drain steps ``nx, nx+1`` keep that block index, so
      nothing is fetched again;
    * the output block of step ``j`` is slab ``j-2``, written back to HBM
      only when the output block index changes, i.e. after step ``j >= 2``;
      steps 0 and 1 keep index 0 and write nothing;
    * so slab ``s`` is read before step ``s`` and overwritten after step
      ``s + 2``, and every later read of it comes from the window;
    * the x∓ faces and the y/z face rows are arrays of their own (zeros
      on a one-chip mesh, ghost-ring slots on a larger one), never views
      of ``x``."""
    j = pl.program_id(0)
    tx, by, bz = x_ref.shape
    k = _coefs(c_ref, x_ref.dtype)
    diag = k[0]
    yz = (jax.lax.broadcasted_iota(jnp.int32, (by, bz), 0)
          + jax.lax.broadcasted_iota(jnp.int32, (by, bz), 1) + ph_ref[0])

    def even(gx):
        """Colour-0 mask of the plane at global row ``gx``."""
        return jnp.bitwise_and(yz + gx, 1) == 0

    def slot(n, q):
        return jax.lax.rem(j + n, np.int32(q))

    def halo(s, t):
        return yw[s, 0, t], yw[s, 1, t], zw[s, 0, t], zw[s, 1, t]

    @pl.when(j < nx)
    def _():   # slab j joins the x window
        xw[slot(0, 3)] = x_ref[...]

    @pl.when(j == 0)
    def _():   # the plane before the block, for colour 0 and colour 1
        xw[2, tx - 1] = gxm_ref[0]
        uw[2, tx - 1] = gxm_ref[0]

    @pl.when(j == nx)
    def _():   # the plane after the block, for colour 0
        xw[nx % 3, 0] = gxp_ref[0]

    @pl.when(j == nx + 1)
    def _():   # the plane after the block, for colour 1
        uw[nx % 3, 0] = gxp_ref[0]

    @pl.when((j >= 1) & (j <= nx))
    def _():   # colour 0 and the input-state residual of slab j-1
        x0 = (j - 1) * tx
        sx, sb = slot(2, 3), slot(1, 2)
        acc = None
        for t in range(tx):
            c = xw[sx, t]
            xm = xw[sx, t - 1] if t else xw[slot(1, 3), tx - 1]
            xp = xw[sx, t + 1] if t < tx - 1 else xw[slot(0, 3), 0]
            off = _plane_off(c, xm, xp, *halo(sb, t), k)
            b = bw[sb, t]
            acc = _accumulate(acc, b - (diag * c + off), linf)
            uw[sx, t] = jnp.where(even(x0 + t), (b - off) / diag, c)
        _write_partial(res_ref, acc, linf)

    @pl.when(j >= 2)
    def _():   # colour 1 of slab j-2, against same-sweep colour-0 values
        x0 = (j - 2) * tx
        su, sb = slot(1, 3), slot(0, 2)
        for t in range(tx):
            u = uw[su, t]
            um = uw[su, t - 1] if t else uw[slot(0, 3), tx - 1]
            up = uw[su, t + 1] if t < tx - 1 else uw[slot(2, 3), 0]
            off1 = _plane_off(u, um, up, *halo(sb, t), k)
            new_ref[t] = jnp.where(even(x0 + t), u, (bw[sb, t] - off1) / diag)

    @pl.when(j < nx)
    def _():   # slab j's b and face rows replace slab j-2's
        s = slot(0, 2)
        bw[s] = b_ref[...]
        yw[s, 0], yw[s, 1] = gym_ref[...], gyp_ref[...]
        zw[s, 0], zw[s, 1] = gzm_ref[...], gzp_ref[...]


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------


def _face_specs(by, bz, tx, slab=lambda i: i):
    """Blocks of the six face planes, reshaped by ``_face_arrays``; grid
    step ``i`` takes the y/z face rows of x-slab ``slab(i)``."""
    return [
        pl.BlockSpec((1, by, bz), lambda i: (0, 0, 0)),       # gxm
        pl.BlockSpec((1, by, bz), lambda i: (0, 0, 0)),       # gxp
        pl.BlockSpec((tx, 1, bz), lambda i: (slab(i), 0, 0)),  # gym
        pl.BlockSpec((tx, 1, bz), lambda i: (slab(i), 0, 0)),  # gyp
        pl.BlockSpec((tx, by, 1), lambda i: (slab(i), 0, 0)),  # gzm
        pl.BlockSpec((tx, by, 1), lambda i: (slab(i), 0, 0)),  # gzp
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


def _params(semantics="parallel"):
    return pltpu.CompilerParams(dimension_semantics=(semantics,),
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

    The new block does not alias ``x``: step ``i`` reads plane ``i·tx-1``
    as an extra block after step ``i-1`` has written it.

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
            *_face_specs(by, bz, tx),
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
    block and explicit halo planes, in one grid pass that reads each plane
    of ``x`` and ``b`` once (see ``_rbgs_kernel``).  The new block aliases
    ``x``: inside a loop whose carry is ``x``, the kernel writes it in
    place and XLA copies nothing; a caller that keeps ``x`` (an undonated
    jit argument) gets a copy made before the call, so ``x`` stays valid.

    Returns ``(new_block [bx,by,bz], residual partials [nx])`` where the
    partials reduce ``b − A x_in`` (the *input* state's residual — the free
    by-product of the relaxation)."""
    bx, by, bz = x.shape
    tx = _slab_planes(bx, by, bz, x.dtype.itemsize)
    nx = bx // tx
    dtype = x.dtype

    # step j reads slab j, writes the partial of slab j-1 and the new
    # planes of slab j-2: two steps past the last slab drain the window
    def lead(j):
        return jnp.minimum(j, nx - 1)

    slab = pl.BlockSpec((tx, by, bz), lambda j: (lead(j), 0, 0))
    new, res = pl.pallas_call(
        functools.partial(_rbgs_kernel, linf=linf, nx=nx),
        grid=(nx + 2,),
        in_specs=[_SMEM, _SMEM, slab, *_face_specs(by, bz, tx, lead), slab],
        out_specs=[
            pl.BlockSpec((tx, by, bz), lambda j: (jnp.maximum(j - 2, 0), 0, 0)),
            pl.BlockSpec(_PART, lambda j: (jnp.clip(j - 1, 0, nx - 1), 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, dtype),
            jax.ShapeDtypeStruct((nx * _PART[0], _PART[1]), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((3, tx, by, bz), dtype),       # x window
            pltpu.VMEM((3, tx, by, bz), dtype),       # colour-0 window
            pltpu.VMEM((2, tx, by, bz), dtype),       # b
            pltpu.VMEM((2, 2, tx, 1, bz), dtype),     # y face rows
            pltpu.VMEM((2, 2, tx, by, 1), dtype),     # z face columns
        ],
        input_output_aliases={2: 0},   # x -> new, see ``_rbgs_kernel``
        compiler_params=_params("arbitrary"),
        interpret=interpret,
    )(stencil_coefs.astype(dtype),
      jnp.asarray(oxyz, jnp.int32).reshape((1,)),
      x, *_face_arrays(halos, x), b.astype(dtype))
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
