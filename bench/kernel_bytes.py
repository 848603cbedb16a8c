"""Bytes each ``jacobi3d`` kernel call needs, from its shapes.

A call's need is one read of each distinct operand and one write of each
result: the block ``x``, the right-hand side ``b``, the six halo faces,
the new block (sweeps only) and the residual partials, one ``(8, 128)``
f32 tile per x-slab.  How a kernel moves them (re-read neighbour planes,
a pipelined second copy) does not change the count, so bytes over the
chip's bandwidth is the least time the call can take, and a roofline
share built on it cannot pass 100%.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

#: the partials tile each grid step of a kernel writes
PART_BYTES = 8 * 128 * 4
#: planes per x-slab are capped at this many bytes of one block's slab
SLAB_BYTES = 1 << 20
MAX_PLANES = 8


def slab_planes(bx: int, by: int, bz: int, itemsize: int) -> int:
    """Planes per grid step of the kernels: the largest divisor of ``bx``
    whose slab fits 1 MiB, between 1 and 8."""
    cap = max(1, min(MAX_PLANES, SLAB_BYTES // (by * bz * itemsize)))
    return max(t for t in range(1, cap + 1) if bx % t == 0)


def call_bytes(kind: str, block: Sequence[int], itemsize: int = 4) -> int:
    """Bytes one call needs: ``kind`` is ``"sweep"`` (Jacobi or red-black
    Gauss-Seidel: reads x, b, faces; writes new x, partials) or
    ``"residual"`` (reads x, b, faces; writes partials)."""
    bx, by, bz = (int(v) for v in block)
    cells = bx * by * bz
    faces = 2 * (by * bz + bx * bz + bx * by)
    partials = (bx // slab_planes(bx, by, bz, itemsize)) * PART_BYTES
    if kind == "sweep":
        return itemsize * (3 * cells + faces) + partials
    if kind == "residual":
        return itemsize * (2 * cells + faces) + partials
    raise ValueError(f"kernel call kind {kind!r} not in ('sweep', 'residual')")


def block_shape(n: int, mesh: Sequence[int]) -> Tuple[int, int, int]:
    """One chip's block of the n^3 grid over a (px[, py[, pz]]) mesh."""
    full = tuple(mesh) + (1,) * (3 - len(mesh))
    return tuple(n // p for p in full)


def calls_per_solve(reduction: str, outer_iters: int,
                    verifications: int = 0) -> Dict[str, int]:
    """Kernel calls one solve makes on each chip: one sweep per outer step;
    the blocking reduction adds a residual-only pass per step, and each
    NFAIS2 verification one more."""
    calls = {"sweep": int(outer_iters), "residual": int(verifications)}
    if reduction == "blocking":
        calls["residual"] += int(outer_iters)
    return calls
