"""Fused diff-norm partial reduction — Pallas TPU.

The detection layer's hot path: ``r_i = ‖a − b‖_l`` (l ∈ {2, ∞}) evaluated
every outer iteration.  Unfused XLA does subtract → abs/square → reduce as
separate HBM passes at production sizes; this kernel streams both operands
through VMEM tiles once and emits per-tile partials (σ is applied by the
wrapper / the mesh reduction).

Layout: the flattened operands are viewed as lane-dense ``(rows, 128)``
slabs; each grid step reduces ``block // 128`` rows and writes its partial
into its own ``(8, 128)`` output tile (the smallest f32 block the TPU
tiling accepts).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128
_PART = (8, _LANES)


def _kernel(a_ref, b_ref, out_ref, *, linf: bool):
    # subtract in the wider of (operand dtype, f32), cast the *difference*:
    # narrow tiles (bf16) still upcast before differencing, while wide
    # inputs (the x64 host path, via interpret mode) keep update
    # differences far below the states' f32 resolution from quantising to
    # zero — the shard runtime detects on ‖x⁺ − x‖ at thresholds
    # ~1e-7 · diag⁻¹ relative to the state
    ct = jnp.promote_types(a_ref.dtype, jnp.float32)
    d = (a_ref[...].astype(ct) - b_ref[...].astype(ct)).astype(jnp.float32)
    part = jnp.max(jnp.abs(d)) if linf else jnp.sum(d * d)
    out_ref[...] = jnp.full(out_ref.shape, part, jnp.float32)


@functools.partial(jax.jit, static_argnames=("block", "linf", "interpret"))
def diff_norm_partials(
    a: jax.Array,
    b: jax.Array,
    block: int = 65536,
    linf: bool = True,
    interpret: bool = False,
):
    """Flattens inputs, returns per-block partials [nblocks] (f32).

    ``block`` (elements per partial) is rounded up to whole 128-lane rows
    and capped at the padded input size."""
    af = a.reshape(-1)
    bf = b.reshape(-1)
    n = af.shape[0]
    rows = -(-min(block, n) // _LANES)
    block = rows * _LANES
    pad = (-n) % block
    if pad:
        af = jnp.pad(af, (0, pad))
        bf = jnp.pad(bf, (0, pad))  # equal padding → zero diff
    nblk = af.shape[0] // block
    spec = pl.BlockSpec((rows, _LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, linf=linf),
        grid=(nblk,),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec(_PART, lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblk * _PART[0], _LANES), jnp.float32),
        interpret=interpret,
    )(af.reshape(-1, _LANES), bf.reshape(-1, _LANES))
    return out[::_PART[0], 0]
