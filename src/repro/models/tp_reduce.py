"""Explicit bf16 tensor-parallel reductions (beyond-paper §Perf opt).

GSPMD reduces TP dot partial-sums in the dot's f32 accumulation type — on
the wire that doubles every activation all-reduce.  For the two
down-projections (attention output, MLP down) we instead run the dot inside
a tiny shard_map and ``psum`` the **bf16** partials explicitly: within-chip
accumulation stays f32 (inside the dot), but the cross-chip payload is bf16.

Enabled by ``ParallelConfig.tp_reduce_bf16``; the baseline keeps the
GSPMD-implicit (f32-wire) reduction so both variants are measurable.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def tp_matmul_psum(
    h: jax.Array,        # [B, S, F] activations, F sharded over "model"
    w: jax.Array,        # [F, D] weight, F sharded over "model"
    mesh,
    dp_axes: Tuple[str, ...],
    model_axis: str = "model",
) -> jax.Array:
    """h @ w with an explicit bf16 all-reduce over the model axis."""

    def body(h_blk, w_blk):
        partial_out = jnp.einsum("bsf,fd->bsd", h_blk, w_blk)
        return jax.lax.psum(partial_out.astype(jnp.bfloat16), model_axis)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(dp_axes, None, model_axis), P(model_axis, None)),
        out_specs=P(dp_axes, None, None),
        check_vma=False,
    )(h, w.astype(jnp.bfloat16))
