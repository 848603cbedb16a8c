"""Blocked online-softmax (flash) attention — Pallas TPU.

Grid: (B·N·P heads, q-blocks); each program streams kv-blocks by windowed
ref indexing from HBM, keeping the f32 (m, l, acc) accumulators in registers/
VMEM across the inner ``fori_loop``.  MXU-aligned 128×head_dim tiles.

Causal **block skipping**: the kv loop runs only over blocks intersecting
the causal (and sliding-window) band of the current q-block — the pure-jnp
path computes all S² scores and masks, so the kernel does ~2× less work at
train_4k and ~S/window less with a window (see EXPERIMENTS.md §Perf).

GQA is expressed by the wrapper: q heads are flattened to B·N·P rows while
k/v keep B·N rows; the kernel maps q-row → kv-row by integer division.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, block_q: int,
            block_kv: int, causal: bool, window: int, q_per_kv: int,
            seq_kv: int):
    bh = pl.program_id(0)
    iq = pl.program_id(1)
    kv_row = bh // q_per_kv
    q = q_ref[0].astype(jnp.float32) * scale          # [bq, H]
    H = q.shape[-1]
    q_start = iq * block_q
    q_pos = q_start + jax.lax.iota(jnp.int32, block_q)

    n_kv = seq_kv // block_kv
    if causal:
        hi = jnp.minimum((q_start + block_q - 1) // block_kv + 1, n_kv)
    else:
        hi = n_kv
    if window > 0:
        lo = jnp.maximum((q_start - window + 1) // block_kv, 0)
    else:
        lo = 0

    def body(jb, carry):
        m, l, acc = carry
        kv = (kv_row, pl.ds(jb * block_kv, block_kv), slice(None))
        k = k_ref[kv].astype(jnp.float32)
        v = v_ref[kv].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bkv]
        kv_pos = jb * block_kv + jax.lax.iota(jnp.int32, block_kv)
        mask = jnp.ones((block_q, block_kv), jnp.bool_)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window > 0:
            mask &= kv_pos[None, :] > (q_pos[:, None] - window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ()))
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, H), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_kv", "interpret"),
)
def flash_attention_flat(
    q: jax.Array,   # [BH, Sq, H]  (BH = B·N·P)
    k: jax.Array,   # [BN, Skv, H]
    v: jax.Array,   # [BN, Skv, H]
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
):
    BH, Sq, H = q.shape
    BN, Skv, _ = k.shape
    assert BH % BN == 0
    q_per_kv = BH // BN
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0
    scale = 1.0 / math.sqrt(H)

    return pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, block_q=block_q, block_kv=block_kv,
            causal=causal, window=window, q_per_kv=q_per_kv, seq_kv=Skv,
        ),
        grid=(BH, Sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, H), lambda bh, iq: (bh, iq, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, block_q, H), lambda bh, iq: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, H), q.dtype),
        interpret=interpret,
    )(q, k, v)
