"""Mixture-of-Experts with explicit expert parallelism (shard_map + a2a).

Layout
------
Experts are sharded over the ``model`` axis.  When ``E < tp`` (grok-1: 8
experts on a 16-wide axis) each expert is split into ``r = tp/E`` *virtual
experts* along d_ff — an exact decomposition of the gated FFN (the partial
down-projections sum), so every device owns ``ps = E_v/tp ≥ 1`` expert
shards.  Tokens are sequence-split across the model axis, routed top-k,
packed into per-(rank, slot) capacity buffers, exchanged with a single
``all_to_all``, transformed, and returned with a second ``all_to_all``.

FSDP: expert weights are additionally sharded over the ``data`` axis on
d_model and all-gathered per layer inside the block (transient), so resident
parameter memory scales with the full mesh.

Everything is static-shape (capacity-based, dropped tokens contribute zero)
and differentiable — a2a transposes to a2a.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import ceil_to


@dataclass(frozen=True)
class MoEPlan:
    num_experts: int       # E (logical)
    top_k: int
    tp: int
    d_model: int
    d_ff: int              # logical per-expert width
    capacity_factor: float = 1.0

    @property
    def virt_per_expert(self) -> int:
        return max(1, self.tp // self.num_experts) if self.num_experts < self.tp else 1

    @property
    def virtual_experts(self) -> int:
        return self.num_experts * self.virt_per_expert

    @property
    def d_ff_virtual(self) -> int:
        return self.d_ff // self.virt_per_expert

    @property
    def per_rank_slots(self) -> int:
        return self.virtual_experts // self.tp

    @property
    def kr(self) -> int:
        return self.top_k * self.virt_per_expert

    def capacity(self, tokens_per_rank: int) -> int:
        c = math.ceil(self.capacity_factor * tokens_per_rank * self.kr / self.virtual_experts)
        return max(1, c)


def plan_moe(cfg, tp: int, capacity_factor: float = 1.0) -> MoEPlan:
    if cfg.num_experts >= tp and cfg.num_experts % tp:
        raise ValueError(f"num_experts={cfg.num_experts} not divisible by tp={tp}")
    if cfg.num_experts < tp and tp % cfg.num_experts:
        raise ValueError(f"tp={tp} not divisible by num_experts={cfg.num_experts}")
    if cfg.num_experts < tp and cfg.d_ff % (tp // cfg.num_experts):
        raise ValueError("d_ff not divisible by virtual split")
    return MoEPlan(
        num_experts=cfg.num_experts, top_k=cfg.experts_per_token, tp=tp,
        d_model=cfg.d_model, d_ff=cfg.d_ff, capacity_factor=capacity_factor,
    )


def moe_init(key, plan: MoEPlan, gated: bool, dtype) -> Dict[str, jax.Array]:
    """Virtual-expert-layout weights: w1/w3 [Ev, D, Fv], w2 [Ev, Fv, D]."""
    kr, k1, k2, k3 = jax.random.split(key, 4)
    Ev, D, Fv = plan.virtual_experts, plan.d_model, plan.d_ff_virtual
    s_in, s_out = 1.0 / math.sqrt(D), 1.0 / math.sqrt(plan.d_ff)
    p = {
        "router": (jax.random.normal(kr, (D, plan.num_experts)) * s_in).astype(jnp.float32),
        "w1": (jax.random.normal(k1, (Ev, D, Fv)) * s_in).astype(dtype),
        "w2": (jax.random.normal(k2, (Ev, Fv, D)) * s_out).astype(dtype),
    }
    if gated:
        p["w3"] = (jax.random.normal(k3, (Ev, D, Fv)) * s_in).astype(dtype)
    return p


# ---------------------------------------------------------------------------
# Routing / packing (runs per model-rank on its token slice)
# ---------------------------------------------------------------------------


def _route_and_pack(tokens, router_w, plan: MoEPlan, capacity: int, valid_mask):
    """tokens [t, D] → (send [Ev, C, D], combine info).

    combine info: slots [t, kr], pos [t, kr], weights [t, kr] (0 if dropped).
    """
    t, D = tokens.shape
    Ev, r, kr = plan.virtual_experts, plan.virt_per_expert, plan.kr
    logits = jnp.einsum("td,de->te", tokens.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, plan.top_k)           # [t, k]
    # virtual expansion: expert e → slots e*r .. e*r+r-1, same weight each
    slots = (topi[:, :, None] * r + jnp.arange(r)[None, None, :]).reshape(t, kr)
    weights = jnp.repeat(topv, r, axis=-1)                   # [t, kr]
    weights = weights * valid_mask[:, None]
    # capacity positions: order entries by (slot, token) and count
    flat_slot = slots.reshape(-1)                            # [t*kr]
    active = (weights.reshape(-1) > 0.0)
    onehot = jax.nn.one_hot(flat_slot, Ev, dtype=jnp.int32) * active[:, None].astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot                # count before me
    flat_pos = jnp.sum(pos * onehot, axis=1)                 # [t*kr]
    keep = active & (flat_pos < capacity)
    # scatter into [Ev, C+1, D]; dropped entries go to the overflow row C
    sp = jnp.where(keep, flat_pos, capacity)
    token_rep = jnp.repeat(tokens, kr, axis=0)               # [t*kr, D]
    send = jnp.zeros((Ev, capacity + 1, D), tokens.dtype)
    send = send.at[flat_slot, sp].add(token_rep, mode="drop")
    send = send[:, :capacity, :]
    pos2 = flat_pos.reshape(t, kr)
    w2 = jnp.where(keep.reshape(t, kr), weights, 0.0)
    aux = _load_balance_loss(probs, topi, plan)
    return send, (slots, pos2, w2), aux


def _load_balance_loss(probs, topi, plan: MoEPlan):
    """Switch-style aux loss: E · Σ_e f_e · P_e (per-rank partial)."""
    E = plan.num_experts
    f = jnp.mean(jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0)
    pmean = jnp.mean(probs, axis=0)
    return E * jnp.sum(f * pmean)


def _unpack_combine(out_buf, info, capacity: int):
    """out_buf [Ev, C, D] + combine info → token outputs [t, D]."""
    slots, pos, w = info
    t, kr = slots.shape
    pos_c = jnp.minimum(pos, capacity - 1)
    gathered = out_buf[slots.reshape(-1), pos_c.reshape(-1)].reshape(t, kr, -1)
    return jnp.einsum("tkd,tk->td", gathered.astype(jnp.float32), w).astype(out_buf.dtype)


# ---------------------------------------------------------------------------
# The shard_map MoE block
# ---------------------------------------------------------------------------


def moe_block_local(
    x_block: jax.Array,          # [b, S, D] — this data-shard's tokens (replicated over model)
    weights: Dict[str, jax.Array],  # sharded leaves (see specs in model.py)
    plan: MoEPlan,
    gated: bool,
    model_axis: str = "model",
    fsdp_axis: Optional[str] = "data",
):
    """Body to run under shard_map.  Returns (y_block [b,S,D], aux_loss)."""
    b, S, D = x_block.shape
    tp = plan.tp
    rank = jax.lax.axis_index(model_axis)
    tokens_all = x_block.reshape(b * S, D)
    T = b * S
    t_pad = ceil_to(max(T, tp), tp)
    tpr = t_pad // tp  # tokens per model-rank
    pad = t_pad - T
    if pad:
        tokens_all = jnp.pad(tokens_all, ((0, pad), (0, 0)))
    my = jax.lax.dynamic_slice_in_dim(tokens_all, rank * tpr, tpr, axis=0)
    valid = (rank * tpr + jnp.arange(tpr)) < T

    C = plan.capacity(tpr)
    send, info, aux = _route_and_pack(my, weights["router"], plan, C, valid.astype(jnp.float32))
    ps = plan.per_rank_slots
    send = send.reshape(tp, ps, C, D)
    recv = jax.lax.all_to_all(send, model_axis, split_axis=0, concat_axis=0, tiled=False)
    # recv [tp(src), ps, C, D]; local expert shards [ps, D, Fv/fsdp]
    w1, w2, w3 = weights["w1"], weights["w2"], weights.get("w3")
    if fsdp_axis is not None:
        # Expert-TP over the fsdp axis: d_ff is sharded over "data", so we
        # all-gather *tokens* (cheap) instead of expert *weights* (huge),
        # compute the partial FFN on the local d_ff slice, and psum-scatter
        # the partial down-projections back.  Exact for (gated) MLPs.
        xg = jax.lax.all_gather(recv, fsdp_axis, axis=0, tiled=True)  # [dp·tp, ps, C, D]
        h = jnp.einsum("xpcd,pdf->xpcf", xg, w1)
        if gated:
            h = jax.nn.silu(h) * jnp.einsum("xpcd,pdf->xpcf", xg, w3)
        else:
            h = jax.nn.gelu(h)
        out_partial = jnp.einsum("xpcf,pfd->xpcd", h, w2)
        out = jax.lax.psum_scatter(out_partial, fsdp_axis, scatter_dimension=0, tiled=True)
    else:
        h = jnp.einsum("xpcd,pdf->xpcf", recv, w1)
        if gated:
            h = jax.nn.silu(h) * jnp.einsum("xpcd,pdf->xpcf", recv, w3)
        else:
            h = jax.nn.gelu(h)
        out = jnp.einsum("xpcf,pfd->xpcd", h, w2)
    back = jax.lax.all_to_all(out, model_axis, split_axis=0, concat_axis=0, tiled=False)
    y_my = _unpack_combine(back.reshape(plan.virtual_experts, C, D), info, C)
    # reassemble the full token set on every model-rank
    y_all = jax.lax.all_gather(y_my, model_axis, axis=0, tiled=True)  # [t_pad, D]
    y = y_all[:T].reshape(b, S, D)
    aux = jax.lax.psum(aux, model_axis) / tp
    return y, aux


def moe_apply(
    x: jax.Array,
    weights: Dict[str, jax.Array],
    plan: MoEPlan,
    gated: bool,
    mesh,
    dp_axes: Tuple[str, ...],
    model_axis: str = "model",
    fsdp_axis: Optional[str] = "data",
):
    """shard_map wrapper usable inside a jit'd/scanned transformer block."""
    from jax.sharding import PartitionSpec as P

    x_spec = P(dp_axes, None, None)
    # expert dim over "model" (EP); d_ff over "data" (expert-TP = FSDP-free
    # storage scaling without per-layer weight gathers)
    w_specs = {
        "router": P(None, None),
        "w1": P(model_axis, None, fsdp_axis),
        "w2": P(model_axis, fsdp_axis, None),
    }
    if gated:
        w_specs["w3"] = P(model_axis, None, fsdp_axis)

    fn = partial(
        moe_block_local, plan=plan, gated=gated,
        model_axis=model_axis, fsdp_axis=fsdp_axis,
    )
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(x_spec, w_specs),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, weights)
