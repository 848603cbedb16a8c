"""MoE: routing/packing invariants + distributed vs local-reference parity."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import moe as moe_mod
from repro.models.moe import moe_init, plan_moe
from repro.models.transformer import moe_local_reference
import pytest


def _cfg(E=4, k=2, d=32, f=64):
    return ModelConfig(
        name="t", family="moe", num_layers=2, d_model=d, vocab_size=128,
        num_heads=4, num_kv_heads=2, d_ff=f, num_experts=E, experts_per_token=k,
    )


def test_plan_virtual_experts_when_E_lt_tp():
    plan = plan_moe(_cfg(E=8, f=64), tp=16)
    assert plan.virt_per_expert == 2
    assert plan.virtual_experts == 16
    assert plan.d_ff_virtual == 32
    assert plan.per_rank_slots == 1


def test_plan_direct_when_E_ge_tp():
    plan = plan_moe(_cfg(E=32), tp=16)
    assert plan.virt_per_expert == 1
    assert plan.per_rank_slots == 2


def test_virtual_split_is_exact():
    """A gated FFN split along d_ff into r virtual experts sums exactly."""
    key = jax.random.PRNGKey(0)
    d, f, r = 16, 32, 2
    w1 = jax.random.normal(key, (d, f))
    w3 = jax.random.normal(jax.random.fold_in(key, 1), (d, f))
    w2 = jax.random.normal(jax.random.fold_in(key, 2), (f, d))
    x = jax.random.normal(jax.random.fold_in(key, 3), (5, d))
    full = (jax.nn.silu(x @ w1) * (x @ w3)) @ w2
    parts = 0
    for i in range(r):
        sl = slice(i * f // r, (i + 1) * f // r)
        parts = parts + (jax.nn.silu(x @ w1[:, sl]) * (x @ w3[:, sl])) @ w2[sl]
    np.testing.assert_allclose(np.asarray(full), np.asarray(parts), atol=1e-5)


def test_route_and_pack_capacity_invariants():
    plan = plan_moe(_cfg(E=4, k=2), tp=1)
    key = jax.random.PRNGKey(0)
    weights = moe_init(key, plan, gated=True, dtype=jnp.float32)
    t = 16
    tokens = jax.random.normal(jax.random.fold_in(key, 5), (t, plan.d_model))
    C = plan.capacity(t)
    send, (slots, pos, w), aux = moe_mod._route_and_pack(
        tokens, weights["router"], plan, C, jnp.ones((t,))
    )
    assert send.shape == (plan.virtual_experts, C, plan.d_model)
    pos_np, slots_np, w_np = map(np.asarray, (pos, slots, w))
    # every kept entry has a unique (slot, pos) and pos < C
    kept = w_np > 0
    assert np.all(pos_np[kept] < C)
    coords = list(zip(slots_np[kept].ravel(), pos_np[kept].ravel()))
    assert len(coords) == len(set(coords))
    assert np.isfinite(float(aux))


def test_shard_map_moe_matches_local_reference_single_device():
    """On a 1×1 mesh the a2a/AG collapse; with ample capacity the packed
    path must equal the dense one-hot reference exactly."""
    cfg = _cfg(E=4, k=2, d=32, f=64)
    plan = plan_moe(cfg, tp=1, capacity_factor=float(cfg.num_experts))  # no drops
    key = jax.random.PRNGKey(0)
    weights = moe_init(key, plan, gated=True, dtype=jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 7), (2, 8, cfg.d_model))
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    y_ref, aux_ref = moe_local_reference(x, weights, plan, gated=True)
    y_sm, aux_sm = jax.jit(
        lambda xx, ww: moe_mod.moe_apply(xx, ww, plan, True, mesh, dp_axes=("data",))
    )(x, weights)
    np.testing.assert_allclose(np.asarray(y_sm), np.asarray(y_ref), atol=1e-5)
    np.testing.assert_allclose(float(aux_sm), float(aux_ref), rtol=1e-5)


@pytest.mark.slow
def test_moe_is_differentiable_through_dispatch():
    cfg = _cfg(E=4, k=1, d=16, f=32)
    plan = plan_moe(cfg, tp=1, capacity_factor=4.0)
    key = jax.random.PRNGKey(0)
    weights = moe_init(key, plan, gated=True, dtype=jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 9), (1, 8, cfg.d_model))
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))

    def loss(w):
        y, aux = moe_mod.moe_apply(x, w, plan, True, mesh, dp_axes=("data",))
        return jnp.sum(y**2) + 0.01 * aux

    g = jax.grad(loss)(weights)
    gn = sum(float(jnp.sum(jnp.abs(leaf))) for leaf in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0
