"""§Perf optimization variants must be numerically faithful to the baseline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ParallelConfig, reduced
from repro.configs.registry import get_arch
from repro.models import Model
from repro.models.attention import attention_fwd, attention_fwd_pairs

B, S = 2, 64


def _batch(cfg, key=3):
    return {
        "inputs": jax.random.randint(jax.random.PRNGKey(key), (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(key + 1), (B, S), 0, cfg.vocab_size),
    }


@pytest.mark.parametrize("window", [0, 48])
def test_pairs_attention_exact_vs_blocked(window):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 128, 2, 3, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 128, 2, 32)), jnp.float32)
    a = attention_fwd(q, k, v, causal=True, window=window, block_kv=32)
    b = attention_fwd_pairs(q, k, v, causal=True, window=window,
                            block_q=32, block_kv=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_pairs_skips_work():
    """The pair list drops ~half the blocks for causal, more with a window."""
    # indirectly: gradients still flow and loss matches blocked impl
    cfg = reduced(get_arch("qwen2-1.5b"))
    m1 = Model(cfg)
    m2 = Model(cfg, parallel=ParallelConfig(attn_impl="pairs"))
    params = m1.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    l1, _ = jax.jit(m1.loss_fn)(params, batch)
    l2, _ = jax.jit(m2.loss_fn)(params, batch)
    assert abs(float(l1) - float(l2)) < 5e-3


@pytest.mark.slow
def test_save_mixer_remat_grad_parity():
    cfg = reduced(get_arch("qwen2-1.5b"))
    m1 = Model(cfg)
    m2 = Model(cfg, parallel=ParallelConfig(remat="save_mixer"))
    params = m1.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    g1 = jax.grad(lambda p: m1.loss_fn(p, batch)[0])(params)
    g2 = jax.grad(lambda p: m2.loss_fn(p, batch)[0])(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=2e-2,  # bf16 recompute-order rounding
        )


def test_tp_reduce_bf16_loss_parity_single_device_mesh():
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = reduced(get_arch("qwen2-1.5b"))
    m1 = Model(cfg, mesh=mesh)
    m2 = Model(cfg, mesh=mesh, parallel=ParallelConfig(tp_reduce_bf16=True))
    params = m1.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    l1, _ = jax.jit(m1.loss_fn)(params, batch)
    l2, _ = jax.jit(m2.loss_fn)(params, batch)
    assert abs(float(l1) - float(l2)) < 5e-3


def test_variant_train_step_runs_end_to_end():
    from repro.optim import AdamW, constant_schedule

    cfg = reduced(get_arch("qwen2-1.5b"))
    m = Model(cfg, parallel=ParallelConfig(attn_impl="pairs", remat="save_mixer"))
    opt = AdamW(constant_schedule(1e-3))
    ts = m.init_train_state(jax.random.PRNGKey(0), opt)
    step, _ = m.make_train_step(opt, microbatches=2)
    ts2, metrics = jax.jit(step)(ts, _batch(cfg))
    assert np.isfinite(float(metrics["loss"]))
