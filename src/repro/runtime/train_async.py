"""Asynchronous data-parallel training runtime — SGD convergence certified
by the protocol-free non-blocking residual.

This is the ML half of the tentpole: each mesh shard is a *data-parallel
worker* holding a full parameter replica and a row shard of the training
set (``solvers/mlfixed.py`` tasks: ridge least squares or ℓ2-regularised
logistic regression).  Per exchange round, shard i

1. consumes the **stale** parameter average from ``view_delay[i]`` rounds
   ago (the delayed all-reduce of async data parallelism),
2. runs ``inner_steps[i]`` **heterogeneous local SGD steps** on its own
   rows, rotating deterministically through ``num_batches`` minibatches
   (seeded-deterministic stochastic gradients — same spec, same run),
3. publishes its new replica into the next average.

Formally this is the lifted fixed-point map of El-Baz's asynchronous
convex-optimization setting: the state is the replica stack
X = (x_1 … x_p), worker i's update is T_i(X) = LocalSGD_i^{s_i}(mean(X)),
and the natural residual is the **update difference** T_i(X) − x_i — it
vanishes exactly when training has converged (replicas consistent, mean
at the local-SGD fixed point), and near consensus it tracks γ‖∇F‖.  So
global convergence is certified by the *unchanged* ``core.detection``
monitor fed through the shard runtime's reduction modes:

* ``blocking``    — the synchronized-eval baseline: every round pays an
  *extra* evaluation pass of the worker map from the fresh average (the
  cost the paper's technique removes), psum consumed the same round, K
  forced 0.
* ``nonblocking`` — the paper: the contribution is the free by-product of
  the SGD step already taken (no eval pass), lanes k-lagged, the monitor
  consumes the reduction launched K rounds earlier.
* ``rdoubling``   — modified recursive doubling over the same lanes.

NFAIS2's blocking verification evaluates the deterministic full-batch
residual (the synchronized eval), paid lazily only when a candidate
fires.  Host-side oracles (``exact_train_residual``, ``reference_trace``)
reproduce the same map synchronously in numpy; ``core.termination``'s
``oracle_detect_step`` scores the async detection against them.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import detection
from repro.core import residual as res
from repro.core.reduction import get_reduction
from repro.runtime.shard_runtime import (
    _butterfly_rounds,
    _butterfly_step,
    _per_shard,
    _preduce,
    _ring_fill,
    _ring_read,
    _ring_write,
)
from repro.solvers.mlfixed import MLFixedPointProblem, _sigmoid

P = jax.sharding.PartitionSpec


@dataclass(frozen=True)
class TrainAsyncConfig:
    """Asynchrony knobs of the data-parallel loop (per-shard fields accept
    a scalar or a length-p sequence, like ``ShardRuntimeConfig``)."""

    monitor: detection.MonitorConfig
    reduction: str = "nonblocking"   # blocking | nonblocking | rdoubling
    inner_steps: Union[int, Sequence[int]] = 1   # local SGD steps / round
    view_delay: Union[int, Sequence[int]] = 0    # staleness of the average
    contrib_lag: Union[int, Sequence[int]] = 0   # reduction-lane age
    num_batches: int = 1             # minibatch rotation per shard
    gamma: Optional[float] = None    # None → safe_gamma(problem, p, nb)
    max_rounds: int = 10_000
    trace_len: int = 0               # >0: record launched residuals
    axis: str = "shard"

    def __post_init__(self):
        get_reduction(self.reduction)  # registry validation at construction
        if self.num_batches < 1:
            raise ValueError(f"num_batches={self.num_batches} must be >= 1")

    def effective_monitor(self) -> detection.MonitorConfig:
        """Same convention as the shard runtime: blocking consumes its
        reduction immediately and recursive doubling pipelines internally,
        so both force the monitor's K to 0."""
        if get_reduction(self.reduction).forces_zero_staleness \
                and self.monitor.staleness:
            return dataclasses.replace(self.monitor, staleness=0)
        return self.monitor


class TrainRunResult(NamedTuple):
    x: jax.Array              # [p, n] final per-shard parameter replicas
    residual: jax.Array       # the (possibly stale) residual that fired
    rounds: jax.Array         # exchange rounds performed
    converged: jax.Array
    local_steps: jax.Array    # [p] per-shard SGD step counts
    verifications: jax.Array  # NFAIS2 synchronized evals paid
    loss: jax.Array           # final full-data objective Σ_i F_i(x_i)
    trace: jax.Array          # [trace_len] launched global residual / round


# ---------------------------------------------------------------------------
# Step size (host-side): every worker's every minibatch map must contract
# ---------------------------------------------------------------------------


def _shard_rows(problem: MLFixedPointProblem, p: int):
    if problem.m % p:
        raise ValueError(f"m_rows={problem.m} not divisible by p={p}")
    m_loc = problem.m // p
    return [(problem.A[i * m_loc:(i + 1) * m_loc],
             problem.y[i * m_loc:(i + 1) * m_loc]) for i in range(p)]


def safe_gamma(problem: MLFixedPointProblem, p: int,
               num_batches: int = 1) -> float:
    """Largest-curvature-safe step: 1 / max over (shard, minibatch) of the
    local gradient's Lipschitz bound, so every local map is a contraction
    (lstsq: eigmax(A_bᵀA_b/m_b) + λ; logistic: the σ'≤1/4 bound)."""
    L = 0.0
    for A_loc, _ in _shard_rows(problem, p):
        m_loc = A_loc.shape[0]
        if m_loc % num_batches:
            raise ValueError(
                f"local rows {m_loc} not divisible by "
                f"num_batches={num_batches}")
        mb = m_loc // num_batches
        for b in range(num_batches):
            Ab = A_loc[b * mb:(b + 1) * mb]
            sv = np.linalg.svd(Ab, compute_uv=False)[0]
            if problem.task == "lstsq":
                L = max(L, sv * sv / mb + problem.l2)
            else:
                L = max(L, sv * sv / (4.0 * mb) + problem.l2)
    return 1.0 / L


# ---------------------------------------------------------------------------
# Device loop
# ---------------------------------------------------------------------------


def make_train_runtime(problem: MLFixedPointProblem, cfg: TrainAsyncConfig,
                       mesh):
    """Build ``run(X0, A, y) -> TrainRunResult`` over a 1-D shard mesh.

    .. deprecated:: Prefer ``repro.runtime.api.run_train`` (unified
       ``RuntimeConfig``/``RunReport`` surface).  This builder remains the
       compatibility shim the unified API routes through — signature and
       return type are frozen.

    ``X0`` — [p, n] replica stack sharded ``P(axis, None)``; ``A`` — the
    [m, n] design row-sharded ``P(axis, None)``; ``y`` — [m] targets
    (lstsq) or ±1 labels (logistic) sharded ``P(axis)``.
    """
    axis = cfg.axis
    p = mesh.shape[axis]
    mon_cfg = cfg.effective_monitor()
    ord_ = mon_cfg.ord
    if problem.m % p:
        raise ValueError(f"m_rows={problem.m} not divisible by p={p}")
    m_loc = problem.m // p
    if m_loc % cfg.num_batches:
        raise ValueError(f"local rows {m_loc} not divisible by "
                         f"num_batches={cfg.num_batches}")
    mb = m_loc // cfg.num_batches
    nb = cfg.num_batches
    inner = _per_shard(cfg.inner_steps, p, "inner_steps")
    if (inner < 1).any():
        raise ValueError("inner_steps must be >= 1 per shard")
    delay = _per_shard(cfg.view_delay, p, "view_delay")
    lag = _per_shard(cfg.contrib_lag, p, "contrib_lag")
    if cfg.reduction == "blocking" and (delay.any() or lag.any()):
        raise ValueError("blocking mode is the synchronized reference: "
                         "view_delay and contrib_lag must be 0")
    if cfg.reduction == "rdoubling":
        _butterfly_rounds(p)
    gamma = float(cfg.gamma if cfg.gamma is not None
                  else safe_gamma(problem, p, nb))
    l2 = problem.l2
    task = problem.task
    Lv = int(delay.max()) + 1
    Lc = int(lag.max()) + 1
    tlen = max(int(cfg.trace_len), 1)

    def grad_at(A_rows, y_rows, x):
        """Local-data gradient normalised by its own row count + full λ
        (so the mean over shards of local gradients is ∇F)."""
        if task == "lstsq":
            return A_rows.T @ (A_rows @ x - y_rows) / A_rows.shape[0] \
                + l2 * x
        w = -y_rows * jax.nn.sigmoid(-y_rows * (A_rows @ x))
        return A_rows.T @ w / A_rows.shape[0] + l2 * x

    def loss_at(A_rows, y_rows, x):
        """Local objective share F_i (Σ_i F_i = F at consensus)."""
        if task == "lstsq":
            r = A_rows @ x - y_rows
            return r @ r / (2.0 * problem.m) + l2 * (x @ x) / (2.0 * p)
        margin = y_rows * (A_rows @ x)
        return jnp.sum(jnp.logaddexp(0.0, -margin)) / problem.m \
            + l2 * (x @ x) / (2.0 * p)

    def loop(X0, A_loc, y_loc):
        rank = jax.lax.axis_index(axis)
        my_inner = jnp.asarray(inner)[rank]
        my_delay = jnp.asarray(delay)[rank]
        my_lag = jnp.asarray(lag)[rank]
        x0 = X0[0]   # [1, n] shard block → [n] replica

        def sgd_steps(x_start, k, steps):
            """``steps`` local minibatch steps; the batch counter keeps
            rotating across rounds (phase k·steps + t mod nb)."""
            def stepf(t, x):
                b = jnp.mod(k * steps + t, nb)
                rows = jax.lax.dynamic_slice_in_dim(A_loc, b * mb, mb, 0)
                tgt = jax.lax.dynamic_slice_in_dim(y_loc, b * mb, mb, 0)
                return x - gamma * grad_at(rows, tgt, x)
            return jax.lax.fori_loop(0, steps, stepf, x_start)

        def body(state):
            x, vring, cring, partial, visible, mon, trace, k = state
            view = _ring_read(vring, k - my_delay)   # stale average
            x_new = sgd_steps(view, k, my_inner)
            fresh = jax.lax.pmean(x_new, axis)
            vring = _ring_write(vring, fresh, k + 1)

            if cfg.reduction == "blocking":
                # synchronized-eval baseline: an extra evaluation pass of
                # the worker map from the fresh average, every round, on
                # the critical path (the map itself — same minibatch
                # schedule — so its fixed point is the one being monitored)
                contrib = res.local_contribution(
                    sgd_steps(fresh, k + 1, my_inner) - x_new, ord_)
            else:
                # the paper: the update difference is already in hand
                contrib = res.local_contribution(x_new - x, ord_)
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

            def exact_fn(x_new=x_new, fresh=fresh, k=k):
                # NFAIS2 verification: blocking synchronized eval of the
                # lifted residual at the fresh state
                return res.psum_sigma(
                    res.local_contribution(
                        sgd_steps(fresh, k + 1, my_inner) - x_new, ord_),
                    axis, ord_)

            mon = detection.step(mon_cfg, mon, g_pre, axis_names=None,
                                 exact_residual_fn=exact_fn)
            return x_new, vring, cring, partial, visible, mon, trace, k + 1

        def cond(state):
            mon, k = state[5], state[7]
            return (~mon.converged) & (k < cfg.max_rounds)

        mean0 = jax.lax.pmean(x0, axis)
        state0 = (
            x0,
            _ring_fill(mean0, Lv),
            jnp.full((Lc,), jnp.inf, jnp.float32),
            jnp.full((), jnp.inf, jnp.float32),   # butterfly partial
            jnp.full((), jnp.inf, jnp.float32),   # butterfly visible
            detection.init_state(mon_cfg),
            jnp.full((tlen,), jnp.inf, jnp.float32),
            jnp.zeros((), jnp.int32),
        )
        x, _, _, _, _, mon, trace, k = jax.lax.while_loop(cond, body, state0)
        loss = jax.lax.psum(loss_at(A_loc, y_loc, x), axis)
        return TrainRunResult(
            x=x[None],
            residual=mon.detected_residual,
            rounds=k,
            converged=mon.converged,
            local_steps=(k * my_inner)[None],
            verifications=mon.verifications,
            loss=loss,
            trace=trace,
        )

    row_spec = P(axis, None)
    out_specs = TrainRunResult(
        x=row_spec, residual=P(), rounds=P(), converged=P(),
        local_steps=P(axis), verifications=P(), loss=P(), trace=P(),
    )
    return jax.shard_map(loop, mesh=mesh,
                         in_specs=(row_spec, row_spec, P(axis)),
                         out_specs=out_specs, check_vma=False)


def init_replicas(problem: MLFixedPointProblem, p: int) -> np.ndarray:
    """Zero-initialised replica stack [p, n] (matches ``init_local``)."""
    return np.zeros((p, problem.n))


# ---------------------------------------------------------------------------
# Host-side oracles (numpy): the synchronized eval the async loop replaces
# ---------------------------------------------------------------------------


def _np_grad(A_rows, y_rows, x, task, l2):
    if task == "lstsq":
        return A_rows.T @ (A_rows @ x - y_rows) / A_rows.shape[0] + l2 * x
    w = -y_rows * _sigmoid(-y_rows * (A_rows @ x))
    return A_rows.T @ w / A_rows.shape[0] + l2 * x


def _np_contrib(r, ord_):
    if np.isinf(ord_):
        return float(np.max(np.abs(r)))
    return float(np.sum(np.abs(r) ** ord_))


def _np_sigma(c, ord_):
    if np.isinf(ord_):
        return float(c)
    return float(c ** (1.0 / ord_))


def exact_train_residual(problem: MLFixedPointProblem, X: np.ndarray,
                         inner_steps, gamma: float, ord: float = 2.0,
                         num_batches: int = 1, phase: int = 0) -> float:
    """Exact lifted residual at replica stack ``X`` [p, n]: one
    deterministic application of every worker's map (same minibatch
    schedule, rotation phase ``phase``) from the fresh average — the
    ground truth a synchronized eval would compute, and exactly what
    NFAIS2's verifier evaluates on device.  ``num_batches=1`` is the
    full-batch special case."""
    X = np.asarray(X, dtype=np.float64)
    p = X.shape[0]
    inner = np.broadcast_to(np.asarray(inner_steps, np.int64), (p,))
    shards = _shard_rows(problem, p)
    m_loc = problem.m // p
    if m_loc % num_batches:
        raise ValueError(f"local rows {m_loc} not divisible by "
                         f"num_batches={num_batches}")
    mb = m_loc // num_batches
    mean = X.mean(axis=0)
    total = 0.0 if not np.isinf(ord) else -np.inf
    for i in range(p):
        A_loc, y_loc = shards[i]
        xi = mean.copy()
        s = int(inner[i])
        for t in range(s):
            b = (phase * s + t) % num_batches
            rows = A_loc[b * mb:(b + 1) * mb]
            tgt = y_loc[b * mb:(b + 1) * mb]
            xi = xi - gamma * _np_grad(rows, tgt, xi, problem.task,
                                       problem.l2)
        c = _np_contrib(xi - X[i], ord)
        total = max(total, c) if np.isinf(ord) else total + c
    return _np_sigma(total, ord)


def reference_trace(problem: MLFixedPointProblem, p: int,
                    inner_steps, num_batches: int, gamma: float,
                    rounds: int, ord: float = 2.0):
    """Synchronous (zero-delay) trajectory of the same map, minibatch
    rotation included: returns ``(X_final, residuals[rounds])`` where
    entry k is the monitored residual σ(Σ_i ‖T_i(X_k) − x_i‖^l) the
    blocking device run reproduces round for round."""
    inner = np.broadcast_to(np.asarray(inner_steps, np.int64), (p,))
    shards = _shard_rows(problem, p)
    m_loc = problem.m // p
    if m_loc % num_batches:
        raise ValueError(f"local rows {m_loc} not divisible by "
                         f"num_batches={num_batches}")
    mb = m_loc // num_batches
    X = np.zeros((p, problem.n))
    out = np.empty(rounds)
    for k in range(rounds):
        mean = X.mean(axis=0)
        X_new = np.empty_like(X)
        total = 0.0 if not np.isinf(ord) else -np.inf
        for i in range(p):
            A_loc, y_loc = shards[i]
            xi = mean.copy()
            s = int(inner[i])
            for t in range(s):
                b = (k * s + t) % num_batches
                rows = A_loc[b * mb:(b + 1) * mb]
                tgt = y_loc[b * mb:(b + 1) * mb]
                xi = xi - gamma * _np_grad(rows, tgt, xi, problem.task,
                                           problem.l2)
            X_new[i] = xi
            c = _np_contrib(xi - X[i], ord)
            total = max(total, c) if np.isinf(ord) else total + c
        out[k] = _np_sigma(total, ord)
        X = X_new
    return X, out
