"""ML fixed-point problem family: asynchronous gradient descent as the
paper's iterative process.

El-Baz's line of work ("unbounded delays … for Convex Optimization
Problems and Machine Learning", PAPERS.md) treats asynchronous SGD on a
strongly-convex objective as exactly the fixed-point setting the detection
paper assumes: the map

    f(x) = x − γ ∇F(x)

is a contraction for γ < 2/L (L the gradient's Lipschitz constant), its
fixed point is the empirical risk minimiser, and the natural residual is
the *update difference* f(x) − x = −γ∇F(x) — the gradient norm in
disguise.  That makes the whole detection stack (event-sim protocols, the
reliability oracle, elastic scenarios, batched detection grids) apply to
ML training runs with **zero** monitor changes.

Two strongly-convex tasks, both on synthetic data with a planted model:

* ``lstsq``    — ridge least squares, F(x) = ‖Ax−y‖²/(2m) + λ‖x‖²/2.
  The gradient is affine (Hx − c with H = AᵀA/m + λI), so the async
  iteration is *linear* — the same class as ConvDiff/PageRank but with a
  dense, ill-conditioned coupling instead of a stencil/graph.
* ``logistic`` — ℓ2-regularised logistic regression,
  F(x) = Σ softplus(−s_k·a_kᵀx)/m + λ‖x‖²/2, s ∈ {−1,+1}.  Non-linear
  gradients: the contraction factor varies over the trajectory, which is
  the stochastic-residual regime the oracle-scoring helpers in
  ``core.termination`` exist for.

Decomposition is **parameter-blocked** (async block-Jacobi gradient
descent): worker i owns coordinate block x_i and needs every other
worker's block to evaluate its gradient slice, so the dependency graph is
all-to-all — the data-parallel "parameter exchange" communication pattern,
and the densest block graph of the three families (ConvDiff: 2·dim
neighbours; PageRank: hub-skewed sparse; here: complete).

Residual convention follows core/residual.py: the fused
``update_with_residual`` returns the pre-σ contribution Σ|r|^l (max|r|
for l=∞) of r = −γ∇_i F at the worker's current *view*, and
``exact_residual`` scores the assembled iterate — the synchronized-eval
oracle an async training loop never pays for.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def _sigmoid(z: np.ndarray) -> np.ndarray:
    # numerically stable logistic function (no overflow for |z| large)
    out = np.empty_like(z)
    pos = z >= 0
    out[pos] = 1.0 / (1.0 + np.exp(-z[pos]))
    ez = np.exp(z[~pos])
    out[~pos] = ez / (1.0 + ez)
    return out


class MLFixedPointProblem:
    """Gradient descent on a strongly-convex ML objective as a
    ``core.async_engine.DecomposedProblem``."""

    TASKS = ("lstsq", "logistic")

    def __init__(
        self,
        n: int = 32,
        p: int = 4,
        m_rows: int = 192,
        task: str = "lstsq",
        gamma: float = None,
        l2: float = 1e-2,
        cond: float = 20.0,
        noise: float = 0.05,
        ord: float = 2.0,
        seed: int = 0,
    ):
        if n % p:
            raise ValueError(f"n={n} not divisible by p={p}")
        if task not in self.TASKS:
            raise ValueError(f"task {task!r} not in {self.TASKS}")
        if m_rows < n:
            raise ValueError(f"m_rows={m_rows} < n={n}: need an "
                             "overdetermined design for a unique minimiser")
        if l2 < 0.0:
            raise ValueError(f"l2={l2} must be >= 0")
        if cond < 1.0:
            raise ValueError(f"cond={cond} must be >= 1")
        self.n = n
        self.p = p
        self.m = m_rows
        self.task = task
        self.l2 = float(l2)
        self.ord = float(ord)
        self.block = n // p
        rng = np.random.default_rng(seed)

        # design matrix with controlled conditioning: Gaussian columns
        # scaled geometrically so eig(AᵀA/m) spans ~cond² before the ridge
        col_scale = cond ** (-np.arange(n) / max(n - 1, 1))
        self.A = rng.standard_normal((m_rows, n)) * col_scale
        self.x_true = rng.standard_normal(n)
        z = self.A @ self.x_true
        if task == "lstsq":
            self.y = z + noise * rng.standard_normal(m_rows)
            self.H = self.A.T @ self.A / m_rows + self.l2 * np.eye(n)
            self.c = self.A.T @ self.y / m_rows
            ev = np.linalg.eigvalsh(self.H)
            self.L = float(ev[-1])
            self.mu = float(ev[0])
        else:
            # planted labels s ∈ {−1,+1}; Bernoulli flips keep the problem
            # realisable but not separable (bounded minimiser even at λ→0)
            prob1 = _sigmoid(z)
            self.s = np.where(rng.random(m_rows) < prob1, 1.0, -1.0)
            self.y = self.s
            # L = eigmax(AᵀA)/(4m) + λ (logistic curvature bound σ' ≤ 1/4)
            sv = np.linalg.svd(self.A, compute_uv=False)[0]
            self.L = float(sv * sv / (4.0 * m_rows) + self.l2)
            self.mu = self.l2
        if gamma is None:
            gamma = 1.0 / self.L     # safe step: contraction factor 1 − μ/L
        if not 0.0 < gamma * self.L < 2.0:
            raise ValueError(
                f"gamma={gamma:g} outside the contraction range "
                f"(0, 2/L) = (0, {2.0 / self.L:g})")
        self.gamma = float(gamma)
        # per-block gradient slices of the lstsq affine map (hot path)
        if task == "lstsq":
            blk = self.block
            self._Hrows = [self.H[i * blk:(i + 1) * blk] for i in range(p)]
            self._crows = [self.c[i * blk:(i + 1) * blk] for i in range(p)]
        self._Acols = [self.A[:, i * self.block:(i + 1) * self.block]
                       for i in range(p)]

    # -- DecomposedProblem interface ----------------------------------------
    def neighbors(self, i: int) -> List[int]:
        # all-to-all: every block's gradient couples every other block
        return [j for j in range(self.p) if j != i]

    def init_local(self, i: int) -> np.ndarray:
        # x0 = 0: a worker's view of an undelivered neighbour block is the
        # init value, so missing deps assemble to the correct async view
        return np.zeros(self.block)

    def interface(self, i: int, x_i: np.ndarray, j: int) -> np.ndarray:
        return x_i.copy()   # parameter exchange: the whole block escapes

    def _assemble_view(self, i: int, x_i: np.ndarray,
                       deps: Dict[int, np.ndarray]) -> np.ndarray:
        blk = self.block
        x = np.zeros(self.n)
        x[i * blk:(i + 1) * blk] = x_i
        for j, dep in deps.items():
            if dep is not None and dep.size:
                x[j * blk:(j + 1) * blk] = dep
        return x

    def _grad_block(self, i: int, x: np.ndarray) -> np.ndarray:
        """∇_i F at the assembled view ``x``."""
        blk = self.block
        if self.task == "lstsq":
            return self._Hrows[i] @ x - self._crows[i]
        margin = self.s * (self.A @ x)
        w = -self.s * _sigmoid(-margin)      # d softplus(−s·z)/dz
        return (self._Acols[i].T @ w) / self.m \
            + self.l2 * x[i * blk:(i + 1) * blk]

    def update(self, i: int, x_i: np.ndarray,
               deps: Dict[int, np.ndarray]) -> np.ndarray:
        x = self._assemble_view(i, x_i, deps)
        return x_i - self.gamma * self._grad_block(i, x)

    def update_with_residual(self, i: int, x_i: np.ndarray,
                             deps: Dict[int, np.ndarray],
                             need_residual: bool = True):
        """Fused sweep + residual: the update difference IS −γ·∇_i F, so
        the residual contribution is a by-product of the gradient step."""
        x = self._assemble_view(i, x_i, deps)
        g = self._grad_block(i, x)
        x_new = x_i - self.gamma * g
        if not need_residual:
            return x_new, None
        return x_new, self._contribution(-self.gamma * g)

    def _contribution(self, r: np.ndarray) -> float:
        if np.isinf(self.ord):
            return float(np.max(np.abs(r))) if r.size else 0.0
        if self.ord == 2.0:
            return float(r @ r)
        if self.ord == 1.0:
            return float(np.abs(r).sum())
        return float(np.sum(np.abs(r) ** self.ord))

    def local_residual(self, i: int, x_i: np.ndarray,
                       deps: Dict[int, np.ndarray]) -> float:
        x = self._assemble_view(i, x_i, deps)
        return self._contribution(-self.gamma * self._grad_block(i, x))

    def grad(self, x: np.ndarray) -> np.ndarray:
        """Full gradient ∇F(x) (oracle / reference path)."""
        if self.task == "lstsq":
            return self.H @ x - self.c
        margin = self.s * (self.A @ x)
        w = -self.s * _sigmoid(-margin)
        return self.A.T @ w / self.m + self.l2 * x

    def objective(self, x: np.ndarray) -> float:
        if self.task == "lstsq":
            r = self.A @ x - self.y
            return float(r @ r / (2 * self.m) + self.l2 * (x @ x) / 2)
        margin = self.s * (self.A @ x)
        return float(np.logaddexp(0.0, -margin).sum() / self.m
                     + self.l2 * (x @ x) / 2)

    def exact_residual(self, xs: Sequence[np.ndarray]) -> float:
        """σ-reduced norm of the update difference −γ∇F(x̄): the
        synchronized-eval ground truth the async monitor replaces."""
        r = -self.gamma * self.grad(self.assemble(xs))
        if np.isinf(self.ord):
            return float(np.max(np.abs(r)))
        if self.ord == 1.0:
            return float(np.abs(r).sum())
        return float(np.sum(np.abs(r) ** self.ord) ** (1.0 / self.ord))

    # -- batched device path -------------------------------------------------
    def update_with_residual_batched(self, X, H=None, c=None, A=None,
                                     s=None, gamma=None):
        """Synchronous global GD step + pre-step residual contribution for
        a batch of lanes, as one jittable device program.

        ``X`` — [B, n] lane states.  For seed-batched problems pass stacked
        operators: lstsq ``H`` [B, n, n] + ``c`` [B, n]; logistic ``A``
        [B, m, n] + ``s`` [B, m]; plus per-lane ``gamma`` [B] (each seed's
        1/L differs).  Defaults evaluate this instance on every lane.
        Returns ``(X_next, contrib[B])`` under the repo contribution
        convention — the same by-product ``update_with_residual`` yields
        per worker.
        """
        import jax.numpy as jnp

        g = jnp.asarray(self.gamma if gamma is None else gamma)
        g = g[..., None] if g.ndim else g
        # f32 products in full precision (a TPU's default rounds to bf16)
        hi = "highest"
        if self.task == "lstsq":
            H = jnp.asarray(self.H if H is None else H)
            c = jnp.asarray(self.c if c is None else c)
            G = (jnp.matmul(X, H.T, precision=hi) if H.ndim == 2
                 else jnp.einsum("bij,bj->bi", H, X, precision=hi)) - c
        else:
            A = jnp.asarray(self.A if A is None else A)
            s = jnp.asarray(self.s if s is None else s)
            import jax.nn

            Z = (jnp.matmul(X, A.T, precision=hi) if A.ndim == 2
                 else jnp.einsum("bmn,bn->bm", A, X, precision=hi))
            W = -s * jax.nn.sigmoid(-s * Z)
            G = ((jnp.matmul(W, A, precision=hi) / self.m if A.ndim == 2
                  else jnp.einsum("bm,bmn->bn", W, A, precision=hi) / self.m)
                 + self.l2 * X)
        R = -g * G
        Y = X + R
        if np.isinf(self.ord):
            contrib = jnp.max(jnp.abs(R), axis=-1)
        else:
            contrib = jnp.sum(jnp.abs(R) ** self.ord, axis=-1)
        return Y, contrib

    def lane_x0(self) -> np.ndarray:
        """Canonical initial state of one detection-service lane (f32)."""
        return np.zeros((self.n,), np.float32)

    def lane_operands(self) -> dict:
        """This instance's per-lane operands for the batched step.

        The seeded data matrices and the per-seed safe step size γ are
        per-lane; ``m_rows`` and ``l2`` are shape-bucket constants shared
        from any instance.  Used by ``launch/serve.py`` and the
        ``detection_grid`` campaign cells.
        """
        if self.task == "lstsq":
            return {"H": np.asarray(self.H, np.float32),
                    "c": np.asarray(self.c, np.float32),
                    "gamma": np.float32(self.gamma)}
        return {"A": np.asarray(self.A, np.float32),
                "s": np.asarray(self.s, np.float32),
                "gamma": np.float32(self.gamma)}

    # -- helpers -------------------------------------------------------------
    def assemble(self, xs: Sequence[np.ndarray]) -> np.ndarray:
        return np.concatenate(list(xs))

    def split(self, x: np.ndarray) -> List[np.ndarray]:
        blk = self.block
        return [x[i * blk:(i + 1) * blk].copy() for i in range(self.p)]

    def solve_reference(self, tol: float = 1e-14,
                        max_iter: int = 200_000) -> np.ndarray:
        """Minimiser to high precision (test / oracle path): closed form
        for lstsq, full-batch GD for logistic."""
        if self.task == "lstsq":
            return np.linalg.solve(self.H, self.c)
        x = np.zeros(self.n)
        for _ in range(max_iter):
            g = self.grad(x)
            x = x - self.gamma * g
            if float(np.max(np.abs(g))) < tol:
                break
        return x
