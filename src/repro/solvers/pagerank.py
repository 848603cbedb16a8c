"""Sparse PageRank / D-iteration fixed point as a second problem family.

The conv-diff substrate (solvers/convdiff.py) has a *symmetric* 4-neighbour
dependency structure — every worker talks to every neighbour in both
directions with equal-size interfaces.  Detection reliability is easier
there than the general asynchronous-iterations setting (Hong's D-iteration
work, arXiv:1202.3108): web-graph fixed points have hub-skewed, *directed*
dependencies, so some workers feed many others while consuming almost
nothing, and interface sizes differ per direction.

This module implements

    x = d · P x + (1 − d)/n · 1,        0 < d < 1,  P column-stochastic,

decomposed over ``p`` contiguous node blocks, as a
``core.async_engine.DecomposedProblem``.  The random graph is hub-biased
(Zipf-weighted targets), so the block dependency graph is genuinely
asymmetric: ``interface(i, x_i, j)`` returns exactly the components of
block i that block j's rows reference — possibly the empty array when j
never reads from i (the engine still exchanges messages both ways, as a
real sparse solver's symmetrised communicator would).

The iteration contracts in l1 with factor d per sweep (column-stochastic
P), so the natural residual order is ``ord=1``; contributions follow the
repo convention (core/residual.py): Σ|r|^l pre-reduction for finite l,
max|r| for l=∞.  The fused ``update_with_residual`` extension is free
here: the D-iteration residual *is* the update difference f(x) − x.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np


class PageRankProblem:
    """Damped PageRank over a random hub-biased directed graph."""

    def __init__(
        self,
        n: int = 256,
        p: int = 4,
        damping: float = 0.85,
        avg_deg: float = 6.0,
        hub_skew: float = 0.8,
        ord: float = 1.0,
        seed: int = 0,
    ):
        if n % p:
            raise ValueError(f"n={n} not divisible by p={p}")
        if not 0.0 < damping < 1.0:
            raise ValueError(f"damping={damping} must be in (0, 1)")
        self.n = n
        self.p = p
        self.d = float(damping)
        self.ord = float(ord)
        self.block = n // p
        rng = np.random.default_rng(seed)

        # hub-biased directed graph: targets drawn Zipf-weighted toward
        # low-indexed nodes, so block 0 is everyone's dependency while the
        # tail blocks are mostly read-only consumers (asymmetry).
        w = 1.0 / (np.arange(n) + 1.0) ** hub_skew
        w /= w.sum()
        cols: List[np.ndarray] = []       # per source node: its out-targets
        for j in range(n):
            deg = 1 + int(rng.poisson(max(avg_deg - 1.0, 0.0)))
            deg = min(deg, n - 1)
            targets = rng.choice(n, size=deg, replace=False, p=w)
            targets = targets[targets != j]
            if targets.size == 0:  # no dangling columns: keep P stochastic
                targets = np.array([(j + 1) % n])
            cols.append(np.unique(targets))

        # block-compressed column storage: for each (dst block i, src block
        # j) the needed source components and the dense compressed operator
        # W[i][j] : (block, |support(i←j)|), plus the diagonal block A_ii.
        blk = self.block
        def owner(node):
            return node // blk
        entries: Dict[tuple, List[tuple]] = {}
        for j, targets in enumerate(cols):
            val = 1.0 / targets.size
            for r in targets:
                entries.setdefault((owner(r), owner(j)), []).append(
                    (r % blk, j % blk, val))
        self._W: List[Dict[int, np.ndarray]] = [dict() for _ in range(p)]
        self._supp: List[Dict[int, np.ndarray]] = [dict() for _ in range(p)]
        self._A: List[np.ndarray] = [np.zeros((blk, blk)) for _ in range(p)]
        for (bi, bj), es in entries.items():
            if bi == bj:
                for r, c, v in es:
                    self._A[bi][r, c] += v
                continue
            support = np.unique(np.array([c for _, c, _ in es]))
            pos = {c: k for k, c in enumerate(support)}
            W = np.zeros((blk, support.size))
            for r, c, v in es:
                W[r, pos[c]] += v
            # support(i←j): which of j's components i reads
            self._supp[bj].setdefault(bi, support)
            self._W[bi][bj] = W
        self._neighbors: List[List[int]] = []
        for i in range(p):
            nb = set(self._W[i]) | set(self._supp[i])
            nb.discard(i)
            self._neighbors.append(sorted(nb))
        self.v = (1.0 - self.d) / n  # uniform teleport component
        # packed per-worker operator for the hot `_apply` path: one
        # (blk, blk + Σ|support|) matrix [A_i | W_ij …] against the
        # concatenated [x_i; deps…] replaces the per-neighbour matvec loop
        # (the engine delivers every dependency at init, so the packed view
        # is almost always complete; partial snapshot views fall back)
        self._packed_js: List[List[int]] = [sorted(self._W[i])
                                            for i in range(p)]
        self._packed_M: List[np.ndarray] = [
            np.concatenate([self._A[i]] + [self._W[i][j]
                                           for j in self._packed_js[i]],
                           axis=1)
            for i in range(p)
        ]
        # preallocated packed input [x_i; deps…] + per-neighbour slot
        # slices: two small copies per neighbour beat a fresh concatenate
        # in the sweep hot loop
        self._packed_buf: List[np.ndarray] = []
        self._packed_slots: List[List[tuple]] = []
        for i in range(p):
            slots, pos = [], blk
            for j in self._packed_js[i]:
                w = self._W[i][j].shape[1]
                slots.append((j, slice(pos, pos + w)))
                pos += w
            self._packed_buf.append(np.empty(pos))
            self._packed_slots.append(slots)
        self._P_dense: Optional[np.ndarray] = None  # lazy (exact_residual)

    # -- DecomposedProblem interface ----------------------------------------
    def neighbors(self, i: int) -> List[int]:
        return self._neighbors[i]

    def init_local(self, i: int) -> np.ndarray:
        return np.full(self.block, 1.0 / self.n)

    def _apply(self, i: int, x_i: np.ndarray,
               deps: Dict[int, np.ndarray]) -> np.ndarray:
        """f_i(x): d · (row-block of P x) + teleport."""
        buf = self._packed_buf[i]
        buf[: self.block] = x_i
        for j, slot in self._packed_slots[i]:
            dep = deps.get(j)
            if dep is None:
                break
            buf[slot] = dep
        else:
            return self.d * (self._packed_M[i] @ buf) + self.v
        # partial view (snapshot records mid-round): per-neighbour fallback
        y = self._A[i] @ x_i
        for j, W in self._W[i].items():
            dep = deps.get(j)
            if dep is not None and dep.size:
                y += W @ dep
        return self.d * y + self.v

    def update(self, i: int, x_i: np.ndarray,
               deps: Dict[int, np.ndarray]) -> np.ndarray:
        return self._apply(i, x_i, deps)

    def update_with_residual(self, i: int, x_i: np.ndarray,
                             deps: Dict[int, np.ndarray],
                             need_residual: bool = True):
        """Fused sweep + residual: the D-iteration residual is exactly the
        update difference, so fusion costs nothing extra."""
        x_new = self._apply(i, x_i, deps)
        if not need_residual:
            return x_new, None
        return x_new, self._contribution(x_new - x_i)

    def interface(self, i: int, x_i: np.ndarray, j: int) -> np.ndarray:
        supp = self._supp[i].get(j)
        if supp is None:
            return np.empty(0)  # j never reads from i (asymmetric edge)
        return x_i.take(supp)   # fresh array — the reference escapes

    def _contribution(self, r: np.ndarray) -> float:
        if np.isinf(self.ord):
            return float(np.max(np.abs(r))) if r.size else 0.0
        if self.ord == 1.0:     # |r|¹ — skip the generic power (hot path)
            return float(np.abs(r).sum())
        if self.ord == 2.0:
            return float(r @ r)
        return float(np.sum(np.abs(r) ** self.ord))

    def local_residual(self, i: int, x_i: np.ndarray,
                       deps: Dict[int, np.ndarray]) -> float:
        return self._contribution(self._apply(i, x_i, deps) - x_i)

    def to_dense(self) -> np.ndarray:
        """Dense column-stochastic P assembled from the block storage
        (cached; used by ``exact_residual`` and the batched device path)."""
        if self._P_dense is None:
            P = np.zeros((self.n, self.n))
            blk = self.block
            for i in range(self.p):
                rows = slice(i * blk, (i + 1) * blk)
                P[rows, rows] = self._A[i]
                for j, W in self._W[i].items():
                    P[rows, j * blk + self._supp[j][i]] = W
            self._P_dense = P
        return self._P_dense

    def exact_residual(self, xs: Sequence[np.ndarray]) -> float:
        """r(x̄) via one dense matvec — mathematically identical to the
        per-block contribution sum (Σ_blocks Σ|r_block|^l)^{1/l}, an order
        of magnitude cheaper per trajectory sample."""
        x = self.assemble(xs)
        r = self.d * (self.to_dense() @ x) + self.v - x
        if np.isinf(self.ord):
            return float(np.max(np.abs(r)))
        if self.ord == 1.0:
            return float(np.abs(r).sum())
        return float(np.sum(np.abs(r) ** self.ord) ** (1.0 / self.ord))

    # -- batched device path -------------------------------------------------
    def update_with_residual_batched(self, X, P=None):
        """Synchronous global D-iteration step + pre-step residual
        contribution for a batch of lanes, as one jittable device program.

        ``X`` — [B, n] lane states; ``P`` — optional dense operator, [n, n]
        (defaults to this instance's) or [B, n, n] for seed-batched graphs.
        Returns ``(X_next, contrib[B])``; the contribution is the update
        difference under the repo convention (Σ|r|^l for finite l, max|r|
        for l=∞) — the same fused by-product ``update_with_residual``
        yields per worker.
        """
        import jax.numpy as jnp

        P = jnp.asarray(self.to_dense() if P is None else P)
        # f32 products in full precision: a TPU's default f32 matmul rounds
        # its inputs to bf16, which moves the fixed point by ~1e-3
        if P.ndim == 2:
            Y = self.d * jnp.matmul(X, P.T, precision="highest") + self.v
        else:
            Y = self.d * jnp.einsum("bij,bj->bi", P, X,
                                    precision="highest") + self.v
        R = Y - X
        if np.isinf(self.ord):
            contrib = jnp.max(jnp.abs(R), axis=1)
        else:
            contrib = jnp.sum(jnp.abs(R) ** self.ord, axis=1)
        return Y, contrib

    def lane_x0(self) -> np.ndarray:
        """Canonical initial state of one detection-service lane (f32)."""
        return np.full((self.n,), 1.0 / self.n, np.float32)

    def lane_operands(self) -> dict:
        """This instance's per-lane operands for the batched step.

        Only the graph operator is seeded; the teleport term ``v`` and the
        damping are shape-bucket constants shared from any instance (see
        ``update_with_residual_batched``).  Used by ``launch/serve.py`` and
        the ``detection_grid`` campaign cells.
        """
        return {"P": np.asarray(self.to_dense(), np.float32)}

    # -- helpers -------------------------------------------------------------
    def assemble(self, xs: Sequence[np.ndarray]) -> np.ndarray:
        return np.concatenate(list(xs))

    def solve_reference(self, tol: float = 1e-14,
                        max_iter: int = 10_000) -> np.ndarray:
        """Synchronous power iteration to high precision (test oracle)."""
        xs = [self.init_local(i) for i in range(self.p)]
        for _ in range(max_iter):
            deps = [
                {j: self.interface(j, xs[j], i) for j in self.neighbors(i)}
                for i in range(self.p)
            ]
            new = [self._apply(i, xs[i], deps[i]) for i in range(self.p)]
            delta = max(float(np.max(np.abs(a - b))) for a, b in zip(new, xs))
            xs = new
            if delta < tol:
                break
        return self.assemble(xs)
