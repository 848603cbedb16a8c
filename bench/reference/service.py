"""Plain per-family references for the detection service's tenants.

Each tenant names a family, its problem sizes and a seed.  The reference
rebuilds the tenant's data from the seed with numpy, rounds it to the
precision the service is given (float32), runs the family's iteration in
float64 from the same start, and returns the exact residual of the
iterate a detection was made on.  The data recipes follow the families'
published definitions and their documented parameters (the
``families`` entries of the service's configuration file); nothing of
the program is imported.

* convdiff — the paper's 7-point stencil (``reference.convdiff``), one
  red-black Gauss-Seidel sweep per step (parity-0 cells first), residual
  ``max |b - A x|``;
* pagerank — damped PageRank over a hub-biased random directed graph
  (targets Zipf-weighted by ``hub_skew``, Poisson out-degrees of mean
  ``avg_deg``), ``x <- d P x + (1-d)/n`` from the uniform vector, residual
  ``sum |d P x + (1-d)/n - x|``;
* mlfixed — gradient descent with step ``1/L`` on a ridge least-squares
  problem whose columns are scaled geometrically to condition ``cond``,
  residual ``|| gamma (H x - c) ||_2``.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import numpy as np

from bench.reference import convdiff as cd

F32 = np.float32


def _r32(v):
    return np.asarray(v, F32).astype(np.float64)


def convdiff_problem(kw: Dict[str, Any], seed: int):
    n = int(kw["n"])
    c = tuple(float(F32(v)) for v in cd.coefficients(
        n, float(kw.get("nu", 1.0)), kw.get("a", (1.0, 1.0, 1.0)),
        float(kw["rho"])))
    b = _r32(cd.rhs_host(n, seed))
    red = (np.indices((n, n, n)).sum(axis=0) % 2) == 1

    def off(x):
        return cd.apply_a(x, c) - c[0] * x

    def step(x):
        even = np.where(red, x, (b - off(x)) / c[0])
        return np.where(red, (b - off(even)) / c[0], even)

    def residual(x):
        return float(np.max(np.abs(b - cd.apply_a(x, c))))

    return np.zeros((n, n, n)), step, residual


def pagerank_graph(n: int, seed: int, avg_deg: float,
                   hub_skew: float) -> np.ndarray:
    """Column-stochastic link matrix: each source j links to 1 + Poisson
    (avg_deg - 1) distinct targets drawn with weights (k+1)^-hub_skew (no
    self-links; a source left with none links to j+1)."""
    rng = np.random.default_rng(seed)
    w = 1.0 / (np.arange(n) + 1.0) ** hub_skew
    w /= w.sum()
    P = np.zeros((n, n))
    for j in range(n):
        deg = min(1 + int(rng.poisson(max(avg_deg - 1.0, 0.0))), n - 1)
        targets = rng.choice(n, size=deg, replace=False, p=w)
        targets = targets[targets != j]
        if targets.size == 0:
            targets = np.array([(j + 1) % n])
        targets = np.unique(targets)
        P[targets, j] = 1.0 / targets.size
    return P


def pagerank_problem(kw: Dict[str, Any], seed: int):
    n = int(kw["n"])
    P = _r32(pagerank_graph(n, seed, float(kw["avg_deg"]),
                            float(kw["hub_skew"])))
    d = float(F32(kw["damping"]))
    v = float(F32((1.0 - float(kw["damping"])) / n))

    def step(x):
        return d * (P @ x) + v

    def residual(x):
        return float(np.sum(np.abs(step(x) - x)))

    return np.full(n, float(F32(1.0 / n))), step, residual


def mlfixed_problem(kw: Dict[str, Any], seed: int):
    n, m = int(kw["n"]), int(kw["m_rows"])
    rng = np.random.default_rng(seed)
    scale = float(kw["cond"]) ** (-np.arange(n) / max(n - 1, 1))
    A = rng.standard_normal((m, n)) * scale
    x_true = rng.standard_normal(n)
    y = A @ x_true + float(kw["noise"]) * rng.standard_normal(m)
    H64 = A.T @ A / m + float(kw["l2"]) * np.eye(n)
    gamma = float(F32(1.0 / np.linalg.eigvalsh(H64)[-1]))
    H, c = _r32(H64), _r32(A.T @ y / m)

    def residual(x):
        return float(np.linalg.norm(gamma * (H @ x - c)))

    def step(x):
        return x - gamma * (H @ x - c)

    return np.zeros(n), step, residual


FAMILIES: Dict[str, Callable[[Dict[str, Any], int], Tuple]] = {
    "convdiff": convdiff_problem,
    "pagerank": pagerank_problem,
    "mlfixed": mlfixed_problem,
}


def residual_at(family: str, kw: Dict[str, Any], seed: int,
                steps: int) -> float:
    """Exact residual of the tenant's iterate after ``steps`` steps."""
    x, step, residual = FAMILIES[family](kw, seed)
    for _ in range(int(steps)):
        x = step(x)
    return residual(x)
