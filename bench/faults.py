"""The control and the planted faults that the comparison must catch.

``planted(name, config, traffic)`` is a context manager that breaks the
timed path underneath a run:

* ``control``     — the plain reference put in the program's place,
  computed in bfloat16 (the precision below the configuration's float32);
* ``unchanged``   — the step returns its state unchanged;
* ``half``        — half of the batch is left out: half of the grid keeps
  its start value, or half of every lane's state never moves (lanes fill
  from the first, so leaving out whole lanes would go unseen at light
  load);
* ``no_exchange`` — the exchange between chips is left out (each block sees
  zero halos: multi-chip meshes only);
* ``altered``     — an answer altered where it is produced: one cell of a
  solution moved, or the residual a service lane reports scaled down.

``python bench/control.py`` runs them on the chip at a cell's own size;
``bench/tests`` runs them at a size a test run holds.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, NamedTuple

FAULTS = ("control", "unchanged", "half", "no_exchange", "altered")


class _Result(NamedTuple):
    """The fields of a solve's result that the solve driver reads."""

    x: Any
    outer_iters: Any
    converged: Any
    verifications: Any = 0


def _solve_patch(name: str, config: Dict[str, Any], traffic: Dict[str, Any]):
    import jax
    import jax.numpy as jnp

    from bench.reference import convdiff as ref
    from repro.runtime import shard_runtime

    real = shard_runtime.make_runtime
    if name == "no_exchange":
        return shard_runtime, "_shift", (
            lambda x, axis, up, axis_size: jnp.zeros_like(x))
    if name == "control":
        eps = float(config["eps_tilde"])
        if traffic["mode"] == "pfait":
            eps /= float(traffic.get("margin", 10.0))
        coefs = ref.config_coefficients(config)

        def make(family, cfg, mesh, n, **kw):
            @jax.jit
            def run(x0, b):
                x, k, conv = ref.solve(b, coefs, config["sweep"], eps,
                                       int(config["max_outer"]),
                                       jnp.bfloat16)
                return _Result(x.astype(jnp.float32), k, conv)
            return run
        return shard_runtime, "make_runtime", make

    def make(family, cfg, mesh, n, **kw):
        inner = real(family, cfg, mesh, n, **kw)

        def run(x0, b):
            res = inner(x0, b)
            x = res.x
            if name == "unchanged":
                x = x0
            elif name == "half":
                x = x.at[n // 2:].set(x0[n // 2:])
            elif name == "altered":
                x = x.at[n // 2, n // 2, n // 2].add(1e-2 * jnp.max(
                    jnp.abs(x)))
            return res._replace(x=x)
        return run
    return shard_runtime, "make_runtime", make


def _bf16_step(family: str, ord_: float, fam: Dict[str, Any]):
    """The family's batched step, written from its definition, in
    bfloat16; returns ``(X_next, contribution)`` in float32 under the
    service's convention (max |r| for the inf-norm, sum |r|^ord else).
    Constants come from the configuration's ``families`` entry, the
    per-lane data from the bucket's operands."""
    import jax.numpy as jnp

    lo = jnp.bfloat16

    def contrib(R):
        R = R.astype(jnp.float32)
        if ord_ == float("inf"):
            return jnp.max(jnp.abs(R).reshape(R.shape[0], -1), axis=1)
        return jnp.sum(jnp.abs(R).reshape(R.shape[0], -1) ** ord_, axis=1)

    def convdiff(prob, X, b):
        from bench.reference import convdiff as ref

        kw = dict(fam["defaults"], **fam["problem"])
        c = [jnp.asarray(v, lo) for v in ref.coefficients(
            int(kw["n"]), float(kw["nu"]), kw["a"], float(kw["rho"]))]
        X, b = X.astype(lo), b.astype(lo)

        def off(Z):
            g = jnp.pad(Z, ((0, 0), (1, 1), (1, 1), (1, 1)))
            return (c[1] * g[:, :-2, 1:-1, 1:-1] + c[2] * g[:, 2:, 1:-1, 1:-1]
                    + c[3] * g[:, 1:-1, :-2, 1:-1] + c[4] * g[:, 1:-1, 2:, 1:-1]
                    + c[5] * g[:, 1:-1, 1:-1, :-2] + c[6] * g[:, 1:-1, 1:-1, 2:])
        red = ref.parity(X.shape[1])[None]
        r = b - c[0] * X - off(X)
        even = jnp.where(red, X, (b - off(X)) / c[0])
        Y = jnp.where(red, (b - off(even)) / c[0], even)
        return Y.astype(jnp.float32), contrib(r)

    def pagerank(prob, X, P):
        damping = float(fam["defaults"]["damping"])
        d = jnp.asarray(damping, lo)
        Y = d * jnp.einsum("bij,bj->bi", P.astype(lo), X.astype(lo)) \
            + jnp.asarray((1.0 - damping) / X.shape[1], lo)
        return Y.astype(jnp.float32), contrib(Y - X.astype(lo))

    def mlfixed(prob, X, H, c, gamma):
        Xl = X.astype(lo)
        G = jnp.einsum("bij,bj->bi", H.astype(lo), Xl) - c.astype(lo)
        R = -gamma.astype(lo)[:, None] * G
        return (Xl + R).astype(jnp.float32), contrib(R)

    return {"convdiff": convdiff, "pagerank": pagerank,
            "mlfixed": mlfixed}[family]


class _Tenant:
    """A tenant problem whose batched step is replaced."""

    def __init__(self, inner, step):
        self._inner = inner
        self._step = step

    def __getattr__(self, attr):
        return getattr(self._inner, attr)

    def update_with_residual_batched(self, X, **ops):
        return self._step(self._inner, X, **ops)


def _service_patch(name: str, config, traffic):
    import jax.numpy as jnp

    from repro.launch import serve

    real = serve.make_serve_problem

    def make(family, seed=0, **kw):
        prob = real(family, seed=seed, **kw)
        if name == "control":
            bf16 = _bf16_step(family, float(prob.ord),
                              config["families"][family])
            return _Tenant(prob, lambda p, X, **ops: bf16(p, X, **ops))

        def step(p, X, **ops):
            Y, c = p.update_with_residual_batched(X, **ops)
            if name == "unchanged":
                Y = X
            elif name == "half":
                Y = Y.at[:, X.shape[1] // 2:].set(X[:, X.shape[1] // 2:])
            elif name == "altered":
                c = c * 1e-3
            return Y, c
        return _Tenant(prob, step)
    return serve, "make_serve_problem", make


@contextlib.contextmanager
def planted(name: str, config: Dict[str, Any], traffic: Dict[str, Any]):
    """Break the timed path of a run as ``name`` says, for the duration."""
    if name not in FAULTS:
        raise ValueError(f"fault {name!r} not in {FAULTS}")
    if traffic["driver"] == "solve":
        module, attr, value = _solve_patch(name, config, traffic)
    elif name == "no_exchange":
        raise ValueError("the service runs on one chip: it has no exchange")
    else:
        module, attr, value = _service_patch(name, config, traffic)
    saved = getattr(module, attr)
    setattr(module, attr, value)
    try:
        yield
    finally:
        setattr(module, attr, saved)
