"""Closed-loop driver of the sharded convdiff solve.

The entry the window drives is the jitted solve that
``runtime.api.run_shard`` builds — ``jax.jit(shard_runtime.make_runtime(
"convdiff", cfg.to_shard_config(), mesh, n, stencil=st))`` — called on
inputs placed with ``shard_runtime.mesh_state_spec``.  ``run_shard``
itself is not the entry: it copies every input through the host, jits
anew and runs the solve twice on every call.

One client submits one solve from x0 = 0, waits for it
(``block_until_ready``), and submits the next, cycling a small pool of
right-hand sides made on the device from the seed, in their sharding.
A seeded reservoir keeps a uniform sample of the window's solutions; once
the window has closed, the plain reference certifies each sampled one by
its exact float64 residual against eps_tilde.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np

from bench.harness import seed32
from bench.reference import convdiff as ref

INF = float("inf")


@dataclass
class SolveState:
    run: Any
    x0: Any
    pool: List[Any]
    eps_tilde: float
    coefs: tuple
    sample: int
    seed: int
    kept: List[Any] = None


def build_solver(config: Dict[str, Any], traffic: Dict[str, Any], mesh):
    """The program's jitted solve for this configuration and mix."""
    import jax

    from repro.core import detection
    from repro.runtime import shard_runtime
    from repro.runtime.api import RuntimeConfig
    from repro.solvers.convdiff import Stencil

    n = int(config["n"])
    st = Stencil.for_contraction(n, float(config["nu"]),
                                 tuple(config["a"]), float(config["rho"]))
    mon = detection.for_mode(traffic["mode"],
                             eps_tilde=float(config["eps_tilde"]),
                             margin=float(traffic.get("margin", 10.0)),
                             ord=INF, staleness=int(traffic["staleness"]))
    cfg = RuntimeConfig(monitor=mon, reduction=traffic["reduction"],
                        sweep=config["sweep"],
                        max_outer=int(config["max_outer"]),
                        mesh_shape=tuple(config["mesh"]))
    return jax.jit(shard_runtime.make_runtime(
        "convdiff", cfg.to_shard_config(), mesh, n, stencil=st))


def setup(config, traffic, seed: int, devices) -> SolveState:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from repro.launch.mesh import make_shard_mesh
    from repro.runtime import shard_runtime

    n = int(config["n"])
    mesh = make_shard_mesh(tuple(config["mesh"]))
    sharding = NamedSharding(mesh, shard_runtime.mesh_state_spec(
        "convdiff", mesh))
    run = build_solver(config, traffic, mesh)
    make_b = jax.jit(lambda k: ref.rhs_device(k, n), out_shardings=sharding)
    key = jax.random.key(seed32(seed))
    pool = [make_b(jax.random.fold_in(key, i))
            for i in range(int(traffic["pool"]))]
    x0 = jax.jit(lambda: jnp.zeros((n, n, n), jnp.float32),
                 out_shardings=sharding)()
    jax.block_until_ready(run(x0, pool[0]))     # compile + first execution
    return SolveState(run, x0, pool, float(config["eps_tilde"]),
                      ref.config_coefficients(config),
                      int(traffic["sample"]), seed)


def window(state: SolveState, seconds: float) -> Dict[str, Any]:
    import jax

    rng = np.random.default_rng(seed32(state.seed, 1))
    kept: List[Any] = []
    solves = []
    t_start = time.perf_counter()
    i = 0
    with jax.profiler.TraceAnnotation("window"):
        while True:
            k = i % len(state.pool)
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("solve"):
                res = jax.block_until_ready(state.run(state.x0,
                                                      state.pool[k]))
            t_end = time.perf_counter()
            solves.append({"wall_s": t_end - t,
                           "outer_iters": int(res.outer_iters),
                           "converged": bool(res.converged),
                           "verifications": int(res.verifications)})
            # reservoir sample of the window's solutions, drawn from the seed
            if len(kept) < state.sample:
                kept.append((res.x, k))
            else:
                j = int(rng.integers(0, i + 1))
                if j < state.sample:
                    kept[j] = (res.x, k)
            del res
            i += 1
            if t_end - t_start >= seconds:
                break
    state.kept = kept
    done = sum(s["converged"] for s in solves)
    return {"t_start": t_start, "t_end": t_end, "solves": solves,
            "attempted": len(solves), "failed": len(solves) - done}


def release(state: SolveState) -> None:
    """Move the sampled solutions and their right-hand sides to the host
    and drop the program's state."""
    host = [(np.asarray(x), k) for x, k in state.kept]
    needed = {k for _, k in host}
    state.pool = {k: np.asarray(b) for k, b in enumerate(state.pool)
                  if k in needed}
    state.kept = host
    state.run = state.x0 = None


def check(state: SolveState, record, config, traffic, seed: int):
    """Every solve detected, and every sampled solution's exact residual
    within the configuration's eps_tilde (as a ratio, limit 1)."""
    worst = 0.0
    for x, k in state.kept:
        r = ref.residual_inf(x, state.pool[k], state.coefs)
        worst = max(worst, r / state.eps_tilde)
    return {"undetected": (record["failed"], 0),
            "residual_ratio": (worst, float(config["residual_ratio_limit"]))}


def end_to_end(record) -> Dict[str, float]:
    done = record["attempted"] - record["failed"]
    window_s = record["t_end"] - record["t_start"]
    return {"solve_s": window_s / done if done else window_s}
