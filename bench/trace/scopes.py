"""Device op time by the scopes the program names inside its step.

The shard runtime opens a ``jax.named_scope`` ``repro.<kind>`` around each
phase of an outer step (``repro.core.trace.device_scope``: ``sweep``,
``halo``, ``reduce``, ``detect``).  The compiler copies the scope path
into each op's HLO ``op_name`` metadata.  The trace reduction keeps each
device op by its HLO instruction name only (``%copy.30``), so the op names
come from the compiled program itself: the cell's solve is compiled again
after the window, with the same function, shapes and mesh as the driver's
(``bench/drivers/solve.build_solver``), and its optimised HLO maps each
instruction name to its ``op_name``.  The harness keeps a persistent
compile cache, so that compile reads back the very executable the window
ran; without one the compiler gives the same module again.

Each op's device time goes to the innermost ``repro.`` component of its
path, so ``.../repro.sweep/.../repro.halo/reshape`` counts as ``halo``.
An op with no ``repro.`` component, or none the program names, counts as
``unscoped``: copies and layout changes the compiler inserts, and work
outside the solve loop.

A fusion carries the metadata of its root op, so a fusion whose ops come
from several scopes counts wholly to its root's scope.  Times are summed
op durations, not a union: ops that overlap on one device count twice.

A device none of whose ops carries a ``repro.`` scope is left out, and
with none left the readers read nothing: the trace of a program that names
no scopes reads null rather than zero.
"""
from __future__ import annotations

import functools
import json
import re
from typing import Dict, Optional

PREFIX = "repro."
UNSCOPED = "unscoped"

# ``[ROOT ]%name = <shape> <opcode>(...), ..., metadata={op_name="..." ...}``
_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def scope_of(op_name: str) -> str:
    """The innermost ``repro.<kind>`` of an ``op_name`` path (its kind), or
    ``unscoped``."""
    kind = UNSCOPED
    for part in op_name.split("/"):
        if part.startswith(PREFIX):
            kind = part[len(PREFIX):]
    return kind


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """Each instruction of an HLO module's text that has an ``op_name``,
    by its name with the ``%`` the trace gives it."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _NAME.match(line)
        if m:
            meta = _OP_NAME.search(line, m.end())
            if meta:
                out["%" + m.group(1)] = meta.group(1)
    return out


@functools.lru_cache(maxsize=1)
def _solve_op_names(key: str) -> Dict[str, str]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from bench.drivers import solve
    from repro.launch.mesh import make_shard_mesh
    from repro.runtime import shard_runtime

    config, traffic = json.loads(key)
    n = int(config["n"])
    mesh = make_shard_mesh(tuple(config["mesh"]))
    sharding = NamedSharding(mesh, shard_runtime.mesh_state_spec(
        "convdiff", mesh))
    state = jax.ShapeDtypeStruct((n, n, n), jnp.float32, sharding=sharding)
    run = solve.build_solver(config, traffic, mesh)
    return hlo_op_names(run.lower(state, state).compile().as_text())


def solve_op_names(config, traffic) -> Dict[str, str]:
    """``hlo_op_names`` of the solve a configuration and mix run, compiled
    as ``bench/drivers/solve.setup`` compiles it (once per process)."""
    return _solve_op_names(json.dumps([config, traffic], sort_keys=True))


def device_ns(data, op_names: Dict[str, str]) -> Dict[str, Dict[str, float]]:
    """Per device, summed op nanoseconds by scope kind and ``unscoped``,
    each op's scope read from ``op_names`` by its name; devices with no
    scoped op are left out."""
    out: Dict[str, Dict[str, float]] = {}
    for dev in data.devices:
        kinds = [scope_of(op_names.get(name, ""))
                 for name, _, _ in data.ops[dev]]
        if all(k == UNSCOPED for k in kinds):
            continue
        tot: Dict[str, float] = {}
        for (_, s, e), kind in zip(data.ops[dev], kinds):
            tot[kind] = tot.get(kind, 0.0) + (e - s)
        out[dev] = tot
    return out


def ms_per_step(ctx, kind: str) -> Optional[float]:
    """Device ms per outer step of the ops in scope ``kind`` (or
    ``unscoped``): over the traced window, divided by the outer steps its
    solves took, averaged over the chips with scoped ops."""
    if ctx.trace is None or not ctx.trace.ops:
        return None
    iters = sum(s["outer_iters"] for s in ctx.record.get("solves") or [])
    if not iters:
        return None
    per = device_ns(ctx.trace, solve_op_names(ctx.config, ctx.traffic))
    if not per:
        return None
    return sum(t.get(kind, 0.0) for t in per.values()) / len(per) / iters / 1e6
