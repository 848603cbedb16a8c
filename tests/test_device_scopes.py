"""The shard runtime names each phase of an outer step inside the device
program: ``jax.named_scope("repro.<kind>")`` (``core.trace.device_scope``)
reaches every compiled op's ``op_name`` metadata, which a profiler trace
of the chip carries and ``bench/trace/scopes.py`` reads.

These tests compile whole solves on the CPU and read the scopes back from
the optimised HLO text: the sweeps under ``repro.sweep``, the face
exchange under ``repro.halo``, the residual reduction (the blocking
mode's residual-only pass, the collective, the butterfly) under
``repro.reduce``, the monitor under ``repro.detect``, and nothing in the
loop body outside a scope but the step counter.  The one-block mesh's
halo is a constant face that the CPU compiler folds away; its scope is
checked on the TPU compile (``test_tpu_compile.py``).
"""
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding

from repro.core import detection
from repro.core.trace import EVENT_KINDS, SCOPE_PREFIX, device_scope
from repro.launch.mesh import make_shard_mesh
from repro.runtime import shard_runtime as sr
from repro.solvers.convdiff import Stencil

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 16

# ``%name = shape opcode(...), ... op_name="..."`` of one HLO instruction
_INSTR = re.compile(r'^\s*(?:ROOT )?%\S+ = (\S+?)(?:\{[^}]*\})? ([\w\-]+)\('
                    r'.*op_name="([^"]*)"')


def scope_rows(hlo_text):
    """``(kind, opcode, shape, op_name)`` of every instruction that has an
    ``op_name``; ``kind`` is the innermost ``repro.<kind>`` or None."""
    rows = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            shape, opcode, name = m.groups()
            kinds = [p[len(SCOPE_PREFIX):] for p in name.split("/")
                     if p.startswith(SCOPE_PREFIX)]
            rows.append((kinds[-1] if kinds else None, opcode, shape, name))
    return rows


def kinds_of(rows, opcode, shape=None):
    return {k for k, op, s, _ in rows
            if op == opcode and (shape is None or s.startswith(shape))}


def assert_body_scoped(rows):
    """Every op of the loop body sits in a scope, but the step counter
    (alone or as a fusion whose root it is)."""
    loose = {(op, s) for k, op, s, name in rows
             if k is None and "/while/body/" in name}
    assert loose <= {("add", "s32[]"), ("fusion", "s32[]")}, loose
    assert {"sweep", "reduce", "detect"} <= {k for k, *_ in rows}


def test_device_scope_names_come_from_event_kinds():
    for kind in ("sweep", "halo", "reduce", "detect"):
        assert kind in EVENT_KINDS

    @jax.jit
    def f(x):
        with device_scope("halo"):
            return x * 2.0

    assert 'op_name="jit(f)/repro.halo/mul"' in \
        f.lower(jnp.ones(4)).compile().as_text()
    with pytest.raises(ValueError):
        device_scope("kernel")


@pytest.mark.parametrize("reduction,mode", [("blocking", "sync"),
                                            ("nonblocking", "pfait")])
def test_scopes_on_one_block(reduction, mode):
    mesh = make_shard_mesh((1, 1))
    st = Stencil.for_contraction(N, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    mon = detection.for_mode(mode, eps_tilde=1e-6, margin=10.0,
                             staleness=2 if reduction == "nonblocking" else 0)
    cfg = sr.ShardRuntimeConfig(monitor=mon, reduction=reduction,
                                sweep="hybrid", max_outer=50,
                                mesh_shape=(1, 1))
    spec = jax.ShapeDtypeStruct(
        (N, N, N), jnp.float32,
        sharding=NamedSharding(mesh, sr.mesh_state_spec("convdiff", mesh)))
    run = jax.jit(sr.make_convdiff_runtime(cfg, mesh, st, N))
    rows = scope_rows(run.lower(spec, spec).compile().as_text())
    block = f"f32[{N},{N},{N}]"
    # stencil arithmetic on the block: the sweep, and in blocking mode the
    # residual-only pass, which the scopes tell apart
    want = {"sweep", "reduce"} if reduction == "blocking" else {"sweep"}
    assert kinds_of(rows, "multiply", block) == want
    assert kinds_of(rows, "all-reduce") == {"reduce"}
    assert_body_scoped(rows)


_MESH22 = textwrap.dedent("""
    import json, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.core import detection
    from repro.launch.mesh import make_shard_mesh
    from repro.runtime import shard_runtime as sr
    from repro.solvers.convdiff import Stencil

    n = 16
    mesh = make_shard_mesh((2, 2))
    st = Stencil.for_contraction(n, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    spec = jax.ShapeDtypeStruct(
        (n, n, n), jnp.float32,
        sharding=NamedSharding(mesh, sr.mesh_state_spec("convdiff", mesh)))
    out = {}
    for red in ("nonblocking", "rdoubling"):
        mon = detection.for_mode("pfait", eps_tilde=1e-6, margin=10.0,
                                 staleness=2)
        cfg = sr.ShardRuntimeConfig(monitor=mon, reduction=red,
                                    max_outer=50, mesh_shape=(2, 2),
                                    overlap=True)
        run = jax.jit(sr.make_convdiff_runtime(cfg, mesh, st, n))
        out[red] = run.lower(spec, spec).compile().as_text()
    print("HLO=" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def mesh22_hlo():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _MESH22], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = next(ln for ln in out.stdout.splitlines() if ln.startswith("HLO="))
    return json.loads(line[len("HLO="):])


@pytest.mark.parametrize("reduction", ["nonblocking", "rdoubling"])
def test_scopes_on_2x2_mesh(mesh22_hlo, reduction):
    rows = scope_rows(mesh22_hlo[reduction])
    half = N // 2
    # the overlapped step: the full block sweep is the sweep; the face
    # slabs swept ahead of it and shipped are halo work
    assert kinds_of(rows, "multiply", f"f32[{half},{half},{N}]") == {"sweep"}
    assert kinds_of(rows, "multiply", f"f32[1,{half},{N}]") == {"halo"}
    # face planes cross chips under halo (the pre-loop exchange included),
    # the butterfly's scalars under reduce
    faces = kinds_of(rows, "collective-permute", f"f32[{half},{N}]")
    scalars = kinds_of(rows, "collective-permute", "f32[]")
    assert faces == {"halo"}
    assert sum(1 for k, op, s, name in rows if op == "collective-permute"
               and s.startswith(f"f32[{half},{N}]")
               and "/while/" not in name) > 0
    if reduction == "rdoubling":
        assert scalars == {"reduce"}
        assert kinds_of(rows, "all-reduce") == set()
    else:
        assert scalars == set()
        assert kinds_of(rows, "all-reduce") == {"reduce"}
    assert_body_scoped(rows)
