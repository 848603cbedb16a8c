"""Device op time by the program's ``repro.<kind>`` scopes: the HLO parse,
the sums on a small synthetic trace with hand-computed answers, the five
readers, and the compile that names the ops of a cell's solve."""
from types import SimpleNamespace as NS

import pytest

from bench import harness
from bench.tests import tiny
from bench.trace import reduce as tr
from bench.trace import scopes

SCOPE_METRICS = ("sweep_ms.solve", "halo_ms.solve", "reduce_ms.solve",
                 "detect_ms.solve", "unscoped_ms.solve")
_BODY = "jit(loop)/while/body/"


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _profile():
    """Two devices, times in ns, events named as a TPU trace names them.

    dev0: fusion [0,10), kernel [10,40), copy [35,50), kernel [60,80).
    dev1: kernel [0,20), collective-permute [20,30), kernel [30,50).
    """
    dev0 = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        _ev("%while.16 = (f32[8]) while(f32[8] %x)", 0, 80),
        _ev("%fusion.1 = f32[8] fusion(f32[8] %rbgs_kernel.2)", 0, 10),
        _ev("%rbgs_kernel.2 = f32[8] custom-call()", 10, 30),
        _ev("%copy.3 = f32[8] copy(f32[8] %x)", 35, 15),
        _ev("%rbgs_kernel.2 = f32[8] custom-call()", 60, 20)])])
    dev1 = NS(name="/device:TPU:1", lines=[NS(name="XLA Ops", events=[
        _ev("%rbgs_kernel.2 = f32[8] custom-call()", 0, 20),
        _ev("%collective-permute-done.4 = f32[8] collective-permute-done()",
            20, 10),
        _ev("%rbgs_kernel.2 = f32[8] custom-call()", 30, 20)])])
    return NS(planes=[dev0, dev1, NS(name="/host:CPU", lines=[])])


#: op_name by instruction name: the fusion is a halo nested in the sweep,
#: the copy has no op_name (unscoped), the permute is the butterfly's
_OP_NAMES = {
    "%while.16": "jit(loop)/while",
    "%fusion.1": _BODY + "repro.sweep/jit(k)/repro.halo/reshape",
    "%rbgs_kernel.2": _BODY + "repro.sweep/jit(k)/pallas_call",
    "%collective-permute-done.4":
        _BODY + "repro.reduce/cond/branch_1_fun/ppermute",
}
#: per device: halo 10, sweep 30 + 20, copy 15; sweep 20 + 20, reduce 10
_NS = {"/device:TPU:0": {"halo": 10.0, "sweep": 50.0, "unscoped": 15.0},
       "/device:TPU:1": {"sweep": 40.0, "reduce": 10.0}}

_HLO = """\
HloModule jit_loop, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %reshape.7 = f32[8]{0} reshape(f32[8]{0} %param_0), metadata={op_name="jit(loop)/while/body/repro.sweep/jit(k)/repro.halo/reshape" source_file="a.py" source_line=3}
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(loop)/while/body/repro.sweep/jit(k)/repro.halo/reshape"}
  %rbgs_kernel.2 = (f32[8]{0}, f32[8,128]{1,0}) custom-call(f32[8]{0} %fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(loop)/while/body/repro.sweep/jit(k)/pallas_call" source_file="k.py"}
  %copy.3 = f32[8]{0} copy(f32[8]{0} %x)
  %collective-permute-done.4 = f32[8]{0} collective-permute-done(f32[8]{0} %x), metadata={op_name="jit(loop)/while/body/repro.reduce/cond/branch_1_fun/ppermute"}
  ROOT %gte.5 = f32[8]{0} get-tuple-element((f32[8]{0}, f32[8,128]{1,0}) %rbgs_kernel.2), index=0, metadata={op_name="jit(loop)/a\\"b"}
}
"""


def _ctx(trace, iters=(2, 3)):
    return harness.Context(
        cell={}, config={}, traffic={}, trace=trace, device_kind="none",
        record={"t_start": 0.0, "t_end": 1.0,
                "solves": [{"outer_iters": k} for k in iters]})


@pytest.fixture
def named(monkeypatch):
    """The readers see ``_OP_NAMES`` as the compiled solve's op names."""
    monkeypatch.setattr(scopes, "solve_op_names", lambda c, t: _OP_NAMES)


def test_scope_of_takes_the_innermost_repro_scope():
    assert scopes.scope_of(_BODY + "repro.sweep/jit(k)/repro.halo/x") == \
        "halo"
    assert scopes.scope_of(_BODY + "repro.reduce/cond/branch_1_fun/ppermute"
                           ) == "reduce"
    assert scopes.scope_of(_BODY + "add") == scopes.UNSCOPED
    assert scopes.scope_of("") == scopes.UNSCOPED


def test_hlo_op_names_by_instruction_name():
    assert scopes.hlo_op_names(_HLO) == {
        "%reshape.7": _BODY + "repro.sweep/jit(k)/repro.halo/reshape",
        "%fusion.1": _BODY + "repro.sweep/jit(k)/repro.halo/reshape",
        "%rbgs_kernel.2": _BODY + "repro.sweep/jit(k)/pallas_call",
        "%collective-permute-done.4":
            _BODY + "repro.reduce/cond/branch_1_fun/ppermute",
        "%gte.5": 'jit(loop)/a\\"b',
    }
    # the trace's op names are the keys
    names = {n for d in tr.from_profile(_profile()).ops.values()
             for n, _, _ in d}
    assert names - set(scopes.hlo_op_names(_HLO)) == {"%copy.3"}


def test_device_time_by_scope():
    data = tr.from_profile(_profile())
    assert scopes.device_ns(data, _OP_NAMES) == _NS
    # a device none of whose ops carries a repro. scope is left out
    only0 = {k: v for k, v in _OP_NAMES.items() if k != "%rbgs_kernel.2"}
    only0["%collective-permute-done.4"] = "ppermute"
    assert scopes.device_ns(data, only0) == {
        "/device:TPU:0": {"halo": 10.0, "unscoped": 65.0}}
    assert scopes.device_ns(data, {}) == {}


@pytest.mark.parametrize("metric,dev0,dev1", [
    ("sweep_ms.solve", 50, 40),
    ("halo_ms.solve", 10, 0),
    ("reduce_ms.solve", 0, 10),
    ("detect_ms.solve", 0, 0),
    ("unscoped_ms.solve", 15, 0),
])
def test_scope_readers_give_ms_per_outer_step(named, metric, dev0, dev1):
    reader = harness.load_metric(metric)
    # ns averaged over the chips, over 2 + 3 outer steps, in ms
    got = reader.read(_ctx(tr.from_profile(_profile())))
    assert got == pytest.approx((dev0 + dev1) / 2 / 5 / 1e6)


@pytest.mark.parametrize("metric", SCOPE_METRICS)
def test_scope_readers_read_nothing_without_scopes(monkeypatch, metric):
    reader = harness.load_metric(metric)
    data = tr.from_profile(_profile())
    # a program that names no scopes
    monkeypatch.setattr(scopes, "solve_op_names",
                        lambda c, t: {"%rbgs_kernel.2": "pallas_call"})
    assert reader.read(_ctx(data)) is None

    # no trace, no device op, no outer step: nothing is compiled
    def no_compile(c, t):
        raise AssertionError("compiled with nothing to read")

    monkeypatch.setattr(scopes, "solve_op_names", no_compile)
    assert reader.read(_ctx(None)) is None
    assert reader.read(_ctx(tr.TraceData())) is None
    assert reader.read(_ctx(data, iters=())) is None


def test_scope_readers_add_up_to_the_op_time(named):
    data = tr.from_profile(_profile())
    total = sum(harness.load_metric(m).read(_ctx(data))
                for m in SCOPE_METRICS)
    ops_ns = sum(e - s for d in data.devices for _, s, e in data.ops[d]) / 2
    assert total == pytest.approx(ops_ns / 5 / 1e6)


@pytest.mark.parametrize("cell,kinds", [
    ("convdiff-512-nonblocking", {"sweep", "reduce", "detect"}),
    ("convdiff-512-blocking", {"sweep", "reduce", "detect"}),
])
def test_solve_op_names_name_the_cells_solve(cell, kinds):
    """The cell's solve compiled as the driver compiles it (on the CPU, at
    a tiny size): its ops carry the program's scopes, by the names a trace
    gives them."""
    c = harness.find_cell(tiny.SPEC, cell)
    config = dict(harness.load_config(tiny.SPEC, c["config"]), **tiny.SOLVE)
    names = scopes.solve_op_names(config, harness.load_traffic(c["traffic"]))
    assert names and all(n.startswith("%") for n in names)
    assert kinds <= {scopes.scope_of(v) for v in names.values()}
