"""Trace reduction on a small synthetic trace, with hand-computed answers."""
from types import SimpleNamespace as NS

import pytest

from bench.trace import reduce as tr


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _profile():
    """Two devices and a host thread, times in ns.

    dev0: fusion [0,10), kernel [10,40), all-reduce [35,50) (5 ns overlap
    with the kernel), kernel [60,80); idle gaps [50,60) and nothing after.
    dev1: kernel [0,20), collective-permute [20,30), kernel [30,50).
    host: window [0,100), solve [0,55), gen_wait [55,65).
    """
    dev0 = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_run", 0, 80)]),
        NS(name="XLA Ops", events=[
            _ev("%while.16 = (f32[8]) while(f32[8] %x)", 0, 80),
            _ev("%fusion.1 = f32[8] fusion(f32[8] %rbgs_kernel.2)", 0, 10),
            _ev("rbgs_kernel", 10, 30),
            _ev("all-reduce.3", 35, 15), _ev("rbgs_kernel", 60, 20)])])
    dev1 = NS(name="/device:TPU:1", lines=[
        NS(name="XLA Ops", events=[
            _ev("rbgs_kernel", 0, 20), _ev("collective-permute-done", 20, 10),
            _ev("rbgs_kernel", 30, 20)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("window", 0, 100), _ev("solve", 0, 55), _ev("gen_wait", 55, 10),
        _ev("unrelated", 0, 5)])])
    return NS(planes=[dev0, dev1, host, NS(name="/host:metadata", lines=[])])


@pytest.fixture
def data():
    return tr.from_profile(_profile())


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert tr.length([(0, 3), (5, 9)]) == 7
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert tr.gaps([(0, 3), (5, 9), (9, 10)]) == [(3, 5)]


def test_devices_and_spans(data):
    assert data.devices == ["/device:TPU:0", "/device:TPU:1"]
    assert sorted(n for n, _, _ in data.spans) == ["gen_wait", "solve",
                                                   "window"]


def test_busy_union_and_idle(data):
    assert data.busy_ns("/device:TPU:0") == 70.0     # [0,50) + [60,80)
    assert data.busy_ns("/device:TPU:1") == 50.0
    assert data.busy_s() == pytest.approx(60e-9)


def test_kernel_time_by_name(data):
    assert data.kernel_ns("/device:TPU:0", "rbgs") == (50.0, 2)
    assert data.kernel_ns("/device:TPU:1", "rbgs|jacobi") == (40.0, 2)
    assert data.kernel_ns("/device:TPU:1", "nomatch") == (0.0, 0)


def test_exposed_collective(data):
    # all-reduce [35,50) overlaps the kernel until 40: 10 ns exposed
    assert data.exposed_collective_ns("/device:TPU:0") == 10.0
    assert data.exposed_collective_ns("/device:TPU:1") == 10.0


def test_breakdown(data):
    b = data.breakdown()
    ops = dict((n, s) for n, s in b["device_ops"])
    assert ops["rbgs_kernel"] == pytest.approx(45e-9)   # (50 + 40) / 2
    assert b["device_ops"][0][0] == "rbgs_kernel"
    # dev0's one gap [50,60): midpoint 55 lies in gen_wait (the innermost)
    assert b["idle_gaps"] == [["gen_wait", pytest.approx(10e-9)]]


def test_container_ops_and_names():
    assert tr.op_name("%fused_x.12 = (f32[8]) custom-call(f32[8] %a)") == \
        "%fused_x.12"
    assert all(n != "%while.16" for n, _, _ in data_ops(_profile()))
    # the fusion that takes the kernel's output as an operand is not the
    # kernel: names are matched before the " = "
    d = tr.from_profile(_profile())
    assert d.kernel_ns("/device:TPU:0", "^rbgs") == (50.0, 2)


def data_ops(profile):
    return tr.from_profile(profile).ops["/device:TPU:0"]


def test_summary(data):
    s = tr.summary(data)
    assert s["/device:TPU:0"][0] == ["rbgs_kernel", 50e-9, 2]
    assert ["%fusion.1", 10e-9, 1] in s["/device:TPU:0"]
