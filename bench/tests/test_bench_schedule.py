"""The seeded open-loop schedule and the due-time arithmetic of the
service driver, with a stand-in service whose ticks take known time."""
import itertools
from collections import Counter
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import harness
from bench.drivers import service

from bench.tests import tiny  # noqa: E402

CONFIG = harness.load_config(tiny.SPEC, "serve-mix")
TRAFFIC = harness.load_traffic("poisson-knee80")


def _kinds(sched):
    return Counter((s.family, s.mode, s.eps_tilde, s.staleness,
                    s.persistence) for _, s in sched)


def test_schedule_is_seeded_and_balanced():
    a = service.make_schedule(CONFIG, TRAFFIC, 2 ** 31 + 5, 10.0)
    b = service.make_schedule(CONFIG, TRAFFIC, 2 ** 31 + 5, 10.0)
    c = service.make_schedule(CONFIG, TRAFFIC, 2 ** 31 + 6, 10.0)
    assert [(d, s) for d, s in a] == [(d, s) for d, s in b]
    assert len(a) == round(TRAFFIC["rate"] * 10.0)
    # another seed: the same gaps and tenant kinds, in another order
    gaps = lambda sch: np.diff([d for d, _ in sch] + [10.0])  # noqa: E731
    assert np.allclose(sorted(gaps(a)), sorted(gaps(c)))
    assert _kinds(a) == _kinds(c)
    assert [s.seed for _, s in a] != [s.seed for _, s in c]
    dues = [d for d, _ in a]
    assert dues[0] == 0.0 and all(np.diff(dues) > 0) and dues[-1] < 10.0


def test_gaps_are_exponential_quantiles():
    sched = service.make_schedule(CONFIG, dict(TRAFFIC, rate=50.0), 3, 20.0)
    gaps = np.diff([d for d, _ in sched] + [20.0])
    assert gaps.mean() == pytest.approx(1 / 50.0)
    assert np.median(gaps) == pytest.approx(np.log(2) / 50.0, rel=0.02)


@pytest.mark.parametrize("count", [12, 32, 163, 400])
def test_every_window_gets_every_mode(count):
    """A window shorter than the list of kinds still takes each family and
    each mode in equal shares (a prefix of the list is balanced)."""
    sched = service.make_schedule(CONFIG, dict(TRAFFIC, rate=count / 10.0),
                                  2 ** 31 + 9, 10.0)
    modes = Counter(s.mode for _, s in sched)
    fams = Counter(s.family for _, s in sched)
    assert set(modes) == set(TRAFFIC["modes"])
    assert max(modes.values()) - min(modes.values()) <= len(fams)
    assert max(fams.values()) - min(fams.values()) <= 1


def test_tenant_kinds_cover_every_combination():
    kinds = service.tenant_kinds(CONFIG, TRAFFIC)
    per = (len(TRAFFIC["modes"]) * 2 * len(TRAFFIC["staleness"])
           * len(TRAFFIC["persistence"]))
    assert len(kinds) == 3 * per
    assert [k[0] for k in kinds[:3]] == ["convdiff", "pagerank", "mlfixed"]
    for fam, grid in ((f, c["eps_tilde"]) for f, c in
                      CONFIG["families"].items()):
        got = {(m, e, k, p) for f, m, e, k, p in kinds if f == fam}
        assert got == set(itertools.product(
            TRAFFIC["modes"], grid, TRAFFIC["staleness"],
            TRAFFIC["persistence"]))


class _FakeService:
    """Serves every tenant ``ticks_to_serve`` ticks after it is submitted;
    each tick takes ``tick_s`` on the clock."""

    def __init__(self, clock, tick_s=0.01, ticks_to_serve=3):
        self.clock, self.tick_s, self.n = clock, tick_s, ticks_to_serve
        self.tick_count, self.reports, self.live = 0, [], []

    @property
    def busy(self):
        return bool(self.live)

    def submit(self, spec):
        self.live.append([spec, self.tick_count, 0])
        return {"admitted": True}

    def step_tick(self):
        self.clock.t += self.tick_s
        for entry in list(self.live):
            entry[2] += 1
            if entry[2] == self.n:
                self.live.remove(entry)
                self.reports.append(NS(tenant=entry[0].tenant,
                                       status="served",
                                       admit_tick=entry[1]))
        self.tick_count += 1


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def test_ttd_counts_from_the_due_time(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(service.time, "perf_counter", clock)
    monkeypatch.setattr(service.time, "sleep", clock.sleep)
    svc = _FakeService(clock)
    state = service.ServiceState(svc, [], 5.0, CONFIG["families"],
                                 dict(TRAFFIC, rate=4.0), 4, 7)
    rec = service.window(state, 2.0)
    assert rec["attempted"] == 8 and rec["failed"] == 0
    # the generator shares the thread with the ticks, so a tenant due
    # during a tick is submitted after it: at most one tick late; it is
    # served three ticks after it was submitted, timed from its due time
    assert 0.0 <= min(rec["late_s"]) and max(rec["late_s"]) < 0.01
    want = [late + 0.03 for late in rec["late_s"]]
    assert rec["ttd_s"] == pytest.approx(want)
    assert rec["t_end"] == rec["ticks"][-1][2]
    got = service.end_to_end(rec)
    assert got["ttd_p95_s"] == pytest.approx(harness.nearest_rank(want, 95))
    assert got["ttd_p50_s"] == pytest.approx(harness.nearest_rank(want, 50))


def test_unserved_tenants_count_beyond_any_limit(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(service.time, "perf_counter", clock)
    monkeypatch.setattr(service.time, "sleep", clock.sleep)
    svc = _FakeService(clock, tick_s=1.0, ticks_to_serve=10 ** 9)
    state = service.ServiceState(svc, [], 5.0, CONFIG["families"],
                                 dict(TRAFFIC, rate=2.0), 4, 7)
    rec = service.window(state, 2.0)
    assert rec["failed"] == rec["attempted"] == 4
    # the drain deadline is 2 + 5 s after the start
    dues = [d for d, _ in state.schedule]
    assert rec["ttd_s"] == pytest.approx([7.0 - d for d in dues])


def test_nearest_rank():
    assert harness.nearest_rank([3, 1, 2, 4], 50) == 2
    assert harness.nearest_rank(list(range(1, 101)), 95) == 95
    assert harness.nearest_rank([5.0], 95) == 5.0
