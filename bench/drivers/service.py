"""Open-loop driver of the multi-tenant detection service.

The entry the window drives is ``launch.serve.DetectionService``: its
``submit`` and ``step_tick``, on one host thread.  Tenants arrive on the
wall clock at the rate the traffic mix fixes: the inter-arrival gaps are
the quantiles of an exponential distribution, so every seed offers the
same gaps and the same multiset of tenant kinds, in another order, and
each tenant's data comes from a seed drawn from the run's seed.  Each
tenant is timed from its due time to the end of the tick that harvests
it; tenants due in the window are drained after it.

Set-up warms every signature the window uses on the same service
instance: one full bucket of tenants per (family, mode), so every lane
position is admitted once.
"""
from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Tuple

import numpy as np

from bench.harness import nearest_rank, seed32
from bench.reference import service as ref


@dataclass
class ServiceState:
    svc: Any
    schedule: List[Tuple[float, Any]]        # (due offset s, TenantSpec)
    drain_s: float
    families: Dict[str, Dict[str, Any]]
    traffic: Dict[str, Any]
    sample: int
    seed: int
    reports: Dict[str, Any] = field(default_factory=dict)


def tenant_kinds(config, traffic) -> List[Tuple[str, str, float, int, int]]:
    """The multiset of tenant kinds, in an order whose every prefix is
    balanced: families alternate fastest, then modes, then eps_tilde, then
    staleness, then persistence.  A window of n tenants takes the first n
    (cycling), so every seed gets the same kinds."""
    per_family = []
    for name, fam in config["families"].items():
        per_family.append([(name, mode, float(eps), int(k), int(m))
                           for m, k, eps, mode in itertools.product(
                               traffic["persistence"], traffic["staleness"],
                               fam["eps_tilde"], traffic["modes"])])
    return [kind for group in zip(*per_family) for kind in group]


def make_schedule(config, traffic, seed: int, seconds: float):
    """``(due offset, TenantSpec)`` for every tenant due in the window."""
    from repro.launch.serve import TenantSpec

    rate = float(traffic["rate"])
    count = max(int(round(rate * seconds)), 1)
    rng = np.random.default_rng(seed32(seed))
    gaps = -np.log(1.0 - (np.arange(count) + 0.5) / count) / rate
    gaps = rng.permutation(gaps) * (seconds / gaps.sum())
    dues = np.cumsum(gaps) - gaps
    kinds = tenant_kinds(config, traffic)
    order = rng.permutation(count)
    sched = []
    for i in range(count):
        family, mode, eps, k, m = kinds[int(order[i]) % len(kinds)]
        spec = TenantSpec(
            tenant=f"t{i:05d}", family=family,
            problem=dict(config["families"][family]["problem"]),
            seed=int(rng.integers(0, 1 << 31)), eps_tilde=eps, mode=mode,
            staleness=k, persistence=m)
        sched.append((float(dues[i]), spec))
    return sched


def setup(config, traffic, seed: int, devices) -> ServiceState:
    from repro.launch.serve import DetectionService, ServeConfig, TenantSpec

    svc = DetectionService(ServeConfig(**config["serve"]))
    lanes = int(config["serve"]["lanes"])
    for name, fam in config["families"].items():
        for mode in traffic["modes"]:
            for lane in range(lanes):
                svc.submit(TenantSpec(
                    tenant=f"warm-{name}-{mode}-{lane}", family=name,
                    problem=dict(fam["problem"]), seed=lane,
                    eps_tilde=float(max(fam["eps_tilde"])), mode=mode,
                    staleness=lane % (max(traffic["staleness"]) + 1),
                    persistence=int(max(traffic["persistence"]))))
    svc.run()
    return ServiceState(svc, [], float(traffic["drain_s"]),
                        config["families"], traffic, int(traffic["sample"]),
                        seed)


def window(state: ServiceState, seconds: float) -> Dict[str, Any]:
    import jax

    annotate = jax.profiler.TraceAnnotation
    svc = state.svc
    state.schedule = make_schedule({"families": state.families},
                                   state.traffic, state.seed, seconds)
    n = len(state.schedule)
    clock = time.perf_counter
    seen = len(svc.reports)
    ticks: List[Tuple[int, float, float]] = []     # (index, start, end)
    submitted: Dict[str, Tuple[float, float]] = {}   # tenant -> (due, at)
    finished: Dict[str, Tuple[str, float]] = {}      # tenant -> (status, at)
    t_start = clock()
    deadline = t_start + seconds + state.drain_s
    i = 0
    with annotate("window"):
        while True:
            now = clock()
            while i < n and t_start + state.schedule[i][0] <= now:
                due, spec = state.schedule[i]
                with annotate("submit"):
                    ack = svc.submit(spec)
                at = clock()
                submitted[spec.tenant] = (t_start + due, at)
                if not ack["admitted"]:
                    finished[spec.tenant] = ("rejected", at)
                i += 1
            if svc.busy:
                index = svc.tick_count
                t0 = clock()
                with annotate("step_tick"):
                    svc.step_tick()
                t1 = clock()
                ticks.append((index, t0, t1))
                for rep in svc.reports[seen:]:
                    finished.setdefault(rep.tenant, (rep.status, t1))
                    state.reports[rep.tenant] = rep
                seen = len(svc.reports)
            elif i < n:
                with annotate("gen_wait"):
                    time.sleep(max(t_start + state.schedule[i][0] - clock(),
                                   0.0))
            else:
                break
            if clock() > deadline:
                break
    t_end = ticks[-1][2] if ticks else clock()
    ttd, late, failed = [], [], 0
    for _, spec in state.schedule:
        due, at = submitted.get(spec.tenant, (None, None))
        status, end = finished.get(spec.tenant, ("unserved", None))
        if due is not None:
            late.append(at - due)
        if status == "served":
            ttd.append(end - due)
        else:
            failed += 1
            # beyond any limit: at least the wait to the drain deadline
            ttd.append(deadline - (due if due is not None else t_start))
    return {"t_start": t_start, "t_end": t_end, "ticks": ticks,
            "ttd_s": ttd, "late_s": late, "attempted": n, "failed": failed,
            "queue_wait_s": _queue_waits(state, submitted, ticks)}


def _queue_waits(state, submitted, ticks) -> List[float]:
    """Admission wait of each served tenant, in order of due time: start
    of the tick that packed it minus its due time."""
    start_of = {index: t0 for index, t0, _ in ticks}
    out = []
    for name, rep in state.reports.items():
        if rep.admit_tick in start_of and name in submitted:
            due = submitted[name][0]
            out.append((due, start_of[rep.admit_tick] - due))
    return [w for _, w in sorted(out)]


def release(state: ServiceState) -> None:
    state.svc = None


def check(state: ServiceState, record, config, traffic, seed: int):
    """Every tenant due in the window served, and a seeded sample of the
    served ones certified: the exact residual of the iterate each
    detection was made on, over the tenant's eps_tilde."""
    served = sorted(name for name, rep in state.reports.items()
                    if rep.status == "served")
    rng = np.random.default_rng(seed32(seed, 2))
    pick = rng.permutation(len(served))[:state.sample]
    specs = {spec.tenant: spec for _, spec in state.schedule}
    worst = 0.0
    for j in sorted(pick):
        rep = state.reports[served[int(j)]]
        spec = specs[rep.tenant]
        kw = dict(state.families[spec.family]["problem"])
        kw.update(state.families[spec.family].get("defaults", {}))
        r = ref.residual_at(spec.family, kw, spec.seed, rep.detect_step)
        worst = max(worst, r / spec.eps_tilde)
    return {"unserved": (record["failed"], 0),
            "residual_ratio": (worst, float(config["residual_ratio_limit"]))}


def end_to_end(record) -> Dict[str, float]:
    return {"ttd_p95_s": nearest_rank(record["ttd_s"], 95),
            "ttd_p50_s": nearest_rank(record["ttd_s"], 50)}
