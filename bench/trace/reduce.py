"""Reduction of a profiler trace to the benchmark's device numbers.

``load_dir`` reads the ``.xplane.pb`` the JAX profiler wrote, with
``jax.profiler.ProfileData``, into a ``TraceData``: per device, the
intervals of the operations on its ``XLA Ops`` line, and the host spans
the benchmark annotated.  The reductions are plain interval arithmetic:

* busy time — the length of the union of a device's op intervals (ops
  that contain others, such as a while loop, left out);
* kernel time — the summed durations of the ops whose name matches;
* exposed collective time — the part of each collective's interval in
  which no other op runs on that device;
* idle gaps — the holes in the busy union, each attributed to the host
  span that covers its midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)
Op = Tuple[str, float, float]           # (name, start_ns, end_ns)

#: ops that contain other ops on the XLA Ops line (a while loop spans
#: every op of its body): left out, so that nothing is counted twice
CONTAINER = re.compile(r"^%?(while|conditional|call)(\.\d+)?$")
#: host spans the benchmark's own files annotate
HOST_SPANS = ("solve", "submit", "step_tick", "gen_wait", "window")
#: op names of collectives on a TPU's XLA Ops line
COLLECTIVE = re.compile(
    r"all-reduce|collective-permute|all-gather|reduce-scatter|all-to-all"
    r"|ppermute|psum", re.IGNORECASE)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of half-open intervals."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: Sequence[Interval]) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """``a`` minus ``b``; both sorted and disjoint."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval]) -> List[Interval]:
    return [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)
            if busy[i + 1][0] > busy[i][1]]


@dataclass
class TraceData:
    """Device ops per device and the benchmark's host spans."""

    ops: Dict[str, List[Op]] = field(default_factory=dict)
    spans: List[Op] = field(default_factory=list)

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)

    def busy_ns(self, device: str) -> float:
        return length(union((s, e) for _, s, e in self.ops[device]))

    def busy_s(self) -> float:
        """Busy seconds, averaged over the traced devices."""
        if not self.ops:
            return 0.0
        return sum(self.busy_ns(d) for d in self.ops) / len(self.ops) / 1e9

    def kernel_ns(self, device: str, pattern: str) -> Tuple[float, int]:
        """(summed duration, call count) of the ops matching ``pattern``."""
        rx = re.compile(pattern)
        hits = [(e - s) for name, s, e in self.ops[device] if rx.search(name)]
        return float(sum(hits)), len(hits)

    def exposed_collective_ns(self, device: str,
                              pattern: re.Pattern = COLLECTIVE) -> float:
        ops = self.ops[device]
        coll = union((s, e) for n, s, e in ops if pattern.search(n))
        other = union((s, e) for n, s, e in ops if not pattern.search(n))
        return length(subtract(coll, other))

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` op names with the most device time, seconds averaged
        over devices."""
        tot: Dict[str, float] = {}
        for ops in self.ops.values():
            for name, s, e in ops:
                tot[name] = tot.get(name, 0.0) + (e - s)
        n = max(len(self.ops), 1)
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / n / 1e9] for name, ns in best]

    def idle_by_span(self, k: int = 10) -> List[List]:
        """Idle gaps of the first device, summed by the innermost host span
        covering each gap's midpoint (``other`` where none does)."""
        if not self.ops:
            return []
        busy = union((s, e) for _, s, e in self.ops[self.devices[0]])
        tot: Dict[str, float] = {}
        spans = sorted(self.spans, key=lambda sp: sp[2] - sp[1])
        for s, e in gaps(busy):
            mid = 0.5 * (s + e)
            name = next((n for n, a, b in spans if a <= mid < b), "other")
            tot[name] = tot.get(name, 0.0) + (e - s)
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9] for name, ns in best]

    def breakdown(self) -> Dict[str, List[List]]:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_by_span()}


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def op_name(text: str) -> str:
    """An op's name from the HLO text the trace gives it:
    ``%fused_rbgs_sweep_residual_halo.12 = (f32[...]) custom-call(...)``
    gives ``%fused_rbgs_sweep_residual_halo.12``."""
    return text.split(" = ", 1)[0].strip()


def from_profile(profile) -> TraceData:
    """``TraceData`` from a ``jax.profiler.ProfileData`` (or anything with
    its ``planes``/``lines``/``events`` shape)."""
    data = TraceData()
    for plane in profile.planes:
        if _is_device_plane(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(op_name(ev.name), float(ev.start_ns),
                            float(ev.start_ns) + float(ev.duration_ns))
                           for ev in line.events]
                    data.ops[plane.name] = [
                        op for op in ops if not CONTAINER.match(op[0])]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        data.spans.append(
                            (ev.name, float(ev.start_ns),
                             float(ev.start_ns) + float(ev.duration_ns)))
    return data


def find_xplane(path: str) -> Optional[str]:
    hits = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    return sorted(hits)[-1] if hits else None


def load_dir(path: str) -> Optional[TraceData]:
    """The trace the profiler wrote under ``path`` (None when there is
    none)."""
    from jax.profiler import ProfileData

    pb = find_xplane(path)
    if pb is None:
        return None
    return from_profile(ProfileData.from_file(pb))


def summary(data: TraceData, k: int = 40) -> Dict[str, object]:
    """Names and totals, for reading a trace by hand."""
    out: Dict[str, object] = {"devices": data.devices,
                              "spans": sorted({n for n, _, _ in data.spans})}
    for d in data.devices:
        tot: Dict[str, Tuple[float, int]] = {}
        for name, s, e in data.ops[d]:
            t, c = tot.get(name, (0.0, 0))
            tot[name] = (t + e - s, c + 1)
        out[d] = sorted(([n, t / 1e9, c] for n, (t, c) in tot.items()),
                        key=lambda r: -r[1])[:k]
    return out
