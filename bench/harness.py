"""The benchmark harness: one run of one cell, driven by data.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``bench/configs/<config>.json``  — the deployment (the ``file`` of its
  ``configs`` entry);
* ``bench/traffic/<traffic>.json`` — the traffic mix: the driver kind and
  its parameters;
* ``bench/drivers/<kind>.py``      — set-up, the measured window, the
  end-to-end metrics and the comparison with the plain reference for one
  kind of traffic;
* ``bench/metrics/<metric>.py``    — one reader per per-layer metric
  (``read(ctx) -> float | None``).

A later cell, configuration, mix or metric is new files plus new
``BENCHMARK.json`` entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import math
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(RuntimeError):
    """The benchmark's own inputs are missing or malformed."""


def load_spec(root: Path = ROOT) -> Dict[str, Any]:
    """``BENCHMARK.json`` at the root of the checkout."""
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def find_cell(spec: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"workload {name!r} not in BENCHMARK.json "
                     f"({[c['name'] for c in spec['workloads']]})")


def load_config(spec: Dict[str, Any], name: str,
                root: Path = ROOT) -> Dict[str, Any]:
    for entry in spec["configs"]:
        if entry["name"] == name:
            return json.loads((root / entry["file"]).read_text())
    raise BenchError(f"config {name!r} not in BENCHMARK.json")


def load_traffic(name: str) -> Dict[str, Any]:
    path = BENCH / "traffic" / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no traffic mix {path}")
    return json.loads(path.read_text())


def _load_module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind[:-1]} {path}")
    mod_name = f"bench.{kind}.{name.replace('-', '_').replace('.', '_')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = module
    spec.loader.exec_module(module)
    return module


def load_driver(kind: str):
    """``bench/drivers/<kind>.py``."""
    return _load_module("drivers", kind)


def load_metric(name: str):
    """``bench/metrics/<name>.py`` (its ``read(ctx)``)."""
    return _load_module("metrics", name)


def peaks_for(device_kind: str) -> Dict[str, Any]:
    """The device's published peaks; a kind missing from the table is an
    error, never a default."""
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} not in "
                         f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]


def applies(metric: Dict[str, Any], cell: str) -> bool:
    """Whether a metric is reported in a cell (its ``workloads`` key, or
    every cell when it has none)."""
    return "workloads" not in metric or cell in metric["workloads"]


def seed32(seed: int, salt: int = 0) -> int:
    """A 32-bit key from any whole-number seed and a salt (a run's seed
    may not fit 32 signed bits)."""
    import numpy as np

    return int(np.random.SeedSequence([seed % (1 << 64), salt])
               .generate_state(1)[0])


def nearest_rank(values: List[float], q: float) -> float:
    """Nearest-rank percentile (q in (0, 100])."""
    if not values:
        raise ValueError("no values")
    s = sorted(values)
    return s[max(int(math.ceil(q / 100.0 * len(s))) - 1, 0)]


@dataclass
class Context:
    """What a per-layer metric reader sees."""

    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    record: Dict[str, Any]
    trace: Any                        # trace.reduce.TraceData or None
    device_kind: str

    def peak(self, key: str) -> float:
        return float(peaks_for(self.device_kind)[key])


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def _compile_counter():
    """Counts XLA backend compiles from now on (``jax.monitoring``)."""
    import jax

    box = {"n": 0, "on": False}

    def listener(event, duration, **_):
        if box["on"] and event.endswith("backend_compile_duration"):
            box["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return box


def execute(cell_name: str, seed: int, seconds: float, trace: bool, *,
            t0: Optional[float] = None, root: Path = ROOT,
            spec: Optional[Dict[str, Any]] = None,
            config_overrides: Optional[Dict[str, Any]] = None,
            traffic_overrides: Optional[Dict[str, Any]] = None
            ) -> Dict[str, Any]:
    """One run of one cell: set-up, window, comparison, metrics.

    Returns the result object whose JSON is the run's last line.  The
    caller has already checked the device; ``t0`` is the process start
    (``setup_s`` runs from it to the first timed operation).  ``spec``
    defaults to the checkout's ``BENCHMARK.json``.
    """
    import jax

    t0 = time.perf_counter() if t0 is None else t0
    spec = load_spec(root) if spec is None else spec
    cell = find_cell(spec, cell_name)
    config = dict(load_config(spec, cell["config"], root))
    config.update(config_overrides or {})
    traffic = dict(load_traffic(cell["traffic"]))
    traffic.update(traffic_overrides or {})
    driver = load_driver(traffic["driver"])
    devices = jax.devices()
    used = devices[:int(cell["chips"])]
    compiles = _compile_counter()

    state = driver.setup(config, traffic, int(seed), used)
    tdata = None
    compiles["on"] = True
    if trace:
        from bench.trace import reduce as trace_reduce

        with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
            jax.profiler.start_trace(tdir)
            try:
                record = driver.window(state, float(seconds))
            finally:
                jax.profiler.stop_trace()
            tdata = trace_reduce.load_dir(tdir)
    else:
        record = driver.window(state, float(seconds))
    compiles["on"] = False
    record["window_compiles"] = compiles["n"]
    setup_s = record["t_start"] - t0
    memory_peak = _memory_peak(used)
    driver.release(state)
    checks = driver.check(state, record, config, traffic, int(seed))
    correct = all(float(v) <= float(lim) for v, lim in checks.values())

    kind = used[0].device_kind
    metrics: Dict[str, Dict[str, Any]] = {}
    if not trace:
        values = dict(driver.end_to_end(record))
        values["setup_s"] = setup_s
        for m in spec["end_to_end"]:
            if applies(m, cell_name) and m["name"] in values:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    else:
        ctx = Context(cell, config, traffic, record, tdata, kind)
        for m in spec["per_layer"]:
            if not applies(m, cell_name):
                continue
            value = load_metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device: Dict[str, Any] = {
        "platform": used[0].platform, "kind": kind, "count": len(devices),
        "memory_peak_bytes": memory_peak}
    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = tdata.busy_s() if tdata else 0.0
        device["window_s"] = record["t_end"] - record["t_start"]
        if tdata is not None:
            result["breakdown"] = tdata.breakdown()
    result["window_compiles"] = compiles["n"]
    result["checks"] = {name: {"value": float(v), "limit": float(lim)}
                        for name, (v, lim) in checks.items()}
    return result
