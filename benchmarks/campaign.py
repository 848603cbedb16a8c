"""Content-addressed, resumable campaign runner for benchmark cells.

The reliability lab's value scales with how many (protocol × problem ×
scenario × seed) cells it can afford to run; PR 2's runner executed its 64
cells serially in one Python process and threw every result away at exit.
This module turns a list of *cell specs* (plain JSON dicts with a ``kind``
key, see ``benchmarks.common.CELL_KINDS``) into a campaign:

* **content-addressed** — each cell's key is the SHA-256 of its canonical
  spec JSON, the code fingerprint (every ``src/repro`` source plus the cell
  API module), and any environment the kind declared sensitivity to (e.g.
  the jax version for HLO-derived cells).  A re-run after an interrupt or a
  code-irrelevant change (README, workflows, this runner itself) recomputes
  zero cells; touching solver/engine code invalidates everything built on
  it.
* **cached** — results live under ``.campaign-cache/<k[:2]>/<key>.json``,
  written atomically (tmp + rename); a truncated file from a killed run is
  treated as a miss.
* **parallel** — cache misses execute across a process pool (fork), longest
  expected cell first (LPT) so two workers keep the makespan near the
  serial-half bound.
* **incremental** — with ``report_path`` set, the strict-JSON report is
  rewritten after every completion with pending cells marked, so a killed
  campaign leaves a usable partial report *and* a warm cache.
* **deterministic** — report cells follow the input spec order, never
  completion order.

Used by ``reliability_matrix.py``, ``bench_fused.py`` and the ``table*.py``
scripts; see EXPERIMENTS.md §Campaign for cache-key details and local
reproduction.
"""
from __future__ import annotations

import hashlib
import json
import multiprocessing
import os
import sys
import time
from concurrent.futures import (
    ProcessPoolExecutor,
    ThreadPoolExecutor,
    as_completed,
)
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

REPO_ROOT = Path(__file__).resolve().parent.parent

#: sources whose content defines cell results (the fingerprint).  The
#: runner itself is deliberately absent: it schedules and caches, it does
#: not compute.  bench_fused.py is included because the cached
#: ``fused_sharded`` kind imports its ``measure_sharded``.
FINGERPRINT_PATHS: Tuple[str, ...] = (
    "src/repro",
    "benchmarks/common.py",
    "benchmarks/bench_fused.py",
    "benchmarks/bench_shard_runtime.py",
    "benchmarks/bench_elastic.py",
    "benchmarks/bench_ml.py",
    "benchmarks/bench_replay.py",
    "benchmarks/bench_serve.py",
)


def code_fingerprint(
    root: Optional[os.PathLike] = None,
    paths: Sequence[str] = FINGERPRINT_PATHS,
) -> str:
    """SHA-256 over the result-defining sources (sorted, path-prefixed).

    A listed path that does not exist under ``root`` hashes as a distinct
    "missing" marker rather than erroring: partial trees (tests, sparse
    checkouts) stay fingerprintable, and creating the file later still
    changes the key.
    """
    h = hashlib.sha256()
    base = Path(root) if root is not None else REPO_ROOT
    for rel in paths:
        p = base / rel
        if not p.exists():
            h.update(rel.encode())
            h.update(b"\0missing\0")
            continue
        files = sorted(p.rglob("*.py")) if p.is_dir() else [p]
        for f in files:
            h.update(str(f.relative_to(base)).encode())
            h.update(b"\0")
            h.update(f.read_bytes())
    return h.hexdigest()


def canonical_json(obj: Any) -> str:
    """Key-sorted, separator-normalised JSON — the hashable spec identity."""
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


def cell_key(spec: Dict, fingerprint: str, env: Optional[Dict] = None) -> str:
    payload = {"spec": spec, "code": fingerprint, "env": env or {}}
    return hashlib.sha256(canonical_json(payload).encode()).hexdigest()


def jsonable(obj):
    """RFC 8259-safe copy: non-finite floats become None (json.dump would
    otherwise emit the non-standard Infinity/NaN tokens — undetected runs
    carry detected_residual/overshoot = inf)."""
    if isinstance(obj, dict):
        return {k: jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [jsonable(v) for v in obj]
    if isinstance(obj, float) and not (obj == obj and abs(obj) != float("inf")):
        return None
    return obj


def write_json_atomic(path: os.PathLike, obj: Any, indent: int = 1) -> None:
    """Strict-JSON write via tmp + rename: a killed run never leaves a
    half-written file where a reader (or the cache) expects JSON."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(path.name + f".tmp.{os.getpid()}")
    with open(tmp, "w") as f:
        json.dump(jsonable(obj), f, indent=indent, allow_nan=False)
    os.replace(tmp, path)


# ---------------------------------------------------------------------------
# Campaign execution
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CampaignConfig:
    cache_dir: str = ".campaign-cache"
    workers: Optional[int] = None  # None → os.cpu_count(); 0 → inline
    executor: str = "process"  # "process" | "thread" | "inline"
    report_path: Optional[str] = None  # incremental strict-JSON report
    report_every_s: float = 2.0  # min seconds between incremental rewrites
    use_cache: bool = True  # False: recompute and overwrite


@dataclass
class CampaignResult:
    """Results aligned with the input spec order (`cached[i]` marks a
    cache hit; `wall_s` is the campaign's own wall-clock)."""

    specs: List[Dict]
    results: List[Dict]
    keys: List[str]
    cached: List[bool]
    fingerprint: str
    wall_s: float = 0.0
    busy_s: float = 0.0  # Σ recomputed-cell wall (work actually done)
    workers: int = 0  # pool size actually used (0 = inline)
    executor: str = "inline"
    meta: Dict = field(default_factory=dict)

    @property
    def hits(self) -> int:
        return sum(self.cached)

    @property
    def recomputed(self) -> int:
        return len(self.cached) - self.hits

    @property
    def pool_scaling(self) -> Optional[float]:
        """Effective parallel speedup: cell-seconds executed per campaign
        wall-second.  On a contended 2-vCPU box this lands near 1 however
        many workers are configured — which is why the 3×-cold-run target
        must be judged against THIS number and ``cpu_count``, not a fixed
        reference box (ROADMAP PR-3 note)."""
        if self.wall_s <= 0 or self.recomputed == 0:
            return None
        return self.busy_s / self.wall_s

    def report(self) -> Dict:
        cells = [
            {"spec": s, "key": k, "cached": c, "result": r}
            for s, k, c, r in zip(self.specs, self.keys, self.cached, self.results)
        ]
        meta = {
            "fingerprint": self.fingerprint,
            "cells": len(self.specs),
            "cache_hits": self.hits,
            "recomputed": self.recomputed,
            "wall_s": self.wall_s,
            "busy_s": self.busy_s,
            "cpu_count": os.cpu_count(),
            "workers": self.workers,
            "executor": self.executor,
            "pool_scaling": self.pool_scaling,
        }
        meta.update(self.meta)
        return {"cells": cells, "meta": meta}


def _fork_is_safe() -> bool:
    """True when no XLA backend is live in this process (best-effort; if
    the private backend registry moves in a future jax, we conservatively
    spawn whenever jax is imported)."""
    jmod = sys.modules.get("jax")
    if jmod is None:
        return True
    xb = getattr(getattr(jmod, "_src", None), "xla_bridge", None)
    if xb is None:
        return False
    return not getattr(xb, "_backends", None)


def _cells_need_chip(specs: Sequence[Dict]) -> bool:
    """True when a cell touches the JAX device (its kind declares the
    ``jax`` environment) and that device is a TPU.  A chip belongs to one
    process: the parent holds it once JAX is up, so a pool child could not
    open it — such campaigns run inline."""
    from benchmarks.common import CELL_KINDS

    if not any("jax" in CELL_KINDS[s["kind"]].env for s in specs):
        return False
    import jax

    return jax.default_backend() == "tpu"


def _exec_cell(spec: Dict) -> Tuple[Dict, float]:
    """Pool worker entry: run one cell through the kind registry."""
    from benchmarks.common import run_cell_spec

    t0 = time.time()
    result = run_cell_spec(spec)
    return result, time.time() - t0


def _cache_path(cfg: CampaignConfig, key: str) -> Path:
    return Path(cfg.cache_dir) / key[:2] / (key + ".json")


def _cache_load(cfg: CampaignConfig, key: str) -> Optional[Dict]:
    try:
        with open(_cache_path(cfg, key)) as f:
            entry = json.load(f)
        return entry["result"]
    except (OSError, json.JSONDecodeError, KeyError):
        return None  # absent, truncated by an interrupt, or foreign: recompute


def _cache_store(
    cfg: CampaignConfig,
    key: str,
    spec: Dict,
    fingerprint: str,
    result: Dict,
    wall_s: float,
) -> None:
    entry = {
        "key": key,
        "spec": spec,
        "fingerprint": fingerprint,
        "result": result,
        "wall_s": wall_s,
        "written": time.strftime("%Y-%m-%d %H:%M:%S"),
    }
    write_json_atomic(_cache_path(cfg, key), entry)


def run_campaign(
    specs: Sequence[Dict],
    cfg: CampaignConfig = CampaignConfig(),
    fingerprint: Optional[str] = None,
    progress: bool = False,
) -> CampaignResult:
    """Execute every spec, serving cache hits and pooling the misses.

    Cells that raise abort the campaign (the exception propagates with the
    offending spec named) — a benchmark cell failing is a finding, not a
    statistic to average over.
    """
    from benchmarks.common import CELL_KINDS, spec_cost, spec_env

    t0 = time.time()
    specs = [dict(s) for s in specs]
    if fingerprint is None:
        fingerprint = code_fingerprint()
    keys = [cell_key(s, fingerprint, spec_env(s)) for s in specs]

    results: List[Optional[Dict]] = [None] * len(specs)
    cached = [False] * len(specs)
    if cfg.use_cache:
        for i, key in enumerate(keys):
            hit = _cache_load(cfg, key)
            if hit is not None:
                results[i] = hit
                cached[i] = True

    out = CampaignResult(
        specs=specs,
        results=results,  # type: ignore[arg-type]
        keys=keys,
        cached=cached,
        fingerprint=fingerprint,
    )

    last_flush = [0.0]

    def flush_report(force: bool = False) -> None:
        # serialising the whole report after every cell would make the
        # coordinator the bottleneck on large campaigns — rewrite at most
        # every report_every_s (interrupt loss: a few seconds of cells,
        # which the cache already holds anyway)
        if cfg.report_path is None:
            return
        now = time.time()
        if not force and now - last_flush[0] < cfg.report_every_s:
            return
        last_flush[0] = now
        rep = out.report()
        for cell in rep["cells"]:
            if cell["result"] is None:
                cell["result"] = {"status": "pending"}
        rep["meta"]["wall_s"] = now - t0
        write_json_atomic(cfg.report_path, rep)

    pending = [i for i in range(len(specs)) if results[i] is None]
    # LPT: longest expected cell first keeps a small pool near the ideal
    # makespan regardless of submission order
    pending.sort(key=lambda i: -spec_cost(specs[i]))
    flush_report()

    workers = cfg.workers if cfg.workers is not None else (os.cpu_count() or 1)
    inline = (cfg.executor == "inline" or workers == 0 or len(pending) <= 1
              or _cells_need_chip([specs[i] for i in pending]))
    out.executor = "inline" if inline else cfg.executor
    out.workers = 0 if inline else min(workers, len(pending))

    def finish(i: int, result: Dict, cell_wall: float) -> None:
        results[i] = result
        out.busy_s += cell_wall
        if cfg.use_cache and CELL_KINDS[specs[i]["kind"]].cache:
            _cache_store(cfg, keys[i], specs[i], fingerprint, result, cell_wall)
        if progress:
            print(
                f"[campaign] {len([r for r in results if r is not None])}"
                f"/{len(specs)} {canonical_json(specs[i])[:96]}"
                f" ({cell_wall:.2f}s)"
            )
        flush_report()

    if inline:
        for i in pending:
            result, cell_wall = _exec_cell(specs[i])
            finish(i, result, cell_wall)
    else:
        if cfg.executor == "process":
            # fork is the fast path (inherits registered kinds + warm numpy),
            # but forking after an XLA backend has initialised its thread
            # pools can deadlock — fall back to spawn there (children
            # re-import benchmarks.common, so registry kinds defined in
            # modules survive; test-local kinds should use the thread or
            # inline executors).  jax being merely *imported* (the campaign
            # stack pulls it transitively) is fine: its threads start with
            # the first backend, which is what the check detects.
            ctx = multiprocessing.get_context(
                "fork" if _fork_is_safe() else "spawn")
            pool = ProcessPoolExecutor(
                max_workers=min(workers, len(pending)), mp_context=ctx)
        else:
            pool = ThreadPoolExecutor(max_workers=min(workers, len(pending)))
        with pool:
            futures = {pool.submit(_exec_cell, specs[i]): i for i in pending}
            try:
                for fut in as_completed(futures):
                    i = futures[fut]
                    try:
                        result, cell_wall = fut.result()
                    except Exception as exc:
                        raise RuntimeError(
                            f"campaign cell failed: {canonical_json(specs[i])}"
                        ) from exc
                    finish(i, result, cell_wall)
            except BaseException:
                for fut in futures:
                    fut.cancel()
                raise

    out.wall_s = time.time() - t0
    flush_report(force=True)
    return out


def map_cells(
    specs: Sequence[Dict],
    cfg: CampaignConfig = CampaignConfig(),
    **kw,
) -> List[Dict]:
    """`run_campaign` for callers that only want the results list."""
    return run_campaign(specs, cfg, **kw).results
