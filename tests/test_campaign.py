"""Campaign runner semantics: content-addressed caching, invalidation,
resume-after-interrupt, deterministic reports (benchmarks/campaign.py).

The cache contract under test:
  * same spec + same fingerprint ⇒ hit (zero recompute),
  * any spec key change ⇒ miss,
  * code-fingerprint change ⇒ every cell misses,
  * deleted/truncated cache files (an interrupted campaign) ⇒ only those
    cells recompute,
  * report cell order follows the input spec order regardless of worker
    completion order, and reports are strict JSON.
"""
import json
import time

import pytest

from benchmarks import campaign
from benchmarks.campaign import CampaignConfig, cell_key, code_fingerprint
from benchmarks.common import CELL_KINDS, cell_kind, spec_env

CALLS = []  # (kind, payload) per executed cell — inline/thread executors only


@cell_kind("t_echo")
def _t_echo(payload, sleep: float = 0.0):
    if sleep:
        time.sleep(sleep)
    CALLS.append(("t_echo", payload))
    return {"payload": payload, "doubled": payload * 2}


@cell_kind("t_nocache", cache=False)
def _t_nocache(payload):
    CALLS.append(("t_nocache", payload))
    return {"payload": payload}


def _cfg(tmp_path, **kw):
    kw.setdefault("cache_dir", str(tmp_path / "cache"))
    kw.setdefault("executor", "inline")
    return CampaignConfig(**kw)


def _specs(n):
    return [{"kind": "t_echo", "payload": i} for i in range(n)]


# ---------------------------------------------------------------------------
# hit / miss
# ---------------------------------------------------------------------------


def test_cold_run_computes_every_cell(tmp_path):
    CALLS.clear()
    out = campaign.run_campaign(_specs(3), _cfg(tmp_path), fingerprint="fp")
    assert [r["doubled"] for r in out.results] == [0, 2, 4]
    assert out.recomputed == 3 and out.hits == 0
    assert len(CALLS) == 3


def test_warm_rerun_recomputes_zero_cells(tmp_path):
    cfg = _cfg(tmp_path)
    campaign.run_campaign(_specs(3), cfg, fingerprint="fp")
    CALLS.clear()
    out = campaign.run_campaign(_specs(3), cfg, fingerprint="fp")
    assert out.hits == 3 and out.recomputed == 0
    assert CALLS == []
    assert [r["doubled"] for r in out.results] == [0, 2, 4]


def test_config_change_misses_only_changed_cell(tmp_path):
    cfg = _cfg(tmp_path)
    campaign.run_campaign(_specs(3), cfg, fingerprint="fp")
    CALLS.clear()
    specs = _specs(3)
    specs[1]["payload"] = 99  # one changed cell
    out = campaign.run_campaign(specs, cfg, fingerprint="fp")
    assert out.hits == 2 and out.recomputed == 1
    assert CALLS == [("t_echo", 99)]
    assert out.results[1]["doubled"] == 198


def test_code_fingerprint_change_invalidates_everything(tmp_path):
    cfg = _cfg(tmp_path)
    campaign.run_campaign(_specs(3), cfg, fingerprint="fp-old")
    CALLS.clear()
    out = campaign.run_campaign(_specs(3), cfg, fingerprint="fp-new")
    assert out.hits == 0 and out.recomputed == 3
    assert len(CALLS) == 3


def test_code_fingerprint_tracks_sources_not_docs(tmp_path):
    """The real fingerprint hashes result-defining sources only — a tree
    with identical sources but different docs fingerprints identically."""
    root = tmp_path / "repo"
    (root / "src" / "repro").mkdir(parents=True)
    (root / "benchmarks").mkdir()
    (root / "src" / "repro" / "a.py").write_text("x = 1\n")
    (root / "benchmarks" / "common.py").write_text("y = 2\n")
    (root / "benchmarks" / "bench_fused.py").write_text("z = 3\n")
    (root / "benchmarks" / "bench_shard_runtime.py").write_text("w = 4\n")
    (root / "README.md").write_text("v1")
    fp1 = code_fingerprint(root=root)
    (root / "README.md").write_text("v2 — docs only")
    assert code_fingerprint(root=root) == fp1
    (root / "src" / "repro" / "a.py").write_text("x = 2\n")
    assert code_fingerprint(root=root) != fp1


def test_uncacheable_kind_always_recomputes(tmp_path):
    cfg = _cfg(tmp_path)
    specs = [{"kind": "t_nocache", "payload": 7}]
    campaign.run_campaign(specs, cfg, fingerprint="fp")
    CALLS.clear()
    out = campaign.run_campaign(specs, cfg, fingerprint="fp")
    assert out.hits == 0 and len(CALLS) == 1


# ---------------------------------------------------------------------------
# resume after interrupt
# ---------------------------------------------------------------------------


def _cache_file(cfg, spec, fingerprint):
    key = cell_key(spec, fingerprint, spec_env(spec))
    return campaign._cache_path(cfg, key)


def test_resume_recomputes_only_missing_and_corrupt_cells(tmp_path):
    cfg = _cfg(tmp_path)
    specs = _specs(4)
    campaign.run_campaign(specs, cfg, fingerprint="fp")
    # simulate an interrupt: one cell never finished (file absent), one was
    # killed mid-write (truncated JSON)
    _cache_file(cfg, specs[0], "fp").unlink()
    _cache_file(cfg, specs[2], "fp").write_text('{"key": "trunc')
    CALLS.clear()
    out = campaign.run_campaign(specs, cfg, fingerprint="fp")
    assert out.hits == 2 and out.recomputed == 2
    assert sorted(p for _, p in CALLS) == [0, 2]
    assert [r["doubled"] for r in out.results] == [0, 2, 4, 6]


# ---------------------------------------------------------------------------
# reports
# ---------------------------------------------------------------------------


def test_report_order_follows_specs_not_completion(tmp_path):
    """Thread executor + inverted sleep times: late specs complete first,
    the report must still list cells in spec order."""
    specs = [
        {"kind": "t_echo", "payload": i, "sleep": 0.05 * (4 - i)}
        for i in range(5)
    ]
    report_path = tmp_path / "report.json"
    out = campaign.run_campaign(
        specs,
        _cfg(tmp_path, executor="thread", workers=4,
             report_path=str(report_path), report_every_s=0.0),
        fingerprint="fp",
    )
    assert [c["spec"]["payload"] for c in out.report()["cells"]] == [0, 1, 2, 3, 4]
    on_disk = json.loads(report_path.read_text())
    assert [c["spec"]["payload"] for c in on_disk["cells"]] == [0, 1, 2, 3, 4]
    assert on_disk["meta"]["recomputed"] == 5


def test_report_is_strict_json(tmp_path):
    @cell_kind("t_inf")
    def _t_inf(payload):  # noqa: F811 — registered once per session
        return {"value": float("inf"), "nan": float("nan"), "ok": 1.0}

    try:
        report_path = tmp_path / "report.json"
        campaign.run_campaign(
            [{"kind": "t_inf", "payload": 0}],
            _cfg(tmp_path, report_path=str(report_path)),
            fingerprint="fp",
        )
        def reject(_):
            raise AssertionError("non-RFC8259 constant in report")

        rep = json.loads(report_path.read_text(), parse_constant=reject)
        assert rep["cells"][0]["result"] == {"value": None, "nan": None, "ok": 1.0}
    finally:
        CELL_KINDS.pop("t_inf", None)


def test_identical_reruns_produce_identical_cells(tmp_path):
    cfg = _cfg(tmp_path)
    a = campaign.run_campaign(_specs(4), cfg, fingerprint="fp").report()
    b = campaign.run_campaign(_specs(4), cfg, fingerprint="fp").report()

    def content(rep):  # the cached flag legitimately flips cold → warm
        return [{k: v for k, v in c.items() if k != "cached"}
                for c in rep["cells"]]

    assert content(a) == content(b)


def test_failing_cell_aborts_with_spec_named(tmp_path):
    @cell_kind("t_boom")
    def _t_boom(payload):
        raise ValueError("boom")

    try:
        with pytest.raises(ValueError, match="boom"):
            campaign.run_campaign(
                [{"kind": "t_boom", "payload": 1}], _cfg(tmp_path),
                fingerprint="fp",
            )
    finally:
        CELL_KINDS.pop("t_boom", None)


def test_device_cells_run_inline_on_a_tpu(tmp_path, monkeypatch):
    """One process per chip: cells that touch JAX never go to pool workers
    when the backend is a TPU, whatever executor was asked for."""
    import jax

    @cell_kind("t_device", env=("jax",))
    def _t_device(payload):
        CALLS.append(("t_device", payload))
        return {"payload": payload}

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    CALLS.clear()
    try:
        out = campaign.run_campaign(
            [{"kind": "t_device", "payload": i} for i in range(3)],
            _cfg(tmp_path, executor="process", workers=2), fingerprint="fp")
    finally:
        CELL_KINDS.pop("t_device", None)
    assert out.executor == "inline" and out.workers == 0
    assert sorted(CALLS) == [("t_device", i) for i in range(3)]


# ---------------------------------------------------------------------------
# process pool (real fork workers, real cell kind)
# ---------------------------------------------------------------------------


def test_process_pool_executes_and_caches_real_cells(tmp_path):
    specs = [
        {"kind": "reliability_run", "family": "pagerank",
         "protocol": "pfait", "scenario": "stable", "seed": s,
         "eps": 1e-4, "max_iters": 400, "problem": {"n": 64, "p": 4},
         "residual_stride": 0}
        for s in range(3)
    ]
    cfg = _cfg(tmp_path, executor="process", workers=2)
    out = campaign.run_campaign(specs, cfg)
    assert out.recomputed == 3
    assert all(r["status"] == "ok" for r in out.results)
    warm = campaign.run_campaign(specs, cfg)
    assert warm.hits == 3 and warm.recomputed == 0
    assert warm.results == out.results
