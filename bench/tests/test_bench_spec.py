"""BENCHMARK.json: every piece loads by name, and the file keeps the
contract's shape."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
SPEC = harness.load_spec(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [c["name"] for c in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


def _text_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_names_units_and_texts():
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(set(names)) == len(names)
    for name in names:
        assert NAME.match(name), name
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _text_ok(c["why"]) and _text_ok(c["source"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _text_ok(w["why"])
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= 1
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _text_ok(m["layer"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert all(w in CELLS for w in m.get("workloads", CELLS))


def test_every_cell_reports_what_it_must():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for cell in CELLS:
        mine = [m["name"] for m in SPEC["end_to_end"]
                if harness.applies(m, cell)]
        assert "setup_s" in mine and len(mine) >= 2, cell
        layer = [m for m in SPEC["per_layer"] if harness.applies(m, cell)]
        assert layer, cell
        for m in layer:
            assert harness.applies(e2e[m["moves"]], cell), (cell, m["name"])


@pytest.mark.parametrize("cell", [c["name"] for c in tiny.CELLS])
def test_cell_pieces_load_by_name(cell):
    w = harness.find_cell(tiny.SPEC, cell)
    config = harness.load_config(tiny.SPEC, w["config"], ROOT)
    assert config["name"] == w["config"]
    traffic = harness.load_traffic(w["traffic"])
    driver = harness.load_driver(traffic["driver"])
    for fn in ("setup", "window", "release", "check", "end_to_end"):
        assert callable(getattr(driver, fn))
    entry = next((c for c in SPEC["configs"] if c["name"] == w["config"]),
                 None)
    if entry is not None:
        assert entry["file"] == f"bench/configs/{w['config']}.json"
        assert sorted(config["reduced"]) == sorted(entry["reduced"])


READERS = sorted(p.stem for p in (ROOT / "bench" / "metrics").glob("*.py")
                 if p.stem != "__init__")


def test_every_listed_metric_has_a_reader():
    assert {m["name"] for m in SPEC["per_layer"]} <= set(READERS)


@pytest.mark.parametrize("metric", READERS)
def test_metric_readers_load_and_read_nothing_from_nothing(metric):
    reader = harness.load_metric(metric)
    ctx = harness.Context(cell={}, config={}, traffic={},
                          record={"t_start": 0.0, "t_end": 1.0},
                          trace=None, device_kind="none")
    assert reader.read(ctx) is None


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v99")


def test_unknown_names_are_errors():
    with pytest.raises(harness.BenchError):
        harness.find_cell(SPEC, "no-such-cell")
    with pytest.raises(harness.BenchError):
        harness.load_traffic("no-such-mix")
    with pytest.raises(harness.BenchError):
        harness.load_metric("no_such_metric")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    proc = _run(ROOT)
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_run_outside_a_checkout_exits_nonzero(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(SPEC))
    subprocess.run(["cp", "-r", str(ROOT / "bench"), str(tmp_path)],
                   check=True)
    proc = _run(tmp_path)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
