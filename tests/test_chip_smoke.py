"""chip_smoke.py: refuses to run without a TPU, and its phases' checks hold
on the CPU at tiny sizes (the rehearsal of the chip run)."""
import importlib.util
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu(smoke, monkeypatch, capsys, tmp_path):
    # a set cache directory keeps the helper from configuring this process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert smoke.main([]) == 1
    out = capsys.readouterr()
    assert "no TPU found" in out.err
    assert '"ok"' not in out.out


TINY_SERVICE_MIX = (
    ("convdiff", {"n": 8, "p": 4, "rho": 0.9}, (1e-3, 1e-4)),
    ("pagerank", {"n": 128, "p": 4}, (1e-4, 1e-5)),
    ("mlfixed", {"n": 16, "p": 4, "m_rows": 48, "cond": 10.0}, (1e-3, 1e-4)),
)


@pytest.mark.parametrize("phase", ["convdiff", "pagerank", "service"])
def test_phase_checks_hold_at_tiny_size(smoke, phase):
    if phase == "convdiff":
        smoke.phase_convdiff(n=8, max_outer=2000)
    elif phase == "pagerank":
        smoke.phase_pagerank(n=256)
    else:
        smoke.phase_service(tenants=12, mix=TINY_SERVICE_MIX)


def test_failed_check_raises(smoke):
    with pytest.raises(smoke.SmokeFailure, match="boom"):
        smoke.require(False, "boom")
