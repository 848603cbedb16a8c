"""The comparison that decides ``correct`` fails the control and every
fault the cells can have: a whole run on the CPU, at a tiny size, with the
timed path broken underneath (the device check is skipped)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import faults, harness
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
SOLVE_CELLS = ["convdiff-512-nonblocking", "convdiff-512-blocking"]


def _pieces(cell):
    w = harness.find_cell(tiny.SPEC, cell)
    return (harness.load_config(tiny.SPEC, w["config"]),
            harness.load_traffic(w["traffic"]))


@pytest.mark.parametrize("fault", ["control", "unchanged", "half",
                                   "altered"])
@pytest.mark.parametrize("cell", SOLVE_CELLS)
def test_solve_faults_come_out_incorrect(cell, fault):
    config, traffic = _pieces(cell)
    config = dict(config, **tiny.SOLVE)
    with faults.planted(fault, config, traffic):
        res = harness.execute(cell, 2 ** 31 + 31, 0.2, False,
                              spec=tiny.SPEC, config_overrides=tiny.SOLVE)
    assert res["correct"] is False, res["checks"]


def test_sound_solve_stays_correct_after_a_fault():
    config, traffic = _pieces(SOLVE_CELLS[0])
    with faults.planted("altered", dict(config, **tiny.SOLVE), traffic):
        pass
    res = harness.execute(SOLVE_CELLS[0], 2 ** 31 + 37, 0.2, False,
                          spec=tiny.SPEC, config_overrides=tiny.SOLVE)
    assert res["correct"] is True, res["checks"]


@pytest.mark.parametrize("fault", ["control", "unchanged", "half",
                                   "altered"])
def test_service_faults_come_out_incorrect(fault):
    config, traffic = _pieces("serve-mix-poisson")
    over_c, over_t = tiny.service_overrides()
    config.update(over_c)
    traffic.update(over_t)
    with faults.planted(fault, config, traffic):
        res = harness.execute("serve-mix-poisson", 2 ** 31 + 41, 1.0, False,
                              spec=tiny.SPEC, config_overrides=over_c,
                              traffic_overrides=dict(over_t, drain_s=20.0))
    assert res["correct"] is False, res["checks"]


def test_service_has_no_exchange_to_break():
    config, traffic = _pieces("serve-mix-poisson")
    with pytest.raises(ValueError):
        with faults.planted("no_exchange", config, traffic):
            pass


_FOUR = r"""
import sys
sys.path[:0] = [{src!r}, {root!r}]
from bench import faults, harness
from bench.tests import tiny
spec = tiny.SPEC
cell = "convdiff-1024-2x2-nonblocking"
w = harness.find_cell(spec, cell)
config = dict(harness.load_config(spec, w["config"]), n=16)
traffic = harness.load_traffic(w["traffic"])
out = {{}}
res = harness.execute(cell, 2 ** 31 + 43, 0.2, False, spec=spec,
                      config_overrides={{"n": 16}})
out["sound"] = res["correct"]
for fault in ("control", "unchanged", "half", "no_exchange", "altered"):
    with faults.planted(fault, config, traffic):
        res = harness.execute(cell, 2 ** 31 + 43, 0.2, False, spec=spec,
                              config_overrides={{"n": 16}})
    out[fault] = res["correct"]
print(out)
"""


def test_four_chip_cell_faults_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _FOUR.format(src=str(ROOT / "src"), root=str(ROOT))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = eval(proc.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "control": False, "unchanged": False,
                   "half": False, "no_exchange": False, "altered": False}
