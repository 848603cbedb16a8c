"""The solve and service drivers end to end at a tiny size on the CPU,
through the function the command calls after its device check; and the
plain references against the program at sizes where both are exact."""
import numpy as np
import pytest

from bench import harness
from bench.reference import convdiff as ref
from bench.reference import service as sref
from bench.tests import tiny


def _sound(result):
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["window_compiles"] == 0
    assert list(result)[-1] == "checks"
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("cell", ["convdiff-512-nonblocking",
                                  "convdiff-512-blocking"])
def test_solve_cell_runs_and_certifies(cell):
    res = harness.execute(cell, 2 ** 31 + 17, 0.5, False, spec=tiny.SPEC,
                          config_overrides=tiny.SOLVE)
    _sound(res)
    assert set(res["metrics"]) == {"solve_s", "setup_s"}
    assert res["metrics"]["solve_s"]["value"] > 0
    assert res["device"]["count"] >= 1


def test_solve_cell_traced_reports_its_layers():
    res = harness.execute("convdiff-512-nonblocking", 2 ** 31 + 19, 0.5,
                          True, spec=tiny.SPEC, config_overrides=tiny.SOLVE)
    _sound(res)
    # the CPU trace has no device plane: the device readers stay silent
    assert set(res["metrics"]) == {"step_ms.solve", "outer_iters.solve"}
    assert res["metrics"]["outer_iters.solve"]["value"] > 0
    assert {"busy_s", "window_s"} <= set(res["device"])


def test_service_cell_runs_and_certifies():
    config, traffic = tiny.service_overrides()
    res = harness.execute("serve-mix-poisson", 2 ** 31 + 23, 2.0, False,
                          spec=tiny.SPEC, config_overrides=config,
                          traffic_overrides=traffic)
    _sound(res)
    assert set(res["metrics"]) == {"ttd_p95_s", "ttd_p50_s", "setup_s"}
    assert (res["metrics"]["ttd_p95_s"]["value"]
            >= res["metrics"]["ttd_p50_s"]["value"] > 0)
    assert res["attempted"] == round(traffic["rate"] * 2.0)


def test_residual_reference_matches_a_direct_evaluation():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((12, 10, 9)).astype(np.float32)
    b = rng.standard_normal((12, 10, 9)).astype(np.float32)
    c = ref.coefficients(12, 1.0, (1.0, 0.5, 2.0), 0.9)
    want = float(np.max(np.abs(b.astype(np.float64) - ref.apply_a(x, c))))
    for slab in (1, 5, 12):
        assert ref.residual_inf(x, b, c, slab=slab, threads=3) == want


def test_stencil_matches_the_program():
    from repro.solvers.convdiff import Stencil

    st = Stencil.for_contraction(64, 1.0, (1.0, 1.0, 1.0), rho=0.9)
    mine = ref.coefficients(64, 1.0, (1.0, 1.0, 1.0), 0.9)
    theirs = (st.diag, st.xm, st.xp, st.ym, st.yp, st.zm, st.zp)
    assert mine == pytest.approx(theirs, rel=1e-13)


@pytest.mark.parametrize("family,kw", [
    ("convdiff", {"n": 8, "p": 4, "rho": 0.9}),
    ("pagerank", {"n": 64, "p": 4}),
    ("mlfixed", {"n": 16, "p": 4, "m_rows": 48, "cond": 10.0}),
])
def test_service_reference_follows_the_tenant_iteration(family, kw):
    """The reference's residual series follows the program's float64
    contribution series for the same tenant (the program's own step in
    float64 is the witness; the reference rounds its constants to float32,
    as the chip does, hence the tolerance)."""
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import make_serve_problem

    fam = tiny.service_overrides()[0]["families"][family]
    seed = 12345
    args = dict(fam["defaults"], **kw)
    x, step, residual = sref.FAMILIES[family](args, seed)
    prob = make_serve_problem(family, seed=seed, **kw)
    ops = {k: np.asarray(np.asarray(v, np.float32), np.float64)[None]
           for k, v in prob.lane_operands().items()}
    with jax.enable_x64(True):
        X = jnp.asarray(np.asarray(prob.lane_x0(), np.float64)[None])
        for k in range(25):
            X_next, contrib = prob.update_with_residual_batched(
                X, **{n: jnp.asarray(v) for n, v in ops.items()})
            c = float(contrib[0])
            c = c if family == "convdiff" else (
                c if family == "pagerank" else np.sqrt(c))
            assert residual(x) == pytest.approx(c, rel=1e-6, abs=1e-7), k
            x, X = step(x), X_next
