"""The back-to-back factor cells at a tiny size: correct, and the check fails
the control and planted faults."""

import gc

import jax
import jax.numpy as jnp
import pytest

import repro.linalg
import repro.linalg.cholesky
from repro.core import data as core_data

from bench import control
from bench.tests import tiny


@pytest.mark.parametrize("cell", [tiny.FACTOR])
def test_factor_cell_runs_and_is_correct(cell):
    res = tiny.run(tiny.cell(cell))
    assert res["correct"], res
    name = "solution_s"
    assert res["metrics"][name]["value"] > 0 and res["metrics"]["setup_s"]["value"] > 0
    assert list(res)[-1] == "checks" and res["attempted"] >= 1


def test_factor_cell_traced_reports_its_per_layer_metrics():
    res = tiny.run(tiny.cell(tiny.FACTOR), trace=True)
    assert res["correct"], res
    # no TPU plane in a CPU trace: the device readers find nothing to read
    assert {"host_ms.factor", "mfu.factor", "compile_s"} <= set(res["metrics"])
    assert "idle_share.factor" not in res["metrics"]


def test_factor_driver_refuses_an_operation_it_does_not_run():
    from bench import harness
    from bench.drivers import repeat_solve

    c = tiny.cell(tiny.FACTOR, op="lu_solve")
    with pytest.raises(ValueError, match="runs cholesky"):
        repeat_solve.Driver(c.config, c.mix, 1, harness.Spans())


def test_window_leaves_no_grids_behind():
    # each entry call leaves its grids in reference cycles; the loop collects
    # them, so the window holds the pool, the kept answer and one call at most
    c = tiny.cell(tiny.FACTOR)
    c.mix.update(pool=2, check=1)
    res = tiny.run(c, seconds=1.0)
    assert res["correct"] and res["attempted"] >= 8, res
    from bench.drivers import repeat_solve
    from bench import harness

    drv = repeat_solve.Driver(c.config, c.mix, 5, harness.Spans())
    gc.collect()
    gc.freeze()  # as run_cell does: no automatic collection of set-up objects
    try:
        before = len(jax.live_arrays())
        out = drv.run(1.0)
    finally:
        gc.unfreeze()
    assert out["attempted"] >= 8
    assert len(jax.live_arrays()) <= before + 1 + 2  # the kept answer, and slack


@pytest.mark.parametrize("cell", [tiny.FACTOR])
def test_control_one_precision_down_fails_the_check(cell):
    c = tiny.cell(cell)
    c.config = control.controlled(c.config)
    res = tiny.run(c)
    assert not res["correct"], res


def _altered(fn):
    return lambda *a: fn(*a).at[-1, 0].add(1e-2)


@pytest.mark.parametrize("fault", ["altered", "unchanged"])
def test_planted_faults_fail_the_check(monkeypatch, fault):
    if fault == "altered":
        # the factor altered where it is produced: the de-grid of the result
        monkeypatch.setattr(repro.linalg.cholesky, "_tril_grid",
                            _altered(repro.linalg.cholesky._tril_grid))
        monkeypatch.setattr(core_data, "_from_grid_jit", _altered(core_data._from_grid_jit))
    else:
        # the entry returns its input unchanged (no factorization)
        monkeypatch.setattr(repro.linalg, "run_cholesky", lambda a, **_: jnp.tril(a))
    res = tiny.run(tiny.cell(tiny.FACTOR))
    assert not res["correct"], res
