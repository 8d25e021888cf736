"""The back-to-back solve cell ``lusolve20k`` at a tiny size, and the prefix
rule that gives each LU kernel the tasks it runs."""

import pytest

import repro.linalg
from bench import control, flops, harness, kernel_tasks, program_trace as pt, readers, reference
from bench.tests import tiny
from bench.tests.test_bench_program_trace import MODULES, SPANS, _ctx, _read
from repro.core import tracing
from repro.core.executors import clear_compile_cache

CELL = "lusolve20k"
# at n = 64 in 16-wide tiles on the CPU the program read a reference gap of
# at most 0.0407 over six seeds, the reference in three bf16 passes at least
# 0.860: 0.2 passes the one with five times its largest reading and fails
# the other by four times
LIMIT = 0.2


def _cell(small: bool = True, **mix) -> harness.Cell:
    """The cell at ``tiny``'s cut (n = 64, 4 x 4 tiles), or with ``small`` at
    2 x 2 tiles of 32, which compiles four times faster."""
    c = tiny.cell(CELL, **mix)
    c.config["limits"] = {"ref_gap.lu_solve": LIMIT}
    if small:
        c.config.update(tile=32, partitions=[[2, 2]])
    return c


def test_lusolve_cell_runs_and_is_correct():
    res = tiny.run(_cell(small=False))
    assert res["correct"], res
    assert res["metrics"]["solution_s"]["value"] > 0 and res["metrics"]["setup_s"]["value"] > 0
    assert res["checks"]["ref_gap.lu_solve"]["value"] <= LIMIT
    assert res["attempted"] >= 1


def test_lusolve_cell_traced_reports_its_host_side_readers():
    tracing.clear()
    clear_compile_cache()
    try:
        res = tiny.run(_cell(), trace=True)
    finally:
        tracing.clear()
    assert res["correct"], res
    got = res["metrics"]
    assert {"host_ms.lusolve", "mfu.lusolve", "compile_s", "setup_lower_s.lusolve"} <= set(got)
    # 16-wide tiles are thinner than the chip's (8, 128) layout tile: every
    # fused group of the 2 x 2 drain reads by BlockSpec
    assert got["blockspec_groups.lusolve"]["value"] == 11
    # no TPU plane in a CPU trace: the device readers find nothing to read
    assert not {"idle_share.lusolve", "roofline.gemmnn", "grid_copy_share.lusolve"} & set(got)


def test_lusolve_driver_refuses_an_operation_it_does_not_run():
    from bench.drivers import repeat_lu_solve

    c = _cell(op="cholesky")
    with pytest.raises(ValueError, match="runs lu_solve"):
        repeat_lu_solve.Driver(c.config, c.mix, 1, harness.Spans())


def _high(a, b, graph=None, partitions=((4, 4),), **_):
    return reference.lu_solve(a, b, block=a.shape[0] // partitions[-1][0], precision="high")


@pytest.mark.parametrize("fault", ["control", "altered", "unsolved"])
def test_planted_faults_fail_the_check(monkeypatch, fault):
    c = _cell()
    if fault == "control":
        # the reference one precision down in the program's place
        monkeypatch.setattr(control, "lu_solve_high", _high, raising=False)
        c.config = control.controlled(c.config)
    elif fault == "altered":
        solve = repro.linalg.run_lu_solve
        monkeypatch.setattr(repro.linalg, "run_lu_solve",
                            lambda a, b, **kw: solve(a, b, **kw).at[-1].add(1e-3))
    else:
        monkeypatch.setattr(repro.linalg, "run_lu_solve", lambda a, b, **_: b)
    res = tiny.run(c)
    assert not res["correct"], res


def test_prefix_rule_gives_each_kernel_the_tasks_it_runs():
    rows = flops.lu_solve_tasks(20480, 512, 1)
    names = lambda k: [t[0] for t in kernel_tasks.kernel_rows(rows, k)]  # noqa: E731
    assert names("trsmul") == ["trsmul.rhs"]
    assert names("trsml") == ["trsml", "trsml.rhs"]
    assert names("trsmu") == ["trsmu"]
    assert names("getrf") == ["getrf"]
    assert names("gemmnn") == ["gemmnn", "gemmnn.rhs_forward", "gemmnn.rhs_backward"]
    # the kernels' ideal times add up to the whole task list's
    peak, bw = tiny.PEAKS["bf16_flops"], tiny.PEAKS["hbm_bytes_per_s"]
    kernels = {kernel_tasks.kernel_of(t[0]) for t in rows}
    parts = sum(flops.roofline_s(kernel_tasks.kernel_rows(rows, k), peak, bw)[0] for k in kernels)
    assert parts == pytest.approx(flops.roofline_s(rows, peak, bw)[0])
    # on a Cholesky task list every row is named after its kernel alone
    chol = flops.cholesky_tasks(16384, 512)
    for k in ("potrf", "trsm", "syrk", "gemm"):
        assert kernel_tasks.kernel_rows(chol, k) == [t for t in chol if t[0] == k]


def test_prefix_rule_agrees_with_kernel_roofline_on_cholesky(monkeypatch):
    ctx = _ctx(monkeypatch, solutions=16)
    for k in ("potrf", "trsm", "syrk", "gemm"):
        assert kernel_tasks.kernel_roofline(ctx, k) == pt.kernel_roofline(ctx, k)


# one LU-solve call's kernels on the device (ns), named as the chip names them
LU_OPS = [
    (0, "getrf.1 custom-call", 930_000, 1_130_000, True, ()),
    (0, "trsml.2 custom-call", 1_130_000, 1_630_000, True, ()),
    (0, "trsmu.3 custom-call", 1_630_000, 2_130_000, True, ()),
    (0, "gemmnn.4 custom-call", 2_130_000, 3_130_000, True, ()),
    (0, "trsml.5 custom-call", 3_130_000, 3_230_000, True, ()),
    (0, "gemmnn.6 custom-call", 3_230_000, 3_330_000, True, ()),
    (0, "trsmul.7 custom-call", 3_330_000, 3_430_000, True, ()),
    (0, "gemmnn.8 custom-call", 3_430_000, 3_530_000, True, ()),
]


def test_lu_kernel_rooflines_combine_into_pallas_roofline(monkeypatch):
    ctx = _ctx(monkeypatch, solutions=4)
    monkeypatch.setattr(pt, "load", lambda _: (LU_OPS, MODULES, SPANS, []))
    ctx.config, ctx.mix = {"n": 2048, "tile": 512}, {"op": "lu_solve", "nrhs": 1}
    kernel_s = pt.summary(ctx)["trace"]["kernel_s"]
    ctx.trace = {"pallas_s": sum(kernel_s.values())}
    shares = {k: _read(f"roofline.{k}", ctx) for k in ("getrf", "trsml", "trsmu", "gemmnn")}
    shares["trsmul"] = kernel_tasks.kernel_roofline(ctx, "trsmul")
    rows = flops.lu_solve_tasks(2048, 512, 1)
    ideal = {k: flops.roofline_s(kernel_tasks.kernel_rows(rows, k), 197e12, 819e9)[0]
             for k in shares}
    for k, v in shares.items():
        assert v == pytest.approx(100 * 4 * ideal[k] / kernel_s[k])
    # each kernel's ideal time over the device time of all five
    combined = sum(ideal.values()) / sum(ideal[k] / shares[k] for k in shares)
    assert combined == pytest.approx(readers.pallas_roofline(ctx))
    assert combined == pytest.approx(_read("pallas_roofline.lusolve", ctx))
