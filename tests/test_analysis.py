"""Static analysis (DESIGN.md §11): hazard cross-check, plan verifier,
operation linter — and the mutation faults proving each pass detects
exactly the bug class it claims to.

Structure:
  - hazard unit tests on hand-built task streams and fabricated DAGs
  - verifier green end-to-end: every drain entry point under verify mode
  - mutation tests: each ``plan.*`` fault site must be caught with the
    right invariant name, and must be SILENT with verification off
  - the ``_StackedAbort`` fallback blind-spot regression
  - linter unit tests on deliberately broken Operations + the registry gate
"""

import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Access, Dispatcher, DepTracker, GData, GTask, Operation
from repro.core import dd_matrix, spd_matrix
from repro.core.executors import clear_compile_cache
from repro.core.versioning import TaskDag
from repro.errors import LintError, ScheduleVerificationError
from repro.analysis import (
    LostParallelismWarning,
    analyze_hazards,
    clear_verified_cache,
    lint_operation,
    lint_or_raise,
    lint_registry,
    recompute_conflicts,
    verifier_stats,
    verify_plan,
    verify_stacked_members,
)
from repro.linalg import run_cholesky, run_lu, run_lu_solve
from repro.linalg.lu import run_inv, run_lu_batched, run_lu_many, utp_getrf
from repro.testing import faults


@pytest.fixture(autouse=True)
def _clean_slate():
    clear_compile_cache()
    clear_verified_cache()
    yield
    faults.reset()


class NopOp(Operation):
    name = "nop"

    def __init__(self, modes):
        self._modes = modes

    def default_modes(self, n):
        return self._modes


def mktask(data, accesses):
    """accesses: list of ((r, c), Access)."""
    views = [data(r, c) for (r, c), _ in accesses]
    modes = [m for _, m in accesses]
    return GTask(NopOp(modes), None, views, modes)


def _tracked(tasks):
    d = DepTracker()
    for t in tasks:
        d.add(t)
    return d.dag()


# -- hazard analysis unit tests ------------------------------------------------
def test_recompute_conflicts_kinds():
    A = GData((4, 4), partitions=((2, 2),))
    w1 = mktask(A, [((0, 0), Access.WRITE)])
    r1 = mktask(A, [((0, 0), Access.READ)])
    w2 = mktask(A, [((0, 0), Access.WRITE)])
    kinds = {
        (c.pred, c.succ): c.kind for c in recompute_conflicts([w1, r1, w2])
    }
    assert kinds[(w1.id, r1.id)] == "RAW"
    assert kinds[(r1.id, w2.id)] == "WAR"
    assert kinds[(w1.id, w2.id)] == "WAW"


def test_hazards_clean_on_tracker_dag():
    A = GData((8, 8), partitions=((2, 2),))
    tasks = [
        mktask(A, [((0, 0), Access.WRITE)]),
        mktask(A, [((0, 0), Access.READ), ((0, 1), Access.WRITE)]),
        mktask(A, [((0, 1), Access.READWRITE)]),
        mktask(A, [((1, 1), Access.WRITE)]),  # independent of the rest
    ]
    report = analyze_hazards(tasks, _tracked(tasks))
    assert report.ok and not report.spurious
    assert report.n_conflicts >= 2


def test_hazards_transitively_implied_edge_is_not_a_race():
    # w1 -> w2 -> w3 WAW chain: the tracker records only last-writer edges
    # (w1->w2, w2->w3); the recomputed conflict (w1, w3) must be accepted
    # through the PATH, not demand a direct edge.
    A = GData((4, 4), partitions=((2, 2),))
    tasks = [mktask(A, [((0, 0), Access.WRITE)]) for _ in range(3)]
    dag = _tracked(tasks)
    assert tasks[2].id not in dag.edges.get(tasks[0].id, set())
    assert analyze_hazards(tasks, dag).ok


def test_hazards_missing_edge_is_a_race():
    A = GData((4, 4), partitions=((2, 2),))
    w = mktask(A, [((0, 0), Access.WRITE)])
    r = mktask(A, [((0, 0), Access.READ)])
    dag = TaskDag({w.id: w, r.id: r}, {}, {})  # tracker "forgot" the edge
    with pytest.raises(ScheduleVerificationError) as ei:
        analyze_hazards([w, r], dag)
    assert ei.value.site == "hazards"
    assert ei.value.pair == (w.id, r.id)
    report = analyze_hazards([w, r], dag, raise_on_race=False)
    assert not report.ok and report.races[0].kind == "RAW"


def test_hazards_spurious_edge_warns_lost_parallelism():
    A = GData((4, 4), partitions=((2, 2),))
    t1 = mktask(A, [((0, 0), Access.WRITE)])
    t2 = mktask(A, [((1, 1), Access.WRITE)])  # disjoint: truly independent
    dag = TaskDag(
        {t1.id: t1, t2.id: t2},
        {t1.id: {t2.id}},
        {t2.id: {t1.id}},
    )
    with pytest.warns(LostParallelismWarning):
        report = analyze_hazards([t1, t2], dag)
    assert report.ok  # pessimal, not racy
    assert report.spurious == [(t1.id, t2.id)]


def test_stacked_member_alias_rejected():
    A = GData((4, 4), partitions=((2, 2),), value=jnp.zeros((4, 4)))
    B = GData((4, 4), partitions=((2, 2),), value=jnp.zeros((4, 4)))
    verify_stacked_members([[A, B]])
    with pytest.raises(ScheduleVerificationError) as ei:
        verify_stacked_members([[A, A]])
    assert ei.value.site == "verify_stacked.lane_alias"


# -- verifier green end-to-end -------------------------------------------------
def _drain_lu(d, n=64, seed=0):
    a = dd_matrix(n, seed=seed)
    A = GData(a.shape, partitions=((4, 4),), dtype=a.dtype, value=jnp.asarray(a))
    utp_getrf(d, A)
    d.run()
    return A


@pytest.mark.parametrize("graph", ["g1", "g2", "g2p"])
def test_verify_green_lu_all_graphs(graph):
    d = Dispatcher(graph=graph, verify=True)
    _drain_lu(d)
    assert d.stats["verified_scopes"] >= 1
    assert d.executor.verify
    if graph != "g1":  # g1 runs leaves eagerly: there is no plan to prove
        assert d.executor.stats["verified_plans"] >= 1


@pytest.mark.parametrize(
    "run",
    [
        lambda a: run_lu(a),
        lambda a: run_cholesky(spd_matrix(64, seed=0)),
        lambda a: run_lu_solve(a, np.asarray(dd_matrix(64, seed=9))[:, :32]),
        lambda a: run_inv(a),
    ],
    ids=["run_lu", "run_cholesky", "run_lu_solve", "run_inv"],
)
def test_verify_green_drains_env(run, monkeypatch):
    monkeypatch.setenv("REPRO_VERIFY", "1")
    before = verifier_stats()["verified"]
    run(dd_matrix(64, seed=3))
    assert verifier_stats()["verified"] > before


def test_verify_green_cross_root_fusion(monkeypatch):
    monkeypatch.setenv("REPRO_VERIFY", "1")
    mats = [dd_matrix(64, seed=s) for s in range(3)]
    for (L, U), a in zip(run_lu_many(mats), mats):
        np.testing.assert_allclose(L @ U, a, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("n_roots", [1, 4, 16])
def test_verify_green_stacked(n_roots, monkeypatch):
    monkeypatch.setenv("REPRO_VERIFY", "1")
    mats = [dd_matrix(32, seed=s) for s in range(n_roots)]
    for (L, U), a in zip(run_lu_batched(mats, partitions=((2, 2),)), mats):
        np.testing.assert_allclose(L @ U, a, rtol=2e-2, atol=2e-2)


def test_verify_env_off_by_default(monkeypatch):
    monkeypatch.delenv("REPRO_VERIFY", raising=False)
    assert not Dispatcher(graph="g2").verify
    # verification off adds no work: no scope or plan is proved
    d = Dispatcher(graph="g2")
    _drain_lu(d)
    assert d.stats["verified_scopes"] == 0
    assert d.executor.stats.get("verified_plans", 0) == 0
    monkeypatch.setenv("REPRO_VERIFY", "0")
    assert not Dispatcher(graph="g2").verify
    monkeypatch.setenv("REPRO_VERIFY", "1")
    assert Dispatcher(graph="g2").verify


def test_verdict_cache_absorbs_structural_repeats():
    d1 = Dispatcher(graph="g2", verify=True)
    _drain_lu(d1, seed=0)
    s1 = verifier_stats()
    assert s1["verified"] >= 1
    # same structure, fresh dispatcher, drain memo cleared: the plan is
    # re-planned but its verdict comes from the structural cache
    clear_compile_cache()
    d2 = Dispatcher(graph="g2", verify=True)
    _drain_lu(d2, seed=1)
    s2 = verifier_stats()
    assert s2["cache_hits"] > s1["cache_hits"]
    assert s2["verified"] == s1["verified"]


def test_replay_skips_verification_entirely():
    d = Dispatcher(graph="g2", verify=True)
    _drain_lu(d, seed=0)
    scopes = d.stats["verified_scopes"]
    stats = verifier_stats()
    ex = dict(d.executor.stats)
    _drain_lu(d, seed=1)  # memo replay
    assert d.stats["memo_hits"] == 1
    assert d.stats["verified_scopes"] == scopes
    assert verifier_stats() == stats
    # and the replay is pure: one launch, no compile, no plan re-proved
    assert d.executor.stats["launches"] == ex["launches"] + 1
    for k in ("compiles", "verified_plans"):
        assert d.executor.stats[k] == ex[k]


# -- mutation faults: the verifier detects what it claims to -------------------
def test_mutation_drop_edge_caught():
    d = Dispatcher(graph="g2", verify=True)
    with faults.inject("plan.drop_edge") as f:
        with pytest.raises(ScheduleVerificationError) as ei:
            _drain_lu(d)
    assert f.fired == 1
    assert ei.value.site == "hazards"
    assert "race" in str(ei.value)


def test_mutation_merge_groups_caught():
    d = Dispatcher(graph="g2", verify=True)
    with faults.inject("plan.merge_groups") as f:
        with pytest.raises(ScheduleVerificationError) as ei:
            _drain_lu(d)
    assert f.fired == 1
    assert ei.value.site == "verify_plan.group_independence"
    assert len(ei.value.pair) == 2


def test_mutation_alias_lane_caught(monkeypatch):
    monkeypatch.setenv("REPRO_VERIFY", "1")
    mats = [dd_matrix(32, seed=s) for s in range(4)]
    with faults.inject("plan.alias_lane") as f:
        with pytest.raises(ScheduleVerificationError) as ei:
            run_lu_batched(mats, partitions=((2, 2),))
    assert f.fired == 1
    assert ei.value.site == "verify_stacked.lane_alias"


def test_mutation_silent_without_verifier():
    """The mutations inject REAL silent bugs: with verification off the
    corrupted drains complete without any error — which is exactly why the
    verifier has to exist."""
    d = Dispatcher(graph="g2", verify=False)
    with faults.inject("plan.merge_groups") as f:
        A = _drain_lu(d)
    assert f.fired == 1
    assert A.has_value  # completed; numerics are garbage, nothing raised


# -- _StackedAbort fallback blind spot (regression) ----------------------------
def test_stacked_fallback_still_verifies(monkeypatch):
    """A value-dependent split aborts the stacked collect and re-drains
    through the normal path; the verify flag lives on the EXECUTOR, so the
    fallback's schedules are still proven (the pre-fix blind spot)."""
    d = Dispatcher(graph="g2", verify=True)
    mats = [dd_matrix(32, seed=s) for s in range(4)]
    roots = []
    with faults.inject("split.value_dependent"):
        for a in mats:
            A = GData(
                a.shape, partitions=((2, 2),), dtype=a.dtype,
                value=jnp.asarray(a),
            )
            utp_getrf(d, A)
            roots.append(A)
        d.run()
    assert d.stats["stacked_drains"] == 0  # the fallback really ran
    assert d.stats["verified_scopes"] >= 1
    assert d.executor.stats["verified_plans"] >= 1


def test_stacked_fallback_catches_corrupt_plan():
    d = Dispatcher(graph="g2", verify=True)
    with faults.inject("split.value_dependent"), faults.inject(
        "plan.merge_groups"
    ):
        for s in range(4):
            a = dd_matrix(32, seed=s)
            A = GData(
                a.shape, partitions=((2, 2),), dtype=a.dtype,
                value=jnp.asarray(a),
            )
            utp_getrf(d, A)
        with pytest.raises(ScheduleVerificationError) as ei:
            d.run()
    assert ei.value.site == "verify_plan.group_independence"


# -- serving: verification failures are non-retryable --------------------------
def test_serve_verification_failure_fails_fast(monkeypatch):
    from repro.serve import BatchServer

    monkeypatch.setenv("REPRO_VERIFY", "1")
    srv = BatchServer(graph="g2", max_retries=3)
    fut = srv.lu(dd_matrix(64, seed=0))
    with faults.inject("plan.merge_groups", times=None):
        srv.tick()
    assert fut.done
    with pytest.raises(ScheduleVerificationError):
        fut.result()
    # fail-fast: no retry budget burned on a deterministic failure
    assert srv.stats["retried"] == 0 and srv.stats["failed"] == 1


# -- operation linter ----------------------------------------------------------
def test_registry_lints_clean():
    import repro.linalg.ops  # noqa: F401 — populate

    assert lint_registry(execute=True) == []
    assert lint_or_raise() >= 10


class _ValueDependentSplitOp(Operation):
    name = "_lint_bad_split"

    def default_modes(self, n):
        return [Access.READWRITE] * n

    def split(self, task, submit):
        v = task.args[0]
        if v.data.value[0, 0] > 0:  # reads values in a memoizable split
            submit(GTask(self, task, [v]))


class _RngSplitOp(Operation):
    name = "_lint_rng_split"

    def split(self, task, submit):
        import random

        if random.random() > 0.5:
            submit(GTask(self, task, [task.args[0]]))


class _BadModesOp(Operation):
    name = "_lint_bad_modes"

    def default_modes(self, n):
        return [Access.READ] * (n + 1)  # arity mismatch

    def leaf_fn(self, backend):
        return lambda a, b: a + b


class _ReadOnlyOp(Operation):
    name = "_lint_read_only"

    def default_modes(self, n):
        return [Access.READ] * n  # no write arg: no output

    def leaf_fn(self, backend):
        return lambda a: a


class _WrongOutputCountOp(Operation):
    name = "_lint_wrong_out"

    def default_modes(self, n):
        return [Access.READWRITE, Access.READ]

    def leaf_fn(self, backend):
        return lambda a, b: (a, b)  # two outputs for one write arg


def test_lint_flags_value_dependent_split():
    issues = lint_operation(_ValueDependentSplitOp())
    assert any(i.check == "L1" and ".value" in i.detail for i in issues)
    # declaring the split value-dependent silences L1 (the contract is met)
    op = _ValueDependentSplitOp()
    op.memoizable = False
    assert not [i for i in lint_operation(op) if i.check == "L1"]


def test_lint_flags_rng_split():
    issues = lint_operation(_RngSplitOp())
    assert any(i.check == "L1" and "random" in i.detail for i in issues)


def test_lint_flags_mode_arity_mismatch():
    issues = lint_operation(_BadModesOp())
    assert any(i.check == "L2" for i in issues)


def test_lint_flags_all_read_op():
    issues = lint_operation(_ReadOnlyOp())
    assert any(i.check == "L2" and "no write-mode" in i.detail for i in issues)


def test_lint_flags_wrong_output_count():
    issues = lint_operation(_WrongOutputCountOp(), execute=True)
    assert any(i.check == "L3" and "returns 2" in i.detail for i in issues)


def test_lint_error_formatting():
    issues = lint_operation(_BadModesOp())
    err = LintError(issues)
    assert err.issues == issues
    assert "_lint_bad_modes" in str(err) and "[L2]" in str(err)


def test_lint_cli_runs_clean():
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, str(repo / "scripts" / "lint_ops.py"),
         "--no-execute"],
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ops lint OK" in out.stdout


# -- error type context --------------------------------------------------------
def test_schedule_verification_error_context():
    e = ScheduleVerificationError("verify_plan.slot_order", "bad", pair=(3, 7))
    assert e.site == "verify_plan.slot_order"
    assert e.pair == (3, 7)
    assert "[verify_plan.slot_order]" in str(e) and "3, 7" in str(e)
