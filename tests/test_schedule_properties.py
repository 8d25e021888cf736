"""Hypothesis property tests for the dependency-exact scheduler.

On random task DAGs: the schedule is a valid topological order, every
fused group is internally edge-free and same-signature (fusion legality,
verified against ``TaskDag.independent``), groups sharing an issue slot
are mutually independent, and slot-launch semantics (gather all reads,
then scatter all writes) reproduce the sequential program order exactly.

Separate module from test_schedule_fusion so the property machinery stays
out of the deterministic tests' import path.
"""

import numpy as np

from repro.core import Access, DepTracker, GData
from repro.core.executors import plan_schedule

from test_schedule_fusion import _track, mktask

from hypothesis import given, settings, strategies as st


@st.composite
def task_stream(draw):
    n_tasks = draw(st.integers(1, 24))
    grid = draw(st.sampled_from([2, 3]))
    stream = []
    for _ in range(n_tasks):
        n_args = draw(st.integers(1, 3))
        accesses = []
        for _ in range(n_args):
            rc = (draw(st.integers(0, grid - 1)), draw(st.integers(0, grid - 1)))
            mode = draw(st.sampled_from(list(Access)))
            accesses.append((rc, mode))
        stream.append(accesses)
    return grid, stream


def _plan(grid, stream):
    A = GData(
        (4 * grid, 4 * grid),
        partitions=((grid, grid),),
        value=np.zeros((4 * grid, 4 * grid), dtype=np.float32),
    )
    tasks = [mktask(A, acc) for acc in stream]
    tr = _track(tasks)
    dag = tr.dag()
    plan = plan_schedule(tr.waves(), dag)
    assert plan is not None
    return tasks, dag, plan


def _plan_groups_as_task_sets(plan, tasks):
    """Partition plan.tasks back into (slot, group) structure by walking
    slot/group sizes in order (plan.tasks is built in that order)."""
    it = iter(plan.tasks)
    out = []
    for slot in plan.slots:
        row = []
        for g in slot:
            row.append([next(it) for _ in range(g.size)])
        out.append(row)
    return out


@settings(max_examples=60, deadline=None)
@given(task_stream())
def test_exact_schedule_properties(spec):
    grid, stream = spec
    tasks, dag, plan = _plan(grid, stream)
    assert sorted(t.id for t in plan.tasks) == sorted(t.id for t in tasks)
    groups = _plan_groups_as_task_sets(plan, tasks)
    slot_of = {
        t.id: si for si, row in enumerate(groups) for ts in row for t in ts
    }
    # (a) valid topological order: every edge crosses to a later slot
    for pred, succs in dag.edges.items():
        for succ in succs:
            assert slot_of[pred] < slot_of[succ]
    # (b) every fused group is edge-free internally (fusion legality), and
    #     all groups sharing a slot are mutually independent
    for row in groups:
        for ts in row:
            ids = [t.id for t in ts]
            assert dag.independent(ids, ids)
        for i in range(len(row)):
            for j in range(i + 1, len(row)):
                assert dag.independent(
                    [t.id for t in row[i]], [t.id for t in row[j]]
                )
    # (c) fused groups share one signature
    for row, slot in zip(groups, plan.slots):
        for ts, g in zip(row, slot):
            assert len({t.op.name for t in ts}) == 1
            assert all(
                tuple(i for i, m in enumerate(t.modes) if m.writes)
                == g.write_pos
                for t in ts
            )


@settings(max_examples=30, deadline=None)
@given(task_stream())
def test_slot_launch_semantics_match_sequential(spec):
    """Executing fused groups slot by slot with launch semantics (gather
    all reads, then scatter all writes) must equal sequential program
    order exactly — the numerics half of the fusion-legality argument."""
    grid, stream = spec
    tasks, dag, plan = _plan(grid, stream)
    by_id = {t.id: acc for t, acc in zip(tasks, stream)}

    def bump(M, acc):
        reads = [M[rc] for rc, m in acc if m.reads]
        return 1.0 + float(np.sum(reads))

    seq = np.zeros((grid, grid))
    for t in tasks:
        b = bump(seq, by_id[t.id])
        for rc, m in by_id[t.id]:
            if m.writes:
                seq[rc] = seq[rc] + b

    par = np.zeros((grid, grid))
    for row in _plan_groups_as_task_sets(plan, tasks):
        pre = par.copy()  # all reads in a slot see the pre-slot state
        writes = []
        for ts in row:
            for t in ts:
                b = bump(pre, by_id[t.id])
                for rc, m in by_id[t.id]:
                    if m.writes:
                        writes.append((rc, b))
        for rc, b in writes:
            par[rc] = par[rc] + b
    np.testing.assert_allclose(par, seq, rtol=1e-12)


