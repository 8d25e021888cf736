"""UTP core: data versioning -> DAG edges -> wave schedule (paper §2.2).

Includes hypothesis property tests: for random task streams over a block
grid, the wave schedule must (a) contain every task exactly once, (b) never
reorder two tasks whose accesses conflict (RAW/WAR/WAW), and (c) equal the
sequential program order semantics when executed.
"""

import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core import Access, DepTracker, GData, GTask, Operation


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


def test_raw_dependency():
    A = GData((4, 4), partitions=((2, 2),))
    t1 = mktask(A, [((0, 0), Access.WRITE)])
    t2 = mktask(A, [((0, 0), Access.READ)])
    d = DepTracker()
    d.add(t1)
    d.add(t2)
    waves = d.waves()
    assert [len(w) for w in waves] == [1, 1]
    assert waves[0][0].id == t1.id


def test_independent_tasks_one_wave():
    A = GData((4, 4), partitions=((2, 2),))
    tasks = [mktask(A, [((i, j), Access.WRITE)]) for i in range(2) for j in range(2)]
    d = DepTracker()
    for t in tasks:
        d.add(t)
    assert [len(w) for w in d.waves()] == [4]


def test_war_and_waw():
    A = GData((4, 4), partitions=((2, 2),))
    r = mktask(A, [((1, 1), Access.READ)])
    w1 = mktask(A, [((1, 1), Access.WRITE)])
    w2 = mktask(A, [((1, 1), Access.WRITE)])
    d = DepTracker()
    d.add(r)
    d.add(w1)
    d.add(w2)
    waves = d.waves()
    order = {t.id: i for i, w in enumerate(waves) for t in w}
    assert order[r.id] < order[w1.id] < order[w2.id]


def test_readers_parallel_between_writes():
    A = GData((4, 4), partitions=((2, 2),))
    w1 = mktask(A, [((0, 1), Access.WRITE)])
    r1 = mktask(A, [((0, 1), Access.READ)])
    r2 = mktask(A, [((0, 1), Access.READ)])
    w2 = mktask(A, [((0, 1), Access.WRITE)])
    d = DepTracker()
    for t in (w1, r1, r2, w2):
        d.add(t)
    waves = d.waves()
    order = {t.id: i for i, w in enumerate(waves) for t in w}
    assert order[r1.id] == order[r2.id]  # readers run together
    assert order[w1.id] < order[r1.id] < order[w2.id]


# -- property tests -----------------------------------------------------------
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


def conflicts(a, b):
    for rc1, m1 in a:
        for rc2, m2 in b:
            if rc1 == rc2 and (m1.writes or m2.writes):
                return True
    return False


@settings(max_examples=60, deadline=None)
@given(task_stream())
def test_wave_schedule_respects_program_order(spec):
    grid, stream = spec
    A = GData((4 * grid, 4 * grid), partitions=((grid, grid),))
    tasks = [mktask(A, acc) for acc in stream]
    d = DepTracker()
    for t in tasks:
        d.add(t)
    waves = d.waves()
    flat = [t.id for w in waves for t in w]
    assert sorted(flat) == sorted(t.id for t in tasks)  # completeness
    order = {t.id: i for i, w in enumerate(waves) for t in w}
    for i, ti in enumerate(tasks):
        for j in range(i + 1, len(tasks)):
            tj = tasks[j]
            if conflicts(stream[i], stream[j]):
                assert order[ti.id] < order[tj.id], (
                    f"conflicting tasks reordered: {stream[i]} vs {stream[j]}"
                )


@settings(max_examples=30, deadline=None)
@given(task_stream())
def test_wave_execution_matches_sequential(spec):
    """Executing add-one tasks per wave == executing them sequentially."""
    grid, stream = spec
    # interpret each task as: out_blocks += 1 + sum(read blocks mean)
    def run(order_tasks, stream_by_id):
        M = np.zeros((grid, grid))
        for t, acc in order_tasks:
            reads = [M[rc] for rc, m in acc if m.reads]
            bump = 1.0 + float(np.sum(reads))
            for rc, m in acc:
                if m.writes:
                    M[rc] = M[rc] + bump
        return M

    A = GData((4 * grid, 4 * grid), partitions=((grid, grid),))
    tasks = [mktask(A, acc) for acc in stream]
    d = DepTracker()
    for t in tasks:
        d.add(t)
    waves = d.waves()
    seq = run(list(zip(tasks, stream)), None)
    by_id = {t.id: acc for t, acc in zip(tasks, stream)}
    wave_order = [(t, by_id[t.id]) for w in waves for t in w]
    par = run(wave_order, None)
    np.testing.assert_allclose(par, seq, rtol=1e-12)
