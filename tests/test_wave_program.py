"""Grid-resident epoch + WaveProgram compiler (DESIGN.md §2).

Covers: GData grid epoch coherence, whole-schedule compilation (one
compiled program per structural schedule, reused across drains), numerical
parity of the grid-resident path against the sequential InlineExecutor
reference across g1/g2/g2p/g3, and the power-of-two bucket padding of the
per-group fallback path (wave sizes 1..9, O(log n) distinct compiles,
duplicate-last-task scatter idempotence).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core import Access, Dispatcher, GData, GTask, Operation, spd_matrix
from repro.core.data import from_grid, to_grid
from repro.core.executors import (
    JitWaveExecutor,
    PallasExecutor,
    clear_compile_cache,
    plan_schedule,
)
from repro.linalg import run_cholesky


def _mesh_1d():
    return make_mesh((1, 1), ("data", "model"))


# --------------------------------------------------------------------------
# GData grid-resident epoch
# --------------------------------------------------------------------------
class TestGridEpoch:
    def test_enter_exit_roundtrip(self):
        a = np.arange(64, dtype=np.float32).reshape(8, 8)
        d = GData((8, 8), partitions=((2, 2),), value=a)
        g = d.enter_grid(4, 4)
        assert d.in_grid_epoch and d.grid_block == (4, 4)
        assert g.shape == (2, 2, 4, 4)
        np.testing.assert_array_equal(np.asarray(g[1, 0]), a[4:, :4])
        # reading .value de-grids lazily and ends the epoch
        np.testing.assert_array_equal(np.asarray(d.value), a)
        assert not d.in_grid_epoch

    def test_reenter_same_block_is_resident(self):
        d = GData((8, 8), value=np.eye(8, dtype=np.float32))
        g1 = d.enter_grid(4, 4)
        g2 = d.enter_grid(4, 4)
        assert g1 is g2  # no layout traffic on re-entry

    def test_set_grid_then_value_reads_through(self):
        a = np.zeros((8, 8), dtype=np.float32)
        d = GData((8, 8), value=a)
        d.enter_grid(4, 4)
        g = jnp.asarray(np.arange(64, dtype=np.float32).reshape(2, 2, 4, 4))
        d.set_grid(g)
        np.testing.assert_array_equal(np.asarray(d.value), np.asarray(from_grid(g)))

    def test_value_write_invalidates_grid(self):
        d = GData((8, 8), value=np.eye(8, dtype=np.float32))
        d.enter_grid(4, 4)
        d.value = jnp.zeros((8, 8))
        assert not d.in_grid_epoch
        np.testing.assert_array_equal(np.asarray(d.value), np.zeros((8, 8)))

    def test_different_block_flushes_through_root(self):
        a = np.arange(64, dtype=np.float32).reshape(8, 8)
        d = GData((8, 8), value=a)
        d.enter_grid(4, 4)
        g = d.enter_grid(2, 2)
        assert d.grid_block == (2, 2)
        np.testing.assert_array_equal(np.asarray(from_grid(g)), a)

    def test_grid_layout_helpers_inverse(self):
        a = jnp.asarray(np.random.default_rng(0).standard_normal((12, 8)))
        np.testing.assert_array_equal(
            np.asarray(from_grid(to_grid(a, 4, 2))), np.asarray(a)
        )


# --------------------------------------------------------------------------
# WaveProgram: one compiled program per structural schedule
# --------------------------------------------------------------------------
def _drain_cholesky(graph, a, parts):
    d = Dispatcher(graph=graph)
    A = GData(a.shape, partitions=parts, dtype=a.dtype, value=a)
    from repro.linalg.cholesky import utp_cholesky

    utp_cholesky(d, A)
    n = d.run()
    return d, A, n


@pytest.mark.parametrize("graph", ["g2", "g2p"])
def test_one_program_per_drain_and_cache_reuse(graph):
    clear_compile_cache()
    a = spd_matrix(64, seed=13)
    d1, A1, n1 = _drain_cholesky(graph, a, ((4, 4),))
    assert n1 == 20
    assert d1.executor.stats["launches"] == 1  # whole schedule = one dispatch
    assert d1.executor.stats["compiles"] == 1  # one compiled program
    assert A1.in_grid_epoch  # root stayed grid-resident
    # repeated drain with the same schedule structure: zero new compiles
    d2, A2, _ = _drain_cholesky(graph, a, ((4, 4),))
    assert d2.executor.stats["launches"] == 1
    assert d2.executor.stats.get("compiles", 0) == 0
    np.testing.assert_allclose(
        np.asarray(A1.value), np.asarray(A2.value), rtol=1e-6
    )


def test_plan_schedule_falls_back_on_nonuniform_blocks():
    class W(Operation):
        name = "w_nonuniform"

        def default_modes(self, n):
            return [Access.READWRITE]

    A = GData((8, 8), partitions=((2, 2), (2, 2)), value=np.eye(8, dtype=np.float32))
    t_big = GTask(W(), None, [A(0, 0)])  # level-0 block (4x4)
    t_small = GTask(W(), None, [A(1, 1)(0, 0)])  # level-1 tile (2x2)
    assert plan_schedule([[t_big], [t_small]]) is None


def test_plan_schedule_requires_value():
    class W(Operation):
        name = "w_novalue"

        def default_modes(self, n):
            return [Access.READWRITE]

    A = GData((8, 8), partitions=((2, 2),))  # no value materialized
    assert plan_schedule([[GTask(W(), None, [A(0, 0)])]]) is None


# --------------------------------------------------------------------------
# Drain memo: structurally repeated drains replay without re-splitting
# --------------------------------------------------------------------------
def test_drain_memo_is_value_independent():
    """The memo keys on structure; fresh GData with different *values* must
    replay the captured programs and still be numerically exact."""
    clear_compile_cache()
    a1 = spd_matrix(64, seed=21)
    a2 = spd_matrix(64, seed=22)
    L1 = run_cholesky(a1, graph="g2", partitions=((4, 4),))
    L2 = run_cholesky(a2, graph="g2", partitions=((4, 4),))  # replayed drain
    np.testing.assert_allclose(
        np.asarray(L1), np.asarray(jnp.linalg.cholesky(a1)), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(L2), np.asarray(jnp.linalg.cholesky(a2)), rtol=2e-4, atol=2e-4
    )


def test_drain_memo_replay_preserves_stats_and_count():
    clear_compile_cache()
    a = spd_matrix(32, seed=5)

    def drain():
        d = Dispatcher(graph="g2")
        A = GData(a.shape, partitions=((4, 4),), dtype=a.dtype, value=a)
        from repro.linalg.cholesky import utp_cholesky

        task = utp_cholesky(d, A)
        n = d.run()
        return d, task, n

    d1, t1, n1 = drain()  # capture
    d2, t2, n2 = drain()  # replay
    assert n1 == n2 == 20
    assert d1.stats["split"] == d2.stats["split"] == 1
    assert d1.stats["waves"] == d2.stats["waves"]
    assert t2.state.name == "FINISHED"
    assert d2.executor.stats["launches"] == 1
    assert d2.executor.stats.get("compiles", 0) == 0


def test_memoize_drains_opt_out():
    clear_compile_cache()
    a = spd_matrix(32, seed=6)
    outs = []
    for _ in range(2):
        d = Dispatcher(graph="g2", memoize_drains=False)
        A = GData(a.shape, partitions=((4, 4),), dtype=a.dtype, value=a)
        from repro.linalg.cholesky import utp_cholesky

        utp_cholesky(d, A)
        n = d.run()
        assert d.stats["split"] == 1  # really re-split, not replayed
        assert n == 20
        outs.append(np.asarray(A.value))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-6)


def test_flat_idxs_built_at_plan_time_and_replay_reuses_device_array():
    """The concatenated block-index array is constructed ONCE at plan time
    (a SchedulePlan field, not a per-execution host concatenation) and the
    drain memo's ProgramRecord carries that device array, so replays reuse
    it without any host work or transfer."""
    from repro.core.executors.jit_wave import _DRAIN_MEMO

    clear_compile_cache()
    a = spd_matrix(32, seed=9)
    _drain_cholesky("g2", a, ((4, 4),))  # capture
    assert len(_DRAIN_MEMO) == 1
    (memo,) = _DRAIN_MEMO.values()
    (rec,) = memo["records"]
    assert isinstance(rec.idxs, jnp.ndarray) and rec.idxs.shape[1] == 2
    before = id(rec.idxs)
    _drain_cholesky("g2", a, ((4, 4),))  # replay
    (memo2,) = _DRAIN_MEMO.values()
    (rec2,) = memo2["records"]
    assert id(rec2.idxs) == before  # device-resident array reused as-is
    # plan-time construction: SchedulePlan.flat_idxs is data, not a method
    from repro.core import DepTracker, GData as GD
    from repro.linalg.ops import SYRK

    A = GD((8, 8), partitions=((2, 2),), value=np.eye(8, dtype=np.float32))
    tasks = [GTask(SYRK, None, [A(i, i), A(1 - i, 1 - i)]) for i in range(1)]
    tr = DepTracker()
    for t in tasks:
        tr.add(t)
    plan = plan_schedule(tr.waves(), tr.dag())
    assert isinstance(plan.flat_idxs, jnp.ndarray)


# --------------------------------------------------------------------------
# Numerical parity: grid-resident path vs sequential InlineExecutor (g1)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("graph", ["g2", "g2p", "g3"])
@pytest.mark.parametrize("n", [32, 64])
def test_grid_resident_matches_inline_reference(graph, n):
    a = spd_matrix(n, seed=n + 1)
    ref = run_cholesky(a, graph="g1", partitions=((4, 4),))
    if graph == "g3":
        got = run_cholesky(
            a, graph=graph, partitions=((2, 2), (2, 2)), mesh=_mesh_1d()
        )
    else:
        got = run_cholesky(a, graph=graph, partitions=((4, 4),))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


# --------------------------------------------------------------------------
# Power-of-two bucket padding in the per-group fallback path (_run_group)
# --------------------------------------------------------------------------
class AddBiasOp(Operation):
    """WRITE-mode op: out block <- bias (constant), ignores prior contents."""

    name = "add_bias_w"

    def default_modes(self, n):
        return [Access.READ, Access.WRITE]

    def leaf_fn(self, backend):
        return lambda src, dst: src + 1.0


class BumpOp(Operation):
    """READWRITE op: block <- block + 1 (gather-before-scatter sensitivity)."""

    name = "bump_rw"

    def default_modes(self, n):
        return [Access.READWRITE]

    def leaf_fn(self, backend):
        return lambda b: b + 1.0


def _grid_data(p, b=4):
    val = np.zeros((p * b, p * b), dtype=np.float32)
    return GData((p * b, p * b), partitions=((p, p),), value=val)


@pytest.mark.parametrize("size", range(1, 10))
def test_bucket_padding_correct_for_all_wave_sizes(size):
    """Wave sizes 1..9 through the padded fallback path all scatter exactly
    once per distinct block — the duplicated last task is idempotent."""
    ex = JitWaveExecutor()
    p = 3  # 9 blocks
    A = _grid_data(p)
    tasks = [
        GTask(BumpOp(), None, [A(i // p, i % p)]) for i in range(size)
    ]
    ex._run_group(tasks)
    got = np.asarray(A.value)
    exp = np.zeros_like(got)
    for i in range(size):
        r, c = i // p, i % p
        exp[r * 4 : r * 4 + 4, c * 4 : c * 4 + 4] = 1.0
    np.testing.assert_array_equal(got, exp)


def test_bucket_padding_idempotent_for_write_mode_op():
    ex = JitWaveExecutor()
    p = 2
    A = _grid_data(p)
    B = _grid_data(p)
    # 3 tasks -> bucket 4 -> last task duplicated once in the batch
    tasks = [
        GTask(AddBiasOp(), None, [A(i // p, i % p), B(i // p, i % p)])
        for i in range(3)
    ]
    ex._run_group(tasks)
    got = np.asarray(B.value)
    exp = np.zeros_like(got)
    exp[:4, :] = 1.0  # blocks (0,0), (0,1)
    exp[4:, :4] = 1.0  # block (1,0)
    np.testing.assert_array_equal(got, exp)


def test_bucket_padding_compiles_olog_n():
    """Sizes 1..9 bucket to {1, 2, 4, 8, 16}: at most 5 distinct compiles."""
    clear_compile_cache()
    op = BumpOp()
    compiles = []
    for size in range(1, 10):
        ex = JitWaveExecutor()
        A = _grid_data(4)  # 16 blocks >= max size
        tasks = [GTask(op, None, [A(i // 4, i % 4)]) for i in range(size)]
        ex._run_group(tasks)
        compiles.append(ex.stats.get("compiles", 0))
    assert sum(compiles) <= 5, compiles


# --------------------------------------------------------------------------
# Exact (unpadded) group sizes through the WaveProgram path, incl. fused
# pallas groups, across wave sizes 1..9
# --------------------------------------------------------------------------
@pytest.mark.parametrize("cls", [JitWaveExecutor, PallasExecutor])
@pytest.mark.parametrize("size", [1, 2, 5, 9])
def test_program_path_wave_sizes(cls, size):
    from repro.linalg.ops import SYRK

    p = 3
    rng = np.random.default_rng(size)
    base = rng.standard_normal((4 * p, 4 * p)).astype(np.float32)
    A = GData((4 * p, 4 * p), partitions=((p, p),), value=base)
    C = GData((4 * p, 4 * p), partitions=((p, p),), value=np.array(base))
    tasks = [
        GTask(SYRK, None, [A(i // p, i % p), C(i // p, i % p)])
        for i in range(size)
    ]
    ex = cls()
    n = ex.execute_waves([tasks])
    assert n == size
    got = np.asarray(C.value)
    exp = np.array(base)
    for i in range(size):
        r, c = i // p, i % p
        blk_a = base[r * 4 : r * 4 + 4, c * 4 : c * 4 + 4]
        exp[r * 4 : r * 4 + 4, c * 4 : c * 4 + 4] = (
            exp[r * 4 : r * 4 + 4, c * 4 : c * 4 + 4] - blk_a @ blk_a.T
        )
    np.testing.assert_allclose(got, exp, rtol=1e-5, atol=1e-5)
