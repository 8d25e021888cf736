"""Fault injection (DESIGN.md §10): registry semantics and the recovery
invariants at every named site.

Each site test asserts the post-failure guarantee the failure model
promises — a failed drain leaves no half-captured memo entry, the executor
and dispatcher stay reusable, corruption is detectable via ``check_finite``,
and the stacked path's value-dependent-split fallback produces the same
numerics as the healthy stacked drain.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import Dispatcher, GData, GTask, dd_matrix
from repro.core.executors import clear_compile_cache, drain_memo_stats
from repro.core.operation import OpRegistry
from repro.errors import NumericalError
from repro.linalg import run_lu
from repro.linalg.lu import _unpack
from repro.serve import BatchServer
from repro.testing import faults


@pytest.fixture(autouse=True)
def _no_leaked_faults():
    yield
    faults.reset()


# -- registry semantics --------------------------------------------------------
def test_unknown_site_rejected():
    with pytest.raises(ValueError, match="unknown fault site"):
        with faults.inject("no.such.site", RuntimeError("x")):
            pass
    with pytest.raises(ValueError, match="probability"):
        faults.Fault("leaf.fn", p=1.5)


def test_arming_scoped_to_context():
    assert not faults.active()
    with faults.inject("executor.launch", RuntimeError("boom")):
        assert faults.active()
        with pytest.raises(RuntimeError, match="boom"):
            faults.fire("executor.launch")
    assert not faults.active()
    faults.fire("executor.launch")  # disarmed: no-op


def test_times_after_and_when():
    with faults.inject(
        "executor.launch",
        RuntimeError("boom"),
        when=lambda ctx: ctx.get("batch", 0) > 1,
        after=1,
        times=1,
    ) as f:
        faults.fire("executor.launch", batch=0)  # when=False: not a match
        faults.fire("executor.launch", batch=4)  # match 1 skipped by after
        with pytest.raises(RuntimeError):
            faults.fire("executor.launch", batch=4)  # fires
        faults.fire("executor.launch", batch=4)  # times budget spent
        assert f.matches == 3 and f.fired == 1


def test_delay_injection_sleeps_at_site():
    import time

    with faults.inject("drain.stall", delay_s=0.05) as f:
        t0 = time.perf_counter()
        faults.fire("drain.stall")  # delay-only: sleeps, does NOT raise
        assert time.perf_counter() - t0 >= 0.05
        assert f.fired == 1
    # delay composes with an exception: sleep first, then raise
    with faults.inject("drain.stall", RuntimeError("late"), delay_s=0.01):
        t0 = time.perf_counter()
        with pytest.raises(RuntimeError, match="late"):
            faults.fire("drain.stall")
        assert time.perf_counter() - t0 >= 0.01
    with pytest.raises(ValueError, match="delay_s"):
        faults.Fault("drain.stall", delay_s=-1.0)


def test_self_healing_sites_registered():
    assert {"drain.stall", "launch.oom"} <= faults.KNOWN_SITES
    assert len(faults.KNOWN_SITES) == 12


def test_probabilistic_firing_is_seeded():
    def run(seed):
        hits = []
        with faults.inject(
            "executor.launch", RuntimeError("x"), p=0.5, seed=seed, times=None
        ):
            for i in range(20):
                try:
                    faults.fire("executor.launch")
                    hits.append(False)
                except RuntimeError:
                    hits.append(True)
        return hits

    a, b = run(7), run(7)
    assert a == b and 0 < sum(a) < 20  # reproducible, actually probabilistic


def test_record_probe_observes_without_perturbing():
    with faults.inject("serve.drain", record=True, times=None) as probe:
        faults.fire("serve.drain", rids=[3, 4], op="getrf", size=2)
        faults.fire("serve.drain", rids=[5], op="getrf", size=1)
    assert [e["rids"] for e in probe.log] == [[3, 4], [5]]


def test_reset_disarms_everything():
    cm = faults.inject("executor.launch", RuntimeError("x"))
    cm.__enter__()
    assert faults.active()
    faults.reset()
    assert not faults.active()
    faults.fire("executor.launch")  # no-op after reset


# -- site recovery invariants --------------------------------------------------
def _lu_ref(n, seed, parts=((2, 2),)):
    a = dd_matrix(n, seed=seed)
    l, u = run_lu(a, partitions=parts)
    return a, np.asarray(l), np.asarray(u)


def test_launch_failure_then_clean_retry():
    """A raised program launch propagates, but the very next identical
    call succeeds with correct numerics — no capture window or epoch state
    leaks out of the failed drain."""
    clear_compile_cache()
    a, rl, ru = _lu_ref(32, seed=0)
    with faults.inject("executor.launch", RuntimeError("device lost")):
        with pytest.raises(RuntimeError, match="device lost"):
            run_lu(a, partitions=((2, 2),))
    assert drain_memo_stats()["entries"] == 1  # no half-captured second
    l, u = run_lu(a, partitions=((2, 2),))
    np.testing.assert_allclose(np.asarray(l), rl, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(u), ru, rtol=1e-6)


def test_leaf_kernel_failure_propagates_and_recovers():
    clear_compile_cache()  # programs must actually build for leaf.fn to hit
    a = dd_matrix(32, seed=1)
    with faults.inject("leaf.fn", RuntimeError("bad kernel")):
        with pytest.raises(RuntimeError, match="bad kernel"):
            run_lu(a, partitions=((2, 2),))
    assert drain_memo_stats()["entries"] == 0  # nothing half-captured
    l, u = run_lu(a, partitions=((2, 2),))
    np.testing.assert_allclose(
        np.asarray(l) @ np.asarray(u), np.asarray(a), rtol=2e-4, atol=2e-4
    )
    assert drain_memo_stats()["entries"] == 1  # the recovery is memoized


def test_capture_failure_leaves_memo_unchanged():
    """Satellite invariant: an injected failure in the drain-memo capture
    path leaves ``drain_memo_stats()`` unchanged — no half-captured entry —
    and the next drain recompiles and memoizes cleanly."""
    clear_compile_cache()  # the injected drain must be a memo MISS
    a = dd_matrix(32, seed=2)
    with faults.inject("memo.capture", RuntimeError("capture torn")):
        with pytest.raises(RuntimeError, match="capture torn"):
            run_lu(a, partitions=((2, 2),))
    assert drain_memo_stats()["entries"] == 0  # nothing half-captured
    l, u = run_lu(a, partitions=((2, 2),))
    np.testing.assert_allclose(
        np.asarray(l) @ np.asarray(u), np.asarray(a), rtol=2e-4, atol=2e-4
    )
    assert drain_memo_stats()["entries"] == 1  # clean re-capture
    hits0 = drain_memo_stats()["hits"]
    run_lu(a, partitions=((2, 2),))
    assert drain_memo_stats()["hits"] == hits0 + 1  # and it replays


def test_memo_replay_observed_via_probe():
    clear_compile_cache()
    a = dd_matrix(32, seed=3)
    with faults.inject("executor.launch", record=True, times=None) as probe:
        run_lu(a, partitions=((2, 2),))
        run_lu(a, partitions=((2, 2),))
    replays = [e["replay"] for e in probe.log]
    assert not any(replays[: len(replays) // 2])  # first drain: fresh launches
    assert all(replays[len(replays) // 2 :])  # second drain: pure replay


def test_output_corruption_caught_by_check_finite():
    clear_compile_cache()
    a = dd_matrix(32, seed=4)
    with faults.inject("executor.output"):
        with pytest.raises(NumericalError, match="non-finite"):
            run_lu(a, partitions=((2, 2),), check_finite=True)
    # without the check, corruption flows through silently (the default
    # hot path must not pay a materializing reduce)
    with faults.inject("executor.output"):
        l, _ = run_lu(a, partitions=((2, 2),))
        assert np.isnan(np.asarray(l)).any()
    l, _ = run_lu(a, partitions=((2, 2),), check_finite=True)  # healthy again
    assert np.isfinite(np.asarray(l)).all()


def test_value_dependent_split_falls_back_with_identical_numerics():
    """Satellite invariant: forcing the collect-mode abort on a stacked
    drain falls back to the interleaved path and still produces the same
    results the stacked path would have."""
    clear_compile_cache()
    n, N = 32, 4
    mats = [dd_matrix(n, seed=s) for s in range(N)]
    srv = BatchServer(graph="g2")
    futs = [srv.lu(m, partitions=((2, 2),)) for m in mats]
    rep = srv.tick()
    assert rep.stacked_drains == 1
    stacked = [tuple(np.asarray(x) for x in f.result()) for f in futs]

    clear_compile_cache()
    srv2 = BatchServer(graph="g2")
    futs2 = [srv2.lu(m, partitions=((2, 2),)) for m in mats]
    with faults.inject("split.value_dependent", times=None) as f:
        rep2 = srv2.tick()
    assert f.fired > 0 and rep2.stacked_drains == 0  # abort -> interleaved
    assert rep2.resolved == N
    for (sl, su), f2 in zip(stacked, futs2):
        l2, u2 = f2.result()
        np.testing.assert_allclose(np.asarray(l2), sl, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(u2), su, rtol=1e-5, atol=1e-5)


def test_dispatcher_reusable_after_failed_drain():
    """The same Dispatcher instance serves a clean drain after one of its
    drains raised mid-flight."""
    clear_compile_cache()
    d = Dispatcher(graph="g2")
    op = OpRegistry.get("getrf")

    def submit(seed):
        a = dd_matrix(32, seed=seed)
        data = GData(
            a.shape, partitions=((2, 2),), dtype=a.dtype, value=a
        )
        d.submit_task(GTask(op, None, [data.root_view()]))
        return a, data

    a0, _ = submit(0)
    with faults.inject("executor.launch", RuntimeError("flaky")):
        with pytest.raises(RuntimeError, match="flaky"):
            d.run()
    a1, data1 = submit(1)
    d.run()
    l, u = _unpack(data1)
    np.testing.assert_allclose(
        np.asarray(l) @ np.asarray(u), np.asarray(a1), rtol=2e-4, atol=2e-4
    )
