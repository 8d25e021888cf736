"""BatchServer: signature-bucketed batched serving (DESIGN.md §7, §10).

Covers: future resolution + numerics for lu / cholesky / lu_solve requests
(vector and matrix right-hand sides), per-signature bucketing inside one
tick, the repeat-tick contract (0 compiles / 1 launch / 1 stacked drain per
signature bucket), max_batch chunking, the unresolved-future error, and the
failure model — bisect isolation of poisoned requests, lane-isolated finite
checks, deadlines, admission control, retry budget/backoff, FIFO re-queue
ordering, and latency percentiles.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import dd_matrix, spd_matrix
from repro.core.executors import clear_compile_cache
from repro.errors import (
    DeadlineExceeded,
    DrainError,
    NumericalError,
    RejectedError,
)
from repro.linalg import run_lu, run_lu_solve
from repro.serve import BatchServer
from repro.testing import faults


@pytest.fixture(autouse=True)
def _no_leaked_faults():
    yield
    faults.reset()


def _rhs(n, m=None, seed=0):
    rng = np.random.default_rng(seed)
    shape = (n,) if m is None else (n, m)
    return rng.standard_normal(shape).astype(np.float32)


def test_lu_solve_requests_resolve_and_match():
    clear_compile_cache()
    n, N = 64, 5
    srv = BatchServer(graph="g2")
    futs, refs = [], []
    for s in range(N):
        a = dd_matrix(n, seed=s)
        b = _rhs(n, seed=s)
        futs.append(srv.lu_solve(a, b))
        refs.append(run_lu_solve(a, b, partitions=((4, 4),)))
    assert srv.pending() == N and not futs[0].done
    rep = srv.tick()
    assert rep.requests == N and rep.buckets == 1
    assert rep.stacked_drains == 1 and rep.launches == 1
    assert srv.pending() == 0
    for f, r in zip(futs, refs):
        assert f.done
        x = f.result()
        assert x.shape == (n,)  # vector rhs round-trips as a vector
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(r), rtol=1e-5, atol=1e-5
        )


def test_mixed_signatures_bucket_separately():
    clear_compile_cache()
    srv = BatchServer(graph="g2")
    lu_futs = [srv.lu(dd_matrix(64, seed=s)) for s in range(3)]
    chol_futs = [
        srv.cholesky(spd_matrix(32, seed=s), partitions=((4, 4),))
        for s in range(2)
    ]
    rep = srv.tick()
    assert rep.buckets == 2 and rep.drains == 2
    assert rep.stacked_drains == 2  # each homogeneous bucket stacked
    for s, f in enumerate(lu_futs):
        l, u = f.result()
        np.testing.assert_allclose(
            np.asarray(l) @ np.asarray(u),
            np.asarray(dd_matrix(64, seed=s)),
            rtol=2e-4,
            atol=2e-4,
        )
    for s, f in enumerate(chol_futs):
        L = np.asarray(f.result())
        np.testing.assert_allclose(
            L @ L.T, np.asarray(spd_matrix(32, seed=s)), rtol=2e-4, atol=2e-4
        )


def test_repeat_tick_replays_zero_compiles_one_launch():
    """The serving steady state: a structurally repeated tick must do NO
    Python re-splitting and NO recompilation — one program launch per
    signature bucket (DESIGN.md §7 acceptance contract)."""
    clear_compile_cache()
    n = 64
    srv = BatchServer(graph="g2")

    def one_tick(seed0):
        for s in range(4):
            srv.lu_solve(dd_matrix(n, seed=seed0 + s), _rhs(n, seed=s))
        return srv.tick()

    one_tick(0)  # capture tick (compiles once)
    for seed0 in (10, 20):
        rep = one_tick(seed0)
        assert rep.compiles == 0, rep
        assert rep.launches == 1 and rep.stacked_drains == 1
        assert rep.memo_hits == 1 and rep.memo_misses == 0
        assert rep.host_idle_us == 0.0  # no check_finite: never fences
        for b in rep.per_bucket:
            assert b["compiles"] == 0 and b["launches"] == 1


def test_max_batch_chunks_one_signature():
    clear_compile_cache()
    n = 64
    srv = BatchServer(graph="g2", max_batch=2)
    futs = [srv.lu(dd_matrix(n, seed=s)) for s in range(5)]
    rep = srv.tick()
    assert rep.buckets == 1 and rep.drains == 3  # 2 + 2 + 1
    for s, f in enumerate(futs):
        l, u = f.result()
        np.testing.assert_allclose(
            np.asarray(l) @ np.asarray(u),
            np.asarray(dd_matrix(n, seed=s)),
            rtol=2e-4,
            atol=2e-4,
        )


def test_single_request_tick_still_serves():
    clear_compile_cache()
    srv = BatchServer(graph="g2")
    f = srv.lu(dd_matrix(64, seed=91))
    rep = srv.tick()
    # one request cannot stack (nothing to batch) but must still resolve
    assert rep.requests == 1
    l, u = f.result()
    rl, ru = run_lu(dd_matrix(64, seed=91), partitions=((4, 4),))
    np.testing.assert_allclose(np.asarray(l), np.asarray(rl), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(u), np.asarray(ru), rtol=1e-6)


def test_matrix_rhs_lu_solve():
    clear_compile_cache()
    n = 64
    srv = BatchServer(graph="g2")
    a = dd_matrix(n, seed=7)
    b = _rhs(n, m=8, seed=7)
    f = srv.lu_solve(a, b, b_partitions=((4, 1),))
    srv.tick()
    np.testing.assert_allclose(
        np.asarray(f.result()),
        np.asarray(
            run_lu_solve(a, b, partitions=((4, 4),), b_partitions=((4, 1),))
        ),
        rtol=1e-5,
        atol=1e-5,
    )


def test_result_before_tick_raises():
    srv = BatchServer(graph="g2")
    f = srv.lu(dd_matrix(32, seed=1), partitions=((2, 2),))
    with pytest.raises(RuntimeError, match="not drained"):
        f.result()
    srv.tick()
    f.result()  # resolves after the tick


def test_submit_validation():
    srv = BatchServer(graph="g2")
    with pytest.raises(ValueError, match="arrays vs"):
        srv.submit("getrf", [jnp.eye(8)], [])
    with pytest.raises(ValueError, match="shape mismatch"):
        srv.lu_solve(jnp.eye(8), jnp.ones((4,)))
    for bad in (0, 48):  # must be a pow2 so chunks match program buckets
        with pytest.raises(ValueError, match="max_batch"):
            BatchServer(max_batch=bad)


def test_tick_failure_is_contained_and_typed():
    """Failure containment (DESIGN.md §10): a failing chunk drain never
    unwinds ``tick()`` — bisection isolates the poisoned request, which
    fails with a typed ``DrainError`` carrying the cause, while every
    other request (in the same chunk AND in later chunks) resolves in the
    same tick."""
    clear_compile_cache()
    srv = BatchServer(graph="g2", max_batch=2, max_retries=0)
    futs = [srv.lu(dd_matrix(32, seed=s), partitions=((2, 2),)) for s in range(3)]
    poisoned = futs[0].rid
    boom = RuntimeError("executor down")
    with faults.inject(
        "serve.drain",
        boom,
        when=lambda ctx: poisoned in ctx["rids"],
        times=None,
    ):
        rep = srv.tick()  # must NOT raise
    assert rep.resolved == 2 and rep.failed == 1 and rep.bisected == 1
    assert srv.pending() == 0
    err = futs[0].exception()
    assert isinstance(err, DrainError) and err.__cause__ is boom
    with pytest.raises(DrainError, match=f"rid={poisoned}"):
        futs[0].result()
    for s in (1, 2):  # chunk-mate and later chunk both resolved, correct
        l, u = futs[s].result()
        np.testing.assert_allclose(
            np.asarray(l) @ np.asarray(u),
            np.asarray(dd_matrix(32, seed=s)),
            rtol=2e-4,
            atol=2e-4,
        )


def test_bisect_isolates_poisoned_request_in_large_bucket():
    """ISSUE acceptance: 16 requests, one deterministically poisoned —
    the other 15 resolve with correct numerics in the SAME tick via
    bisection, only the poisoned future fails, and a subsequent healthy
    repeat tick still replays at 0 compiles / 1 launch."""
    clear_compile_cache()
    n, N = 32, 16
    srv = BatchServer(graph="g2", max_retries=0)

    def one_tick(seed0):
        futs = [
            srv.lu(dd_matrix(n, seed=seed0 + s), partitions=((2, 2),))
            for s in range(N)
        ]
        return futs, srv.tick()

    one_tick(0)  # healthy capture tick: compiles + memoizes the 16-bucket
    futs, _ = (
        [srv.lu(dd_matrix(n, seed=100 + s), partitions=((2, 2),)) for s in range(N)],
        None,
    )
    poisoned = futs[3].rid
    with faults.inject(
        "serve.drain",
        RuntimeError("lane poisoned"),
        when=lambda ctx: poisoned in ctx["rids"],
        times=None,
    ):
        rep = srv.tick()
    assert rep.resolved == N - 1 and rep.failed == 1, rep
    assert rep.bisected >= 1 and srv.pending() == 0
    for s, f in enumerate(futs):
        if f.rid == poisoned:
            assert isinstance(f.exception(), DrainError)
            continue
        l, u = f.result()
        np.testing.assert_allclose(
            np.asarray(l) @ np.asarray(u),
            np.asarray(dd_matrix(n, seed=100 + s)),
            rtol=2e-4,
            atol=2e-4,
        )
    # serving loop intact: the next healthy full tick replays from the memo
    _, rep = one_tick(200)
    assert rep.compiles == 0 and rep.launches == 1 and rep.stacked_drains == 1


def test_check_finite_fails_only_poisoned_lane():
    """Lane-isolated numerics (DESIGN.md §10): a NaN input poisons its own
    stacked lane only — with ``check_finite=True`` that one request fails
    with ``NumericalError`` while its lane-mates resolve correct results
    from the same drain, without any retry (deterministic error)."""
    clear_compile_cache()
    n = 32
    srv = BatchServer(graph="g2", check_finite=True)
    mats = [np.asarray(dd_matrix(n, seed=s)) for s in range(4)]
    mats[2] = mats[2].copy()
    mats[2][0, 0] = np.nan
    futs = [srv.lu(jnp.asarray(m), partitions=((2, 2),)) for m in mats]
    rep = srv.tick()
    assert rep.resolved == 3 and rep.failed == 1 and rep.retried == 0
    assert isinstance(futs[2].exception(), NumericalError)
    for s in (0, 1, 3):
        l, u = futs[s].result()
        np.testing.assert_allclose(
            np.asarray(l) @ np.asarray(u), mats[s], rtol=2e-4, atol=2e-4
        )


def test_deadline_expires_without_draining():
    clear_compile_cache()
    t = [0.0]
    srv = BatchServer(graph="g2", clock=lambda: t[0])
    doomed = srv.lu(dd_matrix(32, seed=0), partitions=((2, 2),), deadline=5.0)
    healthy = srv.lu(dd_matrix(32, seed=1), partitions=((2, 2),))
    t[0] = 10.0  # past the deadline before any tick
    rep = srv.tick()
    assert rep.expired == 1 and rep.resolved == 1
    assert isinstance(doomed.exception(), DeadlineExceeded)
    l, u = healthy.result()
    np.testing.assert_allclose(
        np.asarray(l) @ np.asarray(u),
        np.asarray(dd_matrix(32, seed=1)),
        rtol=2e-4,
        atol=2e-4,
    )


def test_admission_reject_policy():
    srv = BatchServer(graph="g2", max_pending=2, overload_policy="reject")
    kept = [srv.lu(dd_matrix(32, seed=s), partitions=((2, 2),)) for s in range(2)]
    shed = srv.lu(dd_matrix(32, seed=9), partitions=((2, 2),))
    assert shed.done and isinstance(shed.exception(), RejectedError)
    assert srv.pending() == 2 and srv.stats["shed"] == 1
    srv.tick()
    for f in kept:
        assert f.exception() is None


def test_admission_drop_oldest_policy():
    srv = BatchServer(graph="g2", max_pending=2, overload_policy="drop_oldest")
    first = srv.lu(dd_matrix(32, seed=0), partitions=((2, 2),))
    rest = [srv.lu(dd_matrix(32, seed=s), partitions=((2, 2),)) for s in (1, 2)]
    # the NEW request was admitted; the OLDEST queued one was evicted
    assert isinstance(first.exception(), RejectedError)
    assert srv.pending() == 2 and srv.stats["shed"] == 1
    srv.tick()
    for s, f in zip((1, 2), rest):
        l, u = f.result()
        np.testing.assert_allclose(
            np.asarray(l) @ np.asarray(u),
            np.asarray(dd_matrix(32, seed=s)),
            rtol=2e-4,
            atol=2e-4,
        )


def test_retry_budget_with_backoff_then_recovery():
    """A transient drain failure consumes the retry budget with
    exponential tick backoff, then the request recovers and resolves."""
    clear_compile_cache()
    srv = BatchServer(graph="g2", max_retries=2, retry_backoff=1)
    f = srv.lu(dd_matrix(32, seed=5), partitions=((2, 2),))
    with faults.inject("serve.drain", RuntimeError("transient"), times=2):
        rep1 = srv.tick()  # attempt 1 fails -> eligible next tick
        assert rep1.retried == 1 and not f.done and srv.pending() == 1
        rep2 = srv.tick()  # attempt 2 fails -> backoff holds 1 extra tick
        assert rep2.retried == 1 and not f.done
        rep3 = srv.tick()  # held back: nothing eligible this tick
        assert rep3.buckets == 0 and srv.pending() == 1
    rep4 = srv.tick()  # fault exhausted: drain succeeds
    assert rep4.resolved == 1
    l, u = f.result()
    np.testing.assert_allclose(
        np.asarray(l) @ np.asarray(u),
        np.asarray(dd_matrix(32, seed=5)),
        rtol=2e-4,
        atol=2e-4,
    )


def test_retry_budget_exhaustion_fails_typed():
    clear_compile_cache()
    srv = BatchServer(graph="g2", max_retries=1, retry_backoff=1)
    f = srv.lu(dd_matrix(32, seed=6), partitions=((2, 2),))
    with faults.inject("serve.drain", RuntimeError("hard down"), times=None):
        assert srv.tick().retried == 1
        assert srv.tick().failed == 1
    err = f.exception()
    assert isinstance(err, DrainError) and "2 attempt(s)" in str(err)


def test_requeue_preserves_fifo_and_carries_retry_count():
    """Satellite regression: a re-queued request keeps FIFO order within
    its signature bucket (drains BEFORE anything submitted later) and
    carries its retry count across ticks."""
    clear_compile_cache()
    srv = BatchServer(graph="g2", max_retries=2, retry_backoff=1)
    r0 = srv.lu(dd_matrix(32, seed=0), partitions=((2, 2),))
    r1 = srv.lu(dd_matrix(32, seed=1), partitions=((2, 2),))
    with faults.inject(
        "serve.drain",
        RuntimeError("transient"),
        when=lambda ctx: r1.rid in ctx["rids"],
        times=2,  # the [r0, r1] chunk, then the bisected [r1] singleton
    ):
        srv.tick()
    assert r0.exception() is None and not r1.done
    (pend,) = [p for q in srv._queues.values() for p in q]
    assert pend.future.rid == r1.rid
    assert pend.attempts == 1 and pend.retries_left == 1  # count carried
    r2 = srv.lu(dd_matrix(32, seed=2), partitions=((2, 2),))
    with faults.inject("serve.drain", record=True, times=None) as probe:
        rep = srv.tick()
    assert rep.resolved == 2
    # ONE drain served both, with the re-queued request at the FRONT
    assert probe.log[0]["rids"] == [r1.rid, r2.rid]
    for s, f in ((1, r1), (2, r2)):
        l, u = f.result()
        np.testing.assert_allclose(
            np.asarray(l) @ np.asarray(u),
            np.asarray(dd_matrix(32, seed=s)),
            rtol=2e-4,
            atol=2e-4,
        )


def test_future_ergonomics():
    """Satellite: the pending error names rid + signature; ``exception()``
    mirrors concurrent.futures (None on success, the error on failure,
    pending error before the tick)."""
    srv = BatchServer(graph="g2")
    f = srv.lu(dd_matrix(32, seed=1), partitions=((2, 2),))
    with pytest.raises(RuntimeError, match=f"rid={f.rid}.*getrf"):
        f.result()
    with pytest.raises(RuntimeError, match="not drained"):
        f.exception()
    srv.tick()
    assert f.exception() is None
    f.result()
    g = srv.lu(dd_matrix(32, seed=2), partitions=((2, 2),))
    rejected = BatchServer(graph="g2", max_pending=1, overload_policy="reject")
    rejected.lu(dd_matrix(32, seed=3), partitions=((2, 2),))
    h = rejected.lu(dd_matrix(32, seed=4), partitions=((2, 2),))
    assert isinstance(h.exception(), RejectedError)
    with pytest.raises(RejectedError):
        h.result()
    assert not g.done  # unrelated server state never leaks across futures


def test_tick_reports_latency_percentiles():
    clear_compile_cache()
    t = [0.0]
    srv = BatchServer(graph="g2", clock=lambda: t[0])
    for s in range(3):
        srv.lu(dd_matrix(32, seed=s), partitions=((2, 2),))
    t[0] = 0.25  # every request queued 250ms before the drain completes
    rep = srv.tick()
    assert rep.resolved == 3
    assert rep.p50_ms >= 250.0 and rep.p99_ms >= rep.p50_ms
    pct = srv.latency_percentiles()
    assert pct["samples"] == 3 and pct["p50_ms"] >= 250.0


# -- overload: shedding, retries and isolation in one run ----------------------


@pytest.fixture(scope="module")
def overload_run():
    """A burst 8 requests past ``max_pending`` (those 8 shed with
    RejectedError), then 8 more with one deterministically poisoned: bisect
    isolates it and its one retry exhausts into DrainError, while every
    healthy request resolves.  Returns what the contract cases read."""
    clear_compile_cache()
    n, max_pending = 32, 12
    srv = BatchServer(
        graph="g2",
        max_batch=8,
        max_pending=max_pending,
        overload_policy="reject",
        max_retries=1,
        retry_backoff=1,
    )
    burst = max_pending + 8
    futs = [
        srv.lu(dd_matrix(n, seed=s), partitions=((2, 2),))
        for s in range(burst)
    ]
    srv.tick()
    poison = [
        srv.lu(dd_matrix(n, seed=100 + s), partitions=((2, 2),))
        for s in range(8)
    ]
    target = poison[3].rid
    with faults.inject(
        "serve.drain",
        RuntimeError("injected: lane poisoned"),
        when=lambda ctx: target in ctx["rids"],
        times=None,
    ):
        srv.tick()  # bisects; the poisoned request consumes its retry
        while srv.pending():
            srv.tick()  # backoff ticks, then the retry exhausts
    resolved = sum(
        1 for f in futs + poison if f.done and f.exception() is None
    )
    return dict(
        srv.stats,
        submitted=burst + 8,
        resolved=resolved,
        latency=srv.latency_percentiles(),
    )


_OVERLOAD_CONTRACT = {
    "shed": lambda r: r["shed"] >= 1,
    "retried": lambda r: r["retried"] >= 1,
    "failed": lambda r: r["failed"] >= 1,
    # every request not shed or failed resolved, with latencies recorded
    "accounting": lambda r: r["resolved"]
    == r["submitted"] - r["shed"] - r["failed"]
    and r["latency"]["samples"] > 0
    and r["latency"]["p99_ms"] >= r["latency"]["p50_ms"] > 0,
}


@pytest.mark.parametrize("gate", list(_OVERLOAD_CONTRACT))
def test_overload_contract(overload_run, gate):
    assert _OVERLOAD_CONTRACT[gate](overload_run), overload_run
