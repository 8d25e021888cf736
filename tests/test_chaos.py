"""Self-healing serving (DESIGN.md §14): breakers, watchdog, degradation.

Deterministic coverage of each mechanism — circuit-breaker trip /
half-open probe / re-close, hung-drain watchdog timeout with typed
``DrainStalledError``, device-OOM cap halving with split re-drains and
slow recovery, the HEALTHY/DEGRADED/DRAINING health machine with graceful
``drain()``, and seeded full-jitter on the retry backoff — plus the chaos
property: a randomized multi-site fault schedule (raise + stall + OOM
across ticks, overlap on and off) must end with every submitted future
resolved-or-typed-failed, no lost futures, no wedged tick, and every
breaker back to CLOSED once faults clear.  The fixed chaos schedule
(``test_chaos_schedule_contract``) runs all three mechanisms in one server
and checks each of its exact contracts as one case.
"""

import time
from contextlib import ExitStack

import numpy as np
import pytest

from repro.core import dd_matrix, spd_matrix
from repro.core.executors import clear_compile_cache
from repro.errors import (
    CircuitOpenError,
    DrainStalledError,
    RejectedError,
    ResourceExhausted,
    ServeError,
)
from repro.serve import BatchServer
from repro.testing import faults

from hypothesis import given, settings, strategies as st


@pytest.fixture(autouse=True)
def _no_leaked_faults():
    yield
    faults.reset()


_N, _P = 32, 2


def _submit_lu(srv, seed=0):
    return srv.lu(dd_matrix(_N, seed=seed), partitions=((_P, _P),))


def _submit_chol(srv, seed=0):
    return srv.cholesky(spd_matrix(_N, seed=seed), partitions=((_P, _P),))


def _tick_healthy(srv, n=1, seed0=0):
    """One healthy tick: n lu requests, all expected to resolve."""
    futs = [_submit_lu(srv, seed=seed0 + s) for s in range(n)]
    rep = srv.tick()
    for f in futs:
        assert f.exception() is None
    return rep


# -- circuit breakers ----------------------------------------------------------


def test_breaker_trips_open_and_fails_fast():
    clear_compile_cache()
    srv = BatchServer(graph="g2", max_retries=0, breaker_threshold=3)
    boom = RuntimeError("persistently poisoned bucket")
    with faults.inject("serve.drain", lambda: boom, times=None):
        for s in range(3):  # three singleton failures = threshold
            f = _submit_lu(srv, seed=s)
            rep = srv.tick()
            assert f.done and f.exception() is not None
        assert rep.breaker_trips == 1
        assert rep.breaker_state == "open"
        assert srv.health() == "DEGRADED"
    # incoming submits fail fast WITHOUT draining (fault already cleared:
    # a drain would succeed — the breaker fails it before any drain)
    f = _submit_lu(srv, seed=99)
    assert isinstance(f.exception(), CircuitOpenError)
    assert srv.stats["breaker_fast_fails"] == 1


def test_breaker_fails_queued_requests_fast():
    """Requests already IN the queue when their bucket trips (here: held by
    retry backoff) fail fast at the next tick — no drain, no retry."""
    clear_compile_cache()
    srv = BatchServer(
        graph="g2",
        max_retries=1,
        retry_backoff=4,
        breaker_threshold=2,
        breaker_cooldown=100,
    )
    futs = [_submit_lu(srv, seed=s) for s in range(2)]
    with faults.inject("serve.drain", RuntimeError("boom"), times=None):
        rep0 = srv.tick()  # both fail + re-queue with backoff; breaker trips
    assert rep0.retried == 2 and rep0.breaker_trips == 1
    assert not futs[0].done and srv.pending() == 2
    rep = srv.tick()  # fault cleared, but the bucket is OPEN: fail fast
    for f in futs:
        assert isinstance(f.exception(), CircuitOpenError)
    assert rep.breaker_fast_fails == 2 and rep.drains == 0


def test_breaker_half_open_probe_recloses():
    clear_compile_cache()
    srv = BatchServer(
        graph="g2", max_retries=0, breaker_threshold=2, breaker_cooldown=2
    )
    with faults.inject("serve.drain", RuntimeError("boom"), times=None):
        for s in range(2):
            _submit_lu(srv, seed=s)
            srv.tick()
    assert srv.breaker_round_trips() == 0
    # cooldown: two empty ticks; the sweep half-opens at tick start
    srv.tick()
    srv.tick()
    # probe + a second request: only the probe drains this tick, the
    # other rides behind it and resolves next tick once the breaker closes
    probe = _submit_lu(srv, seed=10)
    behind = _submit_lu(srv, seed=11)
    rep = srv.tick()
    assert probe.exception() is None
    assert rep.breaker_closes == 1
    assert not behind.done  # held behind the probe
    rep2 = srv.tick()
    assert behind.exception() is None
    assert srv.breaker_round_trips() == 1
    assert srv.health() == "HEALTHY"
    assert rep2.breaker_state == "closed"


def test_half_open_probe_failure_retrips():
    clear_compile_cache()
    srv = BatchServer(
        graph="g2", max_retries=0, breaker_threshold=2, breaker_cooldown=1
    )
    with faults.inject("serve.drain", RuntimeError("boom"), times=None):
        for s in range(2):
            _submit_lu(srv, seed=s)
            srv.tick()
        srv.tick()  # cooldown elapses: breaker half-opens
        probe = _submit_lu(srv, seed=10)
        rep = srv.tick()  # probe drains, fails -> re-trips OPEN
    assert probe.done and probe.exception() is not None
    assert rep.breaker_trips == 1
    assert srv.breaker_round_trips() == 0
    f = _submit_lu(srv, seed=20)
    assert isinstance(f.exception(), CircuitOpenError)


def test_single_poisoned_request_does_not_trip_breaker():
    """Bisect successes reset the consecutive-failure count: one poisoned
    request among healthy bucket-mates, tick after tick, never trips."""
    clear_compile_cache()
    srv = BatchServer(graph="g2", max_retries=0, breaker_threshold=2)
    for round_ in range(3):
        futs = [_submit_lu(srv, seed=round_ * 8 + s) for s in range(4)]
        poison = futs[0].rid
        with faults.inject(
            "serve.drain",
            RuntimeError("poisoned"),
            when=lambda ctx: poison in ctx["rids"],
            times=None,
        ):
            srv.tick()
        assert futs[0].exception() is not None
        for f in futs[1:]:
            assert f.exception() is None
    assert srv.stats["breaker_trips"] == 0
    assert srv.health() == "HEALTHY"


# -- hung-drain watchdog -------------------------------------------------------


def test_watchdog_fails_stalled_chunk_typed():
    clear_compile_cache()
    srv = BatchServer(graph="g2", watchdog_s=0.05, max_retries=3)
    futs = [_submit_lu(srv, seed=s) for s in range(2)]
    with faults.inject("drain.stall", delay_s=0.2):
        t0 = time.perf_counter()
        rep = srv.tick()
        wall = time.perf_counter() - t0
    assert rep.watchdog_fires == 1
    # NOT retried despite the generous retry budget: both futures carry
    # the typed stall error this same tick
    for f in futs:
        assert isinstance(f.exception(), DrainStalledError)
    assert wall < 5.0  # the tick never blocked past budget + injected delay
    # next tick is healthy again (memo was invalidated, re-captures clean)
    rep2 = _tick_healthy(srv, n=2, seed0=10)
    assert rep2.resolved == 2 and rep2.watchdog_fires == 0


def test_watchdog_unarmed_by_default():
    clear_compile_cache()
    srv = BatchServer(graph="g2")
    with faults.inject("drain.stall", delay_s=0.2):
        rep = _tick_healthy(srv, n=1)
    # no watchdog: the stall site never fires, nothing is delayed or failed
    assert rep.watchdog_fires == 0 and rep.resolved == 1


def test_dispatcher_wait_timeout_raises_typed():
    from repro.core import Dispatcher, GData, GTask
    from repro.core.operation import OpRegistry

    clear_compile_cache()

    def drain_async():
        d = Dispatcher(graph="g2")
        a = dd_matrix(_N, seed=0)
        data = GData(a.shape, partitions=((_P, _P),), dtype=a.dtype, value=a)
        d.submit_task(
            GTask(OpRegistry.get("getrf"), None, [data.root_view()])
        )
        return d.run_async()

    with faults.inject("drain.stall", delay_s=0.2):
        with pytest.raises(DrainStalledError):
            drain_async().wait(timeout=0.05)
    # a fresh drain after the stall is clean (memo was invalidated)
    assert drain_async().wait(timeout=30.0) >= 0.0


# -- adaptive degradation under memory pressure --------------------------------


def test_oom_splits_chunk_and_degrades_cap():
    clear_compile_cache()
    srv = BatchServer(graph="g2", max_batch=4, degrade_recovery=3)
    futs = [_submit_lu(srv, seed=s) for s in range(4)]
    with faults.inject(
        "launch.oom", lambda: ResourceExhausted("RESOURCE_EXHAUSTED: injected")
    ):
        rep = srv.tick()
    # the OOM'd 4-chunk re-drained as two healthy halves, same tick
    assert rep.oom_events == 1
    for f in futs:
        assert f.exception() is None
    # the two same-tick half successes count toward recovery (2 of 3)
    assert rep.degraded_buckets == 1
    assert srv.health() == "DEGRADED"
    sig = futs[0].signature
    assert srv._bucket_cap(sig) == 2  # halved
    # one more healthy drain completes the recovery: cap steps back up
    _tick_healthy(srv, n=1, seed0=100)
    assert srv._bucket_cap(sig) == 4
    assert srv.health() == "HEALTHY"


def test_oom_singleton_fails_typed_never_retried():
    clear_compile_cache()
    srv = BatchServer(graph="g2", max_retries=5)
    f = _submit_lu(srv, seed=0)
    with faults.inject(
        "launch.oom",
        lambda: ResourceExhausted("RESOURCE_EXHAUSTED: injected"),
        times=None,
    ):
        rep = srv.tick()
    # a request that OOMs ALONE reproduces at any size: typed, no retry
    assert isinstance(f.exception(), ResourceExhausted)
    assert rep.retried == 0 and rep.failed == 1


def test_oom_textual_match_wraps_generic_error():
    clear_compile_cache()
    srv = BatchServer(graph="g2", max_retries=5)
    f = _submit_lu(srv, seed=0)
    with faults.inject(
        "launch.oom",
        lambda: RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating"),
        times=None,
    ):
        srv.tick()
    err = f.exception()
    assert isinstance(err, ResourceExhausted)
    assert isinstance(err.__cause__, RuntimeError)


# -- health + graceful shutdown ------------------------------------------------


def test_drain_flushes_queue_and_rejects_new_submits():
    clear_compile_cache()
    srv = BatchServer(graph="g2")
    futs = [_submit_lu(srv, seed=s) for s in range(3)]
    assert srv.health() == "HEALTHY"
    reports = srv.drain()
    assert srv.health() == "DRAINING"
    assert srv.pending() == 0
    assert sum(r.resolved for r in reports) == 3
    for f in futs:
        assert f.exception() is None
    late = _submit_lu(srv, seed=9)
    assert isinstance(late.exception(), RejectedError)


def test_drain_flushes_backoff_held_retries():
    clear_compile_cache()
    srv = BatchServer(graph="g2", max_retries=1, retry_backoff=2)
    with faults.inject("serve.drain", RuntimeError("transient")):
        f = _submit_lu(srv, seed=0)
        srv.tick()  # fails once, re-queued with not_before = tick+2
    assert not f.done
    reports = srv.drain()
    assert f.exception() is None  # retried and resolved during the flush
    assert len(reports) >= 2  # at least the backoff-held ticks


# -- retry jitter --------------------------------------------------------------


def test_retry_jitter_seeded_deterministic_and_bounded():
    clear_compile_cache()

    def run(seed):
        srv = BatchServer(
            graph="g2", max_retries=3, retry_backoff=4, retry_jitter_seed=seed
        )
        f = _submit_lu(srv, seed=0)
        delays = []
        with faults.inject("serve.drain", RuntimeError("boom"), times=3):
            for tick_no in range(200):
                if f.done:
                    break
                before = srv.stats["retried"]
                srv.tick()
                q = [p for q_ in srv._queues.values() for p in q_]
                if srv.stats["retried"] > before and q:
                    delays.append(q[0].not_before - tick_no)
        assert f.exception() is None  # recovered on the final attempt
        return delays

    d1, d2 = run(7), run(7)
    assert d1 == d2  # seeded: reproducible schedule
    for attempt, delay in enumerate(d1, start=1):
        cap = 4 * 2 ** (attempt - 1)
        assert 1 <= delay <= cap  # full jitter stays in [1, cap]
    # and a different seed is allowed to (and here does) differ somewhere
    assert len(d1) == 3


def test_no_jitter_default_keeps_exact_backoff():
    clear_compile_cache()
    srv = BatchServer(graph="g2", max_retries=2, retry_backoff=3)
    f = _submit_lu(srv, seed=0)
    with faults.inject("serve.drain", RuntimeError("boom")):
        srv.tick()  # attempt 1 fails -> not_before = 0 + 3, exactly
        p = next(iter(srv._queues.values()))[0]
        assert p.not_before == 3
    for _ in range(3):
        srv.tick()  # held, held, drained at tick 3
    assert f.exception() is None


# -- chaos property ------------------------------------------------------------


@st.composite
def fault_schedule(draw):
    """A few ticks of traffic, each with an independent fault cocktail:
    0-2 transient drain raises, an optional fence stall, an optional
    launch OOM — overlapping on and off across the schedule."""
    ticks = []
    for _ in range(draw(st.integers(2, 4))):
        ticks.append(
            {
                "lu": draw(st.integers(0, 3)),
                "chol": draw(st.integers(0, 2)),
                "raises": draw(st.integers(0, 2)),
                "stall": draw(st.booleans()),
                "oom": draw(st.booleans()),
            }
        )
    return ticks


@settings(max_examples=4, deadline=None)
@given(plan=fault_schedule(), overlap=st.booleans())
def test_chaos_every_future_resolves_or_fails_typed(plan, overlap):
    """Under a randomized multi-site fault schedule the server must (a)
    resolve or typed-fail 100% of submitted futures — no lost futures, (b)
    never wedge a tick (every tick returns, bounded by the watchdog), and
    (c) return every breaker to CLOSED and health to HEALTHY once the
    faults clear."""
    clear_compile_cache()
    srv = BatchServer(
        graph="g2",
        overlap=overlap,
        max_batch=4,
        max_retries=1,
        watchdog_s=0.3,
        breaker_threshold=3,
        breaker_cooldown=2,
        degrade_recovery=1,
        retry_jitter_seed=42,
    )
    all_futs = []
    seed = 0
    for spec in plan:
        for _ in range(spec["lu"]):
            all_futs.append(_submit_lu(srv, seed=seed))
            seed += 1
        for _ in range(spec["chol"]):
            all_futs.append(_submit_chol(srv, seed=seed))
            seed += 1
        with ExitStack() as stack:
            if spec["raises"]:
                stack.enter_context(
                    faults.inject(
                        "serve.drain",
                        lambda: RuntimeError("chaos: transient drain"),
                        times=spec["raises"],
                    )
                )
            if spec["stall"]:
                stack.enter_context(
                    faults.inject("drain.stall", delay_s=1.0)
                )
            if spec["oom"]:
                stack.enter_context(
                    faults.inject(
                        "launch.oom",
                        lambda: ResourceExhausted("RESOURCE_EXHAUSTED"),
                    )
                )
            t0 = time.perf_counter()
            srv.tick()
            assert time.perf_counter() - t0 < 60.0  # no wedged tick
    # faults cleared: recovery ticks — healthy probes re-close breakers,
    # healthy drains step degraded caps back up, backoff-held retries run
    for i in range(10):
        all_futs.append(_submit_lu(srv, seed=1000 + i))
        all_futs.append(_submit_chol(srv, seed=1000 + i))
        srv.tick()
        if (
            srv.pending() == 0
            and srv.health() == "HEALTHY"
            and all(f.done for f in all_futs)
        ):
            break
    # (a) no lost futures: every one resolved or typed-failed
    for f in all_futs:
        assert f.done, f"lost future rid={f.rid}"
        err = f.exception()
        assert err is None or isinstance(err, ServeError), err
    # (c) breakers all CLOSED, nothing degraded, nothing queued
    assert srv.pending() == 0
    for snap in srv.breakers().values():
        assert snap["state"] == "closed"
    assert srv.health() == "HEALTHY"
    # post-fault steady state: a repeated tick is back to the §7 contract
    rep = _tick_healthy(srv, n=2, seed0=5000)
    rep = _tick_healthy(srv, n=2, seed0=6000)
    assert rep.compiles == 0 and rep.failed == 0


# -- fixed chaos schedule --------------------------------------------------------

_WATCHDOG_S = 0.3
_STALL_S = 1.0


@pytest.fixture(scope="module")
def chaos_schedule():
    """One server through a deterministic, seeded fault schedule that runs
    all three self-healing mechanisms: a poisoned bucket that trips its
    breaker and re-closes it, a fence stall past the watchdog budget, an
    OOM on a full stacked chunk, then seeded transient faults, recovery,
    and three steady-state ticks.  Returns what the contract cases read."""
    clear_compile_cache()
    rng = np.random.default_rng(0)
    srv = BatchServer(
        graph="g2",
        max_batch=4,
        max_retries=1,
        watchdog_s=_WATCHDOG_S,
        breaker_threshold=2,
        breaker_cooldown=2,
        degrade_recovery=2,
        retry_jitter_seed=7,
    )
    # a tick that blocks longer than budget + every injected delay + slack
    # has wedged: nothing in the schedule can legitimately take this long
    wedge_budget_s = _WATCHDOG_S + _STALL_S + 30.0
    all_futs = []
    seed = 0
    wedged = 0

    def tick():
        nonlocal wedged
        t0 = time.perf_counter()
        srv.tick()
        if time.perf_counter() - t0 > wedge_budget_s:
            wedged += 1

    def submit(kind):
        nonlocal seed
        submit_fn = _submit_lu if kind == "lu" else _submit_chol
        all_futs.append(submit_fn(srv, seed=seed))
        seed += 1

    # phase 0 — warmup: capture both buckets' programs healthy
    for _ in range(2):
        submit("lu")
        submit("chol")
    tick()

    # phase 1 — poisoned chol bucket: every drain raises until the breaker
    # trips (threshold 2), then the fault clears and the cooldown + probe
    # must complete the round trip
    with faults.inject(
        "serve.drain",
        lambda: RuntimeError("chaos: poisoned bucket"),
        when=lambda ctx: ctx["op"] == "potrf",
        times=None,
    ):
        for _ in range(3):
            submit("chol")
            submit("lu")  # healthy bystander bucket: must keep resolving
            tick()
    for _ in range(4):  # cooldown ticks + half-open probe + re-close
        submit("chol")
        tick()

    # phase 2 — fence stall: the watchdog must fail the chunk typed
    # within budget instead of blocking the tick on the hung fence
    submit("lu")
    submit("lu")
    with faults.inject("drain.stall", delay_s=_STALL_S):
        tick()

    # phase 3 — device OOM on a full stacked chunk: split halves resolve
    # the same tick, the bucket's cap degrades then recovers
    for _ in range(4):
        submit("lu")
    with faults.inject(
        "launch.oom", lambda: ResourceExhausted("RESOURCE_EXHAUSTED: chaos")
    ):
        tick()

    # phase 4 — seeded random transient faults (retry + bisect load)
    for _ in range(2):
        for _ in range(int(rng.integers(1, 4))):
            submit("lu")
        n_raises = int(rng.integers(0, 3))
        if n_raises:
            with faults.inject(
                "serve.drain",
                lambda: RuntimeError("chaos: transient"),
                times=n_raises,
            ):
                tick()
        else:
            tick()

    # phase 5 — recovery: healthy traffic until queue empty, breakers
    # closed, degradation recovered
    for _ in range(12):
        submit("lu")
        submit("chol")
        tick()
        if (
            srv.pending() == 0
            and srv.health() == "HEALTHY"
            and all(f.done for f in all_futs)
        ):
            break

    # phase 6 — steady state: the §7 replay contract must hold again; the
    # first steady tick may still recompile, so the last one is read
    for _ in range(3):
        before = dict(srv.stats)
        for kind in ("lu", "lu", "chol", "chol"):
            submit(kind)
        tick()
    steady = {k: srv.stats[k] - before[k]
              for k in ("compiles", "launches", "failed", "drains")}

    ends = [
        "lost_futures" if not f.done
        else "resolved" if f.exception() is None
        else "typed_failed" if isinstance(f.exception(), ServeError)
        else "untyped_failed"
        for f in all_futs
    ]
    return dict(
        {k: ends.count(k) for k in
         ("resolved", "typed_failed", "untyped_failed", "lost_futures")},
        submitted=len(all_futs),
        wedged_ticks=wedged,
        breaker_round_trips=srv.breaker_round_trips(),
        watchdog_fires=srv.stats["watchdog_fires"],
        oom_events=srv.stats["oom_events"],
        final_health=srv.health(),
        steady_state=steady,
    )


_CHAOS_CONTRACT = {
    # every submitted future ends resolved or typed-failed
    "lost_futures": lambda r: r["lost_futures"] == 0,
    "untyped_failed": lambda r: r["untyped_failed"] == 0,
    "accounting_ok": lambda r: r["resolved"] + r["typed_failed"]
    + r["untyped_failed"] == r["submitted"],
    # no tick blocked past the watchdog budget plus the injected delays
    "wedged_ticks": lambda r: r["wedged_ticks"] == 0,
    # every self-healing mechanism witnessed at least once
    "breaker_round_trips": lambda r: r["breaker_round_trips"] >= 1,
    "watchdog_fires": lambda r: r["watchdog_fires"] >= 1,
    "oom_events": lambda r: r["oom_events"] >= 1,
    # recovered: breakers closed, caps restored, one launch per bucket
    "final_health_healthy": lambda r: r["final_health"] == "HEALTHY",
    "steady_state_ok": lambda r: r["steady_state"]["compiles"] == 0
    and r["steady_state"]["failed"] == 0
    and r["steady_state"]["launches"] == r["steady_state"]["drains"] == 2,
}


@pytest.mark.parametrize("gate", list(_CHAOS_CONTRACT))
def test_chaos_schedule_contract(chaos_schedule, gate):
    assert _CHAOS_CONTRACT[gate](chaos_schedule), chaos_schedule
