"""The task runtime's spans (``repro.core.tracing``) and its kernel names."""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import dd_matrix, spd_matrix, tracing
from repro.core.executors import clear_compile_cache, drain_memo_records
from repro.kernels import tile_linalg
from repro.linalg import run_cholesky, run_lu_solve


@pytest.fixture
def ring():
    tracing.clear()
    yield
    tracing.clear()


def _names(recs):
    return [r[3] for r in recs]


def test_spans_nest_with_their_parent_and_drain_ids(ring):
    with tracing.span("utp.degrid"):
        pass
    with tracing.span("utp.drain") as drain:
        with tracing.span("utp.split") as split:
            with tracing.span("utp.plan", groups=3):
                pass
    recs = {r[3]: r for r in tracing.records()}
    assert _names(tracing.records()) == ["utp.degrid", "utp.plan", "utp.split", "utp.drain"]
    sid, parent, drain_id, name, t0, t1, counts = recs["utp.plan"]
    assert parent == split.id and drain_id == drain.id and counts == {"groups": 3}
    assert recs["utp.split"][1:3] == (drain.id, drain.id)
    assert recs["utp.drain"][1:3] == (0, drain.id)
    assert recs["utp.degrid"][1:3] == (0, 0)
    assert recs["utp.drain"][4] <= recs["utp.split"][4] <= t0 <= t1 <= recs["utp.drain"][5]


def test_a_span_is_recorded_when_its_body_raises(ring):
    with pytest.raises(ValueError):
        with tracing.span("utp.launch"):
            raise ValueError("x")
    assert _names(tracing.records()) == ["utp.launch"]
    with tracing.span("utp.drain") as sp:
        pass
    assert tracing.records()[-1][1] == 0 and sp.parent == 0


def test_the_ring_stays_bounded(ring):
    for _ in range(tracing.RING + 10):
        with tracing.span("utp.launch"):
            pass
    recs = tracing.records()
    assert len(recs) == tracing.RING
    assert recs[-1][0] - recs[0][0] == tracing.RING - 1  # the oldest were dropped


def test_monitoring_events_go_to_the_innermost_open_span(ring):
    with tracing.span("utp.build") as outer:
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/backend_compile_duration", 0.5)
        with tracing.span("utp.launch") as inner:
            jax.monitoring.record_event_duration_secs(
                "/jax/core/compile/jaxpr_trace_duration", 0.25)
            jax.monitoring.record_event_duration_secs("/jax/some/other_duration", 9.0)
    # outside any span an event is counted nowhere
    jax.monitoring.record_event_duration_secs("/jax/core/compile/jaxpr_to_mlir_module_duration", 1.0)
    assert inner.counts == {"trace_s": 0.25}
    assert outer.counts == {"compile_s": 0.5}


def test_a_nested_event_is_counted_once(ring):
    # JAX reports a function traced while its caller is lowered first, then
    # the lowering that covers it
    with tracing.span("utp.build") as sp:
        jax.monitoring.record_event_duration_secs("/jax/core/compile/jaxpr_trace_duration", 0.25)
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/jaxpr_to_mlir_module_duration", 1.0)
    assert sp.counts["trace_s"] == pytest.approx(0.25)
    assert sp.counts["lower_s"] == pytest.approx(0.75, abs=1e-3)


def test_a_real_compile_is_counted_on_the_span_that_compiles(ring):
    with tracing.span("utp.build") as sp:
        jax.jit(lambda x: jnp.tril(x * 3.0 + 1.0))(jnp.ones((7, 7))).block_until_ready()
    assert sp.counts["trace_s"] > 0 and sp.counts["lower_s"] > 0
    assert sp.counts["compile_s"] > 0
    # every second counted once: no more than the span lasted
    (rec,) = tracing.records()
    assert sum(sp.counts.values()) <= (rec[5] - rec[4]) / 1e9


def test_repeated_cholesky_replays_with_a_fixed_set_of_spans(ring):
    clear_compile_cache()
    a = spd_matrix(256)
    run_cholesky(a, graph="g2p", partitions=((4, 4),)).block_until_ready()
    first = tracing.records()
    names = set(_names(first))
    assert {"utp.drain", "utp.split", "utp.plan", "utp.enter_grid", "utp.build",
            "utp.degrid"} <= names
    drain = next(r for r in first if r[3] == "utp.drain")
    assert drain[6]["memo_hit"] == 0 and drain[6]["leaves"] == 20
    split = next(r for r in first if r[3] == "utp.split")
    assert split[6]["split"] == 1
    plan = next(r for r in first if r[3] == "utp.plan")
    assert plan[6]["tasks"] == 20 and plan[6]["groups"] > 0 and plan[6]["slots"] > 0
    build = next(r for r in first if r[3] == "utp.build")
    assert build[6]["trace_s"] > 0 and build[6]["lower_s"] > 0 and build[6]["compile_s"] > 0
    for _ in range(2):
        tracing.clear()
        run_cholesky(a, graph="g2p", partitions=((4, 4),)).block_until_ready()
        recs = tracing.records()
        # replay: no split, no plan, no span per task or group
        assert _names(recs) == ["utp.enter_grid", "utp.launch", "utp.drain", "utp.degrid"]
        drain = recs[2]
        assert drain[6] == {"roots": 1, "memo_hit": 1, "leaves": 20}
        assert recs[0][2] == recs[1][2] == drain[0]  # inside the drain
        assert recs[1][6] == {"tasks": 20, "groups": plan[6]["groups"]}


@pytest.mark.parametrize("graph,p", [("g2p", 3), ("g2p", 4), ("g2", 4)])
def test_build_counts_the_groups_that_share_a_grid(ring, graph, p):
    """A Cholesky drain's trsm, syrk and gemm groups read blocks of the one
    matrix: 2p - 2 + (p - 2) fused groups on g2p, each handed the grid once;
    none on g2, whose groups gather their tiles."""
    clear_compile_cache()
    a = spd_matrix(16 * p)
    run_cholesky(a, graph=graph, partitions=((p, p),)).block_until_ready()
    (build,) = [r for r in tracing.records() if r[3] == "utp.build"]
    want = 2 * p - 2 + (p - 2) if graph == "g2p" else 0
    assert build[6]["shared_grid_groups"] == want


@pytest.mark.parametrize("op", ["lu_solve", "cholesky"])
def test_build_counts_the_groups_read_by_blockspec(ring, op):
    """A fused group reads a grid by BlockSpec when the grid's tile is not
    whole (8, 128) layout tiles, counted by the chip's rule on any backend.
    With 128-wide tiles only a vector right-hand side is that thin: an
    LU-solve at p = 4 reads it in its p + p forward and backward diagonal
    solves, its p - 1 forward updates (one group a step, beside the
    factorization) and its p (p - 1) / 2 backward updates (row by row, each
    row's updates a chain); a Cholesky drain reads none."""
    p = 4
    n = 128 * p
    clear_compile_cache()
    if op == "lu_solve":
        run_lu_solve(dd_matrix(n), jnp.ones((n,), jnp.float32), graph="g2p",
                     partitions=((p, p),)).block_until_ready()
        want = 2 * p + (p - 1) + p * (p - 1) // 2
    else:
        run_cholesky(spd_matrix(n), graph="g2p", partitions=((p, p),)).block_until_ready()
        want = 0
    (build,) = [r for r in tracing.records() if r[3] == "utp.build"]
    assert build[6]["blockspec_groups"] == want


@pytest.mark.parametrize(
    "op,tile,p", [("cholesky", 256, 2), ("lu_solve", 256, 2), ("cholesky", 8, 4)]
)
def test_build_counts_the_blocked_panel_groups(ring, op, tile, p):
    """Every fused panel-solve group is solved in strips when its triangular
    tile holds several 128-wide strips, and none at smaller tiles: a
    Cholesky drain has p - 1 trsm groups; an LU-solve with a vector
    right-hand side has p - 1 trsml and p - 1 trsmu groups on the matrix
    and p forward and p backward diagonal solves on the right-hand side."""
    n = tile * p
    clear_compile_cache()
    if op == "lu_solve":
        run_lu_solve(dd_matrix(n), jnp.ones((n,), jnp.float32), graph="g2p",
                     partitions=((p, p),)).block_until_ready()
        groups = 4 * p - 2
    else:
        run_cholesky(spd_matrix(n), graph="g2p", partitions=((p, p),)).block_until_ready()
        groups = p - 1
    (build,) = [r for r in tracing.records() if r[3] == "utp.build"]
    assert build[6]["blocked_panel_groups"] == (groups if tile > 128 else 0)


def test_g2p_program_lowered_for_tpu_names_each_tile_kernel(monkeypatch):
    clear_compile_cache()
    run_cholesky(spd_matrix(256), graph="g2p", partitions=((4, 4),)).block_until_ready()
    (rec,) = drain_memo_records()
    # trace afresh with Mosaic kernels, as on the chip (interpret mode off)
    monkeypatch.setattr(tile_linalg, "default_interpret", lambda: False)
    jax.clear_caches()
    grid = jax.ShapeDtypeStruct((4, 4, 64, 64), jnp.float32)
    try:
        lowered = rec.fn.trace((grid,), rec.idxs).lower(lowering_platforms=("tpu",))
    finally:
        # drop the Mosaic trace, so no later CPU call of the program finds it
        clear_compile_cache()
        jax.clear_caches()
    text = lowered.as_text(debug_info=True)
    assert "tpu_custom_call" in text
    scopes = set(re.findall(r'"jit\(program\)/(\w+)/', text))
    assert scopes == {"potrf", "trsm", "syrk", "gemm"}
    kernels = set(re.findall(r'kernel_name = "(\w+)"', text))
    assert kernels == {"potrf", "trsm", "syrk", "gemm"}
