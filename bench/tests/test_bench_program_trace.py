"""The program's view of a trace: named kernels, the copies feeding them, the
clock offset, idle time by program span, and set-up from the span ring."""

import pytest

from bench import harness, program_trace as pt, readers
from bench.tests import tiny
from repro.core import tracing

# op texts as the profiler writes them on a v5e (chol16k trace, shortened)
TRSM_TEXT = ('%trsm.31 = f32[32,32,512,512]{3,2,1,0:T(8,128)} custom-call(s32[62]{0:T(128)S(1)} '
             '%reshape.247, f32[32,32,512,512]{3,2,1,0:T(8,128)} %copy.479), '
             'custom_call_target="tpu_custom_call", output_to_operand_aliasing={{}: (1, {})}')
COPY_TEXT = ('%copy.479 = f32[32,32,512,512]{3,2,1,0:T(8,128)} copy(f32[32,32,512,512]'
             '{3,2,1,0:T(8,128)} %potrf.32)')
FUSION_TEXT = ('%fusion = (s32[31,2]{0,1:T(2,128)S(1)}, s32[31,2]{0,1:T(2,128)}) '
               'fusion(s32[16896,2]{0,1:T(2,128)} %idxs.1), kind=kLoop, calls=%fused_computation.39')


def test_operands_and_kernel_names_come_from_the_hlo_text():
    assert pt.operands(TRSM_TEXT) == ("reshape.247", "copy.479")
    assert pt.operands(COPY_TEXT) == ("potrf.32",)
    assert pt.operands(FUSION_TEXT) == ("idxs.1",)
    assert pt.operands("kernel") == ()
    assert pt.kernel("trsm.31 custom-call", True) == "trsm"
    assert pt.kernel("trsm custom-call", True) == "trsm"
    assert pt.kernel("program.125 custom-call", True) is None  # an unnamed kernel
    assert pt.kernel("copy.479 copy", False) is None


# One entry call, host clock in ns.  The device clock runs 400 us behind the
# host's: the relayout program dispatched at 320 us starts on the idle device
# 10 us later, at -70 us by its own clock, so causality bounds the offset at
# 390 us.  The WaveProgram waits behind it, and its launch bounds nothing.
OFFSET = 390_000
SPANS = [
    ("window", 0, 10_000_000, 1),
    ("entry_call", 100_000, 800_000, 1),
    ("utp.drain", 120_000, 640_000, 1),
    ("utp.enter_grid", 130_000, 500_000, 1),
    ("utp.launch", 600_000, 630_000, 1),
    ("utp.degrid", 650_000, 750_000, 1),
    ("wait", 800_000, 9_500_000, 1),
]
DISPATCHES = [320_000, 610_000, 700_000]
MODULES = [
    (0, "jit_to_grid(1)", -70_000, 930_000),
    (0, "jit_program(2)", 930_000, 5_400_000),
    (0, "jit__lambda(3)", 5_400_000, 5_500_000),
]
OPS = [
    (0, "copy.1 copy", -70_000, 930_000, False, ("bitcast.6",)),
    (0, "potrf.32 custom-call", 930_000, 1_130_000, True, ("get-tuple-element", "grids_0_.1")),
    (0, "copy.479 copy", 1_130_000, 2_130_000, False, ("potrf.32",)),
    (0, "trsm.31 custom-call", 2_130_000, 3_130_000, True, ("reshape.247", "potrf.32", "copy.479")),
    (0, "syrk.3 custom-call", 3_130_000, 3_230_000, True, ("reshape.3", "trsm.31", "trsm.31")),
    (0, "copy.480 copy", 3_230_000, 4_130_000, False, ("syrk.3",)),
    (0, "fusion.2 fusion", 4_130_000, 4_200_000, False, ("idxs.1",)),
    (0, "gemm.5 custom-call", 4_200_000, 5_200_000, True,
     ("reshape.9", "trsm.31", "trsm.31", "copy.480")),
    (0, "copy.9 copy", 5_300_000, 5_400_000, False, ("gemm.5",)),  # read by no kernel
    (0, "fusion.9 fusion", 5_400_000, 5_500_000, False, ("copy.9",)),
]


def test_the_offset_is_the_largest_lead_of_an_execution_over_its_launch():
    by_call, by_span = pt.launch_leads(OPS, MODULES, SPANS, DISPATCHES)
    assert by_call == [390_000, 610_000 - 930_000, 700_000 - 5_400_000]
    assert by_span == [600_000 - 930_000]
    red = pt.reduce(OPS, MODULES, SPANS, DISPATCHES)
    assert red["offset_ns"] == OFFSET
    assert red["launch_lead_ns"] == 600_000 - 930_000 - OFFSET <= 0
    # without the runtime's execute calls the WaveProgram bounds it at 0
    assert pt.reduce(OPS, MODULES, SPANS)["offset_ns"] == 0
    assert pt.reduce(OPS, [], [sp for sp in SPANS if sp[0] != "utp.launch"])["offset_ns"] is None


def test_kernels_copies_and_gaps_reduce_as_read_by_hand():
    red = pt.reduce(OPS, MODULES, SPANS, DISPATCHES)
    assert red["kernel_s"] == {"gemm": 1e-3, "potrf": 2e-4, "syrk": 1e-4, "trsm": 1e-3}
    assert sum(red["kernel_s"].values()) == pytest.approx(red["pallas_s"])
    assert red["copy_s"] == {"gemm": 9e-4, "trsm": 1e-3}
    # busy: [320 us, 5590 us) and [5690 us, 5890 us) once shifted
    assert red["busy_s"] == pytest.approx(5.47e-3)
    # the gap before the first op lies under utp.enter_grid only once the
    # offset is applied; the rest under wait
    assert red["idle_by_span"] == pytest.approx({"wait": 4.21e-3, "utp.enter_grid": 3.2e-4})


def _ctx(monkeypatch, **counts):
    monkeypatch.setattr(pt, "load", lambda _: (OPS, MODULES, SPANS, DISPATCHES))
    spans = harness.Spans()
    spans.records = [("window", 1.0, 2.0)]
    return harness.Context(cell="x", config={"n": 16384, "tile": 512}, mix={"op": "cholesky"},
                           window_s=10.0, spans=spans, counts=dict(counts), setup={},
                           peaks=tiny.PEAKS, trace={})


def _read(name, ctx):
    return harness._load_module(harness.metric_path(name), "r_" + name.replace(".", "_")).read(ctx)


def test_kernel_rooflines_combine_into_pallas_roofline(monkeypatch):
    ctx = _ctx(monkeypatch, solutions=16)
    kernel_s = pt.summary(ctx)["trace"]["kernel_s"]
    ctx.trace = {"pallas_s": sum(kernel_s.values())}
    shares = {k: _read(f"roofline.{k}", ctx) for k in ("potrf", "trsm", "syrk", "gemm")}
    from bench import flops

    ideal = {t[0]: t[1] * max(t[2] / 197e12, t[3] / 819e9) for t in flops.cholesky_tasks(16384, 512)}
    for k, v in shares.items():
        assert v == pytest.approx(100 * 16 * ideal[k] / kernel_s[k])
    # each kernel's ideal time over the device time of all four
    combined = sum(ideal.values()) / sum(ideal[k] / shares[k] for k in shares)
    assert combined == pytest.approx(readers.pallas_roofline(ctx))
    share = _read("grid_copy_share.factor", ctx)
    assert share == pytest.approx(100 * 1.9e-3 / 5.47e-3)


def test_a_trace_without_named_kernels_reads_nothing(monkeypatch):
    ctx = _ctx(monkeypatch, solutions=16)
    unnamed = [(d, n.replace("trsm", "program").replace("potrf", "program")
                .replace("syrk", "program").replace("gemm", "program"), s, e, p, a)
               for d, n, s, e, p, a in OPS]
    monkeypatch.setattr(pt, "load", lambda _: (unnamed, MODULES, SPANS, DISPATCHES))
    for name in ("roofline.trsm", "roofline.gemm", "grid_copy_share.factor"):
        assert _read(name, ctx) is None
    ctx.trace = None
    assert _read("roofline.trsm", ctx) is None


def test_setup_metrics_read_the_ring_before_the_window(monkeypatch):
    tracing.clear()
    try:
        with tracing.span("utp.drain"):
            with tracing.span("utp.split"):
                with tracing.span("utp.plan"):
                    pass
                with tracing.span("utp.build") as build:
                    build.counts.update(trace_s=0.25, lower_s=0.5, compile_s=2.0)
        with tracing.span("utp.degrid") as degrid:
            degrid.counts.update(trace_s=0.125)
        recs = tracing.records()
        spans = harness.Spans()
        with spans("window"):
            with tracing.span("utp.split") as late:  # inside the window: not set-up
                late.counts["trace_s"] = 9.0
        ctx = _ctx(monkeypatch)
        ctx.spans = spans
        ns = {r[3]: r[5] - r[4] for r in recs}
        plan_s = (ns["utp.split"] - ns["utp.build"]) / 1e9
        assert _read("setup_plan_s", ctx) == pytest.approx(plan_s)
        assert _read("setup_lower_s", ctx) == pytest.approx(0.875)
        su = pt.summary(ctx)["setup"]
        assert su["spans"]["utp.build"] == {"n": 1, "self_s": ns["utp.build"] / 1e9,
                                            "trace_s": 0.25, "lower_s": 0.5, "compile_s": 2.0}
    finally:
        tracing.clear()
