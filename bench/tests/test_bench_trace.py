"""The reduction from a profiler trace to busy time, idle share and gaps."""

from bench import trace


def test_union_merges_overlapping_and_touching_intervals():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (8, 9)]) == [(0, 4), (5, 7), (8, 9)]


# op names as the profiler writes them on a v5e (chol16k trace, shortened)
PALLAS_TEXT = ('%program.125 = f32[32,32,512,512]{3,2,1,0:T(8,128)} custom-call(s32[62]{0:T(128)S(1)} '
               '%reshape.247, f32[32,32,512,512]{3,2,1,0:T(8,128)} %copy.479), '
               'custom_call_target="tpu_custom_call", output_to_operand_aliasing={{}: (3, {})}')
COPY_TEXT = '%copy.1 = f32[16384,16384]{1,0:T(8,128)} copy(f32[16384,16384]{1,0:T(8,128)} %args_0_.1)'
ASYNC_TEXT = ('%copy-start.71 = (s32[31,2]{0,1:T(2,128)S(1)}, s32[31,2]{0,1:T(2,128)}, u32[]{:S(2)}) '
              'copy-start(s32[31,2]{0,1:T(2,128)} %p)')


def test_pallas_kernels_are_told_apart_from_xla_ops():
    assert trace.is_pallas(PALLAS_TEXT)
    assert not trace.is_pallas(COPY_TEXT)
    assert not trace.is_pallas("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop")


def test_op_names_keep_the_instruction_and_its_opcode():
    assert trace.op_name(PALLAS_TEXT) == "program.125 custom-call"
    assert trace.op_name(COPY_TEXT) == "copy.1 copy"
    assert trace.op_name(ASYNC_TEXT) == "copy-start.71 copy-start"
    assert trace.op_name("kernel") == "kernel"
    assert trace.op_kind("program.125 custom-call") == "custom-call"


def _recorded():
    # two kernels and an overlapping XLA op on one device, spans on two threads
    ops = [
        (0, "program.1 custom-call", 100, 200, True),
        (0, "fusion.1 fusion", 150, 300, False),
        (0, "program.2 custom-call", 500, 600, True),
    ]
    spans = [
        ("window", 0, 1000, 1),
        ("entry_call", 50, 450, 1),
        ("wait", 450, 700, 1),
        ("tick", 600, 900, 2),
    ]
    return ops, spans


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    red = trace.reduce(*_recorded())
    assert red["window_s"] == 1000e-9
    assert red["busy_s"] == 300e-9  # [100, 300] and [500, 600]
    assert abs(red["idle_share"] - 0.7) < 1e-12
    assert red["pallas_s"] == 200e-9 and red["xla_s"] == 150e-9
    assert red["top_ops"][0] == ["fusion.1 fusion", 150e-9]
    assert red["kind_s"] == {"custom-call": 200e-9, "fusion": 150e-9}


def test_gaps_are_labelled_with_the_spans_open_at_their_midpoint():
    red = trace.reduce(*_recorded())
    # gaps [0,100] (entry_call), [300,500] (entry_call), [600,1000] (tick)
    assert red["gaps"] == 3
    assert red["idle_gaps"][0] == ["tick", 400e-9]
    assert red["idle_by_span"] == {"tick": 400e-9, "entry_call": 300e-9}


def test_gap_with_spans_open_on_two_threads_names_both():
    ops = [(0, "kernel", 0, 10, True), (0, "kernel", 90, 100, True)]
    spans = [("wait", 5, 95, 1), ("submit", 20, 80, 2)]
    red = trace.reduce(ops, spans, window=(0, 100))
    assert red["idle_gaps"] == [["submit+wait", 80e-9]]


def test_ops_outside_the_window_are_clipped_and_no_ops_gives_nothing():
    ops = [(0, "kernel", -50, 50, True), (0, "fusion", 900, 1200, False)]
    red = trace.reduce(ops, [("window", 0, 1000, 0)])
    assert red["busy_s"] == 150e-9 and red["pallas_s"] == 50e-9
    assert trace.reduce([], [("window", 0, 1000, 0)]) is None


def test_busy_is_averaged_over_devices():
    ops = [(0, "kernel", 0, 100, True), (1, "kernel", 0, 50, True)]
    red = trace.reduce(ops, [], window=(0, 100))
    assert red["devices"] == 2 and red["busy_s"] == 75e-9


def test_an_excerpt_of_a_chip_trace_reduces_as_read_by_hand():
    # chol16k on a v5e, seed 13, around the second entry call; times in ns
    # from 669879663.  The device finishes the first call's last fusion, idles
    # while the host wakes from the wait, then copies the next input into its
    # grid.  The trace's device clock runs about 1 ms ahead of the host's, so
    # the copies start before the entry_call span that issued them.
    ops = [
        (0, "fusion fusion", -2502328, 741553, False),
        (0, "copy.1 copy", 1995168, 5245607, False),
        (0, "copy.1 copy", 5249680, 8522460, False),
        (0, "bitcast_bitcast_fusion fusion", 8522462, 11784592, False),
    ]
    spans = [
        ("wait", -618356704, 2960900, 2),
        ("entry_call", 3000000, 4567260, 2),
        ("wait", 4595980, 625790523, 2),
    ]
    red = trace.reduce(ops, spans, window=(0, 10567260))
    busy = 741553 + (5245607 - 1995168) + (8522460 - 5249680) + (10567260 - 8522462)
    assert busy == 9309570
    assert red["busy_s"] == busy / 1e9 and red["xla_s"] == busy / 1e9 and red["pallas_s"] == 0
    assert abs(red["idle_share"] - 1257690 / 10567260) < 1e-12
    assert red["gaps"] == 3
    assert red["idle_gaps"][0] == ["wait", (1995168 - 741553) / 1e9]
    assert red["idle_by_span"] == {"wait": 1257690 / 1e9}
    assert red["kind_s"] == {"copy": 6523219 / 1e9, "fusion": 2786351 / 1e9}
