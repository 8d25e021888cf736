"""The program's own spans and tile-kernel names, read from a profiler trace
and from the program's span ring.

The program names every Pallas tile kernel after its operation
(``pallas_call(name="trsm")``).  The chip's profiler does not carry that
name in an event stat: it reaches the trace as the HLO instruction name at
the head of each ``XLA Ops`` event's text (``%trsm.31 = f32[...]
custom-call(...)``), so a named kernel is a Pallas op whose instruction,
less its ``.<n>`` suffix, is one of ``KERNELS``.  The program's spans
(``utp.*``, ``repro.core.tracing``) are ``TraceAnnotation``s on the host's
threads, beside the benchmark's own (``bench/trace.py``).

``reduce`` turns a trace into:

- the host-device clock offset.  The device's clock and the host's disagree
  by about a millisecond.  A program cannot start on the device before the
  host launched it, so the offset is the largest amount by which an
  execution (an ``XLA Modules`` event) starts before its launch on the
  host: a WaveProgram execution (one holding a named kernel) before the
  ``utp.launch`` span that issued it, and, where the device is one, any
  execution before the runtime's execute call (``DISPATCH``) paired with it
  in order.  The WaveProgram mostly waits behind the relayout programs the
  entry call issued first, so the bound comes from the first program of a
  call, which starts on an idle device.  The offset is added to every device
  time before anything else is computed;
- device seconds per named kernel;
- seconds of the XLA ``copy`` ops whose result is an operand of a named
  kernel (matched by instruction name in the kernel's HLO text), by the
  kernel they feed;
- busy and idle time, each idle gap labelled by the innermost span open at
  its midpoint, program spans included (``bench.trace.reduce``).

``setup`` reads the set-up part of the program's span ring, in the process
that ran the cell: self seconds and counts per span name for the spans that
ended before the ``window`` span opened.

``summary(ctx)`` computes both once per run for the per-layer readers and
prints one ``{"program_trace": ...}`` line on standard error.  A program
without spans or kernel names gives ``None`` for what it lacks.
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench import flops, harness, trace

KERNELS = ("potrf", "trsm", "syrk", "gemm", "getrf", "trsml", "trsmu", "trsmul", "gemmnn")
PREFIX = "utp."
LAUNCH = "utp.launch"
MODULE_LINE = "XLA Modules"
DISPATCH = "PJRT_LoadedExecutable_Execute linkage"
SETUP_PLAN = ("utp.split", "utp.plan", "utp.verify")
_OPERAND = re.compile(r"(?<![=\w])%([\w.-]+)")

# (device, name, start_ns, end_ns, pallas, operands)
Op = Tuple[int, str, int, int, bool, Tuple[str, ...]]
Module = Tuple[int, str, int, int]  # (device, name, start_ns, end_ns)


def operands(text: str) -> Tuple[str, ...]:
    """Instruction names an op's HLO text reads, in order (not ``calls=``)."""
    head, _, rest = text.partition("(")
    if " = " not in head:
        return ()
    return tuple(_OPERAND.findall(rest))


def kernel(name: str, pallas: bool) -> Optional[str]:
    """``trsm`` for a Pallas op named ``trsm.31 custom-call``, else None."""
    base = name.split(" ", 1)[0].split(".", 1)[0]
    return base if pallas and base in KERNELS else None


def load(trace_dir: str):
    """Device ops, module executions, host spans (program and benchmark) and
    the runtime's execute calls (start times) of the newest trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    path = trace.newest_xplane(trace_dir)
    if path is None:
        return [], [], [], []
    data = ProfileData.from_file(path)
    ops: List[Op] = []
    modules: List[Module] = []
    spans: List[trace.Span] = []
    dispatches: List[int] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            dev = int(plane.name[12:])
            for line in plane.lines:
                if line.name not in (trace.DEVICE_LINE, MODULE_LINE):
                    continue
                for ev in line.events:
                    start = int(ev.start_ns)
                    end = start + int(ev.duration_ns)
                    if line.name == MODULE_LINE:
                        modules.append((dev, ev.name, start, end))
                    else:
                        ops.append((dev, trace.op_name(ev.name), start, end,
                                    trace.is_pallas(ev.name), operands(ev.name)))
        elif plane.name.startswith("/host:"):
            for tid, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith(PREFIX) or ev.name in trace.SPAN_NAMES:
                        start = int(ev.start_ns)
                        spans.append((ev.name, start, start + int(ev.duration_ns), tid))
                    elif ev.name == DISPATCH:
                        dispatches.append(int(ev.start_ns))
    return ops, modules, spans, dispatches


def launch_leads(ops: Sequence[Op], modules: Sequence[Module], spans: Sequence[trace.Span],
                 dispatches: Sequence[int] = ()) -> Tuple[List[int], List[int]]:
    """How far each execution starts before its launch on the host, in ns:
    ``(by execute call, by utp.launch span)``, each empty where its
    executions and launches do not pair one to one."""
    runs = sorted(m[2] for m in modules)
    by_call: List[int] = []
    if dispatches and len(dispatches) == len(runs) and len({m[0] for m in modules}) == 1:
        by_call = [t - s for t, s in zip(sorted(dispatches), runs)]
    starts = sorted(o[2] for o in ops if kernel(o[1], o[4]))
    programs = []
    for _, _, s, e in sorted(modules, key=lambda m: m[2]):
        i = bisect.bisect_left(starts, s)
        if i < len(starts) and starts[i] < e:
            programs.append(s)
    launches = sorted(sp[1] for sp in spans if sp[0] == LAUNCH)
    by_span: List[int] = []
    if launches and len(launches) == len(programs):
        by_span = [t - s for t, s in zip(launches, programs)]
    return by_call, by_span


def reduce(ops: Sequence[Op], modules: Sequence[Module], spans: Sequence[trace.Span],
           dispatches: Sequence[int] = (), window: Optional[Tuple[int, int]] = None
           ) -> Optional[dict]:
    """The program's view of a trace (module docstring); ``None`` when the
    window holds no device op."""
    if window is None:
        wins = [sp for sp in spans if sp[0] == "window"]
        if not wins:
            return None
        window = (min(sp[1] for sp in wins), max(sp[2] for sp in wins))
    lo, hi = window
    by_call, by_span = launch_leads(ops, modules, spans, dispatches)
    offset = max(0, *by_call, *by_span) if by_call or by_span else None
    shift = offset or 0
    moved = [(d, n, s + shift, e + shift, p, args) for d, n, s, e, p, args in ops]
    red = trace.reduce([o[:5] for o in moved], spans, window)
    if red is None:
        return None
    nd = red["devices"]
    feeds: Dict[str, str] = {}
    kernel_ns: Dict[str, int] = defaultdict(int)
    for _, name, s, e, pallas, args in moved:
        k = kernel(name, pallas)
        if k is None:
            continue
        for a in args:
            feeds[a] = k
        s, e = max(s, lo), min(e, hi)
        if e > s:
            kernel_ns[k] += e - s
    copy_ns: Dict[str, int] = defaultdict(int)
    for _, name, s, e, pallas, _ in moved:
        instr = name.split(" ", 1)[0]
        if pallas or not trace.op_kind(name).startswith("copy") or instr not in feeds:
            continue
        s, e = max(s, lo), min(e, hi)
        if e > s:
            copy_ns[feeds[instr]] += e - s
    return {
        "offset_ns": offset,
        # after the shift no WaveProgram starts before its utp.launch: <= 0
        "launch_lead_ns": max(by_span) - shift if by_span else None,
        "window_s": red["window_s"],
        "busy_s": red["busy_s"],
        "pallas_s": red["pallas_s"],
        "kernel_s": {k: v / nd / 1e9 for k, v in sorted(kernel_ns.items())},
        "copy_s": {k: v / nd / 1e9 for k, v in sorted(copy_ns.items())},
        "idle_by_span": red["idle_by_span"],
    }


# -- the program's span ring ----------------------------------------------------
def _window_open_ns(ctx) -> Optional[int]:
    opens = [t0 for name, t0, _ in ctx.spans.records if name == "window"]
    return int(opens[0] * 1e9) if opens else None


def setup(ctx) -> Optional[dict]:
    """Per span name, over the ring's spans that ended before the window
    opened: how many, their self seconds (less their child spans) and their
    counts summed.  ``None`` without a ring or a window."""
    try:
        from repro.core import tracing
    except ImportError:
        return None
    w0 = _window_open_ns(ctx)
    if w0 is None:
        return None
    recs = [r for r in tracing.records() if r[5] < w0]
    if not recs:
        return None
    child_ns: Dict[int, int] = defaultdict(int)
    for _, parent, _, _, t0, t1, _ in recs:
        child_ns[parent] += t1 - t0
    out: Dict[str, dict] = {}
    for sid, _, _, name, t0, t1, counts in recs:
        row = out.setdefault(name, {"n": 0, "self_s": 0.0})
        row["n"] += 1
        row["self_s"] += (t1 - t0 - child_ns[sid]) / 1e9
        for k, v in counts.items():
            row[k] = row.get(k, 0) + v
    return {
        "spans": out,
        "first_to_window_s": (w0 - min(r[4] for r in recs)) / 1e9,
    }


# -- once per run, for the readers ------------------------------------------------
_last: list = [None, None]  # the Context last summarised (held), its summary


def summary(ctx) -> dict:
    """``{"trace": reduce(...) or None, "setup": setup(...) or None}`` for
    the run of ``ctx`` (the harness hands every reader of a run the same
    Context); printed once on standard error."""
    if _last[0] is ctx:
        return _last[1]
    red = None
    if ctx.trace is not None:
        red = reduce(*load(str(harness.CACHE / "trace" / ctx.cell)))
    out = {"trace": red, "setup": setup(ctx)}
    _last[:] = [ctx, out]
    print(json.dumps({"program_trace": out}), file=sys.stderr, flush=True)
    return out


def kernel_roofline(ctx, name: str) -> Optional[float]:
    """The ideal time of the window's ``name`` tasks at the chip's peaks
    over that kernel's device time, in %."""
    red = summary(ctx)["trace"]
    solutions = ctx.counts.get("solutions")
    if red is None or not red["kernel_s"].get(name) or not solutions:
        return None
    op, n, b = ctx.mix["op"], int(ctx.config["n"]), int(ctx.config["tile"])
    rows = [t for t in flops.tasks(op, n, b, int(ctx.mix.get("nrhs", 1))) if t[0] == name]
    ideal, _ = flops.roofline_s(rows, ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * solutions * ideal / red["kernel_s"][name]


def grid_copy_share(ctx) -> Optional[float]:
    """Device time of the copies that feed a named kernel over busy time, %."""
    red = summary(ctx)["trace"]
    if red is None or not red["kernel_s"]:
        return None
    return 100.0 * sum(red["copy_s"].values()) / red["busy_s"]


def setup_plan_s(ctx) -> Optional[float]:
    """Self seconds of splitting, planning and verifying before the window."""
    su = summary(ctx)["setup"]
    if su is None:
        return None
    return sum(su["spans"][n]["self_s"] for n in SETUP_PLAN if n in su["spans"])


def setup_lower_s(ctx) -> Optional[float]:
    """jaxpr trace and MLIR lowering seconds counted on program spans before
    the window."""
    su = summary(ctx)["setup"]
    if su is None:
        return None
    return sum(row.get("trace_s", 0.0) + row.get("lower_s", 0.0) for row in su["spans"].values())
