"""Reduction of a profiler trace to the device's busy and idle time.

``load`` reads the newest ``*.xplane.pb`` under a trace directory with
``jax.profiler.ProfileData`` and returns plain tuples:

- device ops: ``(device, name, start_ns, end_ns, pallas)`` from the ``XLA Ops``
  line of every ``/device:TPU:<i>`` plane.  The profiler names each op by its
  HLO text (``%program.125 = f32[...] custom-call(...), custom_call_target=
  "tpu_custom_call", ...``); ``name`` keeps the instruction and its opcode
  (``program.125 custom-call``).  An op is a Pallas kernel when its text
  names the Mosaic target ``tpu_custom_call``; every other op is XLA's;
- spans: ``(name, start_ns, end_ns, thread)`` for the benchmark's own
  ``TraceAnnotation`` spans on the host's threads (``SPAN_NAMES``).

``reduce`` turns those into the numbers the benchmark reports: busy time as
the union of op intervals inside the window (averaged over devices), the
idle share, device time by op name and by opcode, Pallas and XLA time, and
every idle gap labelled with the benchmark spans open on each host thread at
its midpoint.
The profiler puts host and device events on one clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_NAMES = ("window", "entry_call", "wait", "gc", "submit", "tick", "extract")
PALLAS_TARGET = "tpu_custom_call"
DEVICE_LINE = "XLA Ops"
_HLO = re.compile(r"^%?([\w.-]+) = .*?\s([a-z][\w-]*)\(")

Op = Tuple[int, str, int, int, bool]  # (device, name, start_ns, end_ns, pallas)
Span = Tuple[str, int, int, int]  # (name, start_ns, end_ns, thread)


def is_pallas(text: str) -> bool:
    """A device op is a Pallas kernel when its HLO text names the Mosaic
    custom-call target."""
    return PALLAS_TARGET in text


def op_name(text: str) -> str:
    """``program.125 custom-call`` from an op's HLO text; other names as they are."""
    m = _HLO.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text


def op_kind(name: str) -> str:
    """The opcode of a name from ``op_name`` (``custom-call``, ``copy``, ...)."""
    return name.rsplit(" ", 1)[-1]


def newest_xplane(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load(trace_dir: str) -> Tuple[List[Op], List[Span]]:
    """Device ops and benchmark spans of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    path = newest_xplane(trace_dir)
    if path is None:
        return [], []
    data = ProfileData.from_file(path)
    ops: List[Op] = []
    spans: List[Span] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            dev = int(plane.name[12:])
            for line in plane.lines:
                if line.name != DEVICE_LINE:
                    continue
                for ev in line.events:
                    start = int(ev.start_ns)
                    ops.append((dev, op_name(ev.name), start, start + int(ev.duration_ns),
                                is_pallas(ev.name)))
        elif plane.name.startswith("/host:"):
            for tid, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name in SPAN_NAMES:
                        start = int(ev.start_ns)
                        spans.append((ev.name, start, start + int(ev.duration_ns), tid))
    return ops, spans


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge overlapping ``(start, end)`` intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _gaps(busy: Sequence[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class _OpenSpans:
    """Which benchmark spans are open at a time, innermost per host thread."""

    def __init__(self, spans: Sequence[Span]):
        self.by_thread: Dict[int, List[Span]] = defaultdict(list)
        for sp in spans:
            if sp[0] != "window":
                self.by_thread[sp[3]].append(sp)
        self.starts = {}
        for tid, lst in self.by_thread.items():
            lst.sort(key=lambda sp: sp[1])
            self.starts[tid] = [sp[1] for sp in lst]

    def label(self, t: int) -> str:
        names = []
        for tid, lst in self.by_thread.items():
            i = bisect.bisect_right(self.starts[tid], t)
            inner = None
            # innermost: the latest-starting span still open at t
            for sp in reversed(lst[max(0, i - 8) : i]):
                if sp[2] > t:
                    inner = sp[0]
                    break
            if inner is not None:
                names.append(inner)
        return "+".join(sorted(names)) if names else "none"


def reduce(ops: Sequence[Op], spans: Sequence[Span], window: Optional[Tuple[int, int]] = None,
           top: int = 10) -> Optional[dict]:
    """Busy/idle share, op time and labelled idle gaps within ``window``
    (default: the ``window`` span, else the extent of the ops).  ``None``
    when the trace holds no device op in the window."""
    if window is None:
        wins = [sp for sp in spans if sp[0] == "window"]
        if wins:
            window = (min(sp[1] for sp in wins), max(sp[2] for sp in wins))
        elif ops:
            window = (min(o[2] for o in ops), max(o[3] for o in ops))
        else:
            return None
    lo, hi = window
    devices = sorted({o[0] for o in ops})
    if hi <= lo or not devices:
        return None
    busy_ns, op_ns, kind_ns = 0, defaultdict(int), defaultdict(int)
    pallas_ns = xla_ns = 0
    gaps: List[Tuple[int, int]] = []
    for dev in devices:
        mine = [o for o in ops if o[0] == dev]
        busy = union(_clip([(o[2], o[3]) for o in mine], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        gaps += _gaps(busy, lo, hi)
        for _, name, s, e, pallas in mine:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            op_ns[name] += e - s
            kind_ns[op_kind(name)] += e - s
            if pallas:
                pallas_ns += e - s
            else:
                xla_ns += e - s
    if busy_ns == 0:
        return None
    nd = len(devices)
    open_spans = _OpenSpans(spans)
    idle_by_span: Dict[str, int] = defaultdict(int)
    labelled = []
    for s, e in gaps:
        lab = open_spans.label((s + e) // 2)
        idle_by_span[lab] += e - s
        labelled.append((lab, e - s))
    labelled.sort(key=lambda x: -x[1])
    window_s = (hi - lo) / 1e9
    busy_s = busy_ns / nd / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "pallas_s": pallas_ns / nd / 1e9,
        "xla_s": xla_ns / nd / 1e9,
        "devices": nd,
        "ops": len(ops),
        "kind_s": {k: v / nd / 1e9 for k, v in sorted(kind_ns.items(), key=lambda kv: -kv[1])},
        "top_ops": [[k, v / nd / 1e9] for k, v in sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[lab, ns / 1e9] for lab, ns in labelled[:top]],
        "idle_by_span": {k: v / nd / 1e9 for k, v in sorted(idle_by_span.items(), key=lambda kv: -kv[1])},
        "gaps": len(gaps),
    }
