"""The benchmark harness: finds a cell's files by name, runs it, reports it.

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration and a
traffic mix.  Everything specific to either is data or a file of its own:

- ``bench/configs/<file>.json``: the deployment (entry points, graph, sizes,
  precision, limits of the correctness check);
- ``bench/mixes/<traffic>.json``: the mix, which names its loop driver;
- ``bench/drivers/<driver>.py``: one module per loop kind, with a ``Driver``
  class (``__init__`` is set-up, ``run`` the window, ``check`` the comparison
  with the reference);
- ``bench/metrics/<metric>.py``: one reader per per-layer metric, with
  ``read(ctx)`` returning a number or ``None``.

So a later cell or metric is a new file and a new entry, never an edit.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".bench_cache"

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# -- finding a cell's files ------------------------------------------------------
def load_benchmark(path: Optional[Path] = None) -> dict:
    return json.loads(Path(path or ROOT / "BENCHMARK.json").read_text())


def _load_module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    driver_path: Path
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str, e2e_names: Optional[List[str]] = None) -> bool:
    """A metric listing ``workloads`` is reported in those cells; a per-layer
    metric without the list wherever the metric it ``moves`` is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def resolve(bench: dict, cell: str) -> Cell:
    """The configuration, mix, driver and metrics of ``cell``, by name."""
    work = {w["name"]: w for w in bench["workloads"]}
    if cell not in work:
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json: {sorted(work)}")
    w = work[cell]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    mix = json.loads((BENCH / "mixes" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, cell)]
    names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"] if _applies(m, cell, names)]
    return Cell(cell, int(w["chips"]), config, mix,
                BENCH / "drivers" / f"{mix['driver']}.py", e2e, per_layer)


def metric_path(name: str) -> Path:
    return BENCH / "metrics" / f"{name}.py"


# -- spans and the compile clock -------------------------------------------------
class Spans:
    """The benchmark's own spans around its calls into the program.

    Each span is kept as ``(name, t0, t1)`` on the host clock.  While
    ``tracing`` is set, a span is also a ``jax.profiler.TraceAnnotation``, so
    the profiler's trace shows what the host was doing in each device gap.
    """

    def __init__(self):
        self.records: List[Tuple[str, float, float]] = []
        self.tracing = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = None
        if self.tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.records.append((name, t0, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def clear(self) -> None:
        self.records = []

    def durations(self, name: str) -> List[float]:
        return [t1 - t0 for n, t0, t1 in self.records if n == name]


class CompileClock:
    """Seconds and count of backend compiles (cache loads included), from
    ``jax.monitoring``; ``mark()`` starts a new interval."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.seconds += secs
            self.count += 1

    def mark(self) -> Tuple[float, int]:
        return self.seconds, self.count


# -- context handed to per-layer readers -----------------------------------------
@dataclass
class Context:
    cell: str
    config: dict
    mix: dict
    window_s: float
    spans: Spans
    counts: Dict[str, Any]
    setup: Dict[str, float]
    peaks: dict
    trace: Optional[dict] = None


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(
            f"device kind {kind!r} is not in bench/peaks.json; add its published "
            f"peaks ({sorted(table['devices'])} are known)"
        )
    return table["devices"][kind]


# -- one run --------------------------------------------------------------------
def _log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def require_chips(chips: int) -> None:
    info = device_info()
    if info["platform"] != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform {info['platform']!r}); nothing measured")
    if info["count"] < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found {info['count']}")


def memory_peak() -> Optional[int]:
    import jax

    peaks = []
    for d in jax.local_devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 - backends without memory stats
            stats = {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: float,
    require_tpu: bool = True,
    peaks: Optional[dict] = None,
) -> dict:
    """Set up, measure, check and reduce one run of ``cell``; returns the
    result object (the last line of standard output)."""
    import jax

    if require_tpu:
        require_chips(cell.chips)
    info = device_info()
    if peaks is None:
        peaks = load_peaks(info["kind"])
    t_devices = time.perf_counter()
    clock = CompileClock()
    spans = Spans()
    drv_mod = _load_module(cell.driver_path, f"bench_driver_{cell.mix['driver']}")
    driver = drv_mod.Driver(cell.config, cell.mix, seed, spans)
    t_driver = time.perf_counter()
    compile_s, _ = clock.mark()
    spans.clear()

    trace_dir = CACHE / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True, exist_ok=True)
        # the benchmark's spans only: no Python function tracer on the host
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        spans.tracing = True
    # what set-up made stays put: the collector walks only what the window
    # makes, so a collection is no pause of tens of milliseconds
    gc.collect()
    gc.freeze()
    c0, n0 = clock.mark()
    setup_s = time.perf_counter() - t_start
    try:
        with spans("window"):
            out = driver.run(seconds)
    finally:
        gc.unfreeze()
        if trace:
            spans.tracing = False
            jax.profiler.stop_trace()
    c1, n1 = clock.mark()
    out.setdefault("counts", {})["window_compiles"] = n1 - n0
    out["counts"]["window_compile_s"] = c1 - c0
    peak = memory_peak()

    checks = driver.check()
    correct = bool(checks) and all(v <= lim for _, v, lim in checks) and out["failed"] == 0

    device = {**info, "memory_peak_bytes": peak}
    result: Dict[str, Any] = {
        "correct": correct,
        "attempted": int(out["attempted"]),
        "failed": int(out["failed"]),
    }
    window_s = out["window_s"]
    _log(json.dumps({
        "window_s": window_s,
        "compiles_in_window": n1 - n0,
        "memory_peak_bytes": peak,
        "counts": out["counts"],
    }))
    if trace:
        from . import trace as trace_mod

        ops, tspans = trace_mod.load(str(trace_dir))
        red = trace_mod.reduce(ops, tspans)
        ctx = Context(cell.name, cell.config, cell.mix, window_s, spans, out["counts"],
                      {"compile_s": compile_s}, peaks, red)
        metrics = {}
        for m in cell.per_layer:
            reader = _load_module(metric_path(m["name"]), "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {"device_ops": red["top_ops"], "idle_gaps": red["idle_gaps"]}
            _log(json.dumps({"trace": {k: red[k] for k in (
                "window_s", "busy_s", "idle_share", "pallas_s", "xla_s", "kind_s", "ops", "gaps",
                "idle_by_span")}}))
    else:
        values = {**out["metrics"], "setup_s": setup_s}
        metrics = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in values
        }
    _log(json.dumps({"setup_s": setup_s, "compile_s_in_setup": compile_s,
                     "to_devices_s": t_devices - t_start, "driver_setup_s": t_driver - t_devices}))
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {name: {"value": v, "limit": lim} for name, v, lim in checks}
    for name, v, lim in checks:
        _log(f"check {name} {v!r} limit {lim!r} {'ok' if v <= lim else 'FAIL'}")
    return result


def setup_process() -> None:
    """Before JAX is imported: the persistent compilation cache at a fixed
    path inside the checkout, taken for every program however short, and the
    TPU runtime's logs inside the checkout too."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    os.environ.setdefault("TPU_LOG_DIR", str(CACHE / "tpu_logs"))
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
