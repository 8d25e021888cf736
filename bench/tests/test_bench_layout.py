"""BENCHMARK.json resolves, by name, to the files of each cell and metric."""

import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import tiny

BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"] and BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_to_its_files(cell):
    c = harness.resolve(BENCH, cell)
    assert c.driver_path.is_file()
    assert c.config["name"] in {x["name"] for x in BENCH["configs"]}
    reported = {m["name"] for m in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert c.per_layer, f"{cell} reports no per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in reported
    for name in c.config["limits"]:
        assert name.startswith("ref_gap.")


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    mod = harness._load_module(harness.metric_path(metric), "m_" + metric.replace(".", "_"))
    assert callable(mod.read)


def test_names_units_and_layers_follow_the_rules():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]


def test_peaks_are_keyed_by_device_kind_and_unknown_devices_are_refused():
    assert harness.load_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary")


def test_run_refuses_to_measure_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=harness.ROOT, timeout=120,
    )
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def _ctx(**kw):
    base = dict(cell="x", config={"n": 16384, "tile": 512}, mix={"op": "cholesky"},
                window_s=10.0, spans=harness.Spans(), counts={}, setup={"compile_s": 3.5},
                peaks=tiny.PEAKS)
    base.update(kw)
    return harness.Context(**base)


def _reader(name):
    return harness._load_module(harness.metric_path(name), "r_" + name.replace(".", "_")).read


def test_readers_compute_from_spans_counters_and_trace():
    spans = harness.Spans()
    spans.records = [("entry_call", 0.0, 0.01), ("entry_call", 1.0, 1.03), ("wait", 1.03, 1.6)]
    ctx = _ctx(spans=spans, counts={"solutions": 16})
    assert _reader("host_ms.factor")(ctx) == pytest.approx(20.0)
    assert _reader("mfu.factor")(ctx) == pytest.approx(100 * 16 * 16384**3 / 3 / 10.0 / 197e12)
    assert _reader("compile_s")(ctx) == 3.5
    assert _reader("idle_share.factor")(ctx) is None
    assert _reader("pallas_roofline")(ctx) is None
    ctx.trace = {"idle_share": 0.25, "pallas_s": 8.0}
    assert _reader("idle_share.factor")(ctx) == 25.0
    ideal = sum(c * max(f / 197e12, b / 819e9) for _, c, f, b in
                __import__("bench.flops", fromlist=["x"]).cholesky_tasks(16384, 512))
    assert _reader("pallas_roofline")(ctx) == pytest.approx(100 * 16 * ideal / 8.0)
