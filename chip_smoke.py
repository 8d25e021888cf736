#!/usr/bin/env python3
"""Bring-up check of the task runtime on TPU, through its user entry points.

    python chip_smoke.py [--seed 0]              # one chip: phases A and B
    python chip_smoke.py --four-chips [--seed 0]  # g3/g4 on a (4, 1) mesh only

Everything runs in this one process, which holds the chip.  Inputs are made
on the device from ``--seed``.

Phase A: one f32 matrix in 512-wide tiles, factored by ``run_cholesky`` and
solved by ``run_lu_solve`` (128 right-hand sides): at n = 16384 (1 GiB)
under g2p (Pallas tile kernels), then at n = 8192 under g2p and g2 (XLA
leaves; at n = 16384 g2's LU drain needs ~16 GB of temporaries).  The g2p
drain programs must contain ``tpu_custom_call``, so no kernel ran in
interpret mode.

Phase B: ``BatchServer`` on g2p serves 16 ``lu_solve`` and 16 ``cholesky``
requests at n = 512 in 4x4 blocks (128-wide tiles) per tick, for two ticks;
the second tick must compile nothing and launch once per bucket.

``--four-chips``: g3 and g4 Cholesky and LU-solve at n = 16384 with
partitions ((4, 4), (8, 8)) on a (4, 1) mesh; each result must stay sharded
as four distinct row blocks.

Every solve is judged by HPL's scaled residual
``|Ax - b| / (eps (|A| |x| + |b|) n) < 16`` and every Cholesky factor by
``|LL^T - A| / (eps |A| n) < 16`` (infinity norms, products at
``Precision.HIGHEST``).  Each phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}``.  Any failure raises, so the exit code is
nonzero and no such line is printed.  JAX must find a TPU: there is no CPU
fallback.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import compat  # noqa: E402
from repro.core.executors import clear_compile_cache, drain_memo_records  # noqa: E402
from repro.linalg import run_cholesky, run_lu_solve  # noqa: E402
from repro.serve import BatchServer  # noqa: E402

HIGHEST = jax.lax.Precision.HIGHEST
LIMIT = 16.0  # HPL's pass threshold on the scaled residual
_EVENTS = {"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}


def _on_duration(event, secs, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _EVENTS["compile_s"] += secs


def _on_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        _EVENTS["cache_hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _EVENTS["cache_misses"] += 1


# -- inputs, made on the device ---------------------------------------------
@functools.partial(jax.jit, static_argnums=1)
def spd_input(key, n):
    """Symmetric positive definite: G G^T / n + 2 I (as ``spd_matrix``)."""
    g = jax.random.normal(key, (n, n), jnp.float32)
    a = jnp.matmul(g, g.T, precision=HIGHEST) / n + 2.0 * jnp.eye(n, dtype=jnp.float32)
    return (a + a.T) / 2


@functools.partial(jax.jit, static_argnums=1)
def dd_input(key, n):
    """Strictly column-diagonally-dominant (as ``dd_matrix``): LU needs no
    pivoting."""
    k1, k2 = jax.random.split(key)
    a = jax.random.normal(k1, (n, n), jnp.float32)
    a = a / (jnp.sum(jnp.abs(a), axis=0, keepdims=True) * 1.5)
    diag = 1.0 + jax.random.uniform(k2, (n,), jnp.float32)
    return jnp.where(jnp.eye(n, dtype=bool), diag[None, :], a)


# -- residuals ---------------------------------------------------------------
def _norm_inf(m):
    return jnp.max(jnp.sum(jnp.abs(m), axis=-1))


@jax.jit
def hpl_residual(a, x, b):
    """|Ax - b| / (eps (|A| |x| + |b|) n), infinity norms."""
    x = x.reshape(x.shape[0], -1)
    b = b.reshape(b.shape[0], -1)
    eps = jnp.finfo(jnp.float32).eps
    r = jnp.matmul(a, x, precision=HIGHEST) - b
    scale = eps * (_norm_inf(a) * _norm_inf(x) + _norm_inf(b)) * a.shape[0]
    return _norm_inf(r) / scale


@jax.jit
def factor_residual(a, l):
    """|LL^T - A| / (eps |A| n), infinity norms."""
    eps = jnp.finfo(jnp.float32).eps
    r = jnp.matmul(l, l.T, precision=HIGHEST) - a
    return _norm_inf(r) / (eps * _norm_inf(a) * a.shape[0])


def _check(name, value):
    value = float(value)
    if not value < LIMIT:  # also catches NaN
        raise AssertionError(f"{name}: scaled residual {value} >= {LIMIT}")
    return value


# -- timing ------------------------------------------------------------------
def _timed(fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


@contextlib.contextmanager
def _compile_clock(into: dict):
    c0 = _EVENTS["compile_s"]
    yield
    into["compile_s"] = _EVENTS["compile_s"] - c0


def _first_and_steady(fn) -> tuple:
    """Run ``fn`` cold (compiles) then warm; returns (out, timings)."""
    t: dict = {}
    with _compile_clock(t):
        _, t["first_s"] = _timed(fn)
    out, t["steady_s"] = _timed(fn)
    return out, t


def _emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def _grid_structs(roots, rec, sharding=None):
    """ShapeDtypeStructs of a program record's resident grids."""
    out = []
    for slot, (br, bc) in zip(rec.root_slots, rec.blocks):
        r, c = roots[slot].shape
        out.append(
            jax.ShapeDtypeStruct(
                (r // br, c // bc, br, bc), roots[slot].dtype, sharding=sharding
            )
        )
    return tuple(out)


def _program_hlo(roots, sharding=None, compiled=False) -> str:
    """HLO of the first drain program captured since the memo was cleared
    (a single-level drain has one; a two-level drain one per level-1 step)."""
    rec = drain_memo_records()[0]
    low = rec.fn.lower(_grid_structs(roots, rec, sharding), rec.idxs)
    return low.compile().as_text() if compiled else low.as_text()


# -- phases ------------------------------------------------------------------
def _cholesky(a, graph, partitions, mesh=None, pallas_check=False):
    clear_compile_cache()
    l, t = _first_and_steady(
        lambda: run_cholesky(a, graph=graph, partitions=partitions, mesh=mesh)
    )
    line = {"op": "cholesky", "graph": graph, **t}
    line["residual"] = _check(f"cholesky {graph}", factor_residual(a, l))
    if pallas_check:
        line["tpu_custom_calls"] = _program_hlo([a]).count("tpu_custom_call")
        assert line["tpu_custom_calls"] > 0, "g2p program has no Pallas kernel"
    return l, line


def _lu_solve(a, b, graph, partitions, b_partitions, mesh=None, pallas_check=False):
    clear_compile_cache()
    x, t = _first_and_steady(
        lambda: run_lu_solve(
            a, b, graph=graph, partitions=partitions,
            b_partitions=b_partitions, mesh=mesh,
        )
    )
    line = {"op": "lu_solve", "graph": graph, **t}
    line["residual"] = _check(f"lu_solve {graph}", hpl_residual(a, x, b))
    if pallas_check:
        line["tpu_custom_calls"] = _program_hlo([a, b]).count("tpu_custom_call")
        assert line["tpu_custom_calls"] > 0, "g2p program has no Pallas kernel"
    return x, line


def phase_a(key, n, graphs, tile=512, nrhs=128):
    """One large factorization and solve per graph, in ``tile``-wide tiles."""
    p = n // tile
    parts = ((p, p),)
    head = {"phase": "A", "n": n, "tile": tile}
    ka, kd, kb = jax.random.split(key, 3)
    a = spd_input(ka, n)
    for graph in graphs:
        l, line = _cholesky(a, graph, parts, pallas_check=graph == "g2p")
        del l
        _emit({**head, **line})
    del a
    a = dd_input(kd, n)
    b = jax.random.normal(kb, (n, nrhs), jnp.float32)
    for graph in graphs:
        x, line = _lu_solve(a, b, graph, parts, ((p, 1),), pallas_check=graph == "g2p")
        del x
        _emit({**head, "nrhs": nrhs, **line})


def phase_b(key, n=512, p=4, requests=16):
    """BatchServer on g2p: two ticks of ``requests`` lu_solve + cholesky."""
    parts = ((p, p),)
    srv = BatchServer(graph="g2p")
    for tick in (1, 2):
        keys = jax.random.split(jax.random.fold_in(key, tick), 3 * requests)
        mats = [dd_input(k, n) for k in keys[:requests]]
        rhss = [jax.random.normal(k, (n,), jnp.float32) for k in keys[requests : 2 * requests]]
        spds = [spd_input(k, n) for k in keys[2 * requests :]]
        t: dict = {}
        with _compile_clock(t):
            t0 = time.perf_counter()
            futs_lu = [srv.lu_solve(a, b, partitions=parts) for a, b in zip(mats, rhss)]
            futs_ch = [srv.cholesky(a, partitions=parts) for a in spds]
            report = srv.tick()
            xs = jax.block_until_ready([f.result() for f in futs_lu])
            ls = jax.block_until_ready([f.result() for f in futs_ch])
            t["tick_s"] = time.perf_counter() - t0
        res_lu = max(
            _check(f"served lu_solve #{i}", hpl_residual(a, x, b))
            for i, (a, x, b) in enumerate(zip(mats, xs, rhss))
        )
        res_ch = max(
            _check(f"served cholesky #{i}", factor_residual(a, l))
            for i, (a, l) in enumerate(zip(spds, ls))
        )
        launches = [bk["launches"] for bk in report.per_bucket]
        _emit({
            "phase": "B", "graph": "g2p", "n": n, "tile": n // p, "tick": tick,
            "requests": report.requests, "buckets": report.buckets,
            "launches": launches, "compiles": report.compiles,
            "residual_lu_solve": res_lu, "residual_cholesky": res_ch, **t,
        })
        assert report.resolved == 2 * requests, report
        if tick == 2:
            assert report.compiles == 0, f"second tick compiled {report.compiles}"
            assert launches == [1] * report.buckets == [1, 1], launches


def _row_blocks(x, count):
    """Assert ``x`` is sharded as ``count`` distinct row blocks."""
    starts = {s.index[0].start or 0 for s in x.addressable_shards}
    if len(starts) != count:
        raise AssertionError(
            f"result holds {len(starts)} distinct row blocks, want {count}: "
            f"{[s.index for s in x.addressable_shards]}"
        )
    return sorted(starts)


def phase_four_chips(key, n=16384, nrhs=128):
    """g3 and g4 on a (4, 1) mesh: the distributed path only."""
    mesh = compat.make_mesh((4, 1), ("data", "model"), devices=jax.devices()[:4])
    parts = ((4, 4), (8, 8))
    head = {"phase": "four_chips", "n": n, "tile": n // 32, "mesh": [4, 1]}
    ka, kd, kb = jax.random.split(key, 3)
    a = spd_input(ka, n)
    for graph in ("g3", "g4"):
        l, line = _cholesky(a, graph, parts, mesh=mesh)
        line["row_blocks"] = _row_blocks(l, 4)
        if graph == "g4":
            sh = NamedSharding(mesh, P("data", None, None, None))
            hlo = _program_hlo([a], sh, compiled=True)
            line["collectives"] = {
                op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
                for op in ("all-gather", "all-reduce", "collective-permute",
                           "all-to-all", "reduce-scatter")
            }
            line["tpu_custom_calls"] = hlo.count("tpu_custom_call")
        del l
        _emit({**head, **line})
    del a
    a = dd_input(kd, n)
    b = jax.random.normal(kb, (n, nrhs), jnp.float32)
    for graph in ("g3", "g4"):
        x, line = _lu_solve(a, b, graph, parts, ((4, 1), (8, 1)), mesh=mesh)
        line["row_blocks"] = _row_blocks(x, 4)
        del x
        _emit({**head, "nrhs": nrhs, **line})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the g3/g4 path on a (4, 1) mesh",
    )
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
            "this check has no CPU fallback"
        )
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        raise SystemExit(f"chip_smoke: needs {want} chips, found {len(devices)}")
    cache_dir = compat.enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)

    key = jax.random.key(args.seed)
    if args.four_chips:
        phase_four_chips(key)
    else:
        phase_a(jax.random.fold_in(key, 0), 16384, ("g2p",))
        # g2's XLA leaves need ~16 GB of temporaries for the LU drain at
        # n = 16384, so g2 and g2p are compared at n = 8192
        phase_a(jax.random.fold_in(key, 1), 8192, ("g2p", "g2"))
        phase_b(jax.random.fold_in(key, 2))
    _emit({"compile_cache": cache_dir, **{k: _EVENTS[k] for k in ("cache_hits", "cache_misses")}})
    d = devices[0]
    _emit({
        "ok": True,
        "device": {"platform": d.platform, "kind": d.device_kind, "count": len(devices)},
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
