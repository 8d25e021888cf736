import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
).strip()

"""Multi-pod dry-run (assignment §MULTI-POD DRY-RUN).

For every supported (architecture x input-shape) cell, lower + compile the
step program for the production mesh — (16,16)=256 chips single-pod and
(2,16,16)=512 chips multi-pod — with ShapeDtypeStruct inputs (no
allocation), then extract:

    compiled.memory_analysis()   -> fits-in-HBM proof
    compiled.cost_analysis()     -> FLOPs / bytes for the roofline
    compiled.as_text()           -> collective bytes (parsed)

Results land in benchmarks/results/<mesh>/<arch>__<shape>.json.

Usage:
    python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k --mesh pod
    python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from ..configs import ARCHS, SHAPES, cell_supported, get_arch, get_shape
from . import roofline as rl
from .mesh import make_production_mesh
from .steps import make_step

RESULTS = Path(__file__).resolve().parents[3] / "benchmarks" / "results"


def memory_stats(compiled):
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None, "memory_analysis unavailable"
    if ma is None:
        return None, "memory_analysis None"
    out = {}
    for k in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
        "alias_size_in_bytes",
    ):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if out:
        out["total"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0)
        )
    return out or None, ""


def _parse_overrides(pairs):
    """['score_dtype=bf16', 'microbatches=8'] -> dict with typed values."""
    out = {}
    for p in pairs or []:
        k, v = p.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        if v in ("true", "True"):
            v = True
        if v in ("false", "False"):
            v = False
        out[k] = v
    return out


def run_cell(arch: str, shape: str, mesh_name: str, save_hlo: bool = False,
             rules_variant: str = "default", tag: str = "",
             overrides=None):
    import dataclasses

    cfg = get_arch(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    sh = get_shape(shape)
    if not cell_supported(cfg, sh):
        print(f"SKIP {arch} x {shape}: needs sub-quadratic attention")
        return None
    mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    chips = mesh.size
    t0 = time.time()
    plan = make_step(cfg, mesh, sh)
    lowered = plan.lower()
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = dict(compiled.cost_analysis() or {})
    mem, mem_note = memory_stats(compiled)
    hlo = compiled.as_text()
    r = rl.analyze(
        cfg, sh, mesh_name, chips, cost, hlo,
        memory_stats=mem, notes=mem_note,
    )
    rec = json.loads(r.to_json())
    rec.update(
        step=plan.name,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        hlo_bytes_text=len(hlo),
        memory=mem,
        rules_variant=rules_variant,
        overrides={k: str(v) for k, v in (overrides or {}).items()},
        tag=tag,
    )
    outdir = RESULTS / mesh_name
    outdir.mkdir(parents=True, exist_ok=True)
    stem = f"{arch}__{shape}" + (f"__{tag}" if tag else "")
    (outdir / f"{stem}.json").write_text(json.dumps(rec, indent=1))
    if save_hlo:
        (outdir / f"{stem}.hlo.txt").write_text(hlo)
    print(
        f"OK {mesh_name} {arch} x {shape}: compile={t_compile:.0f}s "
        f"compute={r.compute_s*1e3:.2f}ms memory={r.memory_s*1e3:.2f}ms "
        f"coll={r.collective_s*1e3:.2f}ms bottleneck={r.bottleneck} "
        f"useful={r.useful_ratio:.2f} mfu_bound={r.mfu_bound:.3f}"
    )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument(
        "--override", action="append", default=[],
        help="cfg field override, e.g. --override score_dtype=bf16",
    )
    args = ap.parse_args()
    overrides = _parse_overrides(args.override)

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    cells = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failed = []
    for mesh_name in meshes:
        for a, s in cells:
            try:
                run_cell(a, s, mesh_name, save_hlo=args.save_hlo, tag=args.tag,
                         overrides=overrides)
            except Exception as e:  # noqa: BLE001 — report, keep sweeping
                failed.append((mesh_name, a, s, repr(e)))
                print(f"FAIL {mesh_name} {a} x {s}: {e}")
                traceback.print_exc()
    if failed:
        raise SystemExit(f"{len(failed)} cells failed: {failed}")


if __name__ == "__main__":
    main()
