"""Compiles of the Pallas tile kernels for a described TPU v5e (no chip).

Interpret mode on the CPU never checks Mosaic lowering, tiling or VMEM
limits; these compiles do.  Every ``GRID_FUSED`` kernel and every
``batched_*`` panel kernel is compiled in f32 at tile widths 128, 256 and 512
(resident and stacked grid forms, plus the rectangular and vector
right-hand sides of the solve drains), and one g4-style fused group is
compiled on a 2x2 mesh inside ``ShardExecutor``'s ``shard_map`` wrapper.
A g2p Cholesky drain program and a stacked gemm group are compiled to show
that a grid the arguments share reaches each kernel once, uncopied.

The topology is described only inside the module-scoped fixtures below:
loading the TPU compiler takes a process-wide lock, so it must happen in
the one worker that runs this file, never at import or collection.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.compat import make_mesh
from repro.core import spd_matrix
from repro.core.executors import ShardExecutor, clear_compile_cache, drain_memo_records
from repro.kernels import tile_linalg as tl
from repro.linalg import run_cholesky

TILES = [128, 256, 512]
ARITY = {
    "potrf": 1, "getrf": 1, "trsm": 2, "syrk": 2, "trsml": 2, "trsmu": 2,
    "trsmul": 2, "gemm": 3, "gemmnn": 3,
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs outside the tree
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2"
                )
            except Exception as e:  # noqa: BLE001
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _struct(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # a Mosaic kernel, not interpreted HLO
    return text


def _grid_call(op):
    """The fused kernel with one grid per argument."""
    fn, _ = tl.GRID_FUSED[op]
    return lambda idxs, grids: fn(
        idxs, grids, tuple(range(len(grids))), interpret=False
    )


@pytest.mark.parametrize("b", TILES)
@pytest.mark.parametrize("op", sorted(tl.GRID_FUSED))
def test_grid_fused_compiles(one_chip, op, b):
    k = ARITY[op]
    idxs = [_struct(one_chip, (3, 2), jnp.int32)] * k
    grids = [_struct(one_chip, (4, 4, b, b))] * k
    _compile(_grid_call(op), idxs, grids)


@pytest.mark.parametrize("b", TILES)
def test_grid_getrf_stacked_compiles(one_chip, b):
    idxs = [_struct(one_chip, (3, 2), jnp.int32)]
    _compile(_grid_call("getrf"), idxs, [_struct(one_chip, (2, 4, 4, b, b))])


@pytest.mark.parametrize(
    "b,width,lanes",
    [(512, 128, None), (512, 1, None), (128, 1, 2)],
    ids=["tile512-rhs128", "tile512-vector", "tile128-vector-stacked"],
)
@pytest.mark.parametrize("op", ["trsml", "trsmul", "gemmnn"])
def test_grid_solve_rhs_compiles(one_chip, op, b, width, lanes):
    lead = () if lanes is None else (lanes,)
    square = _struct(one_chip, lead + (4, 4, b, b))
    rhs = _struct(one_chip, lead + (4, 1, b, width))
    grids = [square, rhs] + ([rhs] if op == "gemmnn" else [])
    idxs = [_struct(one_chip, (3, 2), jnp.int32)] * len(grids)
    _compile(_grid_call(op), idxs, grids)


@pytest.mark.parametrize("b", TILES)
@pytest.mark.parametrize("op", sorted(ARITY))
def test_batched_compiles(one_chip, op, b):
    fn = getattr(tl, f"batched_{op}")
    tiles = [_struct(one_chip, (4, b, b))] * ARITY[op]
    _compile(lambda *t: fn(*t, interpret=False), *tiles)


def test_g4_sharded_group_compiles(topo):
    """A fused gemm group on row-sharded grids of a 2x2 mesh, wrapped the
    way ShardExecutor wraps a g4 program: Mosaic kernels cannot be
    partitioned automatically, so the wrapper must make this compile."""
    mesh = make_mesh((4, 1), ("data", "model"), devices=topo.devices)
    rows = NamedSharding(mesh, P("data", None, None, None))
    wrap = ShardExecutor(mesh, backend="pallas")._wrap_program()
    call = _grid_call("gemm")
    program = jax.jit(
        wrap(lambda grids, idxs: (call(idxs, grids),)), out_shardings=(rows,)
    )
    grids = [_struct(rows, (8, 8, 128, 128))] * 3
    idxs = [_struct(NamedSharding(mesh, P()), (3, 2), jnp.int32)] * 3
    text = program.lower(grids, idxs).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text  # the replicated-compute cost, made explicit


def _assert_grid_passed_once(text: str, grid: str, kernels: int) -> None:
    """No copy of the ``grid``-typed buffer, and each of the ``kernels``
    Mosaic calls takes one operand of that type."""
    lines = text.splitlines()
    copies = [
        ln for ln in lines
        if re.search(r"\scopy(-start)?\(", ln) and grid in ln.split(" copy")[0]
    ]
    assert copies == []
    calls = [ln for ln in lines if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == kernels
    for ln in calls:
        operands = re.search(
            r"operand_layout_constraints=\{(.*?)\}, output_to_operand_aliasing", ln
        ).group(1)
        assert operands.count(grid + "{") == 1, ln


def test_cholesky_drain_program_passes_its_grid_once(one_chip, monkeypatch):
    """The g2p drain at n = 2048 in 512-wide tiles: every trsm, syrk and gemm
    group reads blocks of the one matrix, and none makes XLA copy it."""
    clear_compile_cache()
    run_cholesky(spd_matrix(256), graph="g2p", partitions=((4, 4),)).block_until_ready()
    (rec,) = drain_memo_records()
    # trace afresh with Mosaic kernels, as on the chip (interpret mode off)
    monkeypatch.setattr(tl, "default_interpret", lambda: False)
    jax.clear_caches()
    try:
        grid = _struct(one_chip, (4, 4, 512, 512))
        idxs = _struct(one_chip, rec.idxs.shape, rec.idxs.dtype)
        text = rec.fn.lower((grid,), idxs).compile().as_text()
    finally:
        # drop the Mosaic trace, so no later CPU call of the program finds it
        clear_compile_cache()
        jax.clear_caches()
    # 4 potrf + 3 trsm + 3 syrk + 2 gemm groups
    _assert_grid_passed_once(text, "f32[4,4,512,512]", kernels=12)


def test_stacked_gemm_group_passes_its_grid_once(one_chip):
    def group(idxs, grid):
        return tl.grid_gemm(idxs, (grid,), (0, 0, 0), interpret=False)

    idxs = [_struct(one_chip, (3, 2), jnp.int32)] * 3
    grid = _struct(one_chip, (2, 4, 4, 512, 512))
    text = jax.jit(group, donate_argnums=(1,)).lower(idxs, grid).compile().as_text()
    _assert_grid_passed_once(text, "f32[2,4,4,512,512]", kernels=1)
