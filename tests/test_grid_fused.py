"""Fused grid kernels (``tile_linalg.make_grid_fused``) in interpret mode.

Each kernel takes the distinct grids once and reads its tiles by DMA.  Its
result must equal, bit for bit, the per-tile results of the same tile body
run one task at a time (the ``batched_*`` kernels over gathered tiles,
scattered back), whether every argument reads one grid or the written
argument has a grid of its own, resident or stacked.  The index lists give
consecutive tasks the same read tile, so the path that skips a repeated
fetch is taken.  The kernels run under the TPU interpreter: DMAs land only
when waited on and scratch starts as NaN, so a missing wait or a wrong
buffer slot shows in the result.
"""

import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tile_linalg as tl

ARITY = {
    "potrf": 1, "getrf": 1, "trsm": 2, "syrk": 2, "trsml": 2, "trsmu": 2,
    "trsmul": 2, "gemm": 3, "gemmnn": 3,
}
RHS_OPS = ("trsml", "trsmul", "gemmnn")  # the solves' right-hand side kernels
P, B, WIDTH = 6, 8, 3
LEADS = {"resident": (), "stacked": (3,)}

# block coordinates: writes in rows 0-3, reads in rows 4-5, so no task of a
# group reads a block that another writes; repeated reads take the skip path
WRITES = [(0, 1), (0, 2), (1, 2), (2, 3), (3, 0)]
WRITES_DIAG = [(0, 0), (1, 1), (3, 3)]
READS = [
    [(5, 5), (5, 5), (4, 4), (4, 4), (5, 5)],
    [(5, 1), (4, 1), (4, 1), (4, 1), (5, 0)],
]
WRITES_RHS = [(0, 0), (1, 0), (2, 0), (3, 0)]
READS_RHS = [(5, 0), (5, 0), (4, 0), (4, 0)]

CASES = [
    (op, grids)
    for op in sorted(tl.GRID_FUSED)
    for grids in ("one", "two")
    if grids == "one" or ARITY[op] > 1
]


def _grid(rng, lead, nc, bc):
    """Well-conditioned tiles: each is 2 I plus small noise, so every panel
    body (Cholesky, LU, triangular solves) is stable on any of them."""
    g = rng.standard_normal(lead + (P, nc, B, bc)).astype(np.float32) / B
    return g + 2.0 * np.eye(B, bc, dtype=np.float32)


def _case(op, grids, lead):
    """(grids, arg_grid, idxs) of one group of ``op``.

    ``one``: every argument reads one square grid.  ``two``: the solves'
    kernels read a square factor and a thin right-hand side grid, which
    the written argument (and gemmnn's other X block) share; the other
    kernels write a grid of their own."""
    rng = np.random.default_rng(sorted(tl.GRID_FUSED).index(op))
    k, (_, w) = ARITY[op], tl.GRID_FUSED[op]
    if grids == "two" and op in RHS_OPS:
        gs = [_grid(rng, lead, P, B), _grid(rng, lead, 1, WIDTH)]
        arg_grid = (0,) + (1,) * (k - 1)
        idxs = [READS[k - 2][:4]] + [READS_RHS] * (k - 2) + [WRITES_RHS]
    else:
        gs = [_grid(rng, lead, P, B) for _ in range(1 if grids == "one" else 2)]
        arg_grid = tuple(int(grids == "two" and a == w) for a in range(k))
        if k == 1:
            idxs = [WRITES_DIAG]
        else:
            idxs = [WRITES if a == w else READS[a] for a in range(k)]
    return gs, arg_grid, [np.asarray(ix, np.int32) for ix in idxs]


def _per_tile(op, gs, arg_grid, idxs):
    """The tile body one task at a time: gather each argument's tiles, run
    the batched per-tile kernel, scatter the results."""
    _, w = tl.GRID_FUSED[op]
    stacks = []
    for a, ix in enumerate(idxs):
        t = gs[arg_grid[a]][..., ix[:, 0], ix[:, 1], :, :]
        stacks.append(t.reshape((-1,) + t.shape[-2:]))
    out = np.asarray(getattr(tl, f"batched_{op}")(*stacks, interpret=True))
    ref = gs[arg_grid[w]].copy()
    ix = idxs[w]
    ref[..., ix[:, 0], ix[:, 1], :, :] = out.reshape(
        ref.shape[:-4] + (len(ix),) + out.shape[-2:]
    )
    return ref


@pytest.mark.parametrize("form", sorted(LEADS))
@pytest.mark.parametrize("op,grids", CASES)
def test_fused_call_matches_per_tile_bodies(op, grids, form):
    gs, arg_grid, idxs = _case(op, grids, LEADS[form])
    fn, _ = tl.GRID_FUSED[op]
    out = fn(idxs, tuple(gs), arg_grid, interpret=pltpu.InterpretParams())
    np.testing.assert_array_equal(np.asarray(out), _per_tile(op, gs, arg_grid, idxs))


@pytest.mark.parametrize("form", sorted(LEADS))
@pytest.mark.parametrize("op", RHS_OPS)
def test_thin_grids_read_by_blockspec_match_per_tile_bodies(monkeypatch, op, form):
    """On the chip a grid whose tiles are narrower than a lane row cannot be
    sliced in HBM, so its arguments are read by BlockSpec while the square
    factor is read by DMA; the mix must give the same bits."""
    monkeypatch.setattr(
        tl, "_dma_readable", lambda shape, dtype, interpret: shape[-1] == B
    )
    gs, arg_grid, idxs = _case(op, "two", LEADS[form])
    fn, _ = tl.GRID_FUSED[op]
    out = fn(idxs, tuple(gs), arg_grid, interpret=pltpu.InterpretParams())
    np.testing.assert_array_equal(np.asarray(out), _per_tile(op, gs, arg_grid, idxs))
