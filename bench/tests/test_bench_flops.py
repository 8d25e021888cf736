"""Operations and bytes of the tile drains, from their shapes."""

import pytest

from bench import flops

N, B, NRHS = 16384, 512, 128


def _counts(tasks):
    return {k: c for k, c, _, _ in tasks}


def test_cholesky_task_counts_at_p32_sum_to_n_cubed_over_3():
    tasks = flops.cholesky_tasks(N, B)
    assert _counts(tasks) == {"potrf": 32, "trsm": 496, "syrk": 496, "gemm": 4960}
    fl, _ = flops.total(tasks)
    assert fl == pytest.approx(N**3 / 3, rel=1e-12)
    assert fl == pytest.approx(flops.algorithmic_flops("cholesky", N), rel=1e-12)


def test_lu_solve_task_counts_at_p32_sum_to_the_textbook_total():
    tasks = flops.lu_solve_tasks(N, B, NRHS)
    c = _counts(tasks)
    assert [c[k] for k in ("getrf", "trsml", "trsmu", "gemmnn")] == [32, 496, 496, 10416]
    assert [c[k] for k in ("trsml.rhs", "gemmnn.rhs_forward", "trsmul.rhs", "gemmnn.rhs_backward")] == [
        32, 496, 32, 496]
    fl, _ = flops.total(tasks)
    assert fl == pytest.approx(2 * N**3 / 3 + 2 * N**2 * NRHS, rel=1e-12)
    assert fl == pytest.approx(flops.algorithmic_flops("lu_solve", N, NRHS), rel=1e-12)


@pytest.mark.parametrize("p", [1, 2, 3, 8])
def test_totals_hold_at_other_grid_sizes(p):
    n, b = p * 16, 16
    assert flops.total(flops.tasks("cholesky", n, b))[0] == pytest.approx(n**3 / 3)
    assert flops.total(flops.tasks("lu_solve", n, b, 4))[0] == pytest.approx(2 * n**3 / 3 + 2 * n * n * 4)


def test_bytes_are_the_tiles_each_task_reads_and_writes():
    by = {k: b for k, _, _, b in flops.lu_solve_tasks(N, B, NRHS)}
    tile, rhs = B * B * 4, B * NRHS * 4
    assert by["getrf"] == 2 * tile
    assert by["trsml"] == by["trsmu"] == 3 * tile
    assert by["gemmnn"] == 4 * tile  # (arity + 1) b^2 4, arity 3
    assert by["trsml.rhs"] == tile + 2 * rhs
    assert by["gemmnn.rhs_forward"] == tile + 3 * rhs
    chol = {k: b for k, _, _, b in flops.cholesky_tasks(N, B)}
    assert [chol[k] for k in ("potrf", "trsm", "syrk", "gemm")] == [2 * tile, 3 * tile, 3 * tile, 4 * tile]


def test_roofline_of_the_dense_cells_is_bound_by_bytes():
    for op in ("cholesky", "lu_solve"):
        t, bound = flops.roofline_s(flops.tasks(op, N, B, NRHS), 197e12, 819e9)
        assert bound == "bytes"
        _, by = flops.total(flops.tasks(op, N, B, NRHS))
        assert by / 819e9 <= t <= by / 819e9 + flops.total(flops.tasks(op, N, B, NRHS))[0] / 197e12
    assert flops.total(flops.cholesky_tasks(N, B))[1] == pytest.approx(23.9e9, rel=0.01)


def test_unknown_operation_is_an_error():
    with pytest.raises(ValueError):
        flops.tasks("qr", 64, 16)
