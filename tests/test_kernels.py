"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp oracles in kernels/ref.py (assignment deliverable (c))."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.data import dd_matrix, spd_matrix
from repro.kernels import ops, ref
from repro.kernels.tile_linalg import _panel_strip

DTYPES = [jnp.float32]
SIZES = [8, 16, 32]
# tile orders whose panel solves run in 128-wide strips with MXU dots
BLOCKED = [256, 512]


def rand(key, *shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype) * 0.3


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_potrf(n, dtype):
    a = spd_matrix(n, dtype=dtype, seed=n)
    out = ops.potrf(a, interpret=True)
    np.testing.assert_allclose(out, ref.potrf(a), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n", SIZES + BLOCKED)
def test_trsm(n):
    l = ref.potrf(spd_matrix(n, seed=n))
    b = rand(1, n, n)
    out = ops.trsm(l, b, interpret=True)
    np.testing.assert_allclose(out, ref.trsm(l, b), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("n", SIZES)
def test_syrk(n):
    a, c = rand(2, n, n), rand(3, n, n)
    out = ops.syrk(a, c, interpret=True)
    np.testing.assert_allclose(out, ref.syrk(a, c), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n", SIZES)
def test_gemm(n):
    a, b, c = rand(4, n, n), rand(5, n, n), rand(6, n, n)
    out = ops.gemm(a, b, c, interpret=True)
    np.testing.assert_allclose(out, ref.gemm(a, b, c), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n", SIZES)
def test_getrf(n):
    a = dd_matrix(n, seed=n)
    packed = ops.getrf(a, interpret=True)
    np.testing.assert_allclose(packed, ref.getrf(a), rtol=2e-4, atol=2e-4)
    # packed L\U really factors a: tril(,-1)+I @ triu == a
    l = jnp.tril(packed, -1) + jnp.eye(n)
    u = jnp.triu(packed)
    np.testing.assert_allclose(l @ u, a, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n", SIZES + BLOCKED)
def test_trsml_trsmu_mask_packed_junk(n):
    """Solve leaves read only their triangle: packed L\\U input is fine."""
    packed = ref.getrf(dd_matrix(n, seed=n))
    b = rand(16, n, n)
    np.testing.assert_allclose(
        ops.trsml(packed, b, interpret=True),
        ref.trsml(packed, b), rtol=2e-3, atol=2e-3,
    )
    np.testing.assert_allclose(
        ops.trsmu(packed, b, interpret=True),
        ref.trsmu(packed, b), rtol=2e-3, atol=2e-3,
    )
    np.testing.assert_allclose(
        ops.trsmul(packed, b, interpret=True),
        ref.trsmul(packed, b), rtol=2e-3, atol=2e-3,
    )


@pytest.mark.parametrize("n", SIZES + BLOCKED)
def test_trsmul(n):
    """Left-upper TRSM (the fourth orientation): x = inv(triu(u)) @ b."""
    u = jnp.triu(dd_matrix(n, seed=n))
    b = rand(21, n, n)
    out = ops.trsmul(u, b, interpret=True)
    np.testing.assert_allclose(out, ref.trsmul(u, b), rtol=2e-3, atol=2e-3)
    # solves the actual system
    np.testing.assert_allclose(u @ out, b, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize(
    "n,bc",
    [
        pytest.param(16, 1, id="1"),
        pytest.param(16, 8, id="8"),
        pytest.param(512, 1, id="512-1"),
        pytest.param(512, 8, id="512-8"),
    ],
)
def test_trsmul_nonsquare_rhs(n, bc):
    """RHS tiles may be non-square (blocked vector right-hand sides); the
    strips follow the triangular tile's order, not the right-hand side's."""
    u = jnp.triu(dd_matrix(n, seed=2))
    b = rand(22, n, bc)
    np.testing.assert_allclose(
        ops.trsmul(u, b, interpret=True), ref.trsmul(u, b),
        rtol=2e-3, atol=2e-3,
    )
    np.testing.assert_allclose(
        ops.trsml(u, b, interpret=True), ref.trsml(u, b),
        rtol=2e-3, atol=2e-3,
    )


@pytest.mark.parametrize(
    "nb,want", [(8, 8), (16, 16), (32, 32), (128, 128), (256, 128), (512, 128)]
)
def test_panel_strip_follows_the_tile_order(nb, want):
    """Only tiles of several whole lane-wide strips are blocked; smaller
    tiles keep the one whole-tile recurrence."""
    assert _panel_strip(nb) == want


@pytest.mark.parametrize("n", [8, 16])
def test_lu_solve_leaf(n):
    """The composed LUSOLVE leaf: (packed, x) with a @ x == b."""
    a = dd_matrix(n, seed=n)
    b = rand(23, n, n)
    packed, x = ops.lu_solve(a, b, interpret=True)
    rpacked, rx = ref.lu_solve(a, b)
    np.testing.assert_allclose(packed, rpacked, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(x, rx, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(a @ x, b, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("n", SIZES)
def test_gemmnn(n):
    a, b, c = rand(17, n, n), rand(18, n, n), rand(19, n, n)
    out = ops.gemmnn(a, b, c, interpret=True)
    np.testing.assert_allclose(out, ref.gemmnn(a, b, c), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("batch", [1, 3])
@pytest.mark.parametrize("n", [8, 16])
def test_batched_lu_kernels(batch, n):
    a = jnp.stack([dd_matrix(n, seed=i) for i in range(batch)])
    packed = ops.batched_getrf(a, interpret=True)
    np.testing.assert_allclose(
        packed, jax.vmap(ref.getrf)(a), rtol=2e-4, atol=2e-4
    )
    b = rand(20, batch, n, n)
    np.testing.assert_allclose(
        ops.batched_trsml(packed, b, interpret=True),
        jax.vmap(ref.trsml)(packed, b), rtol=2e-3, atol=2e-3,
    )
    np.testing.assert_allclose(
        ops.batched_trsmu(packed, b, interpret=True),
        jax.vmap(ref.trsmu)(packed, b), rtol=2e-3, atol=2e-3,
    )
    np.testing.assert_allclose(
        ops.batched_trsmul(packed, b, interpret=True),
        jax.vmap(ref.trsmul)(packed, b), rtol=2e-3, atol=2e-3,
    )
    np.testing.assert_allclose(
        ops.batched_gemmnn(packed, b, a, interpret=True),
        jax.vmap(ref.gemmnn)(packed, b, a), rtol=1e-4, atol=1e-4,
    )


@pytest.mark.parametrize("batch", [1, 3])
@pytest.mark.parametrize("n", [8, 16])
def test_batched_kernels(batch, n):
    a = jnp.stack([spd_matrix(n, seed=i) for i in range(batch)])
    L = ops.batched_potrf(a, interpret=True)
    want = jax.vmap(ref.potrf)(a)
    np.testing.assert_allclose(L, want, rtol=2e-4, atol=2e-4)
    b = rand(7, batch, n, n)
    np.testing.assert_allclose(
        ops.batched_trsm(L, b, interpret=True),
        jax.vmap(ref.trsm)(L, b), rtol=2e-3, atol=2e-3,
    )
    np.testing.assert_allclose(
        ops.batched_syrk(b, a, interpret=True),
        jax.vmap(ref.syrk)(b, a), rtol=1e-4, atol=1e-4,
    )


@pytest.mark.parametrize("m,k,n,bm,bk,bn", [
    (32, 32, 32, 16, 16, 16),
    (64, 128, 32, 32, 64, 16),
    (128, 64, 128, 128, 64, 128),
])
def test_matmul_tiled(m, k, n, bm, bk, bn):
    a = rand(8, m, k)
    b = rand(9, k, n)
    out = ops.matmul(a, b, bm=bm, bn=bn, bk=bk, interpret=True)
    np.testing.assert_allclose(out, ref.matmul(a, b), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,S,D", [
    (1, 2, 2, 32, 8),
    (2, 4, 2, 64, 16),
    (1, 8, 1, 32, 32),  # MQA
])
@pytest.mark.parametrize("window", [0, 16])
def test_flash_attention(dtype, B, Hq, Hkv, S, D, window):
    q = rand(10, B, Hq, S, D).astype(dtype)
    k = rand(11, B, Hkv, S, D).astype(dtype)
    v = rand(12, B, Hkv, S, D).astype(dtype)
    out = ops.flash_attention(
        q, k, v, causal=True, window=window, block_q=16, block_k=16,
        interpret=True,
    )
    want = ref.flash_attention(q, k, v, causal=True, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        out.astype(np.float32), want.astype(np.float32), rtol=tol, atol=tol
    )


def test_flash_attention_matches_model_sdpa():
    """The kernel and the model's portable _sdpa agree (same semantics)."""
    from repro.models.attention import _sdpa

    B, H, S, D = 2, 4, 32, 16
    q = rand(13, B, S, H, D)
    k = rand(14, B, S, H, D)
    v = rand(15, B, S, H, D)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    want = _sdpa(q, k, v, pos, pos, None, 0)
    out = ops.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, block_q=16, block_k=16,
        interpret=True,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
