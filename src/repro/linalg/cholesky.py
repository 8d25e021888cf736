"""The paper's application + technical layers for Cholesky (Fig. 2a).

``utp_cholesky`` is the technical-layer subroutine (lines 19-25): it creates
the root POTRF task and submits it to the dispatcher.  ``run_cholesky`` is
the whole application program: define data + partitions, call the
subroutine, wait for completion — identical for every task-flow graph.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import Dispatcher, GData, GTask
from ..core.data import from_grid
from ..core.tracing import span
from .ops import POTRF


def utp_cholesky(dispatcher: Dispatcher, A: GData) -> GTask:
    task = GTask(POTRF, None, [A.root_view()])
    dispatcher.submit_task(task)
    return task


# de-grid + lower-triangle extraction fused into one compiled program (the
# drained root is still grid-resident; see lu._unpack_lu_grid)
_tril_grid = jax.jit(lambda g: jnp.tril(from_grid(g)))


def run_cholesky(
    a: jnp.ndarray,
    graph: str = "g2",
    partitions: Tuple[Tuple[int, int], ...] = ((4, 4),),
    mesh=None,
) -> jnp.ndarray:
    """Factorize SPD ``a``; returns the lower factor L (upper zeroed)."""
    d = Dispatcher(graph=graph, mesh=mesh)
    A = GData(a.shape, partitions=partitions, dtype=a.dtype, value=jnp.asarray(a))
    utp_cholesky(d, A)
    d.run()
    with span("utp.degrid"):
        if A.in_grid_epoch:
            return _tril_grid(A.grid)
        return jnp.tril(A.value)
