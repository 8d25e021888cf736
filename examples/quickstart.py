"""Quickstart: the paper's programming model end-to-end (Fig. 2 analog).

ONE application program (define data, partition, call utp_cholesky, wait)
runs unchanged under every task-flow graph — sequential leaves (G1),
wave-batched multicore-analog (G2), Pallas tile kernels (G2'), and the
two-level hierarchical DuctTeip-over-SuperGlue plan (G3, on whatever
devices exist).

    PYTHONPATH=src python examples/quickstart.py [N] [b1] [b2]
"""

import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.compat import enable_compile_cache, make_mesh
from repro.core import Dispatcher, GData, GTask, spd_matrix, utp_get_parameters
from repro.linalg import POTRF, utp_cholesky


def main():
    n, b1, b2 = utp_get_parameters(defaults=(256, 4, 2))
    a = spd_matrix(n)
    want = jnp.linalg.cholesky(a)
    print(f"Cholesky of {n}x{n} SPD matrix, partitions {b1}x{b1} then {b2}x{b2}")

    for graph, parts in [
        ("g1", ((b1, b1),)),
        ("g2", ((b1, b1),)),
        ("g2p", ((b1, b1),)),
        ("g3", ((b1, b1), (b2, b2))),
    ]:
        mesh = None
        if graph == "g3":
            nd = jax.device_count()
            mesh = make_mesh((nd, 1), ("data", "model"))
        # ---- the application program (identical for every graph) --------
        d = Dispatcher(graph=graph, mesh=mesh)
        A = GData(a.shape, partitions=parts, dtype=a.dtype, value=a)
        utp_cholesky(d, A)
        n_leaf = d.run()
        # ------------------------------------------------------------------
        err = float(jnp.abs(jnp.tril(A.value) - want).max())
        print(
            f"  graph {graph:6s} [{d.graph.describe():47s}] "
            f"leaf_tasks={n_leaf:4d} waves={d.stats['waves']:3d} max_err={err:.2e}"
        )
    print("same program, four execution plans — the paper's portability claim.")


if __name__ == "__main__":
    enable_compile_cache()
    main()
