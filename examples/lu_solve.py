"""Walkthrough: solve ``A x = b`` end-to-end in ONE dispatcher drain.

Executable documentation for the composed factor+solve pipeline
(DESIGN.md §4).  The program below is the paper's Fig. 2 shape — define
data, partition, submit one root task, wait — but the root is the composed
LUSOLVE operation, whose expansion emits LU panel tasks, forward-
substitution (TRSML) tasks, and backward-substitution (TRSMUL) tasks into
one scope.  The dispatcher versions all of them into a single task DAG and
compiles the whole pipeline into ONE WaveProgram, so:

  * there is one launch per drain (not three barrier-separated drains),
  * the cross-wave fusion pass overlaps solve groups with late factor
    groups (watch ``groups < groups_prefusion`` below — single-root LU
    alone cannot fuse anything, the solve slack is what fusion exploits),
  * a structurally repeated drain replays via the drain memo with zero
    recompiles (watch ``compiles`` stay 0 on the second call).

    PYTHONPATH=src python examples/lu_solve.py [N] [b1] [b2]
"""

import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.compat import enable_compile_cache, make_mesh
from repro.core import Dispatcher, GData, dd_matrix, utp_get_parameters
from repro.core.executors import clear_compile_cache
from repro.linalg import run_inv, run_lu_solve
from repro.linalg.lu import utp_lu_solve


def main():
    n, b1, b2 = utp_get_parameters(defaults=(256, 4, 2))
    a = dd_matrix(n)  # column-diagonally dominant -> pivot-free LU is exact
    key = jax.random.PRNGKey(0)
    b = jax.random.normal(key, (n, n), jnp.float32)
    want = jax.scipy.linalg.lu_solve(jax.scipy.linalg.lu_factor(a), b)
    print(f"Solve A x = b for {n}x{n} A, partitions {b1}x{b1} then {b2}x{b2}")

    # ---- one program, every task-flow graph ------------------------------
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
        x = run_lu_solve(a, b, graph=graph, partitions=parts, mesh=mesh)
        err = float(jnp.abs(x - want).max())
        print(f"  graph {graph:4s} max_err={err:.2e}")

    # ---- the single-drain claim, witnessed by the counters ---------------
    def drain(seed):
        d = Dispatcher(graph="g2")
        A = GData(a.shape, partitions=((b1, b1),), dtype=a.dtype,
                  value=dd_matrix(n, seed=seed))
        B = GData(b.shape, partitions=((b1, b1),), dtype=b.dtype,
                  value=jax.random.normal(jax.random.PRNGKey(seed), b.shape))
        utp_lu_solve(d, A, B)
        n_leaf = d.run()
        s = d.executor.stats
        print(
            f"  drain(seed={seed}): leaf_tasks={n_leaf} "
            f"launches={s['launches']} compiles={s['compiles']} "
            f"groups={s['groups']} (prefusion {s['groups_prefusion']})"
        )

    print("factor + L-solve + U-solve in ONE WaveProgram:")
    clear_compile_cache()  # forget the runs above: show a cold first drain
    drain(seed=1)  # compiles=1: one program for the whole pipeline
    drain(seed=2)  # compiles=0: structurally repeated drain -> memo replay

    # ---- second application of the same ops: matrix inverse --------------
    inv = run_inv(a, partitions=((b1, b1),))
    err = float(jnp.abs(inv @ a - jnp.eye(n)).max())
    print(f"run_inv (A X = I through the same pipeline): |inv(a)@a - I| = {err:.2e}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
