"""Paper Fig. 3(b) analog: distributed Cholesky, UTP vs direct.

Runs in this process on the devices present, on a ``(devices, 1)`` mesh:
the DuctTeip-analog shard executor places level-1 block rows over the
``data`` axis (the paper's C7-C9 configs, scaled to this harness).  For a
multi-device run on a CPU host, start the process with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.compat import make_mesh
from repro.core import spd_matrix
from repro.linalg import run_cholesky

from .common import row, timeit


def main(quick: bool = True) -> None:
    nd = jax.device_count()
    mesh = make_mesh((nd, 1), ("data", "model"))
    n = 512
    a = spd_matrix(n)
    runs = {
        "direct": lambda: jnp.linalg.cholesky(a),
        "g3flat": lambda: run_cholesky(
            a, graph="g3flat", partitions=((8, 8),), mesh=mesh
        ),
        "g3": lambda: run_cholesky(
            a, graph="g3", partitions=((4, 4), (2, 2)), mesh=mesh
        ),
        "g4": lambda: run_cholesky(
            a, graph="g4", partitions=((4, 4), (2, 2)), mesh=mesh
        ),
    }
    for k, fn in runs.items():
        t = timeit(fn, iters=1 if quick else 3)
        name = k if k == "direct" else f"{k}_{nd}dev"
        row(f"cholesky_dist_{name}_n{n}", t, f"{(n**3/3)/t/1e9:.2f}GF/s")
    err = float(jnp.abs(runs["g3"]() - jnp.linalg.cholesky(a)).max())
    row("cholesky_dist_g3_max_err", err * 1e-6, "abs_err")


if __name__ == "__main__":
    main()
