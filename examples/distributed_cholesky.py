"""Distributed Cholesky on a real multi-device mesh (paper Fig. 3(b)).

Runs the SAME application program under the hierarchical G3 graph on a
``(devices, 1)`` mesh over every device present — the DuctTeip analog
places level-1 block rows over the data axis; panel movement shows up as
XLA collectives instead of MPI messages.  On a CPU host, ask XLA for
several host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/distributed_cholesky.py
"""

import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.compat import enable_compile_cache, make_mesh
from repro.core import Dispatcher, GData, spd_matrix
from repro.linalg import utp_cholesky


def main():
    n = 1024
    a = spd_matrix(n)
    nd = jax.device_count()
    print(f"devices: {nd}")
    mesh = make_mesh((nd, 1), ("data", "model"))

    d = Dispatcher(graph="g3", mesh=mesh)
    A = GData(a.shape, partitions=((8, 8), (2, 2)), dtype=a.dtype, value=a)
    utp_cholesky(d, A)
    leafs = d.run()

    err = float(jnp.abs(jnp.tril(A.value) - jnp.linalg.cholesky(a)).max())
    shard_shapes = {str(s.data.shape) for s in A.value.addressable_shards}
    print(
        f"g3 on ({nd},1) mesh: {leafs} leaf tasks, {d.stats['waves']} waves, "
        f"max_err={err:.2e}"
    )
    print(f"result stays sharded across devices: shard shapes {shard_shapes}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
