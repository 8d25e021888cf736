"""Back-to-back pivot-free dense solves, each ending in ``block_until_ready``.

The closed loop of ``repeat_solve`` for ``op`` ``lu_solve`` (the entry point
the configuration names for it): ``pool`` pairs of a diagonally dominant
matrix ``a`` and a right-hand side ``b`` of ``nrhs`` columns (a vector for
one), made on the device from the seed; ``warmup``, ``check``, the window,
the collection after every call and the comparison with the reference are
``repeat_solve``'s.
"""

from __future__ import annotations

import jax

from bench import inputs
from bench.drivers import repeat_solve


class Driver(repeat_solve.Driver):
    def __init__(self, config: dict, mix: dict, seed: int, spans):
        self.config, self.mix, self.seed, self.spans = config, mix, seed, spans
        self.op = mix["op"]
        if self.op != "lu_solve":
            raise ValueError(f"repeat_lu_solve runs lu_solve, not {self.op!r}")
        n, nrhs, count = config["n"], int(mix["nrhs"]), int(mix["pool"])
        self.parts = tuple(tuple(lv) for lv in config["partitions"])
        # the right-hand side is cut into row blocks only
        self.b_parts = tuple((rows, 1) for rows, _ in self.parts)
        self.entry = repeat_solve._entry(config["entry_points"][self.op])
        k = inputs.key(seed)
        mats = inputs.unstack(inputs.pool(inputs.fold(k, 1), "dd", count, (n,)))
        rhs = inputs.unstack(
            inputs.pool(inputs.fold(k, 2), "normal", count, (n,) if nrhs == 1 else (n, nrhs))
        )
        self.pool = list(zip(mats, rhs))
        for i in range(int(mix["warmup"])):
            jax.block_until_ready(self._call(i))
        self.kept = {}

    def _call(self, i: int):
        a, b = self.pool[i % len(self.pool)]
        return self.entry(a, b, graph=self.config["graph"], partitions=self.parts,
                          b_partitions=self.b_parts)
