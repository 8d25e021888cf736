"""The control of the correctness check: the reference, one precision down,
put in the program's place.

The configurations state f32 at ``Precision.HIGHEST``; the control computes
the same answers with the three-pass bf16 product (``Precision.HIGH``).  A
check that passes the control cannot tell the precision the configuration
states from the step below it.

``cholesky_high`` stands in for ``run_cholesky`` (same arguments);
``controlled(config)`` returns a copy of a configuration whose entry points
are such stand-ins.
"""

from __future__ import annotations

import copy

from bench import reference

PRECISION = "high"


def _block(a, partitions) -> int:
    return a.shape[0] // partitions[-1][0]


def cholesky_high(a, graph=None, partitions=((4, 4),), **_):
    return reference.cholesky(a, block=_block(a, partitions), precision=PRECISION)


def controlled(config: dict) -> dict:
    out = copy.deepcopy(config)
    if "entry_points" in out:
        out["entry_points"] = {op: f"bench.control:{op}_high" for op in out["entry_points"]}
    return out
