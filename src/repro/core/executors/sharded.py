"""Sharded executor — the DuctTeip wrapper analog for a device mesh.

DuctTeip distributes level-1 blocks over MPI ranks (owner computes) and
moves panel blocks with messages.  On a TPU mesh the analog is: the root
array carries a ``NamedSharding`` over the mesh's ``data`` axis (block rows
owned by mesh rows), every wave launch is jitted *with those shardings*, and
XLA's SPMD partitioner materializes the panel movements as collectives
(all-gather / collective-permute) — explicit, inspectable in the HLO, and
overlappable by the latency-hiding scheduler.

Pallas (Mosaic) kernels cannot be partitioned automatically, so under the
``pallas`` backend (g4) each program runs inside ``shard_map`` with every
argument replicated: XLA all-gathers the row-sharded grids on entry, every
device runs the whole schedule, and the jit's output shardings slice the
result back to owned block rows.  Distributing the compute itself is left
to a later change; the all-gather per drain is the known cost.

``shard_axes`` picks which array dims map to which mesh axes; divisibility
is checked and falls back to replication per-dim (never fails to place).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...compat import shard_map
from ..data import GData
from ..task import GTask
from .jit_wave import JitWaveExecutor


def row_sharding(mesh: Mesh, data: GData, axes: Tuple[Optional[str], ...]):
    """NamedSharding for ``data`` with per-dim mesh axes, replication fallback."""
    spec = []
    for dim, ax in zip(data.shape, axes):
        if ax is None:
            spec.append(None)
            continue
        size = mesh.shape[ax]
        spec.append(ax if dim % size == 0 else None)
    return NamedSharding(mesh, P(*spec))


class ShardExecutor(JitWaveExecutor):
    name = "shard"

    def __init__(
        self,
        mesh: Mesh,
        backend: str = "jnp",
        shard_axes: Tuple[Optional[str], ...] = ("data", None),
        **kw,
    ):
        super().__init__(backend=backend, **kw)
        self.mesh = mesh
        self.shard_axes = shard_axes

    def place(self, data: GData) -> None:
        """Distribute a root datum over the mesh (owner-computes layout)."""
        sh = row_sharding(self.mesh, data, self.shard_axes)
        self._shardings[data.id] = sh
        if data.value is not None:
            data.value = jax.device_put(data.value, sh)

    def memo_key_extra(self) -> tuple:
        # axis sizes alone don't identify a mesh: two meshes with the same
        # ('data', 2) layout over different devices compile different
        # out_shardings, so device identity must be part of every cache key
        mesh_desc = (
            tuple(sorted(self.mesh.shape.items())),
            tuple(d.id for d in self.mesh.devices.flat),
        )
        return super().memo_key_extra() + (mesh_desc, tuple(self.shard_axes))

    def _wrap_program(self):
        if self.backend != "pallas":
            return None
        return lambda fn: shard_map(
            fn, mesh=self.mesh, in_specs=P(), out_specs=P(), check_vma=False
        )

    def _grid_sharding(self, data: GData, br: int, bc: int):
        """Shard the resident (nr, nc, br, bc) grid over its *grid* dims.

        The root's row sharding (block rows owned by mesh rows) becomes a
        sharding of the leading grid dims; block dims stay replicated, so
        the distributed drain rides the same resident layout as the local
        one and XLA's SPMD partitioner materializes panel movement as
        collectives around the compiled WaveProgram.
        """
        nr, nc = data.shape[0] // br, data.shape[1] // bc
        spec = []
        for dim, ax in zip((nr, nc), self.shard_axes):
            if ax is None:
                spec.append(None)
                continue
            size = self.mesh.shape[ax]
            spec.append(ax if dim % size == 0 else None)
        return NamedSharding(self.mesh, P(*spec, None, None))

    def _prepare_roots(self, waves: Sequence[Sequence[GTask]]) -> None:
        # lazily place any root not yet distributed (first drain only; the
        # resident grid keeps its sharding across subsequent drains).
        # Called from execute_schedule before planning, so the distributed
        # graphs ride the same dependency-exact fused schedule as the local
        # ones — a multi-root drain's fused cross-root groups gather from
        # several sharded grids and XLA's SPMD partitioner inserts the
        # collectives around the one compiled program (DESIGN.md §2).
        for wave in waves:
            for t in wave:
                for v in t.args:
                    d = v.data
                    if d.id not in self._shardings and (
                        d.in_grid_epoch or d.value is not None
                    ):
                        self.place(d)

    def _run_group(self, tasks: List[GTask]):
        self._prepare_roots([tasks])
        super()._run_group(tasks)
