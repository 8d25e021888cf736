"""Wave-batched jitted executor — the SuperGlue wrapper analog, TPU-native.

SuperGlue runs ready tasks on multicore threads; the TPU-idiomatic
equivalent batches every wave of independent same-signature tasks into ONE
vmapped + jitted launch so the MXU sees a single large batched kernel
instead of many tiny ones (DESIGN.md §2).

Primary path (``execute_schedule``): the dispatcher's whole leaf schedule
plus its exact task DAG is compiled into a single XLA program over
grid-resident roots by the WaveProgram compiler — dependency-exact issue
slots, same-signature groups fused across former wave boundaries (also
across roots), one Python dispatch per drain; roots stay in
``(nr, nc, br, bc)`` layout for the epoch, and repeated drains with the
same schedule structure reuse one compiled program.

Stacked path (``execute_stacked``, DESIGN.md §7): a homogeneous root
stream runs ONE batched program over ``(B, nr, nc, br, bc)`` stacked grids
with B padded to a pow2 bucket — compiled programs and the drain memo key
depend on the bucket, never on the exact request count, and results hand
back as lazily extracted lanes of a shared ``StackedEpoch``.

Fallback path (``execute_wave``/``_run_group``): per-wave-group jitted
launches with the grid-reshape gather/scatter, used when the schedule is
not grid-uniform (mixed block shapes or unaligned regions on one root).
The jitted group function is cached on the static signature (op, backend,
root/block shapes & dtypes); block *indices* are traced arguments, so every
wave of the same kind reuses the compiled program.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...analysis.verify import verify_plan
from ...testing import faults
from ..data import GData, StackedEpoch, from_grid, to_grid
from ..task import GTask, TaskState
from ..tracing import span
from ..versioning import InFlightEpoch
from .base import Executor, group_wave
from .wave_program import (
    SchedulePlan,
    blocked_panel_groups,
    blockspec_groups,
    build_program,
    plan_schedule,
    shared_grid_groups,
)

# process-global compiled-program cache: keys are purely structural (op
# names, backend, shapes, dtypes, shardings, schedule structure) so every
# Dispatcher instance reuses the same compiled programs — dispatcher
# creation must stay O(tasks), not O(compiles) (paper §3 overhead-parity
# claim).  Holds both per-group functions ("group", ...) and whole-schedule
# WavePrograms ("waveprog", ...).
_GROUP_FN_CACHE: Dict[tuple, callable] = {}

class DrainMemo:
    """Bounded LRU drain memo with hit/miss/eviction counters (DESIGN.md §2).

    Structural root-task-stream key -> the captured sequence of compiled
    program executions for a whole dispatcher drain, so a structurally
    repeated drain skips Python re-splitting/re-versioning and replays the
    programs directly.  A long-running server sees an unbounded stream of
    distinct request signatures, so the memo must not grow without bound:
    entries evict least-recently-used past ``capacity`` (an evicted drain is
    simply re-captured on its next occurrence — correctness is unaffected).
    Counters feed ``Dispatcher.stats`` and the serving tick reports.
    """

    def __init__(self, capacity: int = 256):
        self.capacity = capacity
        self._entries: "OrderedDict[tuple, object]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.invalidations = 0
        self.pressure_sheds = 0

    def get(self, key: tuple):
        entry = self._entries.get(key)
        if entry is None:
            self.misses += 1
            return None
        self._entries.move_to_end(key)
        self.hits += 1
        return entry

    def __setitem__(self, key: tuple, entry: object) -> None:
        self._entries[key] = entry
        self._entries.move_to_end(key)
        while len(self._entries) > self.capacity:
            self._entries.popitem(last=False)
            self.evictions += 1

    def set_capacity(self, capacity: int) -> None:
        if capacity < 1:
            raise ValueError(f"drain memo capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        while len(self._entries) > self.capacity:
            self._entries.popitem(last=False)
            self.evictions += 1

    def discard(self, key: tuple) -> None:
        """Drop one entry (no-op if absent) — the in-flight failure
        hardening hook (DESIGN.md §12): a drain whose program FAILED after
        dispatch may have captured/refreshed an entry this drain can no
        longer vouch for, so the dispatcher's ``DrainHandle`` invalidates
        exactly the keys it stored.  Counted as an invalidation (the entry
        is simply re-captured on the next healthy occurrence)."""
        if key in self._entries:
            del self._entries[key]
            self.invalidations += 1

    def shed(self, fraction: float = 0.5) -> int:
        """Evict the least-recently-used ``fraction`` of entries; returns
        the count shed.  The memory-pressure hook (DESIGN.md §14): a device
        OOM means resident state must shrink NOW, and memo entries pin
        device-side index arrays plus compiled-program references — the LRU
        tail is exactly the state least likely to be replayed soon.
        Correctness is unaffected (a shed drain re-captures on its next
        occurrence); counted under ``pressure_sheds``."""
        if not 0.0 < fraction <= 1.0:
            raise ValueError(f"shed fraction must be in (0, 1], got {fraction}")
        n = min(len(self._entries), max(1, int(len(self._entries) * fraction))) \
            if self._entries else 0
        for _ in range(n):
            self._entries.popitem(last=False)
        self.pressure_sheds += n
        return n

    def stats(self) -> Dict[str, int]:
        return {
            "entries": len(self._entries),
            "capacity": self.capacity,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "invalidations": self.invalidations,
            "pressure_sheds": self.pressure_sheds,
        }

    # dict-compatible surface (tests introspect the memo directly)
    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: tuple) -> bool:
        return key in self._entries

    def values(self):
        return self._entries.values()

    def keys(self):
        return self._entries.keys()

    def clear(self) -> None:
        self._entries.clear()


# owned here (not in dispatcher.py) so one clear call drops every compiled
# artifact; counters are process-global like the compiled-program cache
_DRAIN_MEMO = DrainMemo()


def set_drain_memo_capacity(capacity: int) -> None:
    """Configure the LRU bound of the process-global drain memo."""
    _DRAIN_MEMO.set_capacity(capacity)


def drain_memo_stats() -> Dict[str, int]:
    """Entries/capacity/hits/misses/evictions of the global drain memo."""
    return _DRAIN_MEMO.stats()


def drain_memo_records() -> List["ProgramRecord"]:
    """Every captured program record in the global drain memo, oldest
    first — lets a caller lower a drain's program and inspect its HLO."""
    return [r for entry in _DRAIN_MEMO.values() for r in entry["records"]]


def drain_memo_pressure(fraction: float = 0.5) -> int:
    """Shed the LRU ``fraction`` of the global drain memo (DESIGN.md §14).

    The memory-pressure callback: called by the serving layer on a device
    OOM (and available to any embedder's allocator hooks) so resident
    compiled-program state shrinks alongside the batch-cap degradation.
    Returns the number of entries shed."""
    return _DRAIN_MEMO.shed(fraction)


def clear_compile_cache() -> None:
    """Drop all cached compiled group fns / WavePrograms / drain memos."""
    _GROUP_FN_CACHE.clear()
    _DRAIN_MEMO.clear()


@dataclass(frozen=True)
class ProgramRecord:
    """One compiled-program execution inside a captured drain.

    ``root_slots`` index into the drain's root-argument data order; the
    dispatcher resolves them to fresh ``GData`` objects on replay.
    ``idxs`` is the plan's device-resident flat index array — replay reuses
    it as-is, no host concatenation or transfer.  ``batch`` is the stacked
    pow2 bucket for batched drains (DESIGN.md §7): replay then resolves each
    slot to the LIST of member data handles to restack."""

    fn: object  # the jitted WaveProgram
    root_slots: Tuple[int, ...]
    blocks: Tuple[Tuple[int, int], ...]  # per-root leaf block shape
    idxs: jnp.ndarray  # flat (total, 2) int32 block indices (device)
    n_tasks: int
    n_groups: int = 0  # fused launch count inside the program
    n_groups_prefusion: int = 0  # barrier-wave group count before fusion
    n_slots: int = 0  # dependency-exact issue slots
    batch: Optional[int] = None  # stacked bucket size (None = unstacked)


class JitWaveExecutor(Executor):
    name = "jit_wave"

    def __init__(self, backend: str = "jnp", donate: bool = True, **kw):
        super().__init__(**kw)
        self.backend = backend
        self.donate = donate
        self._fn_cache = _GROUP_FN_CACHE
        # optional: data_id -> jax.sharding.Sharding (set by ShardExecutor)
        self._shardings: Dict[int, object] = {}
        # drain-capture state (dispatcher memo protocol)
        self._capture: Optional[List[ProgramRecord]] = None
        self._capture_ids: Dict[int, int] = {}
        self._capture_ok = True
        # in-flight epoch handles, one per launch since the last take
        # (DESIGN.md §12); launches are asynchronous, so nothing here blocks
        self.inflight: List[InFlightEpoch] = []

    # -- async launch tracking (DESIGN.md §12) ---------------------------------
    def _note_launch(self, outs, label: str) -> None:
        """Record a dispatched program's outputs as an in-flight epoch.

        Launch order is preserved — the donation handshake relies on it
        (a donated grid's completion is covered by a LATER epoch in the
        list).  Already-materialized epochs are pruned opportunistically so
        a dispatcher reused across many drains without ``take_inflight``
        (e.g. ``run_lu`` one-shots) cannot accumulate handles."""
        if len(self.inflight) >= 8:
            self.inflight = [e for e in self.inflight if not e.is_ready()]
        self.inflight.append(InFlightEpoch(outs, label))

    def take_inflight(self) -> List[InFlightEpoch]:
        eps, self.inflight = self.inflight, []
        return eps

    # -- drain capture/replay protocol (DESIGN.md §2) --------------------------
    def memo_key_extra(self) -> tuple:
        """Executor-identity part of the dispatcher's drain-memo key."""
        return (self.name, self.backend, self.donate)

    def begin_capture(self, root_slot_of: Dict[int, int]) -> None:
        """Start recording program executions; ``root_slot_of`` maps the
        drain's root-argument data ids to stable slots."""
        self._capture = []
        self._capture_ids = dict(root_slot_of)
        self._capture_ok = True

    def end_capture(self):
        """Stop recording; returns (records, ok).  ``ok`` is False when any
        leaf work bypassed the WaveProgram path (legacy fallback) or touched
        a datum that is not a root argument — such drains are not memoized."""
        records, ok = self._capture, self._capture_ok
        self._capture = None
        self._capture_ids = {}
        return records or [], ok and bool(records)

    def replay_program(self, rec: ProgramRecord, datas: List) -> int:
        """Re-execute a captured program against fresh data handles.

        For a stacked record (``rec.batch``) each entry of ``datas`` is the
        LIST of member handles for that root slot; they are restacked (with
        pow2 padding) and the per-lane results handed back as lanes of a
        shared ``StackedEpoch`` (DESIGN.md §7)."""
        faults.fire(
            "executor.launch", batch=rec.batch, n_tasks=rec.n_tasks,
            replay=True,
        )
        faults.fire(
            "launch.oom", batch=rec.batch, n_tasks=rec.n_tasks, replay=True,
        )
        with span("utp.enter_grid", roots=len(datas)):
            if rec.batch is not None:
                grids = self._stack_grids(datas, rec.blocks, rec.batch)
            else:
                grids, _ = self._enter_grids(datas, rec.blocks)
        with span("utp.launch", tasks=rec.n_tasks, groups=rec.n_groups):
            outs = rec.fn(grids, rec.idxs)
        outs = faults.corrupt(
            "executor.output", outs, batch=rec.batch, replay=True
        )
        if rec.batch is not None:
            self._note_launch(outs, f"replay:stacked{rec.batch}")
            self._adopt_stacked(datas, outs, rec.blocks)
        else:
            self._note_launch(outs, "replay")
            for data, g in zip(datas, outs):
                data.set_grid(g)
        self.stats["tasks"] += rec.n_tasks
        self.stats["launches"] += 1
        self.stats["groups"] += rec.n_groups
        self.stats["groups_prefusion"] += rec.n_groups_prefusion
        self.stats["slots"] += rec.n_slots
        return rec.n_tasks

    # -- whole-schedule compiled path (DESIGN.md §2) ---------------------------
    def execute_schedule(self, waves: List[List[GTask]], dag=None) -> int:
        """Dependency-exact compiled execution of a whole leaf schedule."""
        waves = [w for w in waves if w]
        if not waves:
            return 0
        self._prepare_roots(waves)
        plan = self._plan(waves, dag)
        if plan is None:
            self._capture_ok = False
            n = 0
            for wave in waves:
                n += self.execute_wave(wave)
            return n
        if self.verify and dag is not None:
            # prove the plan before launching it (DESIGN.md §11); verdicts
            # cache on (structural key, index digest) so a structurally
            # repeated drain pays one dict probe here
            self._verify(plan, dag)
        return self._run_program(plan)

    def execute_waves(self, waves: List[List[GTask]]) -> int:
        return self.execute_schedule(waves)

    # -- stacked (batched) drain path (DESIGN.md §7) ---------------------------
    def execute_stacked(
        self,
        schedules: List[tuple],
        members: Dict[int, List[GData]],
        bucket: int,
    ) -> Optional[int]:
        """Run a homogeneous-root drain as ONE batched program per schedule.

        ``schedules`` is the TEMPLATE root's list of leaf ``(waves, dag)``
        schedules; ``members`` maps each template root-argument data id to
        the per-request member handles (template first).  Every schedule is
        planned up front: if ANY falls off the whole-program path (non-
        grid-uniform), returns None WITHOUT executing anything, so the
        caller can fall back to segment fusion with no partial state.
        """
        plans = []
        for waves, dag in schedules:
            waves = [w for w in waves if w]
            if not waves:
                continue
            plan = self._plan(waves, dag)
            if plan is None or any(
                d not in members for d in plan.roots_order
            ):
                return None
            if self.verify and dag is not None:
                # all template plans are proven up front, before ANY lane
                # executes — a verification failure aborts with no partial
                # state, same contract as the planning fall-off above
                self._verify(plan, dag)
            plans.append(plan)
        n = 0
        for plan in plans:
            n += self._run_program(plan, stack=(members, bucket))
        return n

    @staticmethod
    def _plan(waves, dag) -> Optional[SchedulePlan]:
        with span("utp.plan") as sp:
            plan = plan_schedule(waves, dag)
            if plan is not None:
                sp.counts.update(
                    tasks=len(plan.tasks), groups=plan.n_groups, slots=plan.n_slots
                )
        return plan

    def _verify(self, plan: SchedulePlan, dag) -> None:
        with span("utp.verify", groups=plan.n_groups):
            verify_plan(plan, dag)
        self.stats["verified_plans"] += 1

    def _stack_grids(
        self,
        member_lists: Sequence[List[GData]],
        blocks: Sequence[Tuple[int, int]],
        bucket: int,
    ) -> Tuple[jnp.ndarray, ...]:
        """Per root slot, stack the members' resident grids into one
        ``(bucket, nr, nc, br, bc)`` array, padding the batch by repeating
        the last member (lanes are independent, so padding lanes compute
        junk that is never read back).

        Repeat-tick fast path: when the members are exactly lanes 0..N-1 of
        one prior StackedEpoch with the same block and bucket — and they
        are that epoch's ONLY live holders, so donating its grid into the
        next program cannot invalidate a bystander lane — the grid is
        reused as-is: zero per-request data movement between ticks."""
        out: List[jnp.ndarray] = []
        for members, (br, bc) in zip(member_lists, blocks):
            first = members[0].lane
            if (
                first is not None
                and first[0].block == (br, bc)
                and first[0].batch == bucket
                and first[0].holders == len(members)
                and all(
                    m.lane is not None
                    and m.lane[0] is first[0]
                    and m.lane[1] == i
                    for i, m in enumerate(members)
                )
            ):
                out.append(first[0].grid)
                continue
            gs = [m.enter_grid(br, bc) for m in members]
            gs = gs + [gs[-1]] * (bucket - len(gs))
            out.append(jnp.stack(gs))
        return tuple(out)

    @staticmethod
    def _adopt_stacked(member_lists, outs, blocks) -> None:
        """Hand each member its lane of the stacked result grids."""
        for members, g, (br, bc) in zip(member_lists, outs, blocks):
            epoch = StackedEpoch(g, (br, bc))
            for i, m in enumerate(members):
                m.adopt_lane(epoch, i)

    def _prepare_roots(self, waves: Sequence[Sequence[GTask]]) -> None:
        """Hook: place/distribute roots before planning (ShardExecutor)."""

    def _grid_sharding(self, data: GData, br: int, bc: int):
        """Sharding for ``data``'s resident (nr, nc, br, bc) grid, or None."""
        return None

    def _wrap_program(self):
        """Hook: a wrapper applied to every traced program before jit, or
        None (ShardExecutor runs Pallas programs inside ``shard_map``)."""
        return None

    def _enter_grids(self, datas: Sequence[GData], blocks):
        """Enter grid epochs (resident re-entry is free) and apply grid
        shardings; returns (grids, shardings)."""
        grids: List[jnp.ndarray] = []
        shardings: List[object] = []
        for data, (br, bc) in zip(datas, blocks):
            g = data.enter_grid(br, bc)
            sh = self._grid_sharding(data, br, bc)
            if sh is not None and getattr(g, "sharding", None) != sh:
                g = jax.device_put(g, sh)
                data.set_grid(g)
            grids.append(g)
            shardings.append(sh)
        return tuple(grids), tuple(shardings)

    def _run_program(self, plan: SchedulePlan, stack=None) -> int:
        """Compile-or-fetch and run one planned program.  With ``stack =
        (members, bucket)`` the plan is traced in stacked form over
        ``(bucket, nr, nc, br, bc)`` grids (DESIGN.md §7): the compiled
        program and its cache key depend on the pow2 bucket, never on the
        exact request count."""
        datas = [plan.datas[d] for d in plan.roots_order]
        batch = None
        with span("utp.enter_grid", roots=len(datas)):
            if stack is not None:
                members, batch = stack
                member_lists = [members[d] for d in plan.roots_order]
                grids = self._stack_grids(member_lists, plan.blocks, batch)
                shardings = tuple(None for _ in datas)
            else:
                grids, shardings = self._enter_grids(datas, plan.blocks)
        out_shardings = (
            shardings if all(s is not None for s in shardings) else None
        )
        key = (
            "waveprog",
            batch,
            self.memo_key_extra(),
            tuple(str(s) for s in shardings),
        ) + plan.key
        fn = self._fn_cache.get(key)
        idxs = plan.flat_idxs  # built once at plan time, device-resident
        # a program-cache miss is a ``utp.build``: its first call traces,
        # lowers and compiles (or loads from the persistent cache), and the
        # span carries those seconds
        counts = {"tasks": len(plan.tasks), "groups": plan.n_groups}
        if fn is None:
            counts["shared_grid_groups"] = shared_grid_groups(plan, self.backend)
            counts["blockspec_groups"] = blockspec_groups(plan, self.backend)
            counts["blocked_panel_groups"] = blocked_panel_groups(plan, self.backend)
        with span("utp.launch" if fn is not None else "utp.build", **counts):
            if fn is None:
                fn = build_program(
                    plan,
                    self.backend,
                    self.donate,
                    out_shardings,
                    batch=batch,
                    wrap=self._wrap_program(),
                )
                self._fn_cache[key] = fn
                self.stats["compiles"] += 1
            faults.fire(
                "executor.launch", batch=batch, n_tasks=len(plan.tasks),
                replay=False,
            )
            faults.fire(
                "launch.oom", batch=batch, n_tasks=len(plan.tasks),
                replay=False,
            )
            outs = fn(grids, idxs)
        outs = faults.corrupt(
            "executor.output", outs, batch=batch, replay=False
        )
        self._note_launch(
            outs, f"stacked{batch}" if batch is not None else "program"
        )
        if stack is not None:
            self._adopt_stacked(member_lists, outs, plan.blocks)
        else:
            for data, g in zip(datas, outs):
                data.set_grid(g)
        if self._capture is not None:
            slots = tuple(self._capture_ids.get(d, -1) for d in plan.roots_order)
            if -1 in slots:
                self._capture_ok = False  # touches a non-root-arg datum
            else:
                faults.fire("memo.capture", batch=batch)
                self._capture.append(
                    ProgramRecord(
                        fn,
                        slots,
                        plan.blocks,
                        idxs,
                        len(plan.tasks),
                        plan.n_groups,
                        plan.n_groups_prefusion,
                        plan.n_slots,
                        batch,
                    )
                )
        for t in plan.tasks:
            t.state = TaskState.FINISHED
            self.stats["tasks"] += 1
            self._finished(t)
        self.stats["launches"] += 1
        self.stats["groups"] += plan.n_groups
        self.stats["groups_prefusion"] += plan.n_groups_prefusion
        self.stats["slots"] += plan.n_slots
        return len(plan.tasks)

    # -- per-group fallback path -----------------------------------------------
    def _build_group_fn(
        self,
        op,
        slots: Tuple[int, ...],
        block_shapes: Tuple[Tuple[int, int], ...],
        root_shapes: Tuple[Tuple[int, int], ...],
        root_dtypes: Tuple,
        write_pos: Tuple[int, ...],
        out_shardings,
    ):
        backend = self.backend
        batched = op.batched_leaf_fn(backend)

        def fn(roots: Tuple[jnp.ndarray, ...], idxs: Tuple[jnp.ndarray, ...]):
            roots = list(roots)
            blocks = []
            for a, slot in enumerate(slots):
                br, bc = block_shapes[a]
                g = to_grid(roots[slot], br, bc)
                blocks.append(g[idxs[a][:, 0], idxs[a][:, 1]])
            outs = batched(*blocks)
            if not isinstance(outs, (tuple, list)):
                outs = (outs,)
            for out, a in zip(outs, write_pos):
                slot = slots[a]
                br, bc = block_shapes[a]
                g = to_grid(roots[slot], br, bc)
                g = g.at[idxs[a][:, 0], idxs[a][:, 1]].set(
                    out.astype(root_dtypes[slot])
                )
                roots[slot] = from_grid(g)
            return tuple(roots)

        jit_kwargs = {}
        if out_shardings is not None:
            jit_kwargs["out_shardings"] = out_shardings
        wrap = self._wrap_program()
        if wrap is not None:
            fn = wrap(fn)
        return jax.jit(fn, donate_argnums=(0,) if self.donate else (), **jit_kwargs)

    def _group_fn(self, op, rep: GTask, roots_order: Tuple[int, ...]):
        slot_of = {d: i for i, d in enumerate(roots_order)}
        slots = tuple(slot_of[v.data.id] for v in rep.args)
        block_shapes = tuple(v.region.shape for v in rep.args)
        roots = {v.data.id: v.data for v in rep.args}
        root_shapes = tuple(roots[d].shape for d in roots_order)
        root_dtypes = tuple(roots[d].dtype for d in roots_order)
        write_pos = tuple(i for i, m in enumerate(rep.modes) if m.writes)
        shardings = tuple(self._shardings.get(d) for d in roots_order)
        out_shardings = shardings if any(s is not None for s in shardings) else None
        key = (
            "group",
            op.name,
            self.backend,
            self.donate,
            slots,
            block_shapes,
            root_shapes,
            root_dtypes,
            write_pos,
            tuple(str(s) for s in shardings),
        )
        if key not in self._fn_cache:
            self._fn_cache[key] = self._build_group_fn(
                op,
                slots,
                block_shapes,
                root_shapes,
                root_dtypes,
                write_pos,
                out_shardings,
            )
            self.stats["compiles"] += 1
        return self._fn_cache[key]

    def execute_wave(self, wave: List[GTask]) -> int:
        for key, tasks in group_wave(wave).items():
            self._run_group(tasks)
        return len(wave)

    def _run_group(self, tasks: List[GTask]) -> None:
        rep = tasks[0]
        op = rep.op
        # stable unique root order
        roots_order: List[int] = []
        for v in rep.args:
            if v.data.id not in roots_order:
                roots_order.append(v.data.id)
        roots_order = tuple(roots_order)
        data_of = {v.data.id: v.data for t in tasks for v in t.args}
        fn = self._group_fn(op, rep, roots_order)
        # pad the batch to a power-of-two bucket so retraces are O(log n)
        # across wave sizes; padding repeats the last task, whose duplicate
        # scatter writes the identical value (idempotent: the gather of the
        # whole batch happens before any scatter in the traced fn).
        n = len(tasks)
        bucket = 1
        while bucket < n:
            bucket *= 2
        pad = [tasks[-1]] * (bucket - n)
        batch = tasks + pad
        idxs = tuple(
            jnp.asarray(
                np.array([t.args[a].block_index() for t in batch], dtype=np.int32)
            )
            for a in range(len(rep.args))
        )
        roots_in = tuple(data_of[d].value for d in roots_order)
        roots_out = fn(roots_in, idxs)
        self._note_launch(roots_out, "group")
        for d, arr in zip(roots_order, roots_out):
            data_of[d].value = arr
        for t in tasks:
            t.state = TaskState.FINISHED
            self.stats["tasks"] += 1
            self._finished(t)
        self.stats["launches"] += 1


class PallasExecutor(JitWaveExecutor):
    """cuBLAS wrapper analog: identical wave batching, Pallas tile kernels as
    leaves.  Under the WaveProgram path its groups lower to the fused
    scalar-prefetch grid kernels (gather/compute/scatter in one kernel, no
    gathered tile stacks in HBM); interpret=True on CPU, compiled on TPUs."""

    name = "pallas"

    def __init__(self, **kw):
        kw.setdefault("backend", "pallas")
        super().__init__(**kw)
