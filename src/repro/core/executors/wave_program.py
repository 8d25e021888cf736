"""WaveProgram: dependency-exact whole-schedule compiled execution (DESIGN.md §2).

The dispatcher hands the leaf executor a complete level schedule — an
ordered list of waves of independent tasks — plus the exact task DAG behind
it (``versioning.TaskDag``).  At seed every wave group was a separate
Python-dispatched ``jit`` call; PR 1 compiled the whole barrier-wave
schedule into ONE jitted XLA program over grid-resident roots.  This pass
goes further: the barrier between waves is replaced by a **dependency-exact
group schedule**:

    plan   = plan_schedule(waves, dag)  # fusion + issue slots + indices
    fn     = build_program(plan, ...)   # one traced fn, cached on plan.key
    grids' = fn(grids, plan.flat_idxs)  # one dispatch per drain

Scheduling pass (``dag`` present):

1. **Exact issue.**  Initial groups (same signature within one Kahn wave)
   are re-scheduled by their *actual* predecessor groups: a group's issue
   slot is its longest-path depth in the fused-group DAG, not its Kahn wave
   index.  Groups sharing a slot are mutually independent — that is the
   precondition both for fusing them (below) and for ordering them freely
   (lookahead) without consulting the barrier structure.
2. **Cross-wave fusion.**  Two groups fuse into one larger batched launch —
   one bigger vmap batch — iff they have the same signature (operation,
   write positions, per-arg block shapes and dtypes) and NO path connects
   their tasks (``TaskDag.independent``; the planner uses the conservative
   quotient-graph form of the query, which implies it).  Fusion works
   across roots: a fused group carries per-segment argument slots and the
   program concatenates the per-segment gathers, so independent workloads
   (e.g. LU of A and LU of B in one drain) share launches.
3. **Lookahead.**  Within a slot, groups are ordered by critical-path
   height (longest chain of dependent tasks below them), so the next panel
   factorization (GETRF/POTRF) is traced before independent trailing
   updates that happen to share its slot — the order XLA's scheduler sees
   through the donated in-place grids follows the critical path.

Roots stay in ``(nr, nc, br, bc)`` grid-major layout for the duration (the
``GData`` grid-resident epoch).  Block indices are traced arguments, built
ONCE at plan time into a single ``(total, 2)`` device array
(``SchedulePlan.flat_idxs``); drain replay reuses the device-resident array
untouched.  Two drains whose schedules share a structure (slot/group/
segment signatures, shapes, dtypes) hit the same compiled program.

Per single-segment group the compiler can still emit the operation's fused
grid kernel (``Operation.grid_fused_fn`` — Pallas scalar-prefetch gather/
compute/scatter aliased to the written grid).  Group sizes are exact, never
padded — also after fusion: every group is traced inline into one program,
so pow2 bucketing would buy no compile savings, and duplicate trailing
indices are unsound for read-write fused kernels.  (The *batch* axis of a
stacked drain is different: ``build_program(batch=B)`` pads B to a pow2
bucket upstream, because B is a jit shape every program specializes on —
DESIGN.md §7; lanes are whole independent workloads, so padding lanes
never alias real writes.)

Asynchronous dispatch (DESIGN.md §12): the jitted fn a WaveProgram compiles
to RETURNS BEFORE the device finishes — JAX dispatch is async, so calling
``fn(grids, idxs)`` costs host microseconds and the result grids are array
futures.  Nothing in this module (or downstream of it on the drain path)
forces materialization: outputs go straight back into grid-resident
``GData`` epochs, the executor records them as an ``InFlightEpoch``, and
the next drain's planning/tracing/dispatch proceeds while this program
executes.  The contract that makes this safe is donation discipline:
``donate_argnums=(0,)`` means a program CONSUMES its input grids, so the
only party allowed to hand a possibly-in-flight grid to a new program is
the executor's stacked grid-reuse fast path, which proves sole ownership
via the epoch holder count first — XLA then serializes the two programs on
the donated buffer, no host fence required.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...kernels.tile_linalg import PANEL_SOLVES, _dma_readable, _panel_strip
from ...testing import faults
from ..data import GData
from ..task import GTask
from .base import group_wave


@dataclass(frozen=True, eq=False)
class GroupPlan:
    """One fused task group: static signature + per-segment index data.

    ``segments`` carries one ``(arg_slots, size)`` entry per merged source
    group; a group fused across roots has one segment per distinct slot
    tuple.  ``idxs`` holds per-arg ``(total_size, 2)`` int32 block coords,
    rows ordered segment by segment.
    """

    op: object  # Operation
    write_pos: Tuple[int, ...]  # arg positions with write access
    segments: Tuple[Tuple[Tuple[int, ...], int], ...]  # ((slots...), size)
    idxs: Tuple[np.ndarray, ...]  # per-arg (size, 2) int32 block coords
    height: int  # critical-path priority (lookahead ordering)

    @property
    def arg_slots(self) -> Tuple[int, ...]:
        return self.segments[0][0]

    @property
    def size(self) -> int:
        return sum(s for _, s in self.segments)

    @property
    def sig(self) -> tuple:
        return (self.op.name, self.segments, self.write_pos)


@dataclass
class SchedulePlan:
    """A fully analyzed, dependency-exactly scheduled drain."""

    roots_order: Tuple[int, ...]  # data ids, stable by first appearance
    datas: Dict[int, GData]
    blocks: Tuple[Tuple[int, int], ...]  # per-slot leaf block shape (br, bc)
    slots: List[List[GroupPlan]]  # issue slots; groups in a slot independent
    tasks: List[GTask]  # all tasks in slot order
    key: tuple  # structural cache key (no data identity)
    flat_idxs: jnp.ndarray  # ONE (total, 2) int32 array, built at plan time
    n_groups_prefusion: int  # barrier-wave group count (pre-fusion)

    @property
    def n_groups(self) -> int:
        return sum(len(s) for s in self.slots)

    @property
    def n_slots(self) -> int:
        return len(self.slots)

    def groups(self):
        for slot in self.slots:
            yield from slot


class _Fused:
    """Mutable fusion-pass state for one (eventually fused) group."""

    __slots__ = ("op", "write_pos", "compat", "segments", "preds", "task_ids")

    def __init__(self, op, write_pos, compat, arg_slots, tasks, preds):
        self.op = op
        self.write_pos = write_pos
        self.compat = compat
        self.segments: List[Tuple[Tuple[int, ...], List[GTask]]] = [
            (arg_slots, list(tasks))
        ]
        self.preds: Set[int] = set(preds)
        self.task_ids: Set[int] = {t.id for t in tasks}

    def merge(self, arg_slots, tasks, preds) -> None:
        for slots_, members in self.segments:
            if slots_ == arg_slots:
                members.extend(tasks)
                break
        else:
            self.segments.append((arg_slots, list(tasks)))
        self.preds |= preds
        self.task_ids |= {t.id for t in tasks}


def _fuse(
    waves: Sequence[Sequence[GTask]],
    dag,
    slot_of: Dict[int, int],
) -> Tuple[List[List[_Fused]], int]:
    """Dependency-exact scheduling pass: fusion + issue-slot assignment.

    Returns (slots, prefusion_group_count).  Legality (DESIGN.md §2): a
    group may merge into an earlier one iff their signatures match and no
    path connects them.  The pass maintains the *quotient* DAG over fused
    groups and checks the candidate's transitive quotient ancestors — a
    quotient path implies a task path would be ordered through a third
    launch, so quotient-ancestor-freedom implies ``TaskDag.independent``
    and additionally keeps the fused-group DAG acyclic (schedulable) under
    repeated merging, which pairwise task-level independence alone would
    not guarantee.
    """
    fused: List[_Fused] = []
    owner: Dict[int, int] = {}  # task id -> fused group index
    wave_of: List[int] = []  # fused index -> source wave (dag-less fallback)
    prefusion = 0
    for wi, wave in enumerate(waves):
        for _, tasks in group_wave(wave).items():
            prefusion += 1
            rep = tasks[0]
            arg_slots = tuple(slot_of[v.data.id] for v in rep.args)
            write_pos = tuple(i for i, m in enumerate(rep.modes) if m.writes)
            compat = (
                rep.op.name,
                write_pos,
                tuple(v.region.shape for v in rep.args),
                tuple(str(jnp.dtype(v.data.dtype)) for v in rep.args),
            )
            dpreds: Set[int] = set()
            target = None
            if dag is not None:
                for t in tasks:
                    for p in dag.preds.get(t.id, ()):
                        dpreds.add(owner[p])
                # transitive ancestors in the current quotient DAG
                anc: Set[int] = set()
                stack = list(dpreds)
                while stack:
                    f = stack.pop()
                    if f not in anc:
                        anc.add(f)
                        stack.extend(fused[f].preds - anc)
                for fi, f in enumerate(fused):
                    if f.compat == compat and fi not in anc:
                        target = fi
                        break
            if target is None:
                target = len(fused)
                fused.append(
                    _Fused(rep.op, write_pos, compat, arg_slots, tasks, dpreds)
                )
                wave_of.append(wi)
            else:
                fused[target].merge(arg_slots, tasks, dpreds)
            for t in tasks:
                owner[t.id] = target

    if dag is None:
        # no DAG: keep the barrier-wave structure (slot = Kahn wave)
        depth = {i: w for i, w in enumerate(wave_of)}
    else:
        # issue slot = longest-path depth in the (acyclic) fused-group DAG
        depth = {}
        for i in range(len(fused)):
            stack = [i]
            while stack:
                g = stack[-1]
                if g in depth:
                    stack.pop()
                    continue
                missing = [p for p in fused[g].preds if p not in depth]
                if missing:
                    stack.extend(missing)
                    continue
                depth[g] = (
                    1 + max(depth[p] for p in fused[g].preds)
                    if fused[g].preds
                    else 0
                )
                stack.pop()
    n_slots = 1 + max(depth.values()) if depth else 0
    slots: List[List[_Fused]] = [[] for _ in range(n_slots)]
    for i, f in enumerate(fused):
        slots[depth[i]].append(f)
    return slots, prefusion


def _mutate_merge_dependent_groups(slots: List[List[_Fused]]) -> bool:
    """``plan.merge_groups`` fault site (DESIGN.md §11): force-merge the
    first same-signature group pair sitting in DIFFERENT issue slots.

    Such a pair is dependent by construction — the legal fusion pass has
    already merged every same-signature INDEPENDENT pair — so the merge
    produces exactly the corrupted shape ``verify_plan`` must reject: one
    launch containing path-connected tasks (V1), usually with overlapping
    write blocks as well (V3/V4).  Mutating after slotting (not inside
    ``_fuse``) keeps the quotient DAG acyclic, so planning itself cannot
    hang — the bug ships silently unless the verifier catches it.
    """
    flat = [
        (si, f) for si, groups in enumerate(slots) for f in groups
    ]
    for i, (si, f1) in enumerate(flat):
        for sj, f2 in flat[i + 1 :]:
            if sj > si and f1.compat == f2.compat and faults.fires(
                "plan.merge_groups", op=f1.op.name, slots=(si, sj)
            ):
                for slots_, ts in f2.segments:
                    f1.merge(slots_, ts, f2.preds)
                slots[sj].remove(f2)
                return True
    return False


def plan_schedule(
    waves: Sequence[Sequence[GTask]], dag=None
) -> Optional[SchedulePlan]:
    """Analyze a level schedule for whole-program compilation.

    ``dag`` is the scope's ``versioning.TaskDag``; when given, the
    dependency-exact pass fuses same-signature groups across former wave
    boundaries and re-slots groups by actual predecessors.  Without it the
    barrier-wave structure is kept (one slot per wave).

    Returns None (caller falls back to per-wave launches) when the schedule
    is not grid-uniform: some root lacks a value, or a task's region is not
    one aligned block of that root's uniform leaf grid.
    """
    roots_order: List[int] = []
    datas: Dict[int, GData] = {}
    blocks: Dict[int, Tuple[int, int]] = {}
    for wave in waves:
        for t in wave:
            for v in t.args:
                d = v.data
                if d.id not in datas:
                    if not d.has_value:
                        return None
                    roots_order.append(d.id)
                    datas[d.id] = d
                    blocks[d.id] = v.region.shape
                br, bc = blocks[d.id]
                r = v.region
                if (
                    r.shape != (br, bc)
                    or r.r0 % br
                    or r.c0 % bc
                    or d.shape[0] % br
                    or d.shape[1] % bc
                ):
                    return None
    if not any(waves):
        return None
    slot_of = {d: i for i, d in enumerate(roots_order)}

    heights = dag.heights() if dag is not None else {}
    fused_slots, prefusion = _fuse(waves, dag, slot_of)
    if faults.active():
        _mutate_merge_dependent_groups(fused_slots)

    plan_slots: List[List[GroupPlan]] = []
    tasks: List[GTask] = []
    for slot in fused_slots:
        groups: List[GroupPlan] = []
        for f in slot:
            members = [t for _, ts in f.segments for t in ts]
            n_args = len(f.segments[0][0])
            idxs = tuple(
                np.array(
                    [t.args[a].block_index() for t in members], dtype=np.int32
                )
                for a in range(n_args)
            )
            segments = tuple((slots_, len(ts)) for slots_, ts in f.segments)
            height = max((heights.get(t.id, 0) for t in members), default=0)
            groups.append(
                GroupPlan(f.op, f.write_pos, segments, idxs, height)
            )
        # lookahead: critical-path-first trace order within the slot
        order = sorted(range(len(groups)), key=lambda i: (-groups[i].height, i))
        groups = [groups[i] for i in order]
        slot = [slot[i] for i in order]
        plan_slots.append(groups)
        for f in slot:
            for _, ts in f.segments:
                tasks.extend(ts)

    roots = tuple(roots_order)
    blocks_t = tuple(blocks[d] for d in roots)
    key = (
        tuple(
            (datas[d].shape, str(jnp.dtype(datas[d].dtype)), blocks[d])
            for d in roots
        ),
        tuple(tuple(g.sig for g in slot) for slot in plan_slots),
    )
    parts = [ix for slot in plan_slots for g in slot for ix in g.idxs]
    flat = jnp.asarray(np.concatenate(parts, axis=0))
    return SchedulePlan(
        roots, datas, blocks_t, plan_slots, tasks, key, flat, prefusion
    )


def _fused_call(g: GroupPlan, backend: str):
    """The operation's fused grid kernel for group ``g``, or None (gather
    path): only single-segment groups that write exactly the kernel's write
    argument take it."""
    fused = g.op.grid_fused_fn(backend)
    if fused is not None and len(g.segments) == 1 and g.write_pos == (fused[1],):
        return fused[0]
    return None


def shared_grid_groups(plan: SchedulePlan, backend: str) -> int:
    """Fused groups of ``plan`` whose arguments share a grid, which the
    program hands to the kernel once (``utp.build``'s count)."""
    return sum(
        1
        for g in plan.groups()
        if _fused_call(g, backend) is not None
        and len(set(g.arg_slots)) < len(g.arg_slots)
    )


def blockspec_groups(plan: SchedulePlan, backend: str) -> int:
    """Fused groups of ``plan`` that read at least one grid by BlockSpec, one
    operand per argument, because its tile is not whole (sublane, lane)
    layout tiles and so cannot be sliced by DMA (``utp.build``'s count).
    Counted by the chip's rule, so a CPU run counts what the TPU would."""
    thin = [
        not _dma_readable(plan.blocks[s], plan.datas[d].dtype, interpret=False)
        for s, d in enumerate(plan.roots_order)
    ]
    return sum(
        1
        for g in plan.groups()
        if _fused_call(g, backend) is not None and any(thin[s] for s in g.arg_slots)
    )


def blocked_panel_groups(plan: SchedulePlan, backend: str) -> int:
    """Fused panel-solve groups of ``plan`` whose triangular tile (argument
    0) is solved in diagonal strips with MXU dots between them, not by one
    whole-tile recurrence (``utp.build``'s count)."""
    order = [blk[-1] for blk in plan.blocks]
    return sum(
        1
        for g in plan.groups()
        if g.op.name in PANEL_SOLVES
        and _fused_call(g, backend) is not None
        and _panel_strip(order[g.arg_slots[0]]) < order[g.arg_slots[0]]
    )


def build_program(
    plan: SchedulePlan,
    backend: str,
    donate: bool,
    out_shardings=None,
    batch: Optional[int] = None,
    wrap=None,
):
    """Trace ``plan`` into one jitted fn: (grids, idx_array) -> grids'.

    ``wrap``, when given, maps the traced program to the function that is
    jitted (``ShardExecutor`` runs Pallas programs inside ``shard_map``).

    With ``batch=B`` the SAME plan is traced in stacked form (DESIGN.md §7):
    every root grid carries a leading batch dimension ``(B, nr, nc, br, bc)``
    holding B structurally identical workloads, gathers pull ``(B, size)``
    blocks per group and flatten the two batch axes into one stack for the
    operation's batched leaf (so leaves need no batch awareness beyond the
    existing stacked-tiles convention), and the Pallas fused grid kernels
    run with a leading batch grid dimension.  The block-index array is the
    per-lane one, shared by all lanes — launch count and index traffic stay
    flat in B.

    Groups are traced slot by slot in lookahead order.  Per group: the
    operation's fused grid kernel (single-segment groups only) or gather ->
    batched leaf -> scatter, with multi-segment groups concatenating the
    per-segment gathers and splitting the scatters across their roots.
    Data movement stays per-group: coalescing all of a slot's scatters into
    one big scatter per root was measured as a CPU pessimization (the
    cross-op output concatenation blocks XLA fusion and the larger scatter
    is not cheaper), so slots drive *scheduling* (fusion legality, exact
    issue, lookahead order), not movement batching.

    A group's reads are legal against the current grids even mid-slot: any
    block a group reads and a slot-mate writes would be a RAW/WAR edge,
    and edges force different slots.

    The returned fn dispatches asynchronously (module docstring /
    DESIGN.md §12): callers must treat its outputs as in-flight until a
    fence of their choosing, and must not re-donate an input grid they do
    not solely own.
    """
    dtypes = tuple(plan.datas[d].dtype for d in plan.roots_order)

    # copy only the static fields out of each GroupPlan: the closure (and
    # thus the process-global program cache) must not retain the per-task
    # numpy index arrays, which reach the program as a traced argument
    steps = []
    base = 0
    for g in plan.groups():
        faults.fire("leaf.fn", op=g.op.name, backend=backend)
        fn = _fused_call(g, backend)
        if fn is not None:
            kind = "fused"
        else:
            kind = "gather"
            fn = g.op.batched_leaf_fn(backend)
        steps.append((g.op.name, kind, fn, g.segments, g.write_pos, g.size, base))
        base += len(g.arg_slots) * g.size

    def program(grids: Tuple[jnp.ndarray, ...], idxs: jnp.ndarray):
        grids = list(grids)
        for op, kind, fn, segments, write_pos, size, b0 in steps:
            # each group's ops carry its operation in their ``op_name``
            # metadata (``jit(program)/trsm/...``): metadata only, the
            # compiled code is the same
            with jax.named_scope(op):
                _trace_group(grids, idxs, kind, fn, segments, write_pos, size, b0)
        return tuple(grids)

    def _trace_group(grids, idxs, kind, fn, segments, write_pos, size, b0):
        # static-offset slices of the single flat index array (trace
        # order matches SchedulePlan.flat_idxs)
        n_args = len(segments[0][0])
        gidx = [
            idxs[b0 + a * size : b0 + (a + 1) * size]
            for a in range(n_args)
        ]
        if kind == "fused":
            # each distinct grid is one operand of the kernel: a grid passed
            # twice, once aliased to the output, would be copied by XLA
            slots_ = segments[0][0]
            distinct = list(dict.fromkeys(slots_))
            arg_grid = tuple(distinct.index(s) for s in slots_)
            grids[slots_[write_pos[0]]] = fn(
                gidx, tuple(grids[s] for s in distinct), arg_grid
            )
            return
        blocks = []
        for a in range(n_args):
            chunks = []
            off = 0
            for slots_, ssize in segments:
                ix = gidx[a][off : off + ssize]
                g = grids[slots_[a]]
                if batch is None:
                    chunks.append(g[ix[:, 0], ix[:, 1]])
                else:
                    chunks.append(g[:, ix[:, 0], ix[:, 1]])
                off += ssize
            stack = (
                chunks[0]
                if len(chunks) == 1
                else jnp.concatenate(chunks, axis=0 if batch is None else 1)
            )
            if batch is not None:
                # flatten (B, group) into one leaf stack: the batched
                # leaf is elementwise over the stack, so lane order only
                # has to match the un-flatten below
                stack = stack.reshape((batch * size,) + stack.shape[2:])
            blocks.append(stack)
        outs = fn(*blocks)
        if not isinstance(outs, (tuple, list)):
            outs = (outs,)
        for out, a in zip(outs, write_pos):
            if batch is not None:
                out = out.reshape((batch, size) + out.shape[1:])
            off = 0
            for slots_, ssize in segments:
                r = slots_[a]
                ix = gidx[a][off : off + ssize]
                if batch is None:
                    part = (
                        out
                        if len(segments) == 1
                        else out[off : off + ssize]
                    )
                    grids[r] = grids[r].at[ix[:, 0], ix[:, 1]].set(
                        part.astype(dtypes[r])
                    )
                else:
                    part = (
                        out
                        if len(segments) == 1
                        else out[:, off : off + ssize]
                    )
                    grids[r] = grids[r].at[:, ix[:, 0], ix[:, 1]].set(
                        part.astype(dtypes[r])
                    )
                off += ssize

    jit_kwargs = {}
    if out_shardings is not None:
        jit_kwargs["out_shardings"] = out_shardings
    if wrap is not None:
        program = wrap(program)
    return jax.jit(
        program, donate_argnums=(0,) if donate else (), **jit_kwargs
    )
