from .base import Executor, group_wave
from .inline import InlineExecutor
from .jit_wave import (
    JitWaveExecutor,
    PallasExecutor,
    clear_compile_cache,
    drain_memo_pressure,
    drain_memo_records,
    drain_memo_stats,
    set_drain_memo_capacity,
)
from .sharded import ShardExecutor, row_sharding
from .wave_program import SchedulePlan, build_program, plan_schedule

__all__ = [
    "Executor",
    "InlineExecutor",
    "JitWaveExecutor",
    "PallasExecutor",
    "SchedulePlan",
    "ShardExecutor",
    "build_program",
    "clear_compile_cache",
    "drain_memo_pressure",
    "drain_memo_records",
    "drain_memo_stats",
    "group_wave",
    "plan_schedule",
    "row_sharding",
    "set_drain_memo_capacity",
]
