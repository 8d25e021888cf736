"""Back-to-back calls of one entry point, each ending in ``block_until_ready``.

Mix parameters: ``op`` (``cholesky``, the entry point the configuration names
for it), ``pool`` (inputs made at set-up and used in turn), ``warmup`` (calls
before the window), ``check`` (calls of the window whose answers are
compared with the reference, drawn from the seed).

The window runs calls until ``seconds`` have passed and the last call has
finished; ``solution_s`` is that whole time over the calls made.  After each
call the loop runs Python's cycle collector: each entry call leaves its
grids (1 GiB at n = 16384) in reference cycles that only the collector
frees, and without it the chip runs out of memory after about eight calls.
"""

from __future__ import annotations

import gc
import importlib
import time

import jax

from bench import inputs, reference


def _entry(spec: str):
    mod, name = spec.split(":")
    return getattr(importlib.import_module(mod), name)


class Driver:
    def __init__(self, config: dict, mix: dict, seed: int, spans):
        self.config, self.mix, self.seed, self.spans = config, mix, seed, spans
        self.op = mix["op"]
        if self.op != "cholesky":
            raise ValueError(f"repeat_solve runs cholesky, not {self.op!r}")
        n = config["n"]
        self.parts = tuple(tuple(lv) for lv in config["partitions"])
        self.entry = _entry(config["entry_points"][self.op])
        k = inputs.fold(inputs.key(seed), 1)
        self.pool = [(a,) for a in inputs.unstack(inputs.pool(k, "spd", int(mix["pool"]), (n,)))]
        for i in range(int(mix["warmup"])):
            jax.block_until_ready(self._call(i))
        self.kept = {}

    def _call(self, i: int):
        (a,) = self.pool[i % len(self.pool)]
        return self.entry(a, graph=self.config["graph"], partitions=self.parts)

    def run(self, seconds: float) -> dict:
        rng = inputs.np_rng(self.seed, 3)
        want = int(self.mix["check"])
        t0 = time.perf_counter()
        calls = 0
        while True:
            with self.spans("entry_call"):
                out = self._call(calls)
            with self.spans("wait"):
                out = jax.block_until_ready(out)
            # reservoir sample of the window's answers, drawn from the seed
            if calls < want:
                self.kept[calls] = out
            else:
                j = int(rng.integers(0, calls + 1))
                if j < want:
                    victim = sorted(self.kept)[j]
                    del self.kept[victim]
                    self.kept[calls] = out
            del out
            with self.spans("gc"):
                gc.collect()
            calls += 1
            if time.perf_counter() - t0 >= seconds:
                break
        window = time.perf_counter() - t0
        return {
            "window_s": window,
            "attempted": calls,
            "failed": 0,
            "metrics": {"solution_s": window / calls},
            "counts": {"solutions": calls, "gc_s": sum(self.spans.durations("gc"))},
        }

    def check(self):
        """Free the program's caches, then compare the kept answers with the
        reference, one at a time; returns ``[(name, value, limit)]``."""
        from repro.core.executors import clear_compile_cache

        clear_compile_cache()
        worst = 0.0
        for i in sorted(self.kept):
            x = self.kept.pop(i)
            ref = reference.solve(self.op, self.pool[i % len(self.pool)], block=self.config["tile"])
            worst = max(worst, float(reference.gap(x, ref)))
            del x, ref
        name = f"ref_gap.{self.op}"
        return [(name, worst, float(self.config["limits"][name]))]
