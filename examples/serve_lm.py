"""Batched serving example: continuous batching over a slot pool, with the
audio-frontend arch exercising the stub-embedding path.

    PYTHONPATH=src python examples/serve_lm.py --arch starcoder2-7b --requests 8
"""

import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.compat import enable_compile_cache
from repro.configs import get_arch
from repro.models import build_model
from repro.serving import EngineConfig, Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-7b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=12)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = get_arch(args.arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(
        cfg, params,
        EngineConfig(slots=args.slots, max_seq=128,
                     temperature=args.temperature),
    )
    rng = np.random.default_rng(0)
    t0 = time.time()
    for i in range(args.requests):
        eng.submit(
            Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=6 + i % 5),
                    max_new_tokens=args.new_tokens)
        )
    done = eng.run_until_drained()
    dt = time.time() - t0
    n_tok = sum(len(r.out_tokens) for r in done)
    for r in done[:4]:
        print(f"req {r.rid}: prompt[{len(r.prompt)}] -> {r.out_tokens}")
    ttft = np.mean([r.t_first - r.t_submit for r in done])
    print(
        f"{len(done)} requests, {n_tok} tokens in {dt:.2f}s "
        f"({n_tok/dt:.1f} tok/s, {args.slots} slots, "
        f"{eng.decode_steps} batched decode steps, mean TTFT {ttft*1e3:.0f}ms)"
    )


if __name__ == "__main__":
    enable_compile_cache()
    main()
