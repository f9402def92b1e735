"""Production serving launcher (SISA-aware continuous batching).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-0.5b --smoke \
        --requests 16
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serve import make_engine, Request


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--engine", default="sequential",
                    choices=["sequential", "slot", "paged"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    print(f"[launch.serve] arch={cfg.name} devices={jax.device_count()}")
    params = jax.jit(functools.partial(init_params, cfg))(
        jax.random.PRNGKey(args.seed))
    eng = make_engine(cfg, params, kind=args.engine, max_slots=8,
                      max_seq=args.max_seq)
    rng = np.random.default_rng(args.seed)
    # paper Fig 1a prompt-length distribution: median 12, mean ~42
    lengths = np.minimum(rng.zipf(1.5, size=args.requests) + 11,
                         args.max_seq // 2)
    for i, L in enumerate(lengths):
        eng.submit(Request(rid=i,
                           prompt=rng.integers(2, cfg.vocab_size, size=int(L)
                                               ).astype(np.int32),
                           max_new_tokens=args.max_new_tokens))
    t0 = time.time()
    done = eng.run(max_steps=4096)
    dt = time.time() - t0
    ttft = eng.stats["ttft"]
    print(f"[launch.serve] {len(done)}/{args.requests} done in {dt:.1f}s; "
          f"TTFT p50={np.median(ttft)*1e3:.0f}ms; "
          f"batch choices={eng.stats['batches']}")
    return 0 if len(done) == args.requests else 1


if __name__ == "__main__":
    raise SystemExit(main())
