"""Serving example: continuous-batching decode with the PIMnast-placed
decode path — GEMV-dominated token generation, the paper's target regime.

    PYTHONPATH=src python examples/serve_decode.py [--arch olmo-1b]
    PYTHONPATH=src python examples/serve_decode.py --full-width  # on a TPU
"""

import argparse
import os
import time

import jax
import numpy as np

from repro.configs.registry import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serving.engine import Engine, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--new-tokens", type=int, default=12)
    ap.add_argument("--policy", default="fcfs",
                    choices=("fcfs", "sjf", "gemv_aware"))
    ap.add_argument("--full-width", action="store_true",
                    help="serve the registry config at its published "
                         "widths, depth and dtype (default: reduced())")
    args = ap.parse_args()
    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    cfg = get_config(args.arch)
    if not args.full_width:
        cfg = cfg.reduced()
    params = lm.init_lm_on_device(cfg)
    eng = Engine(cfg, params, batch_slots=args.slots, max_len=96,
                 scheduler=args.policy)

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(args.requests):
        # mixed prompt lengths: the slot-managed cache decodes them in one
        # batch with per-slot positions (DESIGN.md §8.1)
        plen = int(rng.integers(4, 17))
        eng.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab, plen).astype(np.int32),
            max_new_tokens=args.new_tokens,
        ))
    done = eng.run_until_drained()
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.generated) for r in done)
    dev = jax.devices()[0]
    print(f"{len(done)} requests, {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens/dt:.1f} tok/s host clock on {dev.platform} "
          f"{dev.device_kind}, compilation included; {args.slots} slots, "
          f"{args.policy})")
    m = eng.metrics.to_dict(include_steps=False)
    print(f"  ttft p50={m['ttft_ms'].get('p50', 0):.0f}ms "
          f"p99={m['ttft_ms'].get('p99', 0):.0f}ms | per-token "
          f"p50={m['per_token_ms'].get('p50', 0):.1f}ms | dispatch "
          f"gemv={m['dispatch']['gemv_path']} "
          f"matmul={m['dispatch']['matmul_fallback']}")
    for r in done[:3]:
        print(f"  req {r.rid}: {r.generated}")


if __name__ == "__main__":
    main()
