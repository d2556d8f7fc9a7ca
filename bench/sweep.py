"""Find a cell's knee: serve its mix at several fixed rates, one after the
other in one process, and print what a client saw at each.

    python3 bench/sweep.py --workload <cell> --rates 0.5,0.8,1.1 \\
        --seconds 30 --seed 1

The knee is the highest rate whose window delivers the offered tokens
without a growing backlog.  The cell's file (``cells/<cell>.json``)
records the sweep and the rate the benchmark offers.  Benchmark runs do
not run this."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

import run
import spec
import traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    cell = spec.load_cell(args.workload, run.ROOT)
    run.device_info(cell.chips)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    import serve

    enable_compile_cache(run.ROOT)
    _, eng = run.set_up(cell, args.seed, t_start=t_start,
                        log=lambda s: print(s, file=sys.stderr, flush=True))
    mean_out = float(np.mean(traffic.length_quantiles(
        cell.traffic["output"], 1000)))
    for rate in (float(r) for r in args.rates.split(",")):
        r = run.serve_mix(eng, cell, rate, args.seed, args.seconds)
        clients = r["clients"]
        m = serve.client_metrics(clients, r["start"], r["stop"])
        due = [c for c in clients if c.in_window]
        backlog = sum(1 for c in due if not c.token_times)
        t0 = time.perf_counter()
        while eng.active or eng.queue:
            eng.step()
        print(json.dumps(dict(
            m, rate_per_s=rate, offered_tokens_per_s=rate * mean_out,
            due_without_first_token=backlog, drain_s=time.perf_counter() - t0,
            lateness=serve.lateness(clients))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
