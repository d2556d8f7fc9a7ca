"""Readings that set a cell's correctness limits: run the cell on several
seeds in one process and print, for each, the numbers ``check.summary``
gives for the served tokens (the lower reading's candidates) and for the
control on the same sequences (the reference computed in int8 and in
fp8, the upper reading's candidates).

    python3 bench/readings.py --workload <cell> --seeds 11,12,13 --seconds 30

Benchmark runs do not run this."""

from __future__ import annotations

import argparse
import json
import sys
import time

import check
import run
import spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds also read the control")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, run.ROOT)
    device = run.device_info(cell.chips)
    peaks = spec.load_peaks(device["kind"])
    sys.path.insert(0, run.ROOT + "/src")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(run.ROOT)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        extra = {}

        def control(ref, w, reqs, length, extra=extra,
                    on=i < args.control_seeds):
            if on:
                for p in ("int8", "fp8"):
                    extra[p] = check.control_gaps(ref, w, cell.model, reqs,
                                                  length, p)

        r = run.run_cell(cell, seed, args.seconds, False, device, peaks,
                         t_start=t0, after_check=control)
        print(json.dumps({"seed": seed, "program": r["check"],
                          "control": extra,
                          "run_s": time.perf_counter() - t0,
                          "metrics": r["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
