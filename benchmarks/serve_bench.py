"""Serving bench: a synthetic multi-tenant trace through the Engine, one
run per scheduler policy, emitting a schema-versioned JSON document.

This is the serving-layer counterpart of ``kernel_bench.py``: instead of
modeled kernel latencies it measures the END metrics the paper optimizes —
TTFT and per-token decode latency (§V/§VII) — and snapshots the GEMV
dispatcher's decision counters per run, so the scheduler's batch-shaping
policy (``gemv_aware`` keeping decode under the dispatcher's batch gate vs
``fcfs`` filling every slot) shows up as a measurable change in the
GEMV-vs-matmul dispatch mix.  By default it serves ``reduced()`` configs;
``--full-width`` serves the published config.  Its wall-clock numbers are
host-clock harness numbers, not a chip benchmark; the dispatch-mix and
scheduling behavior are real.

    PYTHONPATH=src python benchmarks/serve_bench.py                # full trace
    PYTHONPATH=src python benchmarks/serve_bench.py --smoke --json SERVE.json
    PYTHONPATH=src python benchmarks/serve_bench.py --policy gemv_aware
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python benchmarks/serve_bench.py --mesh 1x4 --smoke
    PYTHONPATH=src python benchmarks/serve_bench.py \\
        --trace shared-prefix --prefix-cache --smoke   # §12 hit-rate leg
    PYTHONPATH=src python benchmarks/serve_bench.py --kv-store int8
    PYTHONPATH=src python benchmarks/serve_bench.py \\
        --smoke --trace-out TRACE.json     # Perfetto flight recording
"""

from __future__ import annotations

import argparse
import os

from repro.launch.compile_cache import enable_compile_cache
from repro.serving.bench import SCHEMA_VERSION, run_serve_trace  # noqa: F401
from repro.serving.scheduler import POLICIES


def print_run(run: dict) -> None:
    ttft, ptok = run["ttft_ms"], run["per_token_ms"]
    disp = run["dispatch"]
    mesh = run.get("mesh")
    mesh_tag = ""
    shard_tag = ""
    if mesh:
        mesh_tag = " mesh=" + "x".join(str(v) for v in mesh.values())
        axes = disp.get("sharded_axes", {})
        if axes:
            shard_tag = " shards[" + " ".join(
                f"{a}:{n}" for a, n in sorted(axes.items())) + "]"
    prefix_tag = ""
    pc = run.get("prefix_cache")
    if pc:
        hit = pc["ttft_hit_ms"].get("p50", float("nan"))
        miss = pc["ttft_miss_ms"].get("p50", float("nan"))
        prefix_tag = (
            f" | prefix hit_rate={pc['hit_rate']:.2f} "
            f"saved={pc['prefill_tokens_saved']}tok "
            f"ttft(hit/miss) p50={hit:.1f}/{miss:.1f}ms"
        )
    store_tag = ""
    if run.get("kv_store", "fp") != "fp":
        store_tag = f" kv={run['kv_store']}"
    print(
        f"serve/{run['policy']} slots={run['batch_slots']} "
        f"thresh={run['gemv_batch_threshold']}{mesh_tag}{store_tag}: "
        f"completed={run['completed']} "
        f"ttft p50={ttft.get('p50', float('nan')):.1f}ms "
        f"p99={ttft.get('p99', float('nan')):.1f}ms | "
        f"tok p50={ptok.get('p50', float('nan')):.1f}ms "
        f"p99={ptok.get('p99', float('nan')):.1f}ms | "
        f"{run['tokens_per_s']:.1f} tok/s | "
        f"dispatch gemv={disp['gemv_path']} "
        f"matmul_fallback={disp['matmul_fallback']} "
        f"program_hits={disp['plan_cache']['program_hits']}"
        f"{shard_tag}{prefix_tag}"
    )


def parse_mesh(arg: str) -> tuple[int, int]:
    from repro.launch.mesh import parse_mesh_arg

    return parse_mesh_arg(arg)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--policy", default="all",
                    choices=("all",) + POLICIES,
                    help="scheduler policy to run (default: every policy, "
                         "for the dispatch-mix comparison)")
    ap.add_argument("--requests", type=int, default=None,
                    help="override trace length")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--threshold", type=int, default=4,
                    help="gemv_batch_threshold (kept below --slots so the "
                         "policies measurably diverge)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default=None,
                    help="pin a registered GemvBackend for decode dispatch")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="run the SHARDED engine on a (data, model) device "
                         "mesh, e.g. 1x4 — needs D*M devices (set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count "
                         "off-hardware); records per-shard dispatch stats")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="split prompts longer than this many tokens into "
                         "one-chunk-per-step prefill splices")
    ap.add_argument("--async-prefill", action="store_true",
                    help="overlapped serving (DESIGN.md §14): prefill "
                         "chunks chain on an in-flight sub-cache and "
                         "splice once at harvest, hidden behind decode; "
                         "with --trace-out the summary reports the "
                         "measured hidden_fraction")
    ap.add_argument("--overlap-collectives", action="store_true",
                    help="defer each decode layer's FFN all-reduce to the "
                         "next layer's entry (sharded decode overlap; "
                         "bit-identical tokens)")
    ap.add_argument("--trace", default="uniform",
                    choices=("uniform", "shared-prefix"),
                    help="trace shape: uniform i.i.d. prompts, or the "
                         "Zipf-tenant shared-prefix mixture (DESIGN.md §12)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="serve through the shared-prefix KV cache; runs "
                         "then report hit-rate / prefill-tokens-saved / "
                         "TTFT split")
    ap.add_argument("--kv-store", default="fp",
                    choices=("fp", "int8", "int4"),
                    help="KV storage format (int8/int4: quantized pages + "
                         "per-page scales, kernels.kv_quant)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace + slot count (CI leg)")
    ap.add_argument("--full-width", action="store_true",
                    help="serve the registry config at its published "
                         "widths, depth and dtype (default: its reduced() "
                         "CPU preset)")
    ap.add_argument("--json", metavar="OUT", default=None,
                    help="write the schema-versioned comparison document")
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="flight-record the last policy run: write a "
                         "Perfetto-loadable Chrome trace to PATH and the "
                         "schema-1 summary (phase breakdowns + dispatch "
                         "drift report) next to it")
    ap.add_argument("--trace-timing", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="time each dispatch decision (block_until_ready) "
                         "for the predicted-vs-measured drift report; "
                         "default: on when --trace-out is set")
    args = ap.parse_args(argv)
    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    policies = POLICIES if args.policy == "all" else (args.policy,)
    tcfg = None
    if args.requests is not None:
        from repro.serving.bench import TraceConfig

        base = (TraceConfig.smoke(kind=args.trace) if args.smoke
                else TraceConfig(kind=args.trace))
        tcfg = TraceConfig(**{**base.__dict__, "n_requests": args.requests})
    doc = run_serve_trace(
        args.arch, policies=policies, smoke=args.smoke, seed=args.seed,
        batch_slots=args.slots, gemv_batch_threshold=args.threshold,
        gemv_backend=args.backend,
        mesh_shape=parse_mesh(args.mesh) if args.mesh else None,
        prefill_chunk=args.prefill_chunk,
        async_prefill=args.async_prefill,
        overlap_collectives=args.overlap_collectives,
        trace_kind=args.trace, prefix_cache=args.prefix_cache,
        kv_store=args.kv_store,
        trace_config=tcfg,
        trace_out=args.trace_out, trace_timing=args.trace_timing,
        full_width=args.full_width, out=args.json,
    )
    for run in doc["runs"]:
        print_run(run)
    if args.json:
        print(f"wrote {len(doc['runs'])} runs -> {args.json}")
    ft = doc.get("flight_trace")
    if ft:
        hf = ft.get("hidden_fraction")
        hf_tag = f" hidden_fraction={hf:.3f}" if hf is not None else ""
        print(f"flight trace ({ft['policy']}) -> {ft['path']} "
              f"(summary: {ft['summary']}){hf_tag}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
