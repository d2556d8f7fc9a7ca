"""Kernel micro-bench: PIMnast-placed Pallas GEMV vs the jnp oracle, plus
dispatcher-picked vs fixed-kernel latency across the config registry.

Off a TPU the Pallas kernels execute in interpret mode, so wall-clock
numbers there characterize the HARNESS, not TPU performance — the
``derived`` column is therefore the max abs error vs the oracle (the
correctness contract), and per-kernel modeled HBM-bound time on v5e
(weight bytes over the TPU backend's modeled bandwidth) is reported as
``v5e_model_us``.

The ``dispatch`` section is the paper's headline experiment in backend
form: for each model-config decode GEMV shape it reports the chosen
backend's picked kernel and its *modeled* latency against every fixed
kernel of that backend — the gap is the balancing win that a hard-coded
kernel leaves on the table.  The ``program`` section does the same for
grouped/fused GEMV *programs* (fused QKV, MLP gate+up, MoE expert groups):
each row compares the jointly planned program against N independent
dispatches — launch counts and modeled latency — the amortization the
``GemvProgram`` API exists for.  ``--backend`` swaps the memory system
under comparison (tpu / cpu / gpu cost models); ``--json OUT`` emits a
``{"schema": .., "rows": .., "program_rows": .., "moe_rows": ..}``
document for the bench trajectory.  The ``moe`` section compares the
capacity-padded einsum/grouped expert paths against the ragged program
(model-only — no expert weights are allocated) for the MoE archs.

    PYTHONPATH=src python benchmarks/kernel_bench.py            # all parts
    PYTHONPATH=src python benchmarks/kernel_bench.py --dispatch # just the
                                                                # comparisons
    PYTHONPATH=src python benchmarks/kernel_bench.py --dispatch \
        --backend cpu --json bench.json
"""

from __future__ import annotations

import argparse
import json
import time

import jax.numpy as jnp
import numpy as np

from repro.kernels import available_backends, dispatch, get_backend, ops
from repro.kernels.backends import ProgramKey
from repro.kernels.dispatch import DispatchPolicy

# --json document version: bump when the record layout changes.
# 1 (implicit): bare list of dispatch rows.
# 2: {"schema", "rows", "program_rows"} with the program comparison.
# 3: + "moe_rows" — capacity-padded einsum/grouped vs ragged expert
#    dispatch per MoE arch (model-only; DESIGN.md §10).
# 4: measured dispatch rows gain predicted_us/<kern> +
#    pred_over_measured/<kern> (every bench run doubles as a model-error
#    probe) and cost_model_source (seed vs calibrated; DESIGN.md §11).
# 5: + "pipeline_rows" — depth-1 vs depth-2 staged variants of the
#    streaming Pallas kernels (the pipeline_depth plan knob, DESIGN.md
#    §14): modeled + measured head-to-head per registry shape, with the
#    bit-identity check (max_abs_diff) as the correctness column.
SCHEMA_VERSION = 5

SHAPES = [
    # (name, M, K, B)  — decode-path GEMVs from the assigned archs
    ("gemma3-1b/ffn_up", 6912, 1152, 1),
    ("gemma3-27b/ffn_up", 21504, 5376, 1),
    ("minitron/qkv", 4096 + 2 * 1024, 4096, 1),
    ("olmo/ffn_down", 2048, 8192, 4),
    ("grok/expert_up", 4096, 6144, 8),
]

# Dispatcher comparison runs over decode projections of registry configs
# (kept to the smaller archs: interpret mode re-executes every kernel body).
DISPATCH_ARCHS = ("gemma3-1b", "olmo-1b", "minitron-8b")


def _interprets(backend_name: str) -> bool:
    """Whether this host runs ``backend_name``'s Pallas kernels through the
    interpreter: always off the backend's own platform, never on it."""
    return (backend_name != "cpu"
            and dispatch.resolve_backend().name != backend_name)


def fixed_kernels(backend_name: str) -> tuple[str, ...]:
    """Fixed-kernel comparison rows: the backend's registered non-quant
    set (quant kernels need quantized weights; these rows are bf16/f32)."""
    return tuple(
        k for k in get_backend(backend_name).kernels
        if not k.startswith("quant")
    )


def kernel_rows() -> list[tuple[str, float, float]]:
    rows = []
    rng = np.random.default_rng(0)
    tpu = get_backend("tpu")
    hbm_bw = tpu.cost_model.bandwidth_bps
    # the TPU kernels: native on a TPU, the Pallas interpreter elsewhere
    interpret = tpu.default_interpret()
    mode = "interp" if interpret else "native"
    for name, M, K, B in SHAPES:
        w = rng.standard_normal((M, K)).astype(np.float32)
        x = rng.standard_normal((B, K)).astype(np.float32)
        packed = ops.pack_weight(jnp.asarray(w))
        t0 = time.perf_counter()
        out = ops.placed_gemv(jnp.asarray(x), packed, interpret=interpret)
        out.block_until_ready()
        dt = (time.perf_counter() - t0) * 1e6
        err = float(np.abs(np.asarray(out) - x @ w.T).max())
        rows.append((f"kernel/{name}/{mode}", dt, err))
        v5e_us = (M * K * 2) / hbm_bw * 1e6
        rows.append((f"kernel/{name}/v5e_hbm_model", v5e_us, 0.0))
        # quantized variant (int8 + block scales)
        pq = ops.quantize_weight(w, bits=8, block=32)
        t0 = time.perf_counter()
        oq = ops.placed_gemv(jnp.asarray(x), pq, interpret=interpret)
        oq.block_until_ready()
        dtq = (time.perf_counter() - t0) * 1e6
        rel = float(
            np.abs(np.asarray(oq) - x @ w.T).max() / np.abs(x @ w.T).max()
        )
        rows.append((f"kernel/{name}/int8", dtq, rel))
    return rows


def registry_gemv_shapes() -> list[tuple[str, int, int, int]]:
    """Decode-path GEMV shapes (M, K, B) from the model-config registry."""
    from repro.configs.registry import ARCHS

    shapes = []
    for name in DISPATCH_ARCHS:
        cfg = ARCHS[name]
        shapes.append((f"{name}/ffn_up", cfg.d_ff, cfg.d_model, 1))
        shapes.append((f"{name}/ffn_down", cfg.d_model, cfg.d_ff, 1))
        shapes.append((f"{name}/lm_head", cfg.vocab, cfg.d_model, 1))
    return shapes


def dispatch_rows(measure: bool = True,
                  backend_name: str = "tpu") -> list[dict]:
    """Backend-picked vs fixed-kernel rows per registry shape.

    Each row carries the backend, its picked kernel, the modeled latency of
    every fixed kernel (the decision basis), and — when ``measure`` —
    measured wall clock for the picked and fixed paths.  On this container
    the TPU/GPU backends measure in interpret mode (harness numbers); the
    CPU backend's figures are real XLA executions.
    """
    backend = get_backend(backend_name)
    fixed = fixed_kernels(backend_name)
    # The TPU/GPU backends need the explicit interpret opt-in to run their
    # Pallas kernels on another platform's host; the CPU backend is XLA.
    interp = _interprets(backend_name)
    rng = np.random.default_rng(0)
    rows = []
    for name, M, K, B in registry_gemv_shapes():
        sel_policy = DispatchPolicy(backend=backend_name, interpret=interp)
        picked, _ = backend.select_kernel(M, K, B, policy=sel_policy)
        row: dict = {
            "shape": name, "M": M, "K": K, "B": B,
            "backend": backend_name, "picked": picked,
        }
        for kern in fixed:
            _, plan = backend.select_kernel(
                M, K, B,
                policy=DispatchPolicy(backend=backend_name, kernel=kern,
                                      interpret=interp),
            )
            row[f"model_us/{kern}"] = backend.estimate_cost_us(
                "ref" if plan is None else kern, M, K, B, plan=plan
            )
        row["model_us/picked"] = row[f"model_us/{picked}"]
        row["cost_model_source"] = backend.cost_model_source
        # interpret mode re-executes the kernel body with jnp per grid
        # program: cap measured shapes (lm_head weights exceed 1 GB in f32)
        if measure and M * K * 4 <= 256 * 2**20:
            w = rng.standard_normal((M, K)).astype(np.float32)
            x = rng.standard_normal((B, K)).astype(np.float32)
            pw = ops.pack_weight(jnp.asarray(w))
            xj = jnp.asarray(x)
            for kern in ("auto",) + fixed:
                pol = DispatchPolicy(backend=backend_name, kernel=kern,
                                     interpret=interp or None)
                measured = dispatch.time_gemv_us(
                    lambda: dispatch.dispatch_gemv(xj, pw, policy=pol),
                    reps=2,
                )
                row[f"measured_us/{kern}"] = measured
                # every measured row doubles as a model-error probe: the
                # prediction is the modeled latency of the kernel this pin
                # actually runs (x_bytes=4 — the measured arrays are f32).
                run_kern, run_plan = backend.select_kernel(
                    M, K, B, x_bytes=4, policy=pol)
                predicted = backend.estimate_cost_us(
                    run_kern, M, K, B, x_bytes=4, plan=run_plan)
                row[f"predicted_us/{kern}"] = predicted
                row[f"pred_over_measured/{kern}"] = predicted / measured
        rows.append(row)
    return rows


def registry_program_shapes() -> list[tuple[str, str, tuple[int, ...],
                                            int, int, int]]:
    """Grouped/fused decode program shapes from the model-config registry.

    Rows are (name, kind, Ms, K, batch, group): fused QKV and MLP gate+up
    for the dense archs, expert groups for the MoE archs (batch = tokens
    per expert at a decode step).
    """
    from repro.configs.registry import ARCHS

    shapes = []
    for name in ("gemma3-1b", "minitron-8b"):
        cfg = ARCHS[name]
        hd = cfg.hd
        qkv = (cfg.n_heads * hd, cfg.n_kv_heads * hd, cfg.n_kv_heads * hd)
        shapes.append((f"{name}/qkv", "fused", qkv, cfg.d_model, 1, 3))
        if cfg.act in ("silu", "geglu"):
            shapes.append((f"{name}/gate_up", "fused",
                           (cfg.d_ff, cfg.d_ff), cfg.d_model, 1, 2))
    for name in ("deepseek-moe-16b", "grok-1-314b"):
        cfg = ARCHS[name]
        e = cfg.moe
        shapes.append((f"{name}/expert_up", "grouped", (e.d_expert,),
                       cfg.d_model, 8, e.n_experts))
    return shapes


def program_rows(backend_name: str = "tpu") -> list[dict]:
    """Program-vs-independent comparison per registry program shape.

    Each row reports the backend's planned mode, the launch count of the
    planned program vs N independent dispatches (the amortization the
    acceptance criteria lock), and the modeled latency of both.
    """
    backend = get_backend(backend_name)
    interp = _interprets(backend_name)
    policy = DispatchPolicy(backend=backend_name, interpret=interp)
    rows = []
    for name, kind, Ms, K, batch, group in registry_program_shapes():
        key = ProgramKey(kind=kind, Ms=Ms, K=K, batch=batch, group=group,
                         bits=16, block=32, dtype="float32",
                         backend=backend_name)
        pplan = backend.plan_program(key, policy=policy)
        rows.append({
            "shape": name, "kind": kind, "Ms": list(Ms), "K": K,
            "B": batch, "group": group, "backend": backend_name,
            "mode": pplan.mode,
            "kernel": pplan.kernel or None,
            "launches_program": pplan.n_launches,
            "launches_independent": key.n_requests,
            "model_us/program": backend.estimate_program_cost_us(
                key, mode=pplan.mode),
            "model_us/independent": backend.estimate_program_cost_us(
                key, mode="per_request"),
        })
    return rows


def pipeline_rows(backend_name: str = "tpu",
                  measure: bool = True) -> list[dict]:
    """Depth-1 vs depth-2 staged kernel comparison per registry shape.

    ``pipeline_depth`` folds d K-blocks into one grid step (one wider
    BlockSpec stream, d accumulating sub-tile dots), trading VMEM for
    fewer per-program overheads — the double-buffering the autotuner
    measures head-to-head (``backends/tpu.py::PIPELINE_DEPTHS``).  Rows
    carry the modeled latency at both depths and, for shapes small enough
    to interpret, measured wall clock plus ``max_abs_diff`` between the
    depth-1 and depth-2 outputs — the staging is bit-identical by
    construction, so the column must read 0.  TPU-plan concept: other
    backends return no rows.
    """
    if backend_name != "tpu":
        return []
    from repro.kernels.tpu_plan import (
        plan_splitk, plan_tpu_gemv, valid_splitk_degree,
        with_pipeline_depth,
    )

    backend = get_backend("tpu")
    interp = backend.default_interpret()
    rng = np.random.default_rng(0)
    rows = []
    for name, M, K, B in registry_gemv_shapes():
        if not ops.pallas_applicable(M, K):
            continue
        base_plans = {"pim": plan_tpu_gemv(M, K, B)}
        deg = valid_splitk_degree(K)
        if deg is not None:
            base_plans["splitk"] = plan_splitk(M, K, B, degree=deg)
        small = M * K * 4 <= 256 * 2**20
        pw = None
        if measure and small:
            w = rng.standard_normal((M, K)).astype(np.float32)
            x = rng.standard_normal((B, K)).astype(np.float32)
            pw = ops.pack_weight(jnp.asarray(w))
            xj = jnp.asarray(x)
        for kern, base in base_plans.items():
            deep = with_pipeline_depth(base, 2, batch=B)
            if deep is None:
                continue  # n_k odd or VMEM budget: depth 2 not plannable
            row = {
                "shape": name, "kernel": kern, "M": M, "K": K, "B": B,
                "backend": backend_name,
                "model_us/depth1": backend.estimate_cost_us(
                    kern, M, K, B, plan=base),
                "model_us/depth2": backend.estimate_cost_us(
                    kern, M, K, B, plan=deep),
            }
            if pw is not None:
                outs = {}
                for depth, plan in ((1, base), (2, deep)):
                    row[f"measured_us/depth{depth}"] = dispatch.time_gemv_us(
                        lambda plan=plan: backend.execute(
                            kern, xj, pw, plan, interpret=interp),
                        reps=2,
                    )
                    outs[depth] = np.asarray(
                        backend.execute(kern, xj, pw, plan,
                                        interpret=interp))
                row["max_abs_diff"] = float(
                    np.abs(outs[1] - outs[2]).max())
            rows.append(row)
    return rows


def print_pipeline_table(rows: list[dict]) -> None:
    for r in rows:
        line = (
            f"pipeline/{r['shape']} [{r['M']}x{r['K']} B={r['B']}] "
            f"{r['kernel']} model d1={r['model_us/depth1']:.1f}us "
            f"d2={r['model_us/depth2']:.1f}us"
        )
        if "measured_us/depth1" in r:
            line += (
                f" | measured d1={r['measured_us/depth1']:.0f}us "
                f"d2={r['measured_us/depth2']:.0f}us "
                f"max_abs_diff={r['max_abs_diff']:.1g}"
            )
        print(line)


MOE_ARCHS = ("deepseek-moe-16b", "grok-1-314b")
MOE_DECODE_BATCH = 8  # decode tokens per step (one per active slot)


def moe_rows(backend_name: str = "tpu") -> list[dict]:
    """Capacity-padded vs ragged expert dispatch, model-only.

    Weights are never allocated (grok's expert stack alone is ~6.4 GB in
    f32): every figure comes from ``estimate_program_cost_us``.  Three
    execution shapes per MoE arch at a decode step of ``MOE_DECODE_BATCH``
    tokens:

    * ``einsum`` — the legacy capacity path decomposed per expert: E
      independent dispatches over [C, K] padded buffers;
    * ``grouped`` — the same padded buffers as ONE batched contraction
      (launch amortization, padding kept);
    * ``ragged`` — the native ragged program: activation traffic is
      exactly the routed tokens, zero capacity-padding FLOPs.

    ``mode`` is the backend's *planned* mode for the ragged key — the CI
    leg asserts it stays on the ragged path at decode shapes.
    """
    from repro.configs.registry import ARCHS
    from repro.kernels.backends.base import expert_batch_bound
    from repro.models.layers import _capacity

    backend = get_backend(backend_name)
    interp = _interprets(backend_name)
    policy = DispatchPolicy(backend=backend_name, interpret=interp)
    B = MOE_DECODE_BATCH
    rows = []
    for name in MOE_ARCHS:
        cfg = ARCHS[name]
        e = cfg.moe
        C = _capacity(1, cfg)  # per-token decode chunks, as the layer runs
        routed = B * e.top_k
        grouped_key = ProgramKey(
            kind="grouped", Ms=(e.d_expert,), K=cfg.d_model, batch=C,
            group=e.n_experts, bits=16, block=32, dtype="float32",
            backend=backend_name)
        ragged_key = ProgramKey(
            kind="ragged", Ms=(e.d_expert,), K=cfg.d_model,
            batch=expert_batch_bound(B, e.top_k, e.n_experts),
            group=e.n_experts, bits=16, block=32, dtype="float32",
            backend=backend_name, tokens=routed)
        pplan = backend.plan_program(ragged_key, policy=policy)
        rows.append({
            "arch": name, "experts": e.n_experts, "top_k": e.top_k,
            "M": e.d_expert, "K": cfg.d_model, "B": B,
            "capacity": C, "routed_tokens": routed,
            "padded_slots": max(B * e.n_experts * C - routed, 0),
            "backend": backend_name, "mode": pplan.mode,
            "model_us/einsum": backend.estimate_program_cost_us(
                grouped_key, mode="per_request"),
            "model_us/grouped": backend.estimate_program_cost_us(
                grouped_key, mode="grouped"),
            "model_us/ragged": backend.estimate_program_cost_us(
                ragged_key, mode="ragged"),
        })
    return rows


def print_moe_table(rows: list[dict]) -> None:
    for r in rows:
        print(
            f"moe/{r['arch']} [{r['M']}x{r['K']} E={r['experts']} "
            f"k={r['top_k']} B={r['B']} cap={r['capacity']}] "
            f"backend={r['backend']} mode={r['mode']} "
            f"einsum={r['model_us/einsum']:.1f}us "
            f"grouped={r['model_us/grouped']:.1f}us "
            f"ragged={r['model_us/ragged']:.1f}us "
            f"(padded_slots={r['padded_slots']})"
        )


def print_program_table(rows: list[dict]) -> None:
    for r in rows:
        ms = "+".join(str(m) for m in r["Ms"])
        print(
            f"program/{r['shape']} [{r['kind']} {ms}x{r['K']} B={r['B']} "
            f"e={r['group']}] backend={r['backend']} mode={r['mode']} "
            f"launches={r['launches_program']} "
            f"(vs {r['launches_independent']} independent) "
            f"model={r['model_us/program']:.1f}us "
            f"(vs {r['model_us/independent']:.1f}us)"
        )


def print_dispatch_table(rows: list[dict]) -> None:
    for r in rows:
        fixed = fixed_kernels(r["backend"])
        fixed_s = " ".join(
            f"{k}={r[f'model_us/{k}']:.1f}us" for k in fixed
        )
        line = (
            f"dispatch/{r['shape']} [{r['M']}x{r['K']} B={r['B']}] "
            f"backend={r['backend']} picked={r['picked']} "
            f"model={r['model_us/picked']:.1f}us | fixed: {fixed_s}"
        )
        if "measured_us/auto" in r:
            meas = " ".join(
                f"{k}={r[f'measured_us/{k}']:.0f}us"
                for k in ("auto",) + fixed
                if f"measured_us/{k}" in r
            )
            line += f" | measured: {meas}"
        if "pred_over_measured/auto" in r:
            line += (f" | pred/meas(auto)="
                     f"{r['pred_over_measured/auto']:.2f} "
                     f"[{r['cost_model_source']}]")
        print(line)


def run_calibrate(args) -> int:
    """The --calibrate mode: sweep -> fit -> artifact -> activate
    (repro.calibration; DESIGN.md §11).  One command, exit 0 on success."""
    from repro.calibration import calibrate_backend

    doc = calibrate_backend(
        args.backend, smoke=args.smoke, trials=args.trials,
        out_dir=args.out_dir, table_path=args.table,
    )
    print(f"calibrate/{doc['backend']}: {doc['n_records']} records, "
          f"mape={doc['mape']:.3f} (seed {doc['seed_mape']:.3f})"
          + (" [degenerate]" if doc["degenerate"] else ""))
    for kern, err in sorted(doc["per_kernel_mape"].items()):
        print(f"calibrate/{doc['backend']}/{kern}: mape={err:.3f}")
    for term, val in sorted(doc["fitted"].items()):
        print(f"calibrate/{doc['backend']}/fit {term}={val:.6g}")
    print(f"wrote calibration artifact -> {doc['path']}")
    if args.table:
        print(f"merged calibration section -> {args.table}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dispatch", action="store_true",
                    help="only the dispatcher-vs-fixed comparison")
    ap.add_argument("--backend", default="tpu",
                    choices=available_backends(),
                    help="GemvBackend whose cost model/kernels to compare")
    ap.add_argument("--no-measure", action="store_true",
                    help="skip measured wall clock (model only)")
    ap.add_argument("--pipeline-depth", action="store_true",
                    help="also print the depth-1 vs depth-2 staged-kernel "
                         "sweep (pipeline_depth plan knob, DESIGN.md §14); "
                         "the rows are always in the --json document")
    ap.add_argument("--json", metavar="OUT", default=None,
                    help="write the dispatcher rows as JSON records")
    ap.add_argument("--calibrate", action="store_true",
                    help="measure + fit this backend's CostModel constants "
                         "and write artifacts/calibration/<backend>.json "
                         "(repro.calibration; DESIGN.md §11)")
    ap.add_argument("--smoke", action="store_true",
                    help="with --calibrate: the small CI sweep tier")
    ap.add_argument("--trials", type=int, default=0,
                    help="with --calibrate: timed trials per record "
                         "(0 = tier default)")
    ap.add_argument("--out-dir", default=None,
                    help="with --calibrate: artifact directory "
                         "(default artifacts/calibration)")
    ap.add_argument("--table", default=None,
                    help="with --calibrate: also merge the fitted "
                         "constants into this v3 autotune table")
    args = ap.parse_args(argv)
    if args.calibrate:
        if args.out_dir is None:
            from repro.calibration.artifact import DEFAULT_OUT_DIR
            args.out_dir = DEFAULT_OUT_DIR
        return run_calibrate(args)
    if not args.dispatch:
        for r in kernel_rows():
            print(f"{r[0]},{r[1]:.3f},{r[2]:.6f}")
    rows = dispatch_rows(measure=not args.no_measure,
                         backend_name=args.backend)
    print_dispatch_table(rows)
    prog_rows = program_rows(backend_name=args.backend)
    print_program_table(prog_rows)
    m_rows = moe_rows(backend_name=args.backend)
    print_moe_table(m_rows)
    p_rows = []
    if args.pipeline_depth or args.json:
        p_rows = pipeline_rows(backend_name=args.backend,
                               measure=not args.no_measure)
    if args.pipeline_depth:
        print_pipeline_table(p_rows)
    if args.json:
        doc = {"schema": SCHEMA_VERSION, "rows": rows,
               "program_rows": prog_rows, "moe_rows": m_rows,
               "pipeline_rows": p_rows}
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        print(f"wrote {len(rows)} + {len(prog_rows)} + {len(m_rows)} "
              f"+ {len(p_rows)} records -> {args.json}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
