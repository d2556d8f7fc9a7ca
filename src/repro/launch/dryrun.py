import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape) cell, build the production mesh
(16x16 single-pod and 2x16x16 multi-pod), plan shardings with the PIMnast
mesh planner, ``jit(step).lower(**ShapeDtypeStructs).compile()``, and record:

  * ``compiled.memory_analysis()``  (bytes/device — proves it fits),
  * ``compiled.cost_analysis()``    (HLO FLOPs / bytes for §Roofline),
  * collective bytes parsed from the post-SPMD HLO (all-gather, all-reduce,
    reduce-scatter, all-to-all, collective-permute),

into ``artifacts/dryrun/<arch>__<shape>__<mesh>.json``. Any sharding
mismatch, compile-time OOM, or unsupported collective is a bug in the
framework and fails the cell.

    PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b \
        --shape train_4k --mesh single          # one cell
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
# (no ``from __future__ import annotations``: the XLA_FLAGS lines must be the
# first statements in this module.)

import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np


ARTIFACT_DIR = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun"
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1,
}

_COLL_RE = re.compile(
    r"(\w[\w.\-]*)\s*=\s*(?:\([^)]*\)|(\w+)\[[^\]]*\]|[\w\[\],<> ]+)?\s*"
)


def parse_collective_bytes(hlo: str) -> dict[str, int]:
    """Sum operand bytes of every collective op in post-SPMD HLO text.

    Counts the op's RESULT shape bytes (per-participant payload) for
    all-gather/all-reduce/reduce-scatter/all-to-all/collective-permute.
    """
    out: dict[str, int] = {}
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    for line in hlo.splitlines():
        line = line.strip()
        m = re.match(
            r".*=\s*((?:\w+)\[[^\]]*\](?:\{[^}]*\})?|\([^=]*\))\s*"
            r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)",
            line,
        )
        if not m:
            continue
        kind = m.group(2)
        total = 0
        for dt, dims in shape_re.findall(m.group(1)):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            if dims:
                for d in dims.split(","):
                    if d:
                        n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0) + total
    return out


def _build_step(cfg, shape, mesh, gemv_backend=None, gemv_fused=True):
    """Returns (fn, kwargs_specs, in_shardings_tree) for this cell.

    ``gemv_backend`` routes decode-cell projections through the unified
    GEMV dispatcher pinned to that registered backend (kernels/backends);
    None keeps the plain einsum path the dry-run has always lowered.
    ``gemv_fused`` additionally plans shared-IV projections (QKV, MLP
    gate+up) and MoE expert groups as joint GEMV programs; False lowers
    the per-matrix dispatch of PR-2 for A/B comparison of the two HLOs.
    """
    from repro.distributed import sharding as shd
    from repro.launch.shapes import input_specs
    from repro.models import lm
    from repro.train.optimizer import OptConfig
    from repro.train.train_step import TrainConfig, build_train_step

    specs = input_specs(cfg, shape)
    key = jax.random.PRNGKey(0)
    param_shapes = jax.eval_shape(lambda: lm.init_lm(key, cfg))
    pspec = shd.plan_params(param_shapes, mesh, cfg)
    p_shard = shd.to_named(pspec, mesh)
    bspec = shd.batch_spec(mesh, shape.global_batch)
    from jax.sharding import NamedSharding

    b_shard = NamedSharding(mesh, bspec)

    def batch_shardings(batch_specs):
        out = {}
        for k, v in batch_specs.items():
            if k == "cache":
                continue
            out[k] = NamedSharding(
                mesh, shd.batch_spec(mesh, v.shape[0])
            ) if v.ndim >= 2 else None
        return out

    if shape.kind == "train":
        tcfg = TrainConfig(opt=OptConfig(name=cfg.optimizer))
        step_fn, opt_init = build_train_step(cfg, tcfg)
        opt_shapes = jax.eval_shape(opt_init, param_shapes)
        ospec = shd.plan_params(opt_shapes, mesh, cfg)
        o_shard = shd.to_named(ospec, mesh)

        def fn(params, opt_state, batch):
            return step_fn(params, opt_state, batch)

        args = (param_shapes, opt_shapes, specs)
        in_sh = (p_shard, o_shard, batch_shardings(specs))
        donate = (0, 1)
        return fn, args, in_sh, donate, (p_shard, o_shard, None)

    # serving (prefill / decode)
    cache_shapes = specs["cache"]
    cspec = shd.plan_cache(cache_shapes, mesh, cfg, shape.global_batch)
    c_shard = shd.to_named(cspec, mesh)

    gemv_policy = None
    if gemv_backend is not None and shape.kind == "decode":
        from repro.kernels.dispatch import DispatchPolicy

        gemv_policy = DispatchPolicy(backend=gemv_backend,
                                     fuse_programs=gemv_fused)

    def fn(params, tokens, cache, extra):
        logits, new_cache, _ = lm.forward(
            params, cfg, tokens, cache=cache,
            frames=extra.get("frames"), vision=extra.get("vision"),
            gemv_policy=gemv_policy,
        )
        return logits[:, -1], new_cache

    extra_specs = {
        k: v for k, v in specs.items() if k in ("frames", "vision")
    }
    args = (param_shapes, specs["tokens"], cache_shapes, extra_specs)
    in_sh = (
        p_shard,
        NamedSharding(mesh, shd.batch_spec(mesh, shape.global_batch)),
        c_shard,
        batch_shardings(extra_specs) if extra_specs else {},
    )
    donate = (2,)
    # Explicit OUTPUT shardings (§Perf iteration B2): without them the new
    # KV cache's output layout is the compiler's choice and can replicate.
    from jax.sharding import PartitionSpec as P

    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    b_ax = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)
    nd = int(np.prod([mesh.shape[a] for a in daxes])) if daxes else 1
    logits_spec = P(
        b_ax if shape.global_batch % max(nd, 1) == 0 else None,
        "model" if cfg.vocab % mesh.shape.get("model", 1) == 0 else None,
    )
    out_sh = (NamedSharding(mesh, logits_spec), c_shard)
    return fn, args, in_sh, donate, out_sh


def _cell_metrics(cfg, shape, mesh) -> dict:
    """Compile one variant and extract (flops, bytes, collective bytes)."""
    from repro.distributed.axes import activation_mesh

    fn, args, in_sh, donate, out_sh = _build_step(cfg, shape, mesh)
    with activation_mesh(mesh):
        compiled = (
            jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                    donate_argnums=donate)
            .lower(*args).compile()
        )
    cost = dict(compiled.cost_analysis())
    coll = parse_collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(sum(coll.values())),
        "coll_by_kind": coll,
    }


def roofline_corrected(cfg, shape) -> dict:
    """Exact per-device HLO counts: XLA's cost analysis counts a scan body
    once regardless of trip count, so we compile UNROLLED L1/L2-layer
    variants (L1 = one attention-pattern period) on the single-pod mesh and
    extrapolate  m(L) = base + L * delta  — exact for everything linear in
    depth (layer fwd/bwd, per-layer optimizer update, per-layer collectives);
    embed/lm-head/encoder live in the base term."""
    from repro.launch.mesh import make_production_mesh

    # Pattern period: VLM group structure must be sampled exactly; for
    # local/global attention the per-layer difference is mask-only (same
    # FLOPs/bytes/collectives), so the sampling period is capped at 6.
    if cfg.cross_attn_every > 0:
        period = cfg.cross_attn_every
    else:
        period = min(max(cfg.global_every, 1), 6)
    L2 = min(2 * period, cfg.n_layers)
    L1 = max(period if L2 > period else L2 // 2, 1)
    mesh = make_production_mesh(multi_pod=False)
    cfg1 = dataclasses.replace(cfg, n_layers=L1, unroll_layers=True)
    cfg2 = dataclasses.replace(cfg, n_layers=L2, unroll_layers=True)
    m1 = _cell_metrics(cfg1, shape, mesh)
    m2 = _cell_metrics(cfg2, shape, mesh)
    out = {"L1": L1, "L2": L2}
    for k in ("flops", "bytes", "coll"):
        delta = (m2[k] - m1[k]) / max(L2 - L1, 1)
        base = m1[k] - L1 * delta
        out[k] = base + cfg.n_layers * delta
        out[f"{k}_per_layer"] = delta
        out[f"{k}_base"] = base
    kinds = set(m1["coll_by_kind"]) | set(m2["coll_by_kind"])
    out["coll_by_kind"] = {}
    for kind in kinds:
        a = m1["coll_by_kind"].get(kind, 0)
        b = m2["coll_by_kind"].get(kind, 0)
        d = (b - a) / max(L2 - L1, 1)
        out["coll_by_kind"][kind] = (a - L1 * d) + cfg.n_layers * d
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             roofline: bool = True, gemv_backend: str | None = None,
             gemv_fused: bool = True) -> dict:
    """Lower + compile one (arch, shape, mesh) cell; returns the record."""
    from repro.configs.registry import get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.shapes import SHAPES, applicable

    cfg = get_config(arch)
    # dry-run numerics: bf16 params/compute as deployed
    cfg = dataclasses.replace(
        cfg, param_dtype="bfloat16", compute_dtype="bfloat16"
    )
    shape = SHAPES[shape_name]
    ok, why = applicable(cfg, shape)
    from repro.kernels.backends import resolve_backend

    rec: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "time": time.time(),
        # Provenance: which GemvBackend decode GEMVs would route through in
        # this process (explicit pin, else resolved from the platform), and
        # whether this cell actually engaged the dispatcher (_build_step
        # only installs the policy for decode-kind cells).
        "gemv_backend": gemv_backend or resolve_backend(None).name,
        "gemv_dispatch": gemv_backend is not None and shape.kind == "decode",
        # Whether decode projections lower as joint GEMV programs (fused
        # QKV / gate+up, grouped MoE experts) vs per-matrix dispatch.
        "gemv_fused": (gemv_fused and gemv_backend is not None
                       and shape.kind == "decode"),
    }
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = int(np.prod(list(mesh.shape.values())))
    from repro.distributed.axes import activation_mesh

    t0 = time.perf_counter()
    fn, args, in_sh, donate, out_sh = _build_step(
        cfg, shape, mesh, gemv_backend=gemv_backend, gemv_fused=gemv_fused
    )
    with activation_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
    t_lower = time.perf_counter() - t0

    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    cost = dict(compiled.cost_analysis())
    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo)

    rec.update(
        status="ok",
        n_chips=n_chips,
        mesh_shape={k: int(v) for k, v in mesh.shape.items()},
        lower_s=t_lower,
        compile_s=t_compile,
        flops=float(cost.get("flops", -1)) if cost else -1.0,
        bytes_accessed=float(cost.get("bytes accessed", -1)) if cost else -1,
        memory={
            "argument_size": getattr(mem, "argument_size_in_bytes", None),
            "output_size": getattr(mem, "output_size_in_bytes", None),
            "temp_size": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size": getattr(
                mem, "generated_code_size_in_bytes", None
            ),
        },
        collective_bytes=coll,
        collective_total=sum(coll.values()),
        hlo_lines=len(hlo.splitlines()),
        model_params=cfg.param_count(),
        model_params_active=cfg.active_param_count(),
    )
    if roofline and mesh_kind == "single":
        try:
            rec["roofline"] = roofline_corrected(cfg, shape)
        except Exception as e:
            rec["roofline"] = {"error": repr(e)}
    return rec


def save_record(rec: dict) -> str:
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
    path = os.path.join(ARTIFACT_DIR, name)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return path


def run_serve_traces(args) -> int:
    """``--serve-trace``: drive the serving subsystem's synthetic
    multi-tenant trace (Poisson arrivals, mixed prompt lengths) on the
    reduced config of each requested arch and record the schema-versioned
    serving document next to the dry-run artifacts.

    Where the compile audit proves each cell *lowers*, this proves the
    serving layer *serves* it — TTFT / per-token-latency percentiles plus
    the per-policy GEMV-vs-matmul dispatch mix (DESIGN.md §8.5).
    """
    from repro.serving.bench import run_serve_trace

    serve_dir = os.path.join(ARTIFACT_DIR, "..", "serving")
    os.makedirs(serve_dir, exist_ok=True)
    policies = tuple(
        p for p in args.serve_policies.split(",") if p
    )
    mesh_shape = None
    tag = "serve_trace"
    if args.serve_mesh:
        from repro.launch.mesh import parse_mesh_arg

        mesh_shape = parse_mesh_arg(args.serve_mesh)
        tag = "serve_trace_{}x{}".format(*mesh_shape)
    archs = [args.arch] if args.arch else ["olmo-1b"]
    if args.all:
        from repro.configs.registry import ARCHS
        archs = sorted(ARCHS)
    failures = 0
    for arch in archs:
        path = os.path.join(serve_dir, f"{arch}__{tag}.json")
        try:
            doc = run_serve_trace(
                arch, policies=policies, smoke=True,
                gemv_backend=args.gemv_backend, mesh_shape=mesh_shape,
                out=path,
            )
        except Exception as e:
            failures += 1
            print(f"[FAIL] serve-trace {arch}: {e!r}")
            if not args.continue_on_error:
                raise
            continue
        for run in doc["runs"]:
            d = run["dispatch"]
            print(
                f"[ok]   serve-trace {arch} x {run['policy']}: "
                f"{run['completed']} done, "
                f"ttft p50 {run['ttft_ms'].get('p50', float('nan')):.0f}ms, "
                f"tok p50 {run['per_token_ms'].get('p50', float('nan')):.1f}ms, "
                f"gemv {d['gemv_path']} / matmul {d['matmul_fallback']} "
                f"-> {os.path.basename(path)}"
            )
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--continue-on-error", action="store_true")
    ap.add_argument("--no-roofline", action="store_true",
                    help="skip the unrolled L1/L2 corrected-metric compiles")
    ap.add_argument("--gemv-backend", default=None,
                    help="route decode-cell GEMVs through this registered "
                         "GemvBackend (cpu|gpu|tpu); default keeps einsum")
    ap.add_argument("--no-gemv-fused", action="store_true",
                    help="with --gemv-backend: per-matrix dispatch instead "
                         "of fused/grouped GEMV programs (A/B the HLOs)")
    ap.add_argument("--serve-trace", action="store_true",
                    help="run the synthetic multi-tenant serving trace "
                         "(repro.serving.bench) on the reduced config "
                         "instead of the compile audit; writes "
                         "artifacts/serving/<arch>__serve_trace.json")
    ap.add_argument("--serve-policies", default="fcfs,sjf,gemv_aware",
                    help="comma-separated scheduler policies for "
                         "--serve-trace")
    ap.add_argument("--serve-mesh", default=None, metavar="DxM",
                    help="with --serve-trace: run the SHARDED engine on a "
                         "(data, model) mesh (e.g. 1x4) — the dry-run's "
                         "forced-host-platform device grid supplies the "
                         "devices; artifacts record per-shard dispatch "
                         "stats (DESIGN.md §9)")
    args = ap.parse_args(argv)

    if args.serve_trace:
        return run_serve_traces(args)

    from repro.configs.registry import ARCHS
    from repro.launch.shapes import SHAPES

    archs = sorted(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                tag = f"{arch} x {shape} x {mesh_kind}"
                try:
                    rec = run_cell(arch, shape, mesh_kind,
                                   roofline=not args.no_roofline,
                                   gemv_backend=args.gemv_backend,
                                   gemv_fused=not args.no_gemv_fused)
                except Exception as e:
                    failures += 1
                    rec = {
                        "arch": arch, "shape": shape, "mesh": mesh_kind,
                        "status": "error", "error": repr(e),
                        "traceback": traceback.format_exc()[-4000:],
                    }
                    print(f"[FAIL] {tag}: {e}")
                    if not args.continue_on_error:
                        save_record(rec)
                        raise
                path = save_record(rec)
                if rec["status"] == "ok":
                    mb = (rec["memory"]["argument_size"] or 0) / 2**20
                    print(
                        f"[ok]   {tag}: compile {rec['compile_s']:.1f}s "
                        f"flops {rec['flops']:.3g} "
                        f"coll {rec['collective_total']/2**20:.1f}MiB "
                        f"args {mb:.0f}MiB -> {os.path.basename(path)}"
                    )
                elif rec["status"] == "skipped":
                    print(f"[skip] {tag}: {rec['reason']}")
    return failures


if __name__ == "__main__":
    raise SystemExit(main())
