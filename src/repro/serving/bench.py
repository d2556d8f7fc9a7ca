"""Synthetic multi-tenant serving trace (DESIGN.md §8.5).

Drives an :class:`~repro.serving.engine.Engine` with a seeded multi-tenant
workload — mixed prompt lengths, Poisson arrivals (exponential
inter-arrival gaps drawn from the rng the caller passes in, in units of
engine steps) — once per scheduler policy, and emits one schema-versioned
JSON document with TTFT / per-token-latency percentiles, throughput, and
the GEMV dispatcher's decision counters per run.

Comparing the ``runs`` entries is the point: the ``gemv_aware`` policy's
batch shaping keeps every decode dispatch on the GEMV path
(``dispatch.matmul_fallback == 0``) where ``fcfs`` fills all slots and
pushes the big-batch shapes onto the XLA matmul fallback — the paper's
orchestration-knob claim (§VII) made measurable at the serving layer.

The dispatcher's plan cache is cleared before each run so decision
counters attribute cleanly per policy (each run constructs a fresh engine,
so its jitted steps re-trace and re-plan; re-planning small shapes is
microseconds).

CLI wrapper: ``benchmarks/serve_bench.py``; the dry-run exposes the same
trace as ``python -m repro.launch.dryrun --serve-trace``.  By default it
serves ``reduced()`` configs (the laptop-scale harness); ``full_width``
serves the published config.  Its times are host-clock numbers, not a
benchmark.
"""

from __future__ import annotations

import json
from dataclasses import dataclass

import numpy as np

# --json document version: bump when the record layout changes.
# v2: per-run "mesh" record (sharded serving) — the dispatch counters then
# carry the ShardedPlan sections (sharded_axes / shard_picks, DESIGN.md §9)
# — and per-request eos_ids in the trace config.
# v3: ragged MoE serving — per-run metrics docs carry the expert_load /
# program_fallbacks dispatch counters and the derived expert_balance
# summary (metrics schema v2, DESIGN.md §10).
# v4: shared-prefix serving (DESIGN.md §12) — the trace config carries the
# ``kind`` / tenant-mixture fields, per-run docs carry ``kv_store`` and
# (with the prefix cache on) the ``prefix_index`` segment-store stats plus
# the metrics doc's ``prefix_cache`` section (metrics schema v3).
# (Tracing is additive, not a schema bump: ``trace_out`` adds the optional
# ``flight_trace`` pointer section; the trace/summary artifacts carry
# their own schema, repro.observability.SCHEMA_VERSION.  Overlapped
# serving (DESIGN.md §14) is additive too: per-run ``async_prefill`` /
# ``overlap_collectives`` booleans, and the trace summary's ``overlap``
# hidden-fraction section rides the observability schema.)
SCHEMA_VERSION = 4

TRACE_KINDS = ("uniform", "shared-prefix")


@dataclass(frozen=True)
class TraceConfig:
    n_requests: int = 24
    arrival_rate: float = 1.5       # mean arrivals per engine step (Poisson)
    prompt_len_range: tuple[int, int] = (4, 24)   # inclusive, mixed tenants
    max_new_range: tuple[int, int] = (4, 12)
    eos_ids: tuple[int, ...] = ()   # tokenizer-aware stop set (empty: none)
    seed: int = 0
    # "uniform": i.i.d. random prompts (the pre-§12 trace).
    # "shared-prefix": the multi-tenant mixture the prefix cache exists
    # for — each tenant owns one seeded system-prompt prefix (length drawn
    # from prefix_len_range), tenants are picked Zipf(zipf_a) per request
    # (a few hot tenants dominate, the realistic skew), and the prompt is
    # that shared prefix plus a private suffix (prompt_len_range).
    kind: str = "uniform"
    n_tenants: int = 4
    zipf_a: float = 1.5             # tenant-popularity skew exponent
    prefix_len_range: tuple[int, int] = (8, 16)

    def __post_init__(self):
        if self.kind not in TRACE_KINDS:
            raise ValueError(
                f"unknown trace kind {self.kind!r}; "
                f"expected one of {TRACE_KINDS}")

    @classmethod
    def smoke(cls, **kw) -> "TraceConfig":
        if kw.get("kind") == "shared-prefix":
            # low arrival rate on purpose: with no queue backlog, TTFT is
            # dominated by prefill work, so the hit/miss TTFT split the CI
            # leg asserts reflects the skipped prefix — not queueing noise
            base = dict(n_requests=16, arrival_rate=0.6,
                        prompt_len_range=(2, 6), max_new_range=(3, 5),
                        n_tenants=3, prefix_len_range=(16, 24))
            base.update(kw)
            return cls(**base)
        return cls(n_requests=10, arrival_rate=4.0,
                   prompt_len_range=(2, 10), max_new_range=(3, 5), **kw)


def build_trace(tcfg: TraceConfig, vocab: int,
                rng: np.random.Generator) -> list[dict]:
    """[{arrival_step, prompt, max_new_tokens, eos_ids}] — arrivals are a
    Poisson process: cumulative exponential gaps from the caller's seeded
    rng; every request carries the trace's stop set (empty = run to
    max_new_tokens, the synthetic-ids default)."""
    lo, hi = tcfg.prompt_len_range
    nlo, nhi = tcfg.max_new_range
    prefixes, weights = [], None
    if tcfg.kind == "shared-prefix":
        plo, phi = tcfg.prefix_len_range
        prefixes = [
            rng.integers(0, vocab,
                         int(rng.integers(plo, phi + 1))).astype(np.int32)
            for _ in range(tcfg.n_tenants)
        ]
        # truncated Zipf over tenant ranks: a few hot system prompts
        w = 1.0 / np.arange(1, tcfg.n_tenants + 1) ** tcfg.zipf_a
        weights = w / w.sum()
    t = 0.0
    out = []
    for i in range(tcfg.n_requests):
        t += rng.exponential(1.0 / tcfg.arrival_rate)
        plen = int(rng.integers(lo, hi + 1))
        prompt = rng.integers(0, vocab, plen).astype(np.int32)
        tenant = None
        if tcfg.kind == "shared-prefix":
            tenant = int(rng.choice(tcfg.n_tenants, p=weights))
            prompt = np.concatenate([prefixes[tenant], prompt])
        out.append({
            "arrival_step": int(t),
            "prompt": prompt,
            "max_new_tokens": int(rng.integers(nlo, nhi + 1)),
            "eos_ids": tuple(tcfg.eos_ids),
            "tenant": tenant,
        })
    return out


def run_policy(cfg, params, policy: str, trace: list[dict], *,
               batch_slots: int, max_len: int, gemv_batch_threshold: int,
               gemv_backend: str | None = None, max_queue: int = 0,
               mesh=None, prefill_chunk: int | None = None,
               async_prefill: bool = False,
               overlap_collectives: bool = False,
               prefix_cache=False, kv_store: str = "fp",
               tracer=None, max_iters: int = 5000) -> dict:
    """Serve one trace under one scheduler policy; returns the metrics doc
    (per-step snapshots dropped — aggregates only) tagged with the run
    configuration.  ``mesh`` runs the sharded engine (DESIGN.md §9): the
    run's dispatch counters then include the per-shard sections.

    ``tracer`` installs a flight recorder (``repro.observability``) for
    this run: the engine records per-request span timelines and the
    dispatcher records per-decision attribution.  The tracer is
    uninstalled before returning, so a traced run never leaks dispatch
    recording into later runs in the same process.
    """
    from repro.kernels import dispatch
    from repro.serving.engine import Engine, Request
    from repro.serving.scheduler import QueueFull

    dispatch.clear_plan_cache()  # attribute dispatch decisions to this run
    eng = Engine(
        cfg, params, batch_slots=batch_slots, max_len=max_len,
        gemv_batch_threshold=gemv_batch_threshold,
        gemv_backend=gemv_backend, scheduler=policy, max_queue=max_queue,
        mesh=mesh, prefill_chunk=prefill_chunk,
        async_prefill=async_prefill,
        overlap_collectives=overlap_collectives,
        prefix_cache=prefix_cache, kv_store=kv_store, tracer=tracer,
    )
    pending = [
        Request(rid=i, prompt=t["prompt"],
                max_new_tokens=t["max_new_tokens"],
                eos_ids=(set(t["eos_ids"]) if t.get("eos_ids") else None))
        for i, t in enumerate(trace)
    ]
    arrivals = [t["arrival_step"] for t in trace]
    done = []
    retry: list = []
    try:
        for step_i in range(max_iters):
            due = retry
            retry = []
            while pending and arrivals[0] <= step_i:
                due.append(pending.pop(0))
                arrivals.pop(0)
            for req in due:
                try:
                    eng.submit(req)
                except QueueFull:
                    retry.append(req)  # backpressure: retry next step
            done.extend(eng.step())
            if (not pending and not retry and not eng.active
                    and not eng._prefilling and not eng.scheduler.queue):
                break
    finally:
        if tracer is not None:
            from repro.observability.trace import uninstall_tracer

            uninstall_tracer(tracer)
    doc = eng.metrics.to_dict(include_steps=False)
    doc.update(
        policy=policy,
        batch_slots=batch_slots,
        gemv_batch_threshold=gemv_batch_threshold,
        async_prefill=bool(async_prefill),
        overlap_collectives=bool(overlap_collectives),
        completed=len(done),
        total_generated=sum(len(r.generated) for r in done),
        mesh=(None if mesh is None
              else {k: int(v) for k, v in mesh.shape.items()}),
        kv_store=kv_store,
        prefix_index=(eng.prefix.stats() if eng.prefix is not None
                      else None),
    )
    return doc


def run_serve_trace(
    arch: str = "olmo-1b", *,
    policies: tuple[str, ...] = ("fcfs", "sjf", "gemv_aware"),
    smoke: bool = False,
    seed: int = 0,
    batch_slots: int = 8,
    max_len: int = 96,
    gemv_batch_threshold: int = 4,
    gemv_backend: str | None = None,
    mesh_shape: tuple[int, int] | None = None,
    prefill_chunk: int | None = None,
    async_prefill: bool = False,
    overlap_collectives: bool = False,
    trace_kind: str = "uniform",
    prefix_cache=False,
    kv_store: str = "fp",
    trace_config: TraceConfig | None = None,
    trace_out: str | None = None,
    trace_timing: bool | None = None,
    full_width: bool = False,
    out: str | None = None,
) -> dict:
    """Serve one synthetic trace under each policy; returns (and optionally
    writes) the schema-versioned comparison document.

    ``gemv_batch_threshold < batch_slots`` on purpose: a slot-filling
    policy then provably crosses the dispatcher's batch gate while
    ``gemv_aware`` stays under it — the dispatch-mix contrast the
    acceptance criteria lock.

    ``mesh_shape=(d, m)`` builds a ``(data, model)`` device mesh and runs
    the SHARDED engine (DESIGN.md §9) — the process needs ``d * m``
    devices (forced-host-platform in CI: ``XLA_FLAGS=--xla_force_host_
    platform_device_count=N``); every run then records the mesh and the
    per-shard dispatch stats.

    ``trace_kind="shared-prefix"`` switches to the Zipf-tenant mixture
    (:class:`TraceConfig`); with ``prefix_cache=True`` every run serves it
    through the shared-prefix subsystem (DESIGN.md §12) and its doc
    carries the hit-rate / prefill-tokens-saved / TTFT-split evidence the
    ``prefix-cache-smoke`` CI leg asserts.  ``kv_store`` selects the KV
    storage format (fp / int8 / int4) for every run.

    ``trace_out=PATH`` flight-records the **last** policy run (one
    artifact per bench; the plan cache is cleared per run so the traced
    run re-plans and every dispatch decision lands in the record) and
    writes a Perfetto-loadable Chrome trace to ``PATH`` plus a schema-1
    summary JSON (per-request phase breakdowns + the predicted-vs-
    measured drift report) next to it (``export.summary_path``).
    ``trace_timing`` adds ``block_until_ready`` measurement to each
    dispatch decision; it defaults to ON when ``trace_out`` is set so the
    drift report prices kernels with both predicted and measured µs out
    of the box — pass ``False`` to record predicted-only.

    ``full_width=True`` serves the registry config as published (its
    widths, depth and dtype) instead of its ``reduced()`` CPU preset; the
    document's ``reduced`` field says which ran.
    """
    from repro.configs.registry import get_config
    from repro.models import lm

    cfg = get_config(arch)
    if not full_width:
        cfg = cfg.reduced()
    params = lm.init_lm_on_device(cfg)
    mesh = None
    if mesh_shape is not None:
        from repro.launch.mesh import make_mesh

        mesh = make_mesh(tuple(mesh_shape), ("data", "model"))
    if smoke:
        batch_slots = min(batch_slots, 4)
        gemv_batch_threshold = min(gemv_batch_threshold, 2)
        tcfg = trace_config or TraceConfig.smoke(kind=trace_kind)
    else:
        tcfg = trace_config or TraceConfig(kind=trace_kind)
    tcfg = TraceConfig(**{**tcfg.__dict__, "seed": seed})
    rng = np.random.default_rng(tcfg.seed)
    trace = build_trace(tcfg, cfg.vocab, rng)
    tracer = None
    if trace_out:
        from repro.observability.trace import Tracer

        timing = True if trace_timing is None else bool(trace_timing)
        tracer = Tracer(timing=timing)
    runs = [
        run_policy(cfg, params, policy, trace, batch_slots=batch_slots,
                   max_len=max_len,
                   gemv_batch_threshold=gemv_batch_threshold,
                   gemv_backend=gemv_backend, mesh=mesh,
                   prefill_chunk=prefill_chunk,
                   async_prefill=async_prefill,
                   overlap_collectives=overlap_collectives,
                   prefix_cache=prefix_cache, kv_store=kv_store,
                   tracer=(tracer if i == len(policies) - 1 else None))
        for i, policy in enumerate(policies)
    ]
    doc = {
        "schema": SCHEMA_VERSION,
        "arch": arch,
        "reduced": not full_width,
        "mesh": (None if mesh is None
                 else {k: int(v) for k, v in mesh.shape.items()}),
        "trace": {
            "kind": tcfg.kind,
            "n_requests": tcfg.n_requests,
            "arrival_rate": tcfg.arrival_rate,
            "prompt_len_range": list(tcfg.prompt_len_range),
            "max_new_range": list(tcfg.max_new_range),
            "eos_ids": list(tcfg.eos_ids),
            "seed": tcfg.seed,
            "n_tenants": (tcfg.n_tenants
                          if tcfg.kind == "shared-prefix" else None),
            "zipf_a": (tcfg.zipf_a
                       if tcfg.kind == "shared-prefix" else None),
            "prefix_len_range": (list(tcfg.prefix_len_range)
                                 if tcfg.kind == "shared-prefix" else None),
        },
        "prefix_cache": bool(prefix_cache),
        "kv_store": kv_store,
        "async_prefill": bool(async_prefill),
        "overlap_collectives": bool(overlap_collectives),
        "runs": runs,
    }
    if tracer is not None:
        from repro.observability import export

        export.write_chrome_trace(tracer, trace_out)
        spath = export.summary_path(trace_out)
        sdoc = export.write_summary(
            tracer, spath,
            extra={"arch": arch, "policy": policies[-1],
                   "run": runs[-1] if runs else None})
        doc["flight_trace"] = {
            "path": trace_out,
            "summary": spath,
            "policy": policies[-1],
            "timing": tracer.timing,
            # surfaced from the summary's overlap section so A/B overlap
            # runs can be compared from the bench doc alone
            "hidden_fraction": (sdoc.get("overlap") or {}).get(
                "hidden_fraction"),
        }
    if out:
        with open(out, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
    return doc
