"""Unified GEMV dispatch: programs of requests, pluggable backends.

The paper's core claim is that GEMV speedup comes from placement decisions
*parameterized by the memory system* (§IV, Algorithm 1) — and its PIM
broadcasts one command stream and one input-vector chunk to all banks, so
GEMVs that share an IV (fused QKV, MLP gate+up) or form an expert group
(MoE) must be planned **together** or the broadcast/launch cost is paid
once per matrix instead of once per group.  The dispatcher's unit of work
is therefore the :class:`GemvProgram` — N :class:`GemvRequest`\\ s planned
jointly (DESIGN.md §7):

* :func:`dispatch_program` — the program entry point.  The resolved
  :class:`~repro.kernels.backends.GemvBackend` plans the group (a fused-M
  kernel on the concatenated weight, a batched expert contraction, or the
  per-request decomposition every backend supports) and executes it;
* :func:`dispatch_fused` / :func:`dispatch_grouped` — conveniences that
  build the two first-class program shapes;
* :func:`dispatch_gemv` / :func:`dispatch_dense` — thin single-request
  wrappers (one request is the degenerate program).

Every entry point:

1. **resolves a backend** — explicit ``DispatchPolicy.backend`` override,
   else the ``interpret=True`` validation opt-in (TPU analogue), else
   ``jax.default_backend()`` (cpu -> XLA-native, tpu -> Pallas,
   gpu -> Pallas-Triton behind a capability check);
2. **normalizes weights** into one :class:`PackedWeights` representation
   (transposed K-major storage; optional int8/int4 + block scales;
   ``pack_fused``/``PackedWeights.stack`` for program shapes),
3. **delegates selection/planning** to the backend — cost model, loaded
   autotune table entry, or measured autotune, in that precedence — and
4. **memoizes** the decision in a process-level, thread-safe plan cache
   keyed on shape + dtype + backend + policy.

Plan cache and autotuning
-------------------------
``_PLAN_CACHE`` / ``_PROGRAM_CACHE`` memoize decisions per
:class:`GemvKey` / :class:`ProgramKey` so repeated dispatches of one shape
(every decode step, every scanned layer) do zero planning work;
``plan_cache_stats()`` exposes hit counts for both.  All cache and table
mutation is lock-guarded: an :class:`~repro.serving.engine.Engine` can be
stepped from a thread pool.  With ``policy.autotune=True`` the backend
times its own candidates and persists winners to the JSON table at
``policy.table_path`` under the backend's namespace — single-GEMV entries
in ``tables``, program entries in the v3 ``programs`` section — so one
table file serves a heterogeneous fleet (``backends/base.py:AutotuneTable``).

Deprecated surface
------------------
The PR-1 free functions (``select_kernel``, ``estimate_cost_us``,
``autotune_gemv``) and cost-model module constants (``HBM_BW``,
``XLA_GEMV_EFF``, ``PALLAS_LAUNCH_US``, ``PROGRAM_US``,
``MIN_PARALLEL_BLOCKS``, ``KERNELS``) remain as thin shims over the ``tpu``
backend — the one whose behavior they described — and warn **once per call
site** (they sit on per-step hot paths; see ``_warn_deprecated_once``).
New code should go through ``get_backend(...)`` / the backend methods.
"""

from __future__ import annotations

import copy
import dataclasses
import threading
import time

import jax.numpy as jnp
import numpy as np

from repro.kernels.backends import (
    AutotuneTable,
    DEFAULT_POLICY,
    DispatchPolicy,
    GemvKey,
    GemvPlan,
    GemvProgram,
    GemvRequest,
    ProgramKey,
    ProgramPlan,
    ShardedPlan,
    available_backends,
    get_backend,
    resolve_backend,
    time_gemv_us,  # noqa: F401  (re-export: benchmarks import it from here)
)
from repro.kernels.backends.base import (
    entry_to_plan as _entry_to_plan,
    entry_to_program_plan as _entry_to_program_plan,
)
from repro.kernels.ops import (
    PackedWeights,
    pack_weight,
)
from repro.kernels.tpu_plan import TPUGemvPlan
from repro.observability.log import reset_warn_once, warn_once
from repro.observability.trace import current_tracer as _current_tracer

__all__ = [
    "DispatchPolicy", "DEFAULT_POLICY", "GemvKey", "GemvPlan",
    "GemvRequest", "GemvProgram", "ProgramKey", "ProgramPlan", "ShardedPlan",
    "dispatch_gemv", "dispatch_dense", "as_packed", "from_transposed",
    "dispatch_program", "dispatch_fused", "dispatch_grouped",
    "dispatch_ragged", "dispatch_prepacked",
    "record_program_fallback", "record_expert_load", "record_overlap",
    "plan_cache_stats", "clear_plan_cache", "dispatch_stats",
    "load_autotune_table", "save_autotune_table", "clear_autotune_table",
    "autotune_table",
    "available_backends", "get_backend", "resolve_backend", "time_gemv_us",
    "PackedWeights",
]

# ---------------------------------------------------------------------------
# Process-level plan cache (thread-safe) + the shared autotune table
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_PLAN_CACHE: dict[tuple[GemvKey, DispatchPolicy],
                  tuple[str, GemvPlan | None]] = {}
_PROGRAM_CACHE: dict[tuple[ProgramKey, DispatchPolicy], ProgramPlan] = {}
# Per-key in-flight guards: concurrent cold-cache dispatches of the SAME
# shape serialize on one selection/autotune sweep instead of each running
# it (the sweep is seconds when autotuning); distinct shapes stay parallel.
_KEY_LOCKS: dict[tuple, threading.Lock] = {}
_CACHE_STATS = {"hits": 0, "misses": 0,
                "program_hits": 0, "program_misses": 0}
# Dispatch DECISION counters (incremented per plan-cache miss — i.e. per
# fresh trace-time selection; cached shapes do zero planning work and so
# add nothing here).  ``gemv_path`` / ``matmul_fallback`` classify each
# decision by the policy's batch gate: above ``batch_threshold`` the shape
# is matmul-bound and selection falls back to the XLA dot — the knob the
# serving scheduler's batch-shaping policy moves (DESIGN.md §8.2).
_DISPATCH_COUNTERS: dict = {
    "kernel_picks": {},     # "backend:kernel" -> decisions
    "program_modes": {},    # "backend:mode"   -> decisions
    "gemv_path": 0,         # decisions with batch <= policy.batch_threshold
    "matmul_fallback": 0,   # decisions the batch gate pushed to the XLA dot
    # ShardedPlan path (policy.model_shards > 1, DESIGN.md §9): how each
    # decision placed the GEMV over the mesh 'model' axis, and which kernel
    # the PER-SHARD shape selected — keyed by the shard shape itself, so
    # stats prove selection reasoned about M/N (or K/N), not full shapes.
    "sharded_axes": {},     # "M" | "K" | "E" | "replicated" -> decisions
    "shard_picks": {},      # "backend:kernel@MsxKs/n" -> decisions
    # Capability-gate rejections on native program paths: a backend that
    # cannot lower its grouped/ragged kernel degrades to the universal
    # executor, but no longer SILENTLY — each degradation is counted here
    # (and warned once per backend:kind, see record_program_fallback).
    "program_fallbacks": {},  # "backend:kind" -> degradations
    # Per-expert load telemetry from the MoE layer (record_expert_load),
    # counted at trace time like every decision counter.  All monotonic
    # ints so serving metrics can delta them: max_tokens accumulates the
    # PLANNED per-expert bound per decision (divide by decisions for the
    # mean planned bound), padded_slots the capacity-padding slots the
    # legacy grouped path allocated beyond the routed tokens (the ragged
    # path records 0 — the zero-padding-FLOPs claim, counter-verified).
    "expert_load": {"decisions": 0, "routed_tokens": 0, "experts": 0,
                    "max_tokens": 0, "padded_slots": 0},
    # Which CostModel priced each decision (DESIGN.md §11): "seed" = the
    # hand-seeded class constants, "calibrated" = constants fitted by
    # repro.calibration and loaded from the table's `calibration` section.
    "cost_model_source": {"seed": 0, "calibrated": 0},
    # Overlap telemetry (DESIGN.md §14): the engine's async-prefill
    # dispatches and the model's deferred (awaited-one-layer-late)
    # collectives, recorded via record_overlap.  ``inflight`` is a gauge
    # (issued - awaited at this instant); everything else is monotonic so
    # serving metrics can delta it per step.
    "overlap": {
        "async_prefill": {"issued": 0, "awaited": 0, "inflight": 0,
                          "max_inflight": 0},
        "deferred": {"collectives": 0},
    },
}
_AUTOTUNE_TABLE = AutotuneTable()


def plan_cache_stats() -> dict[str, int]:
    with _LOCK:
        return dict(_CACHE_STATS)


def dispatch_stats() -> dict:
    """Snapshot of dispatch decision counters + plan-cache stats.

    Decisions are counted when a (shape, policy) is first planned (one per
    plan-cache miss).  Under ``jit`` that is trace time, so the counters
    reflect the *dispatch mix* the traced programs bake in — e.g. a serving
    scheduler that caps decode batches at the GEMV threshold shifts
    decisions from ``matmul_fallback`` to ``gemv_path`` (serving/metrics
    reports it per engine run, as a delta against the engine's start).
    Reset by :func:`clear_plan_cache`.
    """
    # Deep-copy the whole counter tree in ONE lock hold: every section of
    # the returned snapshot is from the same instant, and no returned
    # container aliases live state a concurrent dispatch could mutate
    # under a reader (ServingMetrics.expert_balance and dispatch_delta
    # walk the snapshot lock-free — they must be able to).
    with _LOCK:
        return {
            "plan_cache": dict(_CACHE_STATS),
            **copy.deepcopy(_DISPATCH_COUNTERS),
        }


def record_program_fallback(backend_name: str, kind: str) -> None:
    """Count a capability-gate degradation on a native program path.

    Called by a backend whose native grouped/ragged kernel cannot lower on
    this platform/policy: execution still degrades to the universal
    executor (correctness never depended on the native path), but the
    degradation is recorded in ``dispatch_stats()["program_fallbacks"]``
    and warned ONCE per (backend, kind) — no more silent decomposition.
    """
    tag = f"{backend_name}:{kind}"
    with _LOCK:
        pf = _DISPATCH_COUNTERS["program_fallbacks"]
        pf[tag] = pf.get(tag, 0) + 1
    warn_once(
        f"program_fallback:{tag}",
        f"backend {backend_name!r} cannot lower its native {kind} "
        f"program kernel here; degrading to the portable executor "
        f"(counted in dispatch_stats()['program_fallbacks'])",
        category=RuntimeWarning, depth=2,
    )


def record_expert_load(*, routed_tokens: int, experts: int,
                       max_tokens: int, padded_slots: int) -> None:
    """Accumulate one MoE dispatch decision's per-expert load statistics.

    Called by ``models/layers.py::apply_moe`` at trace time with static
    values (``max_tokens`` is the *planned* per-expert bound — counts are
    traced data); all monotonic ints, so serving metrics can report
    per-step deltas (see ``expert_load`` in ``_DISPATCH_COUNTERS``).
    """
    with _LOCK:
        el = _DISPATCH_COUNTERS["expert_load"]
        el["decisions"] += 1
        el["routed_tokens"] += int(routed_tokens)
        el["experts"] += int(experts)
        el["max_tokens"] += int(max_tokens)
        el["padded_slots"] += int(padded_slots)


def record_overlap(kind: str, *, issued: int = 0, awaited: int = 0,
                   deferred_collectives: int = 0) -> None:
    """Accumulate overlap telemetry from the engine / model layers.

    ``kind="async_prefill"``: the serving engine issued (``issued``) or
    harvested (``awaited``) that many non-blocking prefill-chunk
    dispatches; the inflight gauge and its high-water mark are maintained
    here so every ``dispatch_stats()`` snapshot satisfies
    ``inflight == issued - awaited`` — the invariant the threaded stress
    test pins.  ``kind="deferred"``: the sharded decode path deferred
    ``deferred_collectives`` split-K all-reduces by one layer
    (models/lm.py, DispatchPolicy.overlap_collectives).  Counted under
    the same single lock as every other dispatch counter.
    """
    with _LOCK:
        ov = _DISPATCH_COUNTERS["overlap"]
        if kind == "async_prefill":
            ap = ov["async_prefill"]
            ap["issued"] += int(issued)
            ap["awaited"] += int(awaited)
            ap["inflight"] = ap["issued"] - ap["awaited"]
            ap["max_inflight"] = max(ap["max_inflight"], ap["inflight"])
        elif kind == "deferred":
            ov["deferred"]["collectives"] += int(deferred_collectives)
        else:
            raise ValueError(f"unknown overlap kind {kind!r}")


def _count_decision(backend_name: str, key_batch: int,
                    policy: DispatchPolicy, *, kernel: str | None = None,
                    mode: str | None = None,
                    shard_axis: str | None = None,
                    shard_pick: str | None = None,
                    source: str = "seed") -> None:
    """Record one fresh dispatch decision (caller holds no locks)."""
    with _LOCK:
        src = _DISPATCH_COUNTERS["cost_model_source"]
        src[source] = src.get(source, 0) + 1
        if kernel is not None:
            picks = _DISPATCH_COUNTERS["kernel_picks"]
            k = f"{backend_name}:{kernel}"
            picks[k] = picks.get(k, 0) + 1
        if mode is not None:
            modes = _DISPATCH_COUNTERS["program_modes"]
            m = f"{backend_name}:{mode}"
            modes[m] = modes.get(m, 0) + 1
        if shard_axis is not None:
            axes = _DISPATCH_COUNTERS["sharded_axes"]
            axes[shard_axis] = axes.get(shard_axis, 0) + 1
        if shard_pick is not None:
            sp = _DISPATCH_COUNTERS["shard_picks"]
            key = f"{backend_name}:{shard_pick}"
            sp[key] = sp.get(key, 0) + 1
        if key_batch > policy.batch_threshold:
            _DISPATCH_COUNTERS["matmul_fallback"] += 1
        else:
            _DISPATCH_COUNTERS["gemv_path"] += 1


def clear_plan_cache() -> None:
    with _LOCK:
        _PLAN_CACHE.clear()
        _PROGRAM_CACHE.clear()
        _KEY_LOCKS.clear()
        _CACHE_STATS.update(hits=0, misses=0,
                            program_hits=0, program_misses=0)
        _DISPATCH_COUNTERS["kernel_picks"] = {}
        _DISPATCH_COUNTERS["program_modes"] = {}
        _DISPATCH_COUNTERS["gemv_path"] = 0
        _DISPATCH_COUNTERS["matmul_fallback"] = 0
        _DISPATCH_COUNTERS["sharded_axes"] = {}
        _DISPATCH_COUNTERS["shard_picks"] = {}
        _DISPATCH_COUNTERS["program_fallbacks"] = {}
        _DISPATCH_COUNTERS["expert_load"] = {
            "decisions": 0, "routed_tokens": 0, "experts": 0,
            "max_tokens": 0, "padded_slots": 0}
        _DISPATCH_COUNTERS["cost_model_source"] = {"seed": 0,
                                                   "calibrated": 0}
        _DISPATCH_COUNTERS["overlap"] = {
            "async_prefill": {"issued": 0, "awaited": 0, "inflight": 0,
                              "max_inflight": 0},
            "deferred": {"collectives": 0},
        }
    # fallback warnings live as long as the decisions they describe
    reset_warn_once("program_fallback:")


def clear_autotune_table() -> None:
    """Drop every loaded table entry AND revert backends whose CostModel
    was calibrated from the table back to their seed constants."""
    _AUTOTUNE_TABLE.clear()
    for name in available_backends():
        get_backend(name).reset_calibration()
    # a reloaded table's entry may differ — let a bad one warn again
    reset_warn_once("calibration:")


def _maybe_apply_calibration(backend) -> str:
    """Apply the table's fitted constants to ``backend`` (resolve time).

    Called on every plan-cache miss, before selection prices candidates:
    if the autotune table's ``calibration`` section carries fitted
    constants for this backend (repro.calibration, DESIGN.md §11) and the
    backend isn't already running them, they're applied over the seed
    :class:`CostModel` via ``with_constants``.  Returns the source label
    ("seed" | "calibrated") recorded with the decision, so
    ``dispatch_stats()["cost_model_source"]`` says which model priced it.
    """
    entry = _AUTOTUNE_TABLE.get_calibration(backend.name)
    if entry is None or not isinstance(entry.get("constants"), dict):
        return backend.cost_model_source
    try:
        cm = backend.seed_cost_model.with_constants(**entry["constants"])
    except (TypeError, ValueError) as e:
        # once per backend — the entry won't get better between misses
        warn_once(
            f"calibration:{backend.name}",
            f"ignoring invalid calibration entry for backend "
            f"{backend.name!r}: {e}", category=RuntimeWarning, depth=2,
        )
        return backend.cost_model_source
    if backend.cost_model != cm:
        backend.apply_calibration(cm)
    return "calibrated"


# ---------------------------------------------------------------------------
# Dispatch attribution (DESIGN.md §13): price (and optionally time) each
# fresh decision into the installed tracer.  Hot-path cost when no tracer
# is installed: one module-global read + `is None` — and only on plan-cache
# MISSES; the cached decode path never reaches these at all.
# ---------------------------------------------------------------------------

# Re-entrancy guard for --trace-timing: timing a program decision traces
# its executor, which may plan nested single-GEMV decisions — those still
# *record* (cheap, predicted-only) but must not recursively re-time.
_TIMING_TLS = threading.local()


def _trace_timing_active(tr) -> bool:
    return tr.timing and not getattr(_TIMING_TLS, "active", False)


def _time_trials_us(make_thunk, trials: int = 3) -> tuple[float, ...] | None:
    """Jitted warmup + per-trial ``block_until_ready`` times (µs).

    Mirrors the calibration measurement protocol (measure.py): compile and
    first-touch land in the warmup, each trial syncs.  Returns None when
    the decision cannot execute stand-alone here (e.g. a CUDA-only kernel
    decision resolved on a CPU host) — attribution then stays
    predicted-only rather than failing the dispatch.

    Dispatch decisions mostly resolve at jit-trace time (the engine's step
    functions are jitted), where a plain ``jax.jit(...)(x)`` call would be
    staged into the ambient trace as one more equation — yielding tracers,
    not timeable arrays.  ``ensure_compile_time_eval`` escapes to eager
    evaluation for the synthesized concrete inputs, so the measurement
    runs (and syncs) for real even mid-trace.
    """
    import jax

    _TIMING_TLS.active = True
    try:
        with jax.ensure_compile_time_eval():
            thunk = make_thunk()
            thunk().block_until_ready()
            out = []
            for _ in range(trials):
                t0 = time.perf_counter()
                thunk().block_until_ready()
                out.append((time.perf_counter() - t0) * 1e6)
            return tuple(out)
    except Exception:
        return None
    finally:
        _TIMING_TLS.active = False


def _trace_gemv_decision(tr, backend, key: GemvKey, policy: DispatchPolicy,
                         kernel: str, plan, source: str) -> None:
    """Record one fresh single-GEMV decision with the installed tracer."""
    import jax

    x_bytes = jnp.dtype(key.dtype).itemsize
    try:
        predicted = backend.estimate_cost_us(
            kernel, key.M, key.K, key.batch, bits=key.bits,
            x_bytes=x_bytes, plan=plan)
    except Exception:
        predicted = float("nan")
    trials = None
    if _trace_timing_active(tr):
        from repro.kernels.backends.base import synthesize_gemv

        interpret = (policy.interpret if policy.interpret is not None
                     else backend.default_interpret())

        def make_thunk():
            # synthesized inputs (the caller's arrays may be tracers
            # mid-jit), jitted with the activation as an argument so XLA
            # cannot fold the GEMV into a constant
            x, pw = synthesize_gemv(key)
            fn = jax.jit(lambda xx: backend.execute(
                kernel, xx, pw, plan, interpret))
            return lambda: fn(x)

        trials = _time_trials_us(make_thunk)
    tr.record_dispatch(
        backend=backend.name, kind="single", kernel=kernel,
        shape=key.table_key(), predicted_us=predicted, source=source,
        trials_us=trials, batch=key.batch,
        gate=("matmul_fallback" if key.batch > policy.batch_threshold
              else "gemv_path"))


def _trace_program_decision(tr, backend, key: ProgramKey,
                            policy: DispatchPolicy, pplan: ProgramPlan,
                            source: str) -> None:
    """Record one fresh program decision (mode = the "kernel")."""
    import jax

    x_bytes = jnp.dtype(key.dtype).itemsize
    try:
        predicted = backend.estimate_program_cost_us(
            key, mode=pplan.mode, x_bytes=x_bytes)
    except Exception:
        predicted = float("nan")
    trials = None
    if _trace_timing_active(tr):
        from repro.kernels.backends.base import _synthesize_program

        interpret = (policy.interpret if policy.interpret is not None
                     else backend.default_interpret())

        def make_thunk():
            program = _synthesize_program(key)
            if program.counts is not None:
                fn = jax.jit(lambda xx, cc: backend.execute_program(
                    dataclasses.replace(program, x=xx, counts=cc),
                    pplan, policy, interpret))
                return lambda: fn(program.x, program.counts)
            fn = jax.jit(lambda xx: backend.execute_program(
                dataclasses.replace(program, x=xx), pplan, policy,
                interpret))
            return lambda: fn(program.x)

        trials = _time_trials_us(make_thunk)
    tr.record_dispatch(
        backend=backend.name, kind=key.kind, kernel=pplan.mode,
        shape=key.table_key(), predicted_us=predicted, source=source,
        trials_us=trials, batch=key.batch)


def load_autotune_table(path: str) -> dict[str, dict[str, dict]]:
    """Load a persisted autotune table (v2 namespaced or v1 flat) into the
    process-level table; returns the parsed ``{backend: {key: entry}}``."""
    return _AUTOTUNE_TABLE.load(path)


def save_autotune_table(path: str) -> None:
    """Merge this process's per-backend namespaces into the table at
    ``path`` (read-merge-write, atomic rename; see AutotuneTable.save)."""
    _AUTOTUNE_TABLE.save(path)


def autotune_table() -> AutotuneTable:
    """The process-level table every dispatch reads — the handle the
    calibration subsystem publishes fitted constants through
    (``AutotuneTable.put_calibration``; see repro.calibration)."""
    return _AUTOTUNE_TABLE


# ---------------------------------------------------------------------------
# Weight normalization
# ---------------------------------------------------------------------------


def as_packed(weights) -> PackedWeights:
    """Normalize any accepted weight form to :class:`PackedWeights`.

    Accepts a :class:`PackedWeights`, a dense [M, K] array (packed on the
    fly), or an ``(w_q, scales)`` tuple of *unpacked int8* [K, M] weights
    with [K // block, M] block scales.  Nibble-packed int4 is ambiguous in
    tuple form (K halves, block doubles — the decode would be silently
    wrong) and must come pre-wrapped as PackedWeights.
    """
    if isinstance(weights, PackedWeights):
        return weights
    if isinstance(weights, tuple) and len(weights) == 2:
        w_q, scales = jnp.asarray(weights[0]), jnp.asarray(weights[1])
        if w_q.dtype != jnp.int8:
            raise ValueError(
                f"(w_q, scales) tuples must hold unpacked int8 weights, "
                f"got {w_q.dtype}; wrap other forms in PackedWeights"
            )
        K = w_q.shape[0]
        if (
            scales.ndim != 2 or scales.shape[1] != w_q.shape[1]
            or K % scales.shape[0] != 0
        ):
            raise ValueError(
                f"scales {scales.shape} do not tile int8 weights "
                f"{w_q.shape} as [K // block, M]"
            )
        return PackedWeights(w_t=w_q, scales=scales, bits=8,
                             block=K // scales.shape[0])
    return pack_weight(jnp.asarray(weights))


def from_transposed(w_t: jnp.ndarray) -> PackedWeights:
    """Wrap an already K-major [K, M] dense weight without re-transposing
    (model layers store projections as [d_in, d_out] = [K, M] natively)."""
    return PackedWeights(w_t=w_t)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------


def _priced_placement(backend, key: GemvKey,
                      policy: DispatchPolicy) -> ShardedPlan:
    """Price row (M) vs split-K (K) placement by communication.

    Only reached when BOTH axes divide evenly and the backend's CostModel
    carries a fitted ``collective_gbps`` (the 0.0 seed sentinel keeps the
    static M-before-K preference, so uncalibrated selections are
    bit-identical).  Each candidate is priced as the per-shard GEMV the
    chip would solve plus, for the K placement, the modeled all-reduce of
    the f32-width partial output (``CostModel.collective_us``) — the
    shard-aware tie-break the PR 5 follow-up called for.
    """
    n = policy.model_shards
    x_bytes = jnp.dtype(key.dtype).itemsize

    def cost(axis: str) -> float:
        sp = ShardedPlan(axis=axis, n_shards=n)
        Ms, Ks = sp.shard_shape(key.M, key.K)
        kernel, plan = backend.select_kernel(
            Ms, Ks, key.batch, bits=key.bits, block=key.block,
            x_bytes=x_bytes, policy=policy)
        t = backend.estimate_cost_us(kernel, Ms, Ks, key.batch,
                                     bits=key.bits, x_bytes=x_bytes,
                                     plan=plan)
        if axis == "K":
            t += backend.cost_model.collective_us(
                key.batch * key.M * x_bytes, n)
        return t

    axis = "M" if cost("M") <= cost("K") else "K"
    return ShardedPlan(axis=axis, n_shards=n)


def _shard_gemv_key(key: GemvKey, policy: DispatchPolicy,
                    backend=None) -> tuple[GemvKey, ShardedPlan]:
    """Per-shard selection key under the mesh 'model' axis (DESIGN.md §9).

    Applies Algorithm 1's even-distribution test to (M, K): row placement
    divides M, the split-K fallback divides K, otherwise the weight is
    replicated and the full shape stands.  Only the *selection inputs*
    shrink — execution traces the full-shape op and GSPMD splits it.
    When both axes divide AND the backend has a fitted collective term,
    the M-vs-K choice is priced instead of static
    (:func:`_priced_placement`).
    """
    n = policy.model_shards
    if (backend is not None and n > 1
            and backend.cost_model.collective_gbps > 0
            and key.M % n == 0 and key.K % n == 0):
        sp = _priced_placement(backend, key, policy)
    else:
        sp = ShardedPlan.place(key.M, key.K, n)
    Ms, Ks = sp.shard_shape(key.M, key.K)
    if (Ms, Ks) == (key.M, key.K):
        return key, sp
    return dataclasses.replace(key, M=Ms, K=Ks), sp


def _resolve(backend, key: GemvKey,
             policy: DispatchPolicy) -> tuple[str, GemvPlan | None]:
    """Memoized (kernel, plan) for one shape: cache -> table -> model.

    The cache key includes the (frozen, hashable) policy: a pinned-kernel
    or no-Pallas policy must never inherit another policy's decision for
    the same shape.  Table entries live in the backend's namespace and
    only stand in for the *cost model* — an unpinned auto policy; pins and
    ``use_pallas=False`` outrank any table entry.

    With ``policy.model_shards > 1`` (the ShardedPlan path) the cost
    model, table lookup, and autotune all run on the PER-SHARD shape —
    the GEMV each chip solves after the placement planner sharded the
    weight — and the chosen kernel is then re-planned at the full shape
    (pinned selection) so the traced op stays executable before GSPMD
    partitions it.
    """
    with _LOCK:
        cached = _PLAN_CACHE.get((key, policy))
        if cached is not None:
            _CACHE_STATS["hits"] += 1
            return cached
        key_lock = _KEY_LOCKS.setdefault((key, policy), threading.Lock())
    with key_lock:
        with _LOCK:  # a racer may have finished while we waited
            cached = _PLAN_CACHE.get((key, policy))
            if cached is not None:
                _CACHE_STATS["hits"] += 1
                return cached
            _CACHE_STATS["misses"] += 1
        source = _maybe_apply_calibration(backend)
        shard_axis = shard_pick = None
        sel_key = key
        if policy.model_shards > 1 and policy.kernel == "auto":
            sel_key, sp = _shard_gemv_key(key, policy, backend)
            shard_axis = sp.axis
        tuned = policy.kernel == "auto" and policy.use_pallas
        if tuned and policy.autotune:
            kernel, plan = backend.autotune_gemv(
                sel_key, policy=policy, table=_AUTOTUNE_TABLE
            )
        elif tuned and (
            entry := _AUTOTUNE_TABLE.get(backend.name, sel_key.table_key())
        ) is not None:
            kernel, plan = _entry_to_plan(entry)
        else:
            kernel, plan = backend.select_kernel(
                sel_key.M, sel_key.K, sel_key.batch, bits=sel_key.bits,
                block=sel_key.block,
                x_bytes=jnp.dtype(sel_key.dtype).itemsize, policy=policy,
            )
        if sel_key is not key:
            # The per-shard shape chose the kernel; re-plan it at the full
            # shape (pinned) so grids/chunk degrees fit the traced op.
            shard_pick = (f"{kernel}@{sel_key.M}x{sel_key.K}"
                          f"/{policy.model_shards}")
            kernel, plan = backend.select_kernel(
                key.M, key.K, key.batch, bits=key.bits, block=key.block,
                x_bytes=jnp.dtype(key.dtype).itemsize,
                policy=dataclasses.replace(policy, kernel=kernel),
            )
        # every branch above returns directly executable (aligned) plans
        with _LOCK:
            _PLAN_CACHE[(key, policy)] = (kernel, plan)
        _count_decision(backend.name, key.batch, policy, kernel=kernel,
                        shard_axis=shard_axis, shard_pick=shard_pick,
                        source=source)
        tracer = _current_tracer()
        if tracer is not None:
            _trace_gemv_decision(tracer, backend, key, policy, kernel,
                                 plan, source)
    return kernel, plan


def _kernel_mesh(backend, kernel: str, policy: DispatchPolicy):
    """The activation mesh a Pallas kernel must run per shard on, or None.

    Mosaic kernels cannot be partitioned by GSPMD: under a mesh with a
    'model' axis each TPU kernel runs inside ``shard_map`` on the shard
    its placement gives every chip.  XLA kernels ("ref", and every
    non-TPU backend) stay whole and GSPMD partitions them as before.
    """
    if policy.model_shards <= 1 or backend.name != "tpu" or kernel == "ref":
        return None
    from repro.distributed.axes import current_mesh

    return current_mesh()


def _execute_per_shard(backend, kernel: str, x: jnp.ndarray,
                       pw: PackedWeights, axis: str, policy: DispatchPolicy,
                       interpret: bool, mesh) -> jnp.ndarray:
    """Run ``kernel`` on each chip's shard of ``x @ W`` (``axis`` is the
    ShardedPlan placement: "M" rows, "K" split-K partials summed across
    'model', else replicated) with a plan made for the SHARD's shape."""
    import jax
    from jax.sharding import PartitionSpec as P

    if pw.bits != 16:
        raise NotImplementedError(
            "quantized weights have no per-shard TPU kernel path")
    K, M = pw.shape
    n = policy.model_shards
    Ms, Ks = ShardedPlan(axis=axis, n_shards=n).shard_shape(M, K)
    kernel, plan = backend.select_kernel(
        Ms, Ks, x.shape[0], x_bytes=jnp.dtype(x.dtype).itemsize,
        policy=dataclasses.replace(policy, kernel=kernel, model_shards=1))
    x_spec, w_spec, out_spec = {
        "M": (P(), P(None, "model"), P(None, "model")),
        "K": (P(None, "model"), P("model", None), P()),
    }.get(axis, (P(), P(), P()))

    def local(xs, ws):
        out = backend.execute(kernel, xs, PackedWeights(w_t=ws), plan,
                              interpret)
        if axis == "K":  # split-K partials: the cross-chip reduce
            out = jax.lax.psum(out.astype(jnp.float32), "model")
        return out.astype(xs.dtype)

    return jax.shard_map(local, mesh=mesh, in_specs=(x_spec, w_spec),
                         out_specs=out_spec, check_vma=False)(x, pw.w_t)


def _dispatch_request(
    req: GemvRequest,
    policy: DispatchPolicy,
    plan: TPUGemvPlan | None = None,
) -> jnp.ndarray:
    """Execute ONE request — the shared path under every entry point.

    ``dispatch_gemv`` is this with a single caller-built request;
    a program's ``per_request`` decomposition is N of these.
    """
    backend = resolve_backend(policy)
    pw = req.weights
    K, M = pw.shape
    B = req.x.shape[0]
    assert req.x.shape[1] == K, (req.x.shape, pw.shape)
    interpret = (
        policy.interpret if policy.interpret is not None
        else backend.default_interpret()
    )
    if plan is not None:
        kernel, plan = backend.coerce_plan(plan, M, K, B, pw, policy)
    else:
        key = GemvKey(M=M, K=K, batch=B, bits=pw.bits, block=pw.block,
                      dtype=str(req.x.dtype), backend=backend.name)
        kernel, plan = _resolve(backend, key, policy)
        mesh = _kernel_mesh(backend, kernel, policy)
        if mesh is not None:
            _, sp = _shard_gemv_key(key, policy, backend)
            return _execute_per_shard(backend, kernel, req.x, pw, sp.axis,
                                      policy, interpret, mesh)
    return backend.execute(kernel, req.x, pw, plan, interpret)


def dispatch_gemv(
    x: jnp.ndarray,
    weights,
    *,
    policy: DispatchPolicy | None = None,
    plan: TPUGemvPlan | None = None,
) -> jnp.ndarray:
    """Single-GEMV entry point: out[B, M] = x[B, K] @ W.T.

    A thin single-request wrapper over the request path that
    :func:`dispatch_program` plans in groups — one ``GemvRequest`` is the
    degenerate program.  ``weights`` is anything :func:`as_packed` accepts.
    Backend resolution, kernel selection, and planning happen at trace time
    from static shapes (zero runtime cost under ``jit``); a ``plan``
    argument bypasses selection (the backend coerces it to one of its own
    kernels).

    Eager callers should prepack once (:func:`~repro.kernels.ops.pack_weight`
    / :func:`from_transposed`): passing a raw [M, K] array re-transposes it
    on every eager call — the paper's one-time deployment cost (§V-A2) paid
    per GEMV.  Under ``jit`` the transpose is traced once and fused.
    """
    policy = policy or DEFAULT_POLICY
    return _dispatch_request(
        GemvRequest(x=x, weights=as_packed(weights)), policy, plan
    )


def dispatch_dense(
    x: jnp.ndarray, w_t: jnp.ndarray, *, policy: DispatchPolicy | None = None
) -> jnp.ndarray:
    """Dense-layer adapter: x [B, S, d_in] @ w_t [d_in, d_out] -> [B, S, d_out].

    Model layers store projections K-major already, so this wraps without a
    transpose and flattens (B, S) into the GEMV batch dimension.
    """
    B, S, d = x.shape
    out = dispatch_gemv(x.reshape(B * S, d), from_transposed(w_t),
                        policy=policy)
    return out.reshape(B, S, out.shape[-1])


# ---------------------------------------------------------------------------
# Program dispatch: N requests planned jointly (DESIGN.md §7)
# ---------------------------------------------------------------------------


def _shard_program_key(key: ProgramKey, policy: DispatchPolicy,
                       backend=None) -> tuple[ProgramKey, str]:
    """Per-shard program key under the mesh 'model' axis.

    The even-distribution test walks the program's placement preferences
    in the planner's order: expert-row placement for grouped programs
    (experts divide the axis — each chip owns whole experts), row
    placement for fused ones (every member's M divides — each chip owns
    whole output rows of the concatenated weight), split-K as the shared
    fallback.  As in :func:`_shard_gemv_key`, a fitted collective term
    turns the static M-before-K preference into a priced comparison on
    the concatenated shape.  Returns the (possibly shrunk) selection key
    and the axis label recorded in ``dispatch_stats()["sharded_axes"]``.
    """
    n = policy.model_shards
    if n <= 1:
        return key, "replicated"
    if key.kind in ("grouped", "ragged"):
        splan = ShardedPlan.place_experts(key.group, key.Ms[0], key.K, n)
        if splan.axis == "E":
            if key.kind == "ragged":
                # each chip owns E/n whole experts and, on average, the
                # even share of the flat routed-token buffer
                return dataclasses.replace(
                    key, group=key.group // n,
                    tokens=max(key.tokens // n, 1)), "E"
            return dataclasses.replace(key, group=key.group // n), "E"
    m_ok = all(m % n == 0 for m in key.Ms)
    k_ok = key.K % n == 0
    if (m_ok and k_ok and backend is not None
            and backend.cost_model.collective_gbps > 0):
        gkey = GemvKey(M=key.total_M, K=key.K, batch=key.batch,
                       bits=key.bits, block=key.block, dtype=key.dtype,
                       backend=key.backend)
        if _priced_placement(backend, gkey, policy).axis == "K":
            return dataclasses.replace(key, K=key.K // n), "K"
    if m_ok:
        return dataclasses.replace(
            key, Ms=tuple(m // n for m in key.Ms)), "M"
    if k_ok:
        return dataclasses.replace(key, K=key.K // n), "K"
    return key, "replicated"


def _resolve_program(backend, key: ProgramKey,
                     policy: DispatchPolicy) -> ProgramPlan:
    """Memoized ProgramPlan for one program shape: cache -> table -> plan.

    Mirrors :func:`_resolve`: table entries (the v3 ``programs`` section)
    stand in for the planner only under an unpinned auto policy; a kernel
    pin or ``use_pallas=False`` flows into ``plan_program``'s inner
    selection instead.  ``fuse_programs=False`` outranks table AND
    autotune — it must always force the per-request decomposition (the
    dry-run's A/B arm), never inherit a fused winner tuned under another
    policy, and never persist a per-request "winner" that would disable
    fusing for every auto policy reading the table later.

    With ``policy.model_shards > 1`` the mode and inner kernel are chosen
    from the PER-SHARD program shape (:func:`_shard_program_key`); a fused
    winner's inner plan is then re-built at the full concatenated shape so
    the traced op stays executable before GSPMD partitions it.
    """
    with _LOCK:
        cached = _PROGRAM_CACHE.get((key, policy))
        if cached is not None:
            _CACHE_STATS["program_hits"] += 1
            return cached
        key_lock = _KEY_LOCKS.setdefault((key, policy), threading.Lock())
    with key_lock:
        with _LOCK:  # a racer may have finished while we waited
            cached = _PROGRAM_CACHE.get((key, policy))
            if cached is not None:
                _CACHE_STATS["program_hits"] += 1
                return cached
            _CACHE_STATS["program_misses"] += 1
        source = _maybe_apply_calibration(backend)
        shard_axis = shard_pick = None
        sel_key = key
        if policy.model_shards > 1 and policy.kernel == "auto":
            sel_key, shard_axis = _shard_program_key(key, policy, backend)
        tuned = (policy.kernel == "auto" and policy.use_pallas
                 and policy.fuse_programs)
        if tuned and policy.autotune:
            pplan = backend.autotune_program(
                sel_key, policy=policy, table=_AUTOTUNE_TABLE
            )
        elif tuned and (
            entry := _AUTOTUNE_TABLE.get_program(backend.name,
                                                 sel_key.table_key())
        ) is not None:
            pplan = _entry_to_program_plan(entry)
        else:
            pplan = backend.plan_program(sel_key, policy=policy)
        if sel_key is not key:
            shard_pick = (f"{pplan.mode}@{sel_key.table_key()}"
                          f"/{policy.model_shards}")
            if pplan.mode == "fused":
                # per-shard shape chose the mode + inner kernel; re-plan
                # the inner decision at the full concatenated shape
                kernel, plan = backend.select_kernel(
                    sum(key.Ms), key.K, key.batch, bits=key.bits,
                    block=key.block,
                    x_bytes=jnp.dtype(key.dtype).itemsize,
                    policy=dataclasses.replace(policy, kernel=pplan.kernel),
                )
                pplan = ProgramPlan(mode="fused",
                                    n_launches=pplan.n_launches,
                                    kernel=kernel, plan=plan)
            elif (pplan.kernel and pplan.plan is not None
                  and (sel_key.Ms != key.Ms or sel_key.K != key.K)):
                # a native grouped/ragged tile plan built at the shrunk
                # per-shard (M, K) would fail the full-shape kernel grid
                # asserts; re-plan the same mode at the full shape
                pplan = backend.plan_program(key, policy=policy)
        with _LOCK:
            _PROGRAM_CACHE[(key, policy)] = pplan
        _count_decision(backend.name, key.batch, policy, mode=pplan.mode,
                        shard_axis=shard_axis, shard_pick=shard_pick,
                        source=source)
        tracer = _current_tracer()
        if tracer is not None:
            _trace_program_decision(tracer, backend, key, policy, pplan,
                                    source)
    return pplan


def dispatch_program(
    program: GemvProgram, *, policy: DispatchPolicy | None = None
) -> jnp.ndarray:
    """Execute a :class:`GemvProgram` — N GEMVs planned as one unit.

    The resolved backend plans the whole group (fused-M kernel on the
    concatenated weight, batched expert contraction, or the per-request
    decomposition every backend supports) so the IV-broadcast and
    kernel-launch costs are paid once per *program*, not once per matrix.

    Returns ``[B, sum(Ms)]`` for fused programs (``program.split(out)``
    slices per request), ``[E, C, M]`` for grouped ones, and ``[T, M]``
    for ragged ones (which have no per-request decomposition — the expert
    split is runtime data, so they always execute as one program).
    """
    policy = policy or DEFAULT_POLICY
    backend = resolve_backend(policy)
    interpret = (
        policy.interpret if policy.interpret is not None
        else backend.default_interpret()
    )
    key = program.key(backend.name)
    pplan = _resolve_program(backend, key, policy)
    mesh = (_kernel_mesh(backend, pplan.kernel, policy)
            if pplan.mode == "fused" else None)
    if mesh is not None:
        _, axis = _shard_program_key(key, policy, backend)
        return _execute_per_shard(backend, pplan.kernel, program.x,
                                  program.weights, axis, policy, interpret,
                                  mesh)
    if pplan.mode == "per_request" and program.kind != "ragged":
        # The decomposition IS N single-request dispatches — same plan
        # cache, autotune table, and selection inputs as dispatch_gemv, so
        # the unfused arm reproduces per-matrix dispatch exactly.
        outs = [_dispatch_request(req, policy) for req in program.requests]
        if program.kind == "grouped":
            return jnp.stack(outs)
        return jnp.concatenate(outs, axis=-1)
    return backend.execute_program(program, pplan, policy, interpret)


def dispatch_fused(
    x: jnp.ndarray, weights, *, policy: DispatchPolicy | None = None,
) -> list[jnp.ndarray]:
    """Fused multi-head convenience: shared-IV projections in one program.

    ``x`` is [B, K]; ``weights`` is a sequence whose members are
    :class:`PackedWeights` or K-major ``[K, M_i]`` arrays (the layout model
    layers store — matching :func:`dispatch_dense`, NOT the [M, K] form
    ``dispatch_gemv`` transposes).  Returns the per-member outputs
    ``[B, M_i]`` in order — e.g. ``q, k, v = dispatch_fused(x, [wq, wk,
    wv])``.

    The members are concatenated along M here, at call time — under ``jit``
    that concat executes every step, an extra write+read of the fused
    weight that XLA cannot elide (the dot needs the contiguous operand).
    Callers on a per-step hot path who can restructure their parameters
    should ``ops.pack_fused`` once at deployment and dispatch the prebuilt
    :class:`GemvProgram` instead — the paper's one-time placement cost
    (§V-A2) applied to the fused matrix (ROADMAP: prepacked fused weights
    in the model param tree).
    """
    members = [
        w if isinstance(w, PackedWeights) else from_transposed(jnp.asarray(w))
        for w in weights
    ]
    program = GemvProgram.fused(x, members)
    return program.split(dispatch_program(program, policy=policy))


def dispatch_prepacked(
    x: jnp.ndarray, fused, m_splits, *,
    policy: DispatchPolicy | None = None,
) -> list[jnp.ndarray]:
    """Fused program over a PREPACKED ``[K, sum(Ms)]`` weight.

    The hot-path variant of :func:`dispatch_fused`: the caller concatenated
    the shared-IV members ONCE at deployment (``ops.pack_fused`` or
    ``models.lm.prepack_decode_params`` — the paper's one-time §V-A2
    placement cost), so no per-call concat is traced.  ``m_splits`` gives
    the per-member output widths; returns the per-member ``[B, M_i]``
    outputs in order, exactly like ``dispatch_fused``.

    The per-request decomposition (the unfused arm a backend or policy may
    pick) slices the fused weight lazily; under ``jit`` the slices are
    dead-code-eliminated whenever the fused mode runs.
    """
    policy = policy or DEFAULT_POLICY
    pw = (fused if isinstance(fused, PackedWeights)
          else from_transposed(jnp.asarray(fused)))
    splits = tuple(int(m) for m in m_splits)
    K, M = pw.shape
    if sum(splits) != M:
        raise ValueError(f"m_splits {splits} do not tile M={M}")
    offs = np.concatenate([[0], np.cumsum(splits)])
    reqs = tuple(
        GemvRequest(
            x=x,
            weights=PackedWeights(
                w_t=pw.w_t[:, offs[i]:offs[i + 1]],
                scales=(None if pw.scales is None
                        else pw.scales[:, offs[i]:offs[i + 1]]),
                bits=pw.bits, block=pw.block,
            ),
            tag=f"m{i}",
        )
        for i in range(len(splits))
    )
    program = GemvProgram(kind="fused", x=x, weights=pw, m_splits=splits,
                          requests=reqs)
    return program.split(dispatch_program(program, policy=policy))


def dispatch_grouped(
    xs: jnp.ndarray, weights, *, policy: DispatchPolicy | None = None,
) -> jnp.ndarray:
    """Grouped/expert convenience: out[E, C, M] = xs[E, C, K] @ W[E, K, M].

    ``weights`` is a stacked :class:`PackedWeights` (see
    :meth:`PackedWeights.stack`) or a raw ``[E, K, M]`` array of K-major
    per-expert projections (the layout MoE layers store).
    """
    if not isinstance(weights, PackedWeights):
        weights = PackedWeights(w_t=jnp.asarray(weights))
    program = GemvProgram.grouped(xs, weights)
    return dispatch_program(program, policy=policy)


def dispatch_ragged(
    x: jnp.ndarray, counts: jnp.ndarray, weights, *, bound: int = 0,
    policy: DispatchPolicy | None = None,
) -> jnp.ndarray:
    """Ragged expert convenience: out[T, M] — zero capacity padding.

    ``x`` is the flat ``[T, K]`` token buffer sorted by expert, ``counts``
    the per-expert row counts (runtime data; must sum to at most T — rows
    beyond the sum come back zero).  ``weights`` is a stacked
    :class:`PackedWeights` or raw ``[E, K, M]`` array.  ``bound`` is the
    host-static predicted per-expert token bound used as the program's
    costing batch (see ``expert_batch_bound``; defaults to T).
    """
    if not isinstance(weights, PackedWeights):
        weights = PackedWeights(w_t=jnp.asarray(weights))
    program = GemvProgram.ragged(x, counts, weights, bound=bound)
    return dispatch_program(program, policy=policy)


# ---------------------------------------------------------------------------
# Deprecated PR-1 surface: thin shims over the `tpu` backend
# ---------------------------------------------------------------------------

_DEPRECATED_CONSTANTS = {
    # old module global -> accessor on the tpu backend's CostModel
    "HBM_BW": lambda cm: cm.bandwidth_bps,
    "XLA_GEMV_EFF": lambda cm: cm.gemv_efficiency,
    "PALLAS_LAUNCH_US": lambda cm: cm.launch_us,
    "PROGRAM_US": lambda cm: cm.program_us,
    "MIN_PARALLEL_BLOCKS": lambda cm: cm.min_parallel_blocks,
}

# Deprecation warnings fire ONCE PER CALL SITE, not per call: the shims sit
# on per-dispatch hot paths (a scanned decode loop touched a constant per
# step pre-PR-2), and a warning per step floods logs without adding signal.
# Keyed on (symbol, caller file, caller line) so distinct sites — and
# distinct constants read from one line — each still get their one warning.
def _warn_deprecated_once(name: str, message: str, *, depth: int) -> None:
    """Warn for ``name`` unless this caller site already was warned.

    ``depth`` is the ``sys._getframe`` hop count from this helper to the
    *user's* frame (1 = our direct caller, 2 = its caller, ...); the same
    frame feeds ``stacklevel`` so the warning points at the deprecated
    use, not this helper.  Delegates to the shared per-site
    :func:`repro.observability.log.warn_once` memo (one extra frame).
    """
    warn_once(f"deprecated:{name}", message, category=DeprecationWarning,
              depth=depth + 1, per_site=True)


def __getattr__(name: str):
    if name in _DEPRECATED_CONSTANTS:
        _warn_deprecated_once(
            name,
            f"repro.kernels.dispatch.{name} is deprecated; cost-model "
            f"constants live on get_backend(<name>).cost_model",
            depth=2,  # helper -> __getattr__ -> the attribute access site
        )
        return _DEPRECATED_CONSTANTS[name](get_backend("tpu").cost_model)
    if name == "KERNELS":
        _warn_deprecated_once(
            "KERNELS",
            "repro.kernels.dispatch.KERNELS is deprecated; use "
            "get_backend(<name>).kernels",
            depth=2,
        )
        return get_backend("tpu").kernels
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _warn_deprecated_shim(old: str, new: str) -> None:
    _warn_deprecated_once(
        old, f"repro.kernels.dispatch.{old} is deprecated; use {new}",
        depth=3,  # helper -> this shim -> the deprecated function -> caller
    )


def select_kernel(
    M: int,
    K: int,
    batch: int,
    *,
    bits: int = 16,
    block: int = 32,
    x_bytes: int = 2,
    policy: DispatchPolicy = DEFAULT_POLICY,
) -> tuple[str, GemvPlan | None]:
    """Deprecated: ``get_backend("tpu").select_kernel`` (or resolve one).

    Kept as the PR-1 free function: it always answered for the TPU-analogue
    kernel set regardless of host platform, so it delegates to the ``tpu``
    backend explicitly (honoring a ``policy.backend`` override if set).
    """
    _warn_deprecated_shim("select_kernel",
                          "get_backend(<name>).select_kernel")
    backend = get_backend(policy.backend or "tpu")
    return backend.select_kernel(
        M, K, batch, bits=bits, block=block, x_bytes=x_bytes, policy=policy
    )


def estimate_cost_us(
    kernel: str,
    M: int,
    K: int,
    batch: int,
    *,
    bits: int = 16,
    x_bytes: int = 2,
    plan: GemvPlan | None = None,
) -> float:
    """Deprecated: ``get_backend(<name>).estimate_cost_us``."""
    _warn_deprecated_shim("estimate_cost_us",
                          "get_backend(<name>).estimate_cost_us")
    return get_backend("tpu").estimate_cost_us(
        kernel, M, K, batch, bits=bits, x_bytes=x_bytes, plan=plan
    )


def autotune_gemv(
    key: GemvKey, *, policy: DispatchPolicy
) -> tuple[str, GemvPlan | None]:
    """Deprecated: ``get_backend(<name>).autotune_gemv(key, policy=...,
    table=...)``.

    Like the other PR-1 shims this delegates to the ``tpu`` backend
    (honoring a ``policy.backend`` override): PR-1 always tuned the
    TPU-analogue Pallas candidates and returned their TPU-tiled plans
    regardless of the platform stored in ``key.backend``, and legacy
    callers feed the returned plan to those kernels.
    """
    _warn_deprecated_shim("autotune_gemv",
                          "get_backend(<name>).autotune_gemv")
    backend = get_backend(policy.backend or "tpu")
    return backend.autotune_gemv(key, policy=policy, table=_AUTOTUNE_TABLE)
