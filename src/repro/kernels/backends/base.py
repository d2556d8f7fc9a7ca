"""The `GemvBackend` contract: one pluggable target per memory system.

The paper's thesis is that GEMV placement must be *parameterized by the
memory system* — bank counts, row-open costs, command cadence are inputs to
Algorithm 1, not constants baked into it.  This module is that
parameterization at the software level (DESIGN.md §6): a backend bundles

  (a) its **kernel set** and executors (`kernels`, :meth:`GemvBackend.execute`),
  (b) its **cost-model constants** as a frozen :class:`CostModel` — the
      bandwidth / launch / occupancy numbers that used to live as module
      globals in ``kernels/dispatch.py``,
  (c) a **plan builder** (:meth:`GemvBackend.candidate_plans`), and
  (d) an **autotune-table namespace** (entries are stored per backend name,
      so one JSON table serves a heterogeneous fleet).

``kernels/dispatch.py`` stays the single entry point: it resolves a backend
(:func:`resolve_backend`), then delegates selection, cost estimation,
autotuning, and execution to it.  Registered implementations live in
:mod:`repro.kernels.backends.tpu` / ``.cpu`` / ``.gpu``.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import threading
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import (
    PackedWeights,
    pack_weight,
    quantize_weight,
)
from repro.kernels.tpu_plan import TPUGemvPlan

# The plan dataclass is target-agnostic (block shape + grid + split degree);
# the TPU-prefixed name is historical.
GemvPlan = TPUGemvPlan


# ---------------------------------------------------------------------------
# Cost model constants (frozen, one instance per backend)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CostModel:
    """Per-backend constants for the analytical GEMV latency model.

    These are the memory-system parameters of the paper's performance model
    translated to each execution target; a backend owns exactly one frozen
    instance (no module globals, no cross-backend sharing).

    Class-level instances are **seed** constants — hand-estimated on the
    development host.  The calibration subsystem (``repro.calibration``,
    DESIGN.md §11) fits every continuous term from instrumented sweeps and
    swaps a :meth:`with_constants` copy onto the backend at resolve time;
    ``min_parallel_blocks`` is structural (core/SM/bank count) and is never
    fitted.
    """

    bandwidth_gbps: float      # sustained memory bandwidth, GB/s (1e9 B/s)
    gemv_efficiency: float     # fraction of peak BW the untuned ref GEMV gets
    launch_us: float           # fixed kernel-launch / dispatch overhead
    program_us: float          # per-grid-program (or per-chunk) step overhead
    min_parallel_blocks: int   # grid fill target: fewer blocks starve the
                               # machine (the paper's small-M rule, §VI-F)
    # Per-output-element overhead (ns per batch*M element): index math,
    # store pipeline, reduction bookkeeping — the csl-experiments model's
    # per-FMACS overhead term.  Seeds are 0 (folded into efficiency until
    # a measured sweep separates them).
    elem_ns: float = 0.0
    # Split-K partial traffic multiplier: each of ``degree`` f32 partial
    # outputs is written then re-read by the reduce (factor 2.0); fitted
    # values absorb cache residency of the partials.
    splitk_reduce_factor: float = 2.0
    # Cross-shard all-reduce terms (ring model): per-link interconnect
    # bandwidth and a fixed launch/sync overhead per collective.  0.0 is a
    # deliberate sentinel — "no measured interconnect": collective_us()
    # then prices every placement at 0, so the sharded dispatcher keeps
    # its static M-before-K preference and seed selections stay
    # bit-identical until calibration fits a real value.
    collective_gbps: float = 0.0
    collective_launch_us: float = 0.0

    @property
    def bandwidth_bps(self) -> float:
        return self.bandwidth_gbps * 1e9

    def collective_us(self, nbytes: float, shards: int) -> float:
        """Modeled latency of an all-reduce of ``nbytes`` over ``shards``.

        Ring all-reduce wire traffic: each chip sends and receives
        ``2 * (shards - 1) / shards * nbytes`` (reduce-scatter +
        all-gather), so the time is that volume over the per-link
        bandwidth plus one launch.  Returns 0 when there is nothing to
        reduce (``shards <= 1``) or no fitted interconnect bandwidth (the
        0.0 sentinel) — the term must never perturb selections it has no
        measurement for.
        """
        if shards <= 1 or nbytes <= 0 or self.collective_gbps <= 0:
            return 0.0
        wire = 2.0 * (shards - 1) / shards * float(nbytes)
        return (wire / (self.collective_gbps * 1e9) * 1e6
                + self.collective_launch_us)

    def constants(self) -> dict:
        """All fields as a plain JSON-able dict (calibration artifacts)."""
        import dataclasses as _dc

        return _dc.asdict(self)

    def with_constants(self, **overrides) -> "CostModel":
        """A frozen copy with the named constants replaced.

        The calibration override point: fitted values arrive as a partial
        dict (only the terms a sweep could identify), everything else keeps
        this instance's value.  Unknown names raise — a misspelled constant
        must never silently calibrate nothing.
        """
        import dataclasses as _dc

        fields = {f.name for f in _dc.fields(self)}
        unknown = set(overrides) - fields
        if unknown:
            raise ValueError(
                f"unknown CostModel constants {sorted(unknown)}; "
                f"expected a subset of {sorted(fields)}"
            )
        if "min_parallel_blocks" in overrides:
            overrides["min_parallel_blocks"] = int(
                overrides["min_parallel_blocks"])
        cm = _dc.replace(self, **overrides)
        if cm.bandwidth_gbps <= 0:
            raise ValueError(f"bandwidth_gbps must be > 0, got "
                             f"{cm.bandwidth_gbps}")
        if not 0 < cm.gemv_efficiency <= 1.0:
            raise ValueError(f"gemv_efficiency must be in (0, 1], got "
                             f"{cm.gemv_efficiency}")
        if min(cm.launch_us, cm.program_us, cm.elem_ns,
               cm.splitk_reduce_factor, cm.collective_gbps,
               cm.collective_launch_us) < 0:
            raise ValueError("overhead constants must be >= 0")
        return cm


# ---------------------------------------------------------------------------
# Dispatch policy + plan-cache key (shared vocabulary across backends)
# ---------------------------------------------------------------------------


def _next_pow2(n: int) -> int:
    """Smallest power of two >= max(n, 1) — histogram bucket edges."""
    return 1 << max(int(n) - 1, 0).bit_length()


def expert_batch_bound(n_tokens: int, top_k: int, n_experts: int, *,
                       skew: float = 2.0) -> int:
    """Predicted per-expert token bound for a ragged MoE dispatch.

    ``n_tokens * top_k / n_experts`` is the even-split per-expert load
    (PIMnast's balanced-bank ideal); ``skew`` scales it for router
    imbalance.  Clamped to ``[1, n_tokens]`` — no expert can receive more
    rows than there are tokens.  This is a *statistic*, not a correctness
    bound: the ragged executors handle any count distribution, the value
    only prices the program (``ProgramKey.batch``) and gates admission
    (the expert-aware scheduler shares this formula, so what the
    scheduler admits is exactly what the dispatcher prices).
    """
    even = n_tokens * top_k / max(n_experts, 1)
    return max(1, min(int(n_tokens), math.ceil(even * skew)))


@dataclass(frozen=True)
class DispatchPolicy:
    """How :func:`repro.kernels.dispatch.dispatch_gemv` picks and runs a kernel.

    ``backend`` explicitly selects a registered :class:`GemvBackend` by name;
    ``None`` resolves from the runtime (see :func:`resolve_backend`).
    ``kernel="auto"`` uses the backend's cost model; any other value pins one
    of the backend's kernels.  ``autotune=True`` replaces the model with
    measured timings, memoized per backend namespace in the JSON table at
    ``table_path`` when set.
    """

    kernel: str = "auto"          # auto | one of backend.kernels
    backend: str | None = None    # None -> resolve from the runtime platform
    autotune: bool = False
    table_path: str | None = None
    # None -> the resolved backend decides (GemvBackend.default_interpret:
    # only the tpu backend interprets off-TPU; cpu/gpu run natively).
    interpret: bool | None = None
    use_pallas: bool = True
    batch_threshold: int = 8      # above this, decode is matmul-shaped: XLA
    min_pallas_bytes: int = 1 << 20  # tiny weights: launch overhead dominates
    # Program (multi-request) dispatch: False decomposes every GemvProgram
    # into independent per-request dispatches (the pre-program behavior),
    # True lets the backend plan the group jointly (fused-M / grouped).
    fuse_programs: bool = True
    # Expert execution shape for MoE decode (models/layers.py::apply_moe):
    # "ragged" routes tokens through the capacity-free ragged program
    # (sorted [T, K] buffer + per-expert counts — zero padding FLOPs),
    # "grouped" keeps the capacity-padded [E, C, K] grouped program, and
    # "einsum" bypasses program dispatch entirely (the train/prefill
    # contraction).  Decode-only: prefill/train always use einsum.
    expert_shape: str = "ragged"
    # Size of the mesh 'model' axis the executed ops will be partitioned
    # over (GSPMD).  > 1 engages the ShardedPlan path (DESIGN.md §9): the
    # dispatcher selects kernels from the PER-SHARD GEMV shape — M / N for
    # row placement, K / N for the split-K fallback, per Algorithm 1's
    # even-distribution test — because that is the problem each chip
    # actually solves.  Execution still traces the full-shape op; GSPMD
    # splits it along the axis the placement chose.
    model_shards: int = 1
    # Deferred decode collectives (DESIGN.md §14): when True, decode-mode
    # layer scans thread each layer's unconstrained FFN output through the
    # carry and constrain (replicate) it at the NEXT layer's entry, so a
    # K-sharded FFN's all-reduce can overlap the following layer's
    # attention/dispatch instead of serializing before it.  Bit-identical
    # token streams either way (same f32 add order); default off.
    overlap_collectives: bool = False


DEFAULT_POLICY = DispatchPolicy()


@dataclass(frozen=True)
class ShardedPlan:
    """Per-shard view of one GEMV under the mesh 'model' axis.

    The paper's Algorithm 1 walks tile shapes until rows distribute evenly
    over banks; lifted to the mesh (DESIGN.md §2.2/§9), the "banks" are the
    chips along 'model' and the even-distribution test is exact
    divisibility.  :meth:`place` applies the same preference order the
    placement planner uses for weights: row placement first (shard the
    output dim M — each chip owns whole rows, no cross-chip reduction),
    split-K as the fallback (shard the contraction dim K — GSPMD inserts
    the partial-sum all-reduce, the SoC-reduction analogue), replication
    when neither divides.
    """

    axis: str        # "M" | "K" | "E" (expert groups) | "replicated"
    n_shards: int

    @classmethod
    def place(cls, M: int, K: int, n_shards: int) -> "ShardedPlan":
        if n_shards <= 1:
            return cls(axis="replicated", n_shards=1)
        if M % n_shards == 0:
            return cls(axis="M", n_shards=n_shards)
        if K % n_shards == 0:
            return cls(axis="K", n_shards=n_shards)
        return cls(axis="replicated", n_shards=n_shards)

    @classmethod
    def place_experts(cls, E: int, M: int, K: int,
                      n_shards: int) -> "ShardedPlan":
        """Algorithm-1 even test on the expert dim of grouped/ragged
        programs: ``E % N == 0`` shards whole experts (each chip owns
        complete expert matrices — no cross-chip reduction, the row-
        placement analogue one level up); otherwise fall through to the
        per-expert (M, K) placement of :meth:`place`."""
        if n_shards > 1 and E % n_shards == 0:
            return cls(axis="E", n_shards=n_shards)
        return cls.place(M, K, n_shards)

    def shard_shape(self, M: int, K: int) -> tuple[int, int]:
        """The (M, K) each chip sees under this placement ("E" shards the
        expert count, not the per-expert matrix)."""
        if self.axis == "M":
            return M // self.n_shards, K
        if self.axis == "K":
            return M, K // self.n_shards
        return M, K


@dataclass(frozen=True)
class GemvKey:
    """Process-level plan-cache key: shape + dtype + backend name."""

    M: int
    K: int
    batch: int
    bits: int
    block: int
    dtype: str
    backend: str

    def table_key(self) -> str:
        # Backend-agnostic: the autotune table namespaces entries by backend
        # name, so the shape key itself must not embed one.
        return (
            f"{self.M}x{self.K}xb{self.batch}_w{self.bits}g{self.block}"
            f"_{self.dtype}"
        )


# ---------------------------------------------------------------------------
# GEMV programs: N requests planned jointly (DESIGN.md §7)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class GemvRequest:
    """One GEMV: out[B, M] = x[B, K] @ weights.  The unit a program plans.

    ``weights`` is always a (2-D) :class:`PackedWeights`; ``tag`` labels the
    request in program outputs (``"wq"``, ``"expert3"``, ...).
    """

    x: jnp.ndarray
    weights: PackedWeights
    tag: str = ""


@dataclass(frozen=True)
class GemvProgram:
    """N GEMV requests planned *jointly* — the dispatcher's unit of work.

    The paper's PIM broadcasts one command stream and one IV chunk to all
    banks, so GEMVs that share an input vector or form an expert group must
    be placed together to pay the broadcast/launch cost once.  Two
    first-class shapes:

    * ``fused`` — shared IV, per-request output widths (QKV, gate+up):
      ``x [B, K]``, ``weights.w_t [K, sum(m_splits)]`` (see
      :func:`repro.kernels.ops.pack_fused`); output ``[B, sum(m_splits)]``,
      split back per request with :meth:`split`.
    * ``grouped`` — expert group: ``x [E, C, K]`` per-expert token buffers,
      ``weights.w_t [E, K, M]`` stacked experts
      (:meth:`PackedWeights.stack`); output ``[E, C, M]``.
    * ``ragged`` — capacity-free expert group: ``x [T, K]`` is ONE flat
      token buffer sorted by expert, ``counts [E]`` the per-expert row
      counts (runtime data — the split is not a shape); output ``[T, M]``.
      No padding rows exist: ``T`` is exactly the routed-token count, the
      per-expert balance analogue of PIMnast's per-bank balance.

    ``requests`` always carries the per-request decomposition so any backend
    can fall back to independent dispatches (``ProgramPlan.mode ==
    "per_request"``) — except ``ragged``, whose decomposition is runtime
    data (``requests`` is empty; every backend runs it via the universal
    XLA ragged executor or a native kernel).
    """

    kind: str                            # "fused" | "grouped" | "ragged"
    x: jnp.ndarray
    weights: PackedWeights
    m_splits: tuple[int, ...]
    requests: tuple[GemvRequest, ...]
    # ragged only: per-expert token counts [E] (jnp data, traced under
    # jit) and the host-static predicted per-expert bound used as the
    # costing batch (see expert_batch_bound).
    counts: jnp.ndarray | None = None
    bound: int = 0

    @classmethod
    def fused(cls, x: jnp.ndarray,
              members: "list[PackedWeights]",
              tags: tuple[str, ...] = ()) -> "GemvProgram":
        from repro.kernels.ops import pack_fused

        fused_pw, splits = pack_fused(members)
        tags = tags or tuple(f"m{i}" for i in range(len(members)))
        reqs = tuple(
            GemvRequest(x=x, weights=pw, tag=t)
            for pw, t in zip(members, tags)
        )
        return cls(kind="fused", x=x, weights=fused_pw, m_splits=splits,
                   requests=reqs)

    @classmethod
    def grouped(cls, xs: jnp.ndarray,
                stacked: PackedWeights) -> "GemvProgram":
        if stacked.w_t.ndim != 3:
            raise ValueError(
                f"grouped programs need stacked [E, K, M] weights, got "
                f"{stacked.w_t.shape}"
            )
        E = stacked.group
        if xs.ndim != 3 or xs.shape[0] != E:
            raise ValueError(
                f"grouped inputs must be [E, C, K] with E={E}, got {xs.shape}"
            )
        _, M = stacked.shape
        reqs = tuple(
            GemvRequest(x=xs[e], weights=stacked.member(e), tag=f"expert{e}")
            for e in range(E)
        )
        return cls(kind="grouped", x=xs, weights=stacked, m_splits=(M,),
                   requests=reqs)

    @classmethod
    def ragged(cls, x: jnp.ndarray, counts: jnp.ndarray,
               stacked: PackedWeights, *, bound: int = 0) -> "GemvProgram":
        if stacked.w_t.ndim != 3:
            raise ValueError(
                f"ragged programs need stacked [E, K, M] weights, got "
                f"{stacked.w_t.shape}"
            )
        if x.ndim != 2:
            raise ValueError(
                f"ragged inputs must be a flat sorted [T, K] buffer, got "
                f"{x.shape}"
            )
        if counts.shape != (stacked.group,):
            raise ValueError(
                f"ragged counts must be [E]={stacked.group}, got "
                f"{counts.shape}"
            )
        _, M = stacked.shape
        bound = bound or int(x.shape[0])
        return cls(kind="ragged", x=x, weights=stacked, m_splits=(M,),
                   requests=(), counts=counts, bound=bound)

    @property
    def n_requests(self) -> int:
        return len(self.requests)

    def split(self, out: jnp.ndarray) -> list[jnp.ndarray]:
        """Slice a fused program's [B, sum(M_i)] output back per request."""
        assert self.kind == "fused", self.kind
        bounds = np.cumsum(self.m_splits)[:-1].tolist()
        return jnp.split(out, bounds, axis=-1)

    def key(self, backend_name: str) -> "ProgramKey":
        pw = self.weights
        K, _ = pw.shape
        if self.kind == "ragged":
            # batch is the predicted per-expert bound (a costing statistic
            # — counts are runtime data); the histogram bucket rounds the
            # bound and the even-split load to powers of two so the plan
            # cache and autotune table stay small across traces.
            T = int(self.x.shape[0])
            E = pw.group
            hist = (f"le{_next_pow2(self.bound)}"
                    f"m{_next_pow2(-(-T // max(E, 1)))}")
            return ProgramKey(
                kind="ragged", Ms=self.m_splits, K=K, batch=self.bound,
                group=E, bits=pw.bits, block=pw.block,
                dtype=str(self.x.dtype), backend=backend_name,
                tokens=T, hist=hist,
            )
        if self.kind == "grouped":
            batch = int(self.x.shape[1])          # tokens per expert
        else:
            batch = int(self.x.shape[0])
        return ProgramKey(
            kind=self.kind, Ms=self.m_splits, K=K, batch=batch,
            group=pw.group if self.kind == "grouped" else len(self.m_splits),
            bits=pw.bits, block=pw.block, dtype=str(self.x.dtype),
            backend=backend_name,
        )


@dataclass(frozen=True)
class ProgramKey:
    """Plan-cache / autotune-table key for one program shape.

    ``Ms`` is the per-request output-width tuple for fused programs and the
    single per-expert ``(M,)`` for grouped/ragged ones; ``group`` is the
    request count (fused) or expert count (grouped/ragged); ``batch`` is B
    (fused), the per-expert token count C (grouped), or the predicted
    per-expert bound (ragged — a costing statistic, see
    :func:`expert_batch_bound`).  Ragged keys additionally carry the flat
    buffer length ``tokens`` and the pow2-bucketed count-histogram tag
    ``hist`` (``le<bound>m<mean>``) so cost models and autotune entries
    distinguish balanced from skewed distributions at the same T.
    """

    kind: str
    Ms: tuple[int, ...]
    K: int
    batch: int
    group: int
    bits: int
    block: int
    dtype: str
    backend: str
    tokens: int = 0      # ragged: flat routed-token buffer length T
    hist: str = ""       # ragged: pow2 count-histogram bucket

    @property
    def n_requests(self) -> int:
        return self.group

    @property
    def total_M(self) -> int:
        return sum(self.Ms) if self.kind == "fused" else self.group * self.Ms[0]

    def table_key(self) -> str:
        ms = "+".join(str(m) for m in self.Ms)
        base = (
            f"{self.kind}[{ms}]x{self.K}xb{self.batch}_e{self.group}"
            f"_w{self.bits}g{self.block}_{self.dtype}"
        )
        if self.kind == "ragged":
            return f"{base}_t{self.tokens}.{self.hist}"
        return base


@dataclass(frozen=True)
class ProgramPlan:
    """How a backend executes one program.

    ``mode``: ``fused`` (one joint kernel on the concatenated [K, sum M]
    weight — ``kernel``/``plan`` name the inner decision), ``grouped`` (one
    batched contraction over the expert stack), ``ragged`` (the universal
    XLA ragged executor over the sorted flat buffer), a backend-native
    ragged/grouped mode (``grouped_triton`` / ``ragged_triton`` — ``kernel``
    and ``plan`` carry the Pallas tile decision), or ``per_request`` (N
    independent dispatches — the default decomposition every backend
    supports for fused/grouped).  ``n_launches`` is the kernel-launch count
    the mode costs, the quantity the program API exists to amortize.
    """

    mode: str
    n_launches: int
    kernel: str = ""
    plan: GemvPlan | None = None


def program_plan_to_entry(pplan: ProgramPlan, elapsed_us: float) -> dict:
    entry = {"mode": pplan.mode, "n_launches": pplan.n_launches,
             "us": elapsed_us}
    if pplan.kernel:
        entry.update(plan_to_entry(pplan.kernel, pplan.plan, elapsed_us))
    return entry


def entry_to_program_plan(entry: dict) -> ProgramPlan:
    if entry.get("kernel"):
        kernel, plan = entry_to_plan(entry)
        return ProgramPlan(mode=entry["mode"], n_launches=entry["n_launches"],
                           kernel=kernel, plan=plan)
    return ProgramPlan(mode=entry["mode"], n_launches=entry["n_launches"])


def synthesize_gemv(key: "GemvKey") -> tuple[jnp.ndarray, PackedWeights]:
    """Random ``(x, packed weights)`` matching a single-GEMV key.

    Shared by the autotuner and the dispatch trace-timing hook — neither
    may time the caller's arrays (they may be tracers mid-``jit``)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.standard_normal((key.batch, key.K)).astype(np.float32)
    ).astype(key.dtype)
    w = rng.standard_normal((key.M, key.K)).astype(np.float32)
    if key.bits < 16:
        pw = quantize_weight(w, bits=key.bits, block=key.block)
    else:
        pw = pack_weight(jnp.asarray(w).astype(key.dtype))
    return x, pw


def _synthesize_program(key: ProgramKey) -> GemvProgram:
    """Build a program with random data matching a key — the autotuner must
    never time the caller's arrays (they may be tracers mid-``jit``)."""
    rng = np.random.default_rng(0)

    def one(M: int) -> PackedWeights:
        w = rng.standard_normal((M, key.K)).astype(np.float32)
        if key.bits < 16:
            return quantize_weight(w, bits=key.bits, block=key.block)
        return pack_weight(jnp.asarray(w).astype(key.dtype))

    if key.kind == "ragged":
        T = key.tokens or key.batch * key.group
        x = jnp.asarray(
            rng.standard_normal((T, key.K)).astype(np.float32)
        ).astype(key.dtype)
        stacked = PackedWeights.stack([one(key.Ms[0])
                                       for _ in range(key.group)])
        # balanced counts + remainder on expert 0: a representative (not
        # adversarial) distribution — the counts are data, so the timed
        # executable is the one the caller's distribution runs too.
        base_c, rem = divmod(T, key.group)
        counts = jnp.asarray(
            [base_c + (rem if e == 0 else 0) for e in range(key.group)],
            jnp.int32)
        return GemvProgram.ragged(x, counts, stacked, bound=key.batch)
    if key.kind == "grouped":
        xs = jnp.asarray(rng.standard_normal(
            (key.group, key.batch, key.K)).astype(np.float32)
        ).astype(key.dtype)
        stacked = PackedWeights.stack([one(key.Ms[0])
                                       for _ in range(key.group)])
        return GemvProgram.grouped(xs, stacked)
    x = jnp.asarray(
        rng.standard_normal((key.batch, key.K)).astype(np.float32)
    ).astype(key.dtype)
    return GemvProgram.fused(x, [one(M) for M in key.Ms])


# ---------------------------------------------------------------------------
# Autotune table: per-backend namespaces, one JSON file
# ---------------------------------------------------------------------------

# v3 adds the per-backend "programs" section (grouped/fused GEMV-program
# winners, keyed by ProgramKey.table_key()); v2 namespaced single-GEMV
# tables and v1 flat files still load (see AutotuneTable._parse).
_TABLE_FORMAT = 3


def entry_to_plan(entry: dict) -> tuple[str, GemvPlan | None]:
    """Rebuild a (kernel, plan) decision from a persisted table entry."""
    if entry.get("m_blk") is None:
        return entry["kernel"], None
    return entry["kernel"], GemvPlan(
        m_blk=entry["m_blk"], k_blk=entry["k_blk"], n_m=entry["n_m"],
        n_k=entry["n_k"], vmem_bytes=entry.get("vmem_bytes", 0),
        split_k=entry.get("split_k", 1),
        pipeline_depth=entry.get("pipeline_depth", 1),
    )


def plan_to_entry(kernel: str, plan: GemvPlan | None,
                  elapsed_us: float) -> dict:
    entry = {"kernel": kernel, "us": elapsed_us}
    if plan is not None:
        entry.update(
            m_blk=plan.m_blk, k_blk=plan.k_blk, n_m=plan.n_m, n_k=plan.n_k,
            vmem_bytes=plan.vmem_bytes, split_k=plan.split_k,
            pipeline_depth=plan.pipeline_depth,
        )
    return entry


class AutotuneTable:
    """Measured (kernel, plan) winners, namespaced per backend.

    On disk the table is one JSON document (format 3)::

        {"format": 3,
         "tables":      {"tpu": {<shape key>: entry, ...}, "cpu": {...}},
         "programs":    {"tpu": {<program key>: entry, ...}, ...},
         "calibration": {"cpu": {"constants": {...}, "mape": ..., ...}}}

    so tuners running on different substrates merge into a single file
    without key collisions — the heterogeneous-fleet analogue of the paper
    shipping pre-swept placements per memory configuration.  ``programs``
    (new in v3) holds grouped/fused GEMV-program winners; ``calibration``
    (optional, still format 3) holds fitted per-backend CostModel constants
    (``repro.calibration``, DESIGN.md §11) — dispatch applies them to the
    backend the first time it prices a decision after a load.  v2 files
    simply have no such sections and v1 flat files migrate as before;
    top-level sections this version doesn't know are preserved verbatim
    through load/save (a newer writer's table survives an older reader).
    All mutation is guarded by a lock: engines stepped from a thread pool
    share one table.
    """

    # Sections this version interprets; anything else round-trips opaquely.
    _KNOWN_SECTIONS = ("format", "tables", "programs", "calibration")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._tables: dict[str, dict[str, dict]] = {}
        self._programs: dict[str, dict[str, dict]] = {}
        self._calibration: dict[str, dict] = {}
        self._extras: dict = {}
        self._loaded_paths: set[str] = set()

    # -- in-memory access ---------------------------------------------------

    def get(self, namespace: str, key: str) -> dict | None:
        with self._lock:
            entry = self._tables.get(namespace, {}).get(key)
            return dict(entry) if entry is not None else None

    def put(self, namespace: str, key: str, entry: dict) -> None:
        with self._lock:
            self._tables.setdefault(namespace, {})[key] = dict(entry)

    def get_program(self, namespace: str, key: str) -> dict | None:
        with self._lock:
            entry = self._programs.get(namespace, {}).get(key)
            return dict(entry) if entry is not None else None

    def put_program(self, namespace: str, key: str, entry: dict) -> None:
        with self._lock:
            self._programs.setdefault(namespace, {})[key] = dict(entry)

    def get_calibration(self, namespace: str) -> dict | None:
        with self._lock:
            entry = self._calibration.get(namespace)
            return dict(entry) if entry is not None else None

    def put_calibration(self, namespace: str, entry: dict) -> None:
        with self._lock:
            self._calibration[namespace] = dict(entry)

    def snapshot_calibration(self) -> dict[str, dict]:
        with self._lock:
            return {ns: dict(e) for ns, e in self._calibration.items()}

    def namespaces(self) -> tuple[str, ...]:
        with self._lock:
            return tuple(sorted(self._tables))

    def snapshot(self) -> dict[str, dict[str, dict]]:
        with self._lock:
            return {ns: {k: dict(e) for k, e in t.items()}
                    for ns, t in self._tables.items()}

    def snapshot_programs(self) -> dict[str, dict[str, dict]]:
        with self._lock:
            return {ns: {k: dict(e) for k, e in t.items()}
                    for ns, t in self._programs.items()}

    def clear(self) -> None:
        with self._lock:
            self._tables.clear()
            self._programs.clear()
            self._calibration.clear()
            self._extras.clear()
            self._loaded_paths.clear()

    # -- persistence --------------------------------------------------------

    # PR-1 keys embedded the JAX platform the tuner ran on as a suffix
    # ("..._float32_cpu"); the v2 shape key drops it (the namespace carries
    # the backend instead), so v1 keys must be migrated or they never match.
    _V1_KEY_SUFFIXES = ("cpu", "tpu", "gpu", "cuda", "rocm")

    @classmethod
    def _parse(
        cls, doc: dict
    ) -> tuple[dict[str, dict[str, dict]], dict[str, dict[str, dict]],
               dict[str, dict], dict]:
        """Accept a v3/v2 namespaced document or a v1 flat table; returns
        ``(tables, programs, calibration, extras)``.

        v2 documents have no ``programs`` section (empty mapping); unknown
        namespaces in either section load verbatim — a fleet table may name
        backends this process never registered.  ``calibration`` (optional
        in v3) maps backend namespaces to fitted CostModel records.
        ``extras`` carries any top-level sections this version does not
        interpret, so a table written by a newer repro survives a
        load/save cycle here un-truncated.  v1 files (PR-1) map suffixed
        shape keys straight to entries; they load into the ``tpu``
        namespace — the kernel set those tables named — with the platform
        suffix stripped so v2+ lookups find them.
        """
        if "tables" in doc and isinstance(doc["tables"], dict):
            tables = {ns: dict(t) for ns, t in doc["tables"].items()}
            programs = {
                ns: dict(t)
                for ns, t in doc.get("programs", {}).items()
            } if isinstance(doc.get("programs", {}), dict) else {}
            calibration = {
                ns: dict(e)
                for ns, e in doc.get("calibration", {}).items()
            } if isinstance(doc.get("calibration", {}), dict) else {}
            extras = {k: v for k, v in doc.items()
                      if k not in cls._KNOWN_SECTIONS}
            return tables, programs, calibration, extras
        flat = {}
        for k, v in doc.items():
            if not (isinstance(v, dict) and "kernel" in v):
                continue
            head, _, tail = k.rpartition("_")
            if head and tail in cls._V1_KEY_SUFFIXES:
                k = head
            flat[k] = v
        return ({"tpu": flat} if flat else {}), {}, {}, {}

    def load(self, path: str) -> dict[str, dict[str, dict]]:
        """Merge the table at ``path`` into memory; returns the single-GEMV
        ``{backend: {key: entry}}`` section that was read (program entries
        merge too — inspect them via :meth:`snapshot_programs`).

        The returned mapping is the caller's to mutate: entries are copied
        on insert so the shared table can only change under its lock.
        """
        with open(path) as f:
            parsed, programs, calibration, extras = self._parse(json.load(f))
        with self._lock:
            for ns, entries in parsed.items():
                self._tables.setdefault(ns, {}).update(
                    {k: dict(e) for k, e in entries.items()}
                )
            for ns, entries in programs.items():
                self._programs.setdefault(ns, {}).update(
                    {k: dict(e) for k, e in entries.items()}
                )
            for ns, entry in calibration.items():
                self._calibration[ns] = dict(entry)
            self._extras.update(extras)
            self._loaded_paths.add(os.path.abspath(path))
        return parsed

    def ensure_loaded(self, path: str) -> None:
        """Lazy one-shot load: pick up entries persisted by earlier runs."""
        p = os.path.abspath(path)
        with self._lock:
            if p in self._loaded_paths:
                return
            self._loaded_paths.add(p)
        if os.path.exists(p):
            self.load(p)

    def save(self, path: str) -> None:
        """Merge this process's namespaces into the file at ``path``.

        Read-merge-write with an atomic rename, per namespace: a CPU tuner
        never erases a TPU tuner's entries (different namespace), and never
        erases entries for shapes it didn't tune itself (inner-dict merge).
        The whole read-merge-write runs under the table lock (and the temp
        name carries the thread id): two engine threads saving after
        concurrent autotunes must not interleave on one temp file.  Cross-
        process racing on the same shape keeps the last writer's timing —
        harmless, both are valid.
        """
        path = os.path.abspath(path)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with self._lock:
            merged: dict[str, dict[str, dict]] = {}
            merged_prog: dict[str, dict[str, dict]] = {}
            merged_cal: dict[str, dict] = {}
            extras: dict = {}
            try:
                with open(path) as f:
                    merged, merged_prog, merged_cal, extras = \
                        self._parse(json.load(f))
            except (FileNotFoundError, json.JSONDecodeError):
                pass
            for ns, entries in self._tables.items():
                merged.setdefault(ns, {}).update(entries)
            for ns, entries in self._programs.items():
                merged_prog.setdefault(ns, {}).update(entries)
            merged_cal.update(self._calibration)
            extras.update(self._extras)
            doc = dict(extras)
            doc.update({"format": _TABLE_FORMAT, "tables": merged,
                        "programs": merged_prog})
            if merged_cal:
                doc["calibration"] = merged_cal
            tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
            try:
                with open(tmp, "w") as f:
                    json.dump(doc, f, indent=1, sort_keys=True)
                os.replace(tmp, path)
            except BaseException:
                # never strand a temp file next to the table (CI legs
                # glob the artifact dir); the target is still intact
                # because only os.replace publishes.
                with contextlib.suppress(OSError):
                    os.unlink(tmp)
                raise


# ---------------------------------------------------------------------------
# Timing harness (shared by autotuners and benchmarks)
# ---------------------------------------------------------------------------


def time_gemv_us(run, reps: int = 3) -> float:
    """Best-of-``reps`` wall clock (µs) for a thunk returning a jax array."""
    run().block_until_ready()  # compile / warm up
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        run().block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


# ---------------------------------------------------------------------------
# The backend contract
# ---------------------------------------------------------------------------


class GemvBackend:
    """One execution target behind ``dispatch_gemv``.

    Subclasses set :attr:`name`, :attr:`kernels`, :attr:`cost_model` and
    implement the selection / planning / execution methods.  The autotune
    loop is shared: it times the backend's own candidates with the backend's
    own executor and persists winners under the backend's namespace.
    """

    name: str = ""
    kernels: tuple[str, ...] = ("ref",)
    cost_model: CostModel = CostModel(
        bandwidth_gbps=1.0, gemv_efficiency=1.0, launch_us=0.0,
        program_us=0.0, min_parallel_blocks=1,
    )

    # -- cost-model calibration (repro.calibration, DESIGN.md §11) ----------
    #
    # ``cost_model`` on the CLASS is the hand-seeded constant set; applying
    # a calibration shadows it with a fitted instance attribute, so every
    # estimate/selection path picks the fitted constants up with zero
    # call-site changes.  ``cost_model_source`` is the observability hook:
    # dispatch stamps it into dispatch_stats()["cost_model_source"] per
    # decision, so it is always visible which model priced a pick.

    @property
    def seed_cost_model(self) -> CostModel:
        """The class-level (hand-seeded) constants, ignoring calibration."""
        for klass in type(self).__mro__:
            if "cost_model" in vars(klass):
                return vars(klass)["cost_model"]
        raise AssertionError("no class-level cost_model")  # pragma: no cover

    @property
    def cost_model_source(self) -> str:
        """``"calibrated"`` when fitted constants are active, else ``"seed"``."""
        return "calibrated" if "cost_model" in self.__dict__ else "seed"

    def apply_calibration(self, cm: CostModel) -> CostModel:
        """Activate fitted constants (idempotent; returns the active model)."""
        self.__dict__["cost_model"] = cm
        return cm

    def reset_calibration(self) -> None:
        """Back to the seed constants (no-op when none were applied)."""
        self.__dict__.pop("cost_model", None)

    # -- cost model ---------------------------------------------------------

    def estimate_cost_us(
        self, kernel: str, M: int, K: int, batch: int, *,
        bits: int = 16, x_bytes: int = 2, plan: GemvPlan | None = None,
    ) -> float:
        """Modeled GEMV latency (µs) on this backend.

        Default: memory-bound ref path — bytes over (bandwidth × efficiency)
        plus the per-output-element overhead term.  Backends override to
        model their non-ref kernels.
        """
        io = self.io_bytes(M, K, batch, bits=bits, x_bytes=x_bytes)
        cm = self.cost_model
        return (io / (cm.bandwidth_bps * cm.gemv_efficiency) * 1e6
                + batch * M * cm.elem_ns * 1e-3)

    @staticmethod
    def io_bytes(M: int, K: int, batch: int, *, bits: int = 16,
                 x_bytes: int = 2) -> float:
        return M * K * bits / 8 + batch * K * x_bytes + batch * M * x_bytes

    # -- planning / selection ----------------------------------------------

    def candidate_plans(
        self, M: int, K: int, batch: int, bits: int
    ) -> list[tuple[str, GemvPlan | None]]:
        """Every kernel applicable to this shape, with an executable plan."""
        return [("ref", None)]

    def select_kernel(
        self, M: int, K: int, batch: int, *,
        bits: int = 16, block: int = 32, x_bytes: int = 2,
        policy: DispatchPolicy = DEFAULT_POLICY,
    ) -> tuple[str, GemvPlan | None]:
        """Pure selection: (kernel name, executable plan) for one shape."""
        raise NotImplementedError

    def coerce_plan(
        self, plan: GemvPlan, M: int, K: int, batch: int,
        pw: PackedWeights, policy: DispatchPolicy,
    ) -> tuple[str, GemvPlan | None]:
        """Map a caller-supplied plan to this backend's (kernel, plan).

        Legacy ``placed_gemv(plan=...)`` path; the default ignores the plan
        and falls back to selection.
        """
        return self.select_kernel(
            M, K, batch, bits=pw.bits, block=pw.block, policy=policy
        )

    def _check_pin(self, name: str, bits: int) -> None:
        """Shared validation for explicitly pinned kernels."""
        if name not in self.kernels:
            raise ValueError(
                f"unknown kernel {name!r} for backend {self.name!r}; "
                f"expected one of {self.kernels}"
            )
        if name in ("quant", "quant4") and bits == 16:
            raise ValueError(f"kernel={name!r} requires int8/int4 weights")

    # -- execution ----------------------------------------------------------

    def default_interpret(self) -> bool:
        """Pallas interpret mode when the policy leaves it unset
        (``policy.interpret is None``).

        Base: False — a backend's kernels run natively wherever the backend
        was resolved (the CPU set is pure XLA; the GPU set is capability-
        gated at *selection* time, so a picked Triton kernel can lower).
        Only the TPU backend overrides this: off-TPU it exists as the
        interpret-mode validation harness.
        """
        return False

    def execute(self, kernel: str, x: jnp.ndarray, pw: PackedWeights,
                plan: GemvPlan | None, interpret: bool) -> jnp.ndarray:
        raise NotImplementedError

    def _execute_ref(self, x: jnp.ndarray, pw: PackedWeights) -> jnp.ndarray:
        """The shared XLA reference path: plain dot for float weights,
        block-scale dequant oracles for int8 / packed int4."""
        from repro.kernels import ref

        if pw.bits == 16:
            return ref.gemv_ref(pw.w_t, x)
        if pw.bits == 8:
            return ref.quant_gemv_ref(pw.w_t, pw.scales, x, pw.block)
        return ref.quant4_gemv_ref(pw.w_t, pw.scales, x, pw.block)

    # -- autotune (shared loop, backend-owned candidates + namespace) -------

    def autotune_candidates(
        self, key: GemvKey, pw: PackedWeights, policy: DispatchPolicy
    ) -> list[tuple[str, GemvPlan | None]]:
        """Candidates the autotuner times; default = the planner's set."""
        return self.candidate_plans(key.M, key.K, key.batch, key.bits)

    def autotune_gemv(
        self, key: GemvKey, *, policy: DispatchPolicy, table: AutotuneTable,
    ) -> tuple[str, GemvPlan | None]:
        """Time every candidate on synthetic inputs; persist the winner.

        Inputs are synthesized from the key (never the caller's arrays,
        which may be tracers when dispatch happens inside a ``jit`` trace).
        Entries land in this backend's namespace of ``table``.
        """
        if policy.table_path:
            table.ensure_loaded(policy.table_path)
        tkey = key.table_key()
        entry = table.get(self.name, tkey)
        if entry is not None:
            return entry_to_plan(entry)
        interpret = (
            policy.interpret if policy.interpret is not None
            else self.default_interpret()
        )
        x, pw = synthesize_gemv(key)
        best: tuple[float, str, GemvPlan | None] | None = None
        for kernel, plan in self.autotune_candidates(key, pw, policy):
            try:
                us = time_gemv_us(
                    lambda: self.execute(kernel, x, pw, plan, interpret)
                )
            except Exception:  # a candidate that fails to lower never wins
                continue
            if best is None or us < best[0]:
                best = (us, kernel, plan)
        assert best is not None, key
        table.put(self.name, tkey, plan_to_entry(best[1], best[2], best[0]))
        if policy.table_path:
            table.save(policy.table_path)
        return best[1], best[2]

    # -- GEMV programs (DESIGN.md §7) ----------------------------------------

    # Joint execution modes this backend implements beyond the universal
    # per-request decomposition.  Base: none — an unmodified third-party
    # backend gets correct program dispatch as N independent requests.
    program_modes: tuple[str, ...] = ()

    def estimate_program_cost_us(
        self, key: ProgramKey, *, mode: str, x_bytes: int = 2,
    ) -> float:
        """Modeled latency (µs) of one program under an execution mode.

        Extends the single-GEMV model with the two terms the program API
        exists to amortize: **shared-IV traffic** (a fused program reads the
        input vector once, per-request reads it ``n_requests`` times) and
        **launch cost** (one launch for a joint mode vs one per request).
        Weight and output traffic are mode-independent.
        """
        cm = self.cost_model
        w_bytes = key.total_M * key.K * key.bits / 8
        if key.kind == "ragged":
            # Capacity-free: activation traffic is exactly the routed
            # tokens (the grouped path pays batch * group padded slots).
            # The slowest expert serializes its grid cells, so the
            # per-program term scales with the predicted load imbalance:
            # the per-expert bound (key.batch) over the even split T/E.
            T = max(key.tokens, 1)
            io = w_bytes + T * key.K * x_bytes + T * key.Ms[0] * x_bytes
            t = io / (cm.bandwidth_bps * cm.gemv_efficiency) * 1e6
            launches = 1 if mode != "per_request" else key.group
            imbalance = min(max(key.batch * key.group / T, 1.0),
                            float(key.group))
            return (t + cm.launch_us * launches
                    + cm.program_us * key.group * imbalance
                    + T * key.Ms[0] * cm.elem_ns * 1e-3)
        out_bytes = key.batch * key.total_M * x_bytes
        if key.kind == "grouped":
            # every expert has its own token buffer: IV traffic is
            # per-expert regardless of mode; grouping amortizes launches.
            iv_reads = key.group
        else:
            iv_reads = 1 if mode == "fused" else key.n_requests
        io = w_bytes + iv_reads * key.batch * key.K * x_bytes + out_bytes
        launches = 1 if mode in ("fused", "grouped") else key.n_requests
        t = io / (cm.bandwidth_bps * cm.gemv_efficiency) * 1e6
        return (t + cm.launch_us * launches
                + key.batch * key.total_M * cm.elem_ns * 1e-3)

    def plan_program(
        self, key: ProgramKey, *, policy: DispatchPolicy = DEFAULT_POLICY,
    ) -> ProgramPlan:
        """(mode, launches, inner decision) for one program shape.

        Default: the per-request decomposition.  Backends that register a
        joint mode in :attr:`program_modes` get it planned here — ``fused``
        selects an inner kernel for the concatenated [sum(Ms), K] GEMV with
        the backend's own ``select_kernel`` (so kernel pins and
        ``use_pallas`` gates apply to the fused matrix exactly as they
        would to a single GEMV of that shape); ``grouped`` is one batched
        contraction over the expert stack.
        """
        if key.kind == "ragged":
            # Ragged programs have no per-request decomposition (the
            # expert split is runtime data, not a shape), so this plans
            # one launch regardless of fuse_programs; the universal XLA
            # ragged executor makes the mode available on every backend.
            # Policy gating happens upstream: the MoE layer only builds
            # ragged programs when program fusion is on.
            return ProgramPlan(mode="ragged", n_launches=1)
        if not policy.fuse_programs:
            return ProgramPlan(mode="per_request", n_launches=key.n_requests)
        if key.kind == "grouped":
            if "grouped" in self.program_modes:
                return ProgramPlan(mode="grouped", n_launches=1)
            return ProgramPlan(mode="per_request", n_launches=key.group)
        if "fused" in self.program_modes:
            kernel, plan = self.select_kernel(
                sum(key.Ms), key.K, key.batch, bits=key.bits,
                block=key.block, x_bytes=jnp.dtype(key.dtype).itemsize,
                policy=policy,
            )
            return ProgramPlan(mode="fused", n_launches=1, kernel=kernel,
                               plan=plan)
        return ProgramPlan(mode="per_request", n_launches=len(key.Ms))

    def execute_program(
        self, program: GemvProgram, pplan: ProgramPlan,
        policy: DispatchPolicy, interpret: bool,
    ) -> jnp.ndarray:
        """Run one program under a plan.

        Returns ``[B, sum(Ms)]`` for fused-kind programs (split per request
        with :meth:`GemvProgram.split`), ``[E, C, M]`` for grouped ones,
        and ``[T, M]`` for ragged ones — identical output shape for every
        mode, so a mode change (table entry, policy flip) can never change
        a caller's contract.
        """
        if pplan.mode == "fused":
            return self.execute(pplan.kernel, program.x, program.weights,
                                pplan.plan, interpret)
        if pplan.mode == "grouped":
            return self._execute_grouped(program.x, program.weights)
        if pplan.mode == "ragged":
            return self._execute_ragged(program)
        assert program.kind != "ragged", pplan  # no per-request form exists
        # Per-request decomposition, selected and executed entirely on THIS
        # backend (no registry re-resolution) — the autotune loop times it
        # as a candidate against the joint mode.  The public dispatch path
        # (`dispatch.dispatch_program`) instead decomposes through the
        # plan-cached request path for exact dispatch_gemv parity.
        outs = []
        for req in program.requests:
            K, M = req.weights.shape
            kernel, plan = self.select_kernel(
                M, K, req.x.shape[0], bits=req.weights.bits,
                block=req.weights.block,
                x_bytes=jnp.dtype(req.x.dtype).itemsize, policy=policy,
            )
            outs.append(self.execute(kernel, req.x, req.weights, plan,
                                     interpret))
        if program.kind == "grouped":
            return jnp.stack(outs)
        return jnp.concatenate(outs, axis=-1)

    @staticmethod
    def _dequant_stack(pw: PackedWeights) -> jnp.ndarray:
        """Stacked [E, K, M] weights as floats: identity for 16-bit packs,
        per-expert block-scale dequant for int8 / packed int4 (the scales
        broadcast over the stacked dim)."""
        from repro.kernels import ref

        w = pw.w_t
        if pw.bits == 4:
            w = ref.unpack_int4(w)
        if pw.bits < 16:
            E, K, M = w.shape
            w = w.astype(jnp.float32).reshape(E, K // pw.block, pw.block, M)
            w = (w * pw.scales.astype(jnp.float32)[:, :, None, :]
                 ).reshape(E, K, M)
        return w

    def _execute_grouped(self, xs: jnp.ndarray,
                         pw: PackedWeights) -> jnp.ndarray:
        """Batched expert contraction: out[E, C, M] = xs[E, C, K] @ w[E, K, M].

        XLA reference with f32 accumulation; quantized stacks dequantize
        per expert.  Backends with a native grouped kernel override this.
        """
        w = self._dequant_stack(pw)
        return jnp.einsum(
            "eck,ekm->ecm", xs.astype(jnp.float32), w.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ).astype(xs.dtype)

    def _execute_ragged(self, program: GemvProgram) -> jnp.ndarray:
        """Universal ragged executor: out[T, M], row t against the expert
        whose count range contains t.

        ``jax.lax.ragged_dot`` (it partitions cleanly under GSPMD with
        expert-sharded stacks) with f32 accumulation — zero capacity
        padding; rows at or beyond ``sum(counts)`` come back zero
        (matching the Pallas ragged kernel's tail contract).
        """
        x = program.x
        counts = program.counts.astype(jnp.int32)
        w = self._dequant_stack(program.weights)
        xf = x.astype(jnp.float32)
        wf = w.astype(jnp.float32)
        ends = jnp.cumsum(counts)
        T = x.shape[0]
        out = jax.lax.ragged_dot(xf, wf, group_sizes=counts,
                                 preferred_element_type=jnp.float32)
        valid = (jnp.arange(T) < ends[-1])[:, None]
        return jnp.where(valid, out, 0.0).astype(x.dtype)

    def autotune_program(
        self, key: ProgramKey, *, policy: DispatchPolicy,
        table: AutotuneTable,
    ) -> ProgramPlan:
        """Time the joint mode against the per-request decomposition on a
        synthetic program; persist the winner in the v3 ``programs``
        section of this backend's namespace."""
        if policy.table_path:
            table.ensure_loaded(policy.table_path)
        tkey = key.table_key()
        entry = table.get_program(self.name, tkey)
        if entry is not None:
            return entry_to_program_plan(entry)
        interpret = (
            policy.interpret if policy.interpret is not None
            else self.default_interpret()
        )
        program = _synthesize_program(key)
        candidates = [self.plan_program(key, policy=policy)]
        if key.kind == "ragged":
            # No per-request decomposition exists for ragged programs; the
            # alternative to a native kernel is the universal XLA executor.
            base_ragged = ProgramPlan(mode="ragged", n_launches=1)
            if candidates[0] != base_ragged:
                candidates.append(base_ragged)
        else:
            per_req = ProgramPlan(mode="per_request",
                                  n_launches=key.n_requests)
            if candidates[0].mode != "per_request":
                candidates.append(per_req)
        best: tuple[float, ProgramPlan] | None = None
        for cand in candidates:
            try:
                us = time_gemv_us(
                    lambda: self.execute_program(program, cand, policy,
                                                 interpret)
                )
            except Exception:  # a mode that fails to lower never wins
                continue
            if best is None or us < best[0]:
                best = (us, cand)
        assert best is not None, key
        table.put_program(self.name, tkey,
                          program_plan_to_entry(best[1], best[0]))
        if policy.table_path:
            table.save(policy.table_path)
        return best[1]


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, GemvBackend] = {}
_PLATFORM_MAP: dict[str, str] = {}
_REG_LOCK = threading.Lock()


def register_backend(
    backend: GemvBackend, *, platforms: tuple[str, ...] = ()
) -> GemvBackend:
    """Register a backend instance, optionally claiming JAX platform names
    (``jax.default_backend()`` strings) it should serve by default."""
    if not backend.name:
        raise ValueError("backend must set a non-empty name")
    with _REG_LOCK:
        _REGISTRY[backend.name] = backend
        for p in platforms:
            _PLATFORM_MAP[p] = backend.name
    return backend


def get_backend(name: str) -> GemvBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown GEMV backend {name!r}; registered: "
            f"{sorted(_REGISTRY)}"
        ) from None


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def backend_for_platform(platform: str) -> GemvBackend:
    """Backend serving a JAX platform name.  An unclaimed platform is an
    error: quietly serving it through another backend's kernels would hide
    which device the dispatcher is really planning for."""
    name = _PLATFORM_MAP.get(platform)
    if name is None:
        raise ValueError(
            f"no GEMV backend claims JAX platform {platform!r}; claimed: "
            f"{sorted(_PLATFORM_MAP)} (set DispatchPolicy.backend)")
    return get_backend(name)


def resolve_backend(policy: DispatchPolicy | None = None) -> GemvBackend:
    """Resolution order: explicit ``policy.backend`` override, then the
    explicit ``interpret=True`` opt-in (the TPU-analogue validation harness
    on any host), then ``jax.default_backend()``."""
    if policy is not None and policy.backend:
        return get_backend(policy.backend)
    if policy is not None and policy.interpret:
        return get_backend("tpu")
    return backend_for_platform(jax.default_backend())
