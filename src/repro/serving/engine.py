"""Serving: prefill/decode steps and a slot-managed continuous-batching engine.

``Engine`` is a thin composition of the serving subsystem (DESIGN.md §8):

* :class:`~repro.serving.kv_cache.SlotKVCache` — slot-managed decode state
  with **per-slot position vectors** (heterogeneous prompt lengths decode
  correctly in one batch; the lockstep equal-length restriction of the
  pre-PR-4 engine is gone), slot alloc/free/defrag, batched multi-slot
  prefill splicing;
* :class:`~repro.serving.scheduler.Scheduler` — admission policies (FCFS /
  shortest-prompt-first / a ``gemv_aware`` policy that caps concurrent
  decode slots at ``gemv_batch_threshold`` so decode stays on the
  GEMV-program fast path — the paper's orchestration knob lifted to the
  request level), waiting-queue backpressure, per-request deadlines, and
  (``preempt_margin``) slot eviction for deadline-imminent queued work;
* :class:`~repro.serving.metrics.ServingMetrics` — TTFT / per-token-latency
  / throughput histograms, per-step records and the whole run's
  GEMV-dispatcher counters, exportable as a schema-versioned JSON document;
* :mod:`~repro.serving.sampling` — temperature/top-k/top-p sampling,
  greedy-compatible (the default stays exact argmax);
* :class:`~repro.serving.prefix_cache.PrefixCache` (opt-in,
  ``prefix_cache=True``) — shared-prefix KV reuse (DESIGN.md §12): at
  admission the engine matches the request's longest cached prefix,
  splices the matched segments into the slot, and prefills ONLY the
  private tail through the chunked-prefill continuation seam — the
  matched prefill GEMVs never run.  Prefilled KV is filed back into the
  radix index (including at preemption, so a readmitted request re-
  prefills only its generated tail); segments are refcount-pinned while
  a slot uses them and LRU-evicted under capacity pressure.  Encoder /
  cross-attention families (whisper, llama-vision) are gated off —
  their KV folds in per-request modality features, so token-keyed reuse
  would be unsound.  ``kv_store="int8"`` (``"int4"`` behind the same
  flag) stores KV as quantized pages + per-(position, head) scales
  (``kernels.kv_quant``), multiplying the slots a memory budget holds;
  greedy token identity with the prefix cache on vs off holds in every
  store format because the codec is deterministic.

Decode-time matmuls are where the paper's technique lives: with the decode
batch <= ``gemv_batch_threshold`` the projections route through the unified
GEMV dispatcher (``repro.kernels.dispatch``) as **GEMV programs** — QKV and
MLP gate+up as fused shared-IV programs over weights **prepacked at engine
init** (``lm.prepack_decode_params``, the one-time §V-A2 cost; no per-step
concat), MoE expert FFNs as grouped programs, the LM head as a single
request.  The engine decodes a defragmented power-of-two *bucket* of active
slots, so the scheduler's admission cap is what decides whether those
dispatches stay GEMV-shaped or fall back to the XLA matmul path — the mix
is visible in ``dispatch_stats()`` and in the metrics document.

Sharded mode (DESIGN.md §9): constructed with a ``mesh``, the engine runs
the same serving loop over a device mesh end-to-end — decode params placed
with the PIMnast mesh planner (``distributed.sharding.plan_params``), the
slot cache sharded with ``plan_serve_cache`` (per-slot ``pos`` replicated,
KV on heads along 'model'), prefill-splice / decode / defrag jitted with
explicit ``in_shardings``/``out_shardings``, and the GEMV dispatcher's
``DispatchPolicy.model_shards`` set to the 'model'-axis size so every
kernel decision reasons about the PER-SHARD GEMV (M/N row placement or
K/N split-K — Algorithm 1's even-distribution test at the mesh level).
Greedy decode is token-identical to the single-host engine.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.dispatch import DispatchPolicy
from repro.models import lm
from repro.kernels.kv_quant import validate_kv_store
from repro.serving.kv_cache import POSITIONAL_LEAVES, SlotKVCache
from repro.serving.metrics import ServingMetrics
from repro.serving.prefix_cache import (
    PrefixCache, PrefixCacheConfig, prefix_cacheable,
)
from repro.serving.sampling import SamplingParams, request_rng, sample_token
from repro.serving.scheduler import QueueFull, Scheduler, SchedulerConfig

__all__ = [
    "Engine", "Request", "build_serve_fns", "greedy", "QueueFull",
    "SamplingParams", "Scheduler", "SchedulerConfig", "ServingMetrics",
]


@dataclass
class Request:
    rid: int
    prompt: np.ndarray              # [S] int32
    max_new_tokens: int = 16
    eos_id: int = -1                # -1: never (shim; prefer eos_ids)
    eos_ids: set[int] | None = None  # tokenizer-aware stop set; overrides
                                     # eos_id when set (may be empty)
    sampling: SamplingParams | None = None   # None: greedy
    deadline: float | None = None   # absolute engine-clock time; queued
                                    # requests past it are expired
    generated: list[int] = field(default_factory=list)
    done: bool = False
    expired: bool = False
    slot: int = -1
    submit_time: float = 0.0
    arrival_seq: int = 0
    admit_seq: int = -1             # admission order (preemption victim pick)
    evictions: int = 0              # times this request lost its slot
    first_token_time: float | None = None
    finish_time: float | None = None
    # Prefix-cache outcome of the FIRST admission (None: engine ran
    # without a prefix cache) — keys the TTFT hit/miss split.
    prefix_hit: bool | None = None

    def stop_set(self) -> frozenset[int]:
        """The effective stop-token set (``eos_ids`` over the ``eos_id``
        shim; ``eos_id == -1`` means never stop on a token)."""
        if self.eos_ids is not None:
            return frozenset(self.eos_ids)
        return frozenset((self.eos_id,)) if self.eos_id >= 0 else frozenset()


def build_serve_fns(cfg: ModelConfig, max_len: int,
                    gemv_policy: DispatchPolicy | None = None):
    """Returns (prefill, decode_step), both pure-jittable.

    ``gemv_policy`` routes decode-step projections through the unified GEMV
    dispatcher; prefill keeps the matmul path (Sq > 1 is not GEMV-shaped).
    Kept as the dry-run/examples entry point; the Engine builds its own
    variants (per-slot last-token gather for heterogeneous prefill).
    """

    def prefill(params, tokens, cache, extra):
        logits, cache, _ = lm.forward(
            params, cfg, tokens,
            cache=cache,
            frames=extra.get("frames"), vision=extra.get("vision"),
        )
        return logits[:, -1], cache

    def decode_step(params, last_tok, cache, extra):
        logits, cache, _ = lm.forward(
            params, cfg, last_tok,
            cache=cache,
            frames=extra.get("frames"), vision=extra.get("vision"),
            gemv_policy=gemv_policy,
        )
        return logits[:, -1], cache

    return prefill, decode_step


def greedy(logits: jnp.ndarray) -> jnp.ndarray:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


# Entered in place of a ``Tracer.span`` when no tracer is installed: the
# hot path builds neither a span nor its attrs (``with (tr.span(...) if tr
# is not None else _NO_SPAN)``).
_NO_SPAN = contextlib.nullcontext()


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


class Engine:
    """Continuous batching over a slot-managed KV cache.

    Batch shaping: active slots are kept a contiguous prefix (defrag on
    free), and decode runs over the smallest power-of-two bucket covering
    them — so jit caches stay bounded AND the scheduler's admission cap
    translates directly into the batch size the GEMV dispatcher sees.

    ``mesh`` switches the engine into sharded mode (module docstring /
    DESIGN.md §9); ``prefill_chunk`` splits prompts longer than that many
    tokens into one-chunk-per-step splices so a long prefill no longer
    stalls the decode batch for a full step.
    """

    def __init__(self, cfg: ModelConfig, params, *, batch_slots: int = 4,
                 max_len: int = 128, use_pim_kernels: bool = True,
                 gemv_batch_threshold: int = 8,
                 gemv_backend: str | None = None,
                 gemv_fuse_programs: bool = True,
                 gemv_expert_shape: str = "ragged",
                 scheduler: Scheduler | SchedulerConfig | str = "fcfs",
                 max_queue: int = 0,
                 prepack_weights: bool = True,
                 metrics: ServingMetrics | None = None,
                 mesh=None,
                 prefill_chunk: int | None = None,
                 async_prefill: bool = False,
                 overlap_collectives: bool = False,
                 prefix_cache=None,
                 kv_store: str = "fp",
                 tracer=None,
                 clock=time.monotonic):
        self.cfg = cfg
        self.slots = batch_slots
        self.max_len = max_len
        self.clock = clock
        self.mesh = mesh
        # Overlapped serving (DESIGN.md §14): ``async_prefill`` routes every
        # prefill through the chunked seam WITHOUT per-chunk splices — chunk
        # n+1 chains on chunk n's in-flight b=1 sub-cache, the slot sees ONE
        # splice at harvest (the step after the final chunk issues), and the
        # only host block is the first-token logits fetch at harvest.  The
        # decode steps that run inside that window are what hides the
        # prefill; greedy token streams are identical either way (per-slot
        # rows are independent and the harvest splice fully defines the
        # slot before activation).
        self.async_prefill = bool(async_prefill)
        # Flight recorder (DESIGN.md §13): per-request phase spans, engine
        # spans, per-step gauges.  Installing it process-wide is what arms
        # the dispatch attribution hook in kernels/dispatch.py.
        self.tracer = tracer
        if tracer is not None:
            from repro.observability.trace import install_tracer

            install_tracer(tracer)
        self.prefill_chunk = prefill_chunk
        self.kv_store = validate_kv_store(kv_store)
        model_shards = 1
        if mesh is not None:
            from repro.launch.mesh import model_axis_size

            model_shards = model_axis_size(mesh)
        # Decode GEMV routing: one DispatchPolicy for the engine's lifetime.
        # Above the batch threshold the dispatcher itself falls back to the
        # XLA path (decode becomes matmul-shaped), so the policy is safe to
        # install unconditionally when use_pim_kernels is on.  In sharded
        # mode ``model_shards`` makes every selection reason about the
        # per-shard GEMV (DESIGN.md §9).
        # ``gemv_expert_shape`` picks the MoE decode execution shape
        # (ragged / grouped / einsum — models/layers.py::apply_moe); the
        # default ragged path is the capacity-free one.
        self.gemv_policy = (
            DispatchPolicy(batch_threshold=gemv_batch_threshold,
                           backend=gemv_backend,
                           fuse_programs=gemv_fuse_programs,
                           expert_shape=gemv_expert_shape,
                           model_shards=model_shards,
                           overlap_collectives=bool(overlap_collectives))
            if use_pim_kernels else None
        )
        # One-time fused-weight prepack (§V-A2): dispatch_prepacked then
        # skips the per-step QKV / gate+up concat inside the jitted decode.
        self.params = (
            lm.prepack_decode_params(params, cfg)
            if (prepack_weights and self.gemv_policy is not None
                and gemv_fuse_programs)
            else params
        )
        self.param_shardings = None
        if mesh is not None:
            # Place (prepacked) decode params with the PIMnast mesh planner:
            # row placement over 'model' with the split-K fallback, FSDP on
            # the data axes (DESIGN.md §2.2; fused wqkv / w_gateup leaves
            # shard their concatenated output dim).
            from repro.distributed import sharding as shd

            pspec = shd.plan_params(self.params, mesh, cfg)
            self.param_shardings = shd.to_named(pspec, mesh)
            self.params = jax.device_put(self.params, self.param_shardings)
        if isinstance(scheduler, Scheduler):
            self.scheduler = scheduler
        elif isinstance(scheduler, SchedulerConfig):
            self.scheduler = Scheduler(scheduler)
        else:
            # MoE models make gemv_aware expert-aware: the scheduler's
            # per-expert gate shares expert_batch_bound with apply_moe's
            # ragged dispatch, so admitted batches price exactly as
            # dispatched (serving/scheduler.py module docstring).
            self.scheduler = Scheduler(SchedulerConfig(
                policy=scheduler, max_queue=max_queue,
                gemv_batch_threshold=gemv_batch_threshold,
                moe_experts=(cfg.moe.n_experts if cfg.moe is not None
                             else 0),
                moe_top_k=(cfg.moe.top_k if cfg.moe is not None else 1),
            ))
        if tracer is not None:
            self.scheduler.tracer = tracer
        self.metrics = metrics or ServingMetrics(clock=clock)
        self.kv = SlotKVCache(cfg, batch_slots, max_len, mesh=mesh,
                              kv_store=kv_store)
        # Shared-prefix KV reuse (opt-in; class docstring / DESIGN.md §12).
        # ``prefix_cache`` accepts True (default config), a
        # PrefixCacheConfig, or a prebuilt PrefixCache; encoder /
        # cross-attention families silently stay uncached (their KV is not
        # a pure function of the token prefix).
        self.prefix: PrefixCache | None = None
        if prefix_cache and prefix_cacheable(cfg):
            if isinstance(prefix_cache, PrefixCache):
                self.prefix = prefix_cache
            else:
                has_state = any(
                    name != "pos" and name not in POSITIONAL_LEAVES
                    and leaf.ndim > 1
                    for name, leaf in self.kv.cache.items())
                self.prefix = PrefixCache(
                    prefix_cache if isinstance(prefix_cache,
                                               PrefixCacheConfig) else None,
                    has_state=has_state,
                    placer=self._segment_placer() if mesh is not None
                    else None,
                )
            # Admission prices a cached prefix as near-zero prefill: sjf /
            # gemv_aware sort by the TAIL the request would actually run.
            self.scheduler.prefill_cost = self._prefill_cost
        self.active: dict[int, Request] = {}   # slot -> request
        self._defrag_moves = 0                 # per-step defrag move count
        # slot -> [request, tokens spliced (sync) / issued (async) so far]
        # (chunked prefill in flight: the slot is alloc'd but not decoding)
        self._prefilling: dict[int, list] = {}
        # async_prefill: slot -> {"sub": chained b=1 device cache, "last":
        # device last-token logits, "chunks": issued chunk count, "final":
        # the whole prompt has been issued, "t_final_us": issue time of the
        # final chunk (tracer clock)}.  Keys are a subset of _prefilling.
        self._inflight: dict[int, dict] = {}
        self.expired: list[Request] = []
        # Each slot's last sampled token, the next decode step's input: a
        # host buffer written in place (a device scatter per token costs
        # eight eager programs); each step sends its bucket's rows once.
        self.last_tok = np.zeros((batch_slots, 1), np.int32)
        self._extra = self._make_extra(batch_slots)
        self._rngs: dict[int, np.random.Generator] = {}
        self._admit_seq = 0
        if mesh is None:
            self._jit_prefill = jax.jit(self._prefill_fn)
            self._jit_decode = jax.jit(self._decode_fn)
        else:
            # Explicit shardings on the step functions: params and cache
            # arrive pre-placed (no transfer), everything host-built
            # (tokens, lengths, last tokens, modality rows) replicates, and
            # the new cache leaves are pinned to the cache placement — the
            # decode/prefill output can never come back resharded.
            from jax.sharding import NamedSharding, PartitionSpec as P

            rep = NamedSharding(mesh, P())
            rep_extra = {k: rep for k in self._extra}
            c_sh = self.kv.shardings
            self._jit_prefill = jax.jit(
                self._prefill_fn,
                in_shardings=(self.param_shardings, rep, rep, c_sh,
                              rep_extra),
                out_shardings=(rep, c_sh),
            )
            self._jit_decode = jax.jit(
                self._decode_fn,
                in_shardings=(self.param_shardings, rep, c_sh, rep_extra),
                out_shardings=(rep, c_sh),
            )

    def _mesh_ctx(self):
        """Activation-sharding anchors active while tracing under a mesh."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.distributed.axes import activation_mesh

        return activation_mesh(self.mesh)

    # -- jitted step functions ----------------------------------------------

    def _prefill_fn(self, params, tokens, lengths, cache, extra):
        """Batched heterogeneous prefill: right-padded [n, Lpad] prompts,
        per-slot last-valid-token logits gathered by ``lengths``.  Also the
        chunked-prefill continuation body (the cache carries the per-slot
        write offset, so a chunk is just a shorter right-padded prompt)."""
        logits, cache, _ = lm.forward(
            params, self.cfg, tokens, cache=cache,
            frames=extra.get("frames"), vision=extra.get("vision"),
        )
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None], axis=1
        )[:, 0]
        return last, cache

    def _decode_fn(self, params, last_tok, cache, extra):
        logits, cache, _ = lm.forward(
            params, self.cfg, last_tok, cache=cache,
            frames=extra.get("frames"), vision=extra.get("vision"),
            gemv_policy=self.gemv_policy,
        )
        return logits[:, -1], cache

    def _make_extra(self, b):
        extra = {}
        rng = np.random.default_rng(0)
        if self.cfg.encoder is not None:
            enc = self.cfg.encoder
            extra["frames"] = jnp.asarray(rng.standard_normal(
                (b, enc.n_frames, enc.d_model), dtype=np.float32))
        if self.cfg.cross_attn_every > 0:
            extra["vision"] = jnp.asarray(rng.standard_normal(
                (b, self.cfg.vision_tokens, self.cfg.d_model),
                dtype=np.float32))
        return extra

    # -- prefix-cache integration (DESIGN.md §12) ----------------------------

    def _segment_placer(self):
        """Sharded mode: place segment payloads like the slot cache (heads
        on 'model'), so gather/splice never reshards mid-flight."""
        from repro.distributed import sharding as shd

        def placer(tree, kind):
            if not tree:
                return tree
            spec = shd.plan_segment(tree, self.mesh, self.cfg, kind=kind)
            return jax.device_put(tree, shd.to_named(spec, self.mesh))

        return placer

    def _prefill_cost(self, r: Request) -> int:
        """Prefill tokens this request would ACTUALLY run: pending minus
        the cached prefix (scheduler ordering hook — a pure probe)."""
        toks = self._pending_tokens(r)
        return max(1, len(toks) - self.prefix.match_len(toks))

    def _prefix_match(self, r: Request):
        """Admission-time lookup; records hit/miss metrics and pins the
        request's first-admission outcome for the TTFT split."""
        tr = self.tracer
        t0 = tr.now_us() if tr is not None else 0.0
        m = self.prefix.match(self._pending_tokens(r))
        if tr is not None:
            tr.add_span("prefix_match", t0, tr.now_us(), rid=r.rid,
                        hit=m is not None,
                        matched=m.length if m is not None else 0)
        if r.prefix_hit is None:
            r.prefix_hit = m is not None
        self.metrics.prefix_lookup(m is not None,
                                   m.length if m is not None else 0)
        return m

    def _admit_prefix_hit(self, r: Request, m) -> None:
        """The hit fast path: pin the matched segments, splice them into a
        fresh slot, and hand the PRIVATE TAIL to the chunked-prefill
        continuation seam — the matched prefill GEMVs never run."""
        slot = self.kv.alloc()
        self.prefix.acquire(m)
        # the pin travels with the slot (slot_meta survives defrag) and is
        # dropped in _release_prefix on finish/preemption
        self.kv.slot_meta[slot]["prefix_match"] = m
        self.kv.splice_prefix(slot, self.prefix.gather(m), m.length)
        self._prefilling[slot] = [r, m.length]
        if self.tracer is not None:
            self.tracer.request_annotate(r.rid, slot=slot, prefix_hit=True,
                                         prefix_tokens=m.length)

    def _prefix_insert(self, slot: int, tokens: np.ndarray) -> None:
        """File a slot's freshly prefilled KV into the radix index."""
        if self.prefix is None or len(tokens) == 0:
            return
        self.prefix.insert(tokens,
                           self.kv.extract_prefix(slot, len(tokens)))

    def _release_prefix(self, slot: int) -> None:
        """Unpin the segments a slot acquired at admission (before free)."""
        m = self.kv.slot_meta.get(slot, {}).pop("prefix_match", None)
        if m is not None and self.prefix is not None:
            self.prefix.release(m)

    # -- back-compat views ---------------------------------------------------

    @property
    def cache(self):
        """The slot-managed cache pytree (``pos`` is a per-slot vector)."""
        return self.kv.cache

    @property
    def queue(self) -> list[Request]:
        return self.scheduler.queue

    @property
    def lockstep_cache(self):
        """Deprecated: the pre-PR-4 lockstep cache view (scalar ``pos``).

        The slot-managed layout keeps one position per slot; the lockstep
        scalar was only ever correct for equal prompt lengths.  This shim
        reduces ``pos`` with ``max`` — the old engine's semantics — for
        callers that still read ``engine.cache["pos"]`` as a scalar.
        """
        from repro.kernels.dispatch import _warn_deprecated_once

        _warn_deprecated_once(
            "serving.engine.Engine.lockstep_cache",
            "Engine.lockstep_cache is deprecated; the slot-managed cache "
            "(Engine.kv) keeps per-slot positions — use kv.cache / "
            "kv.kv_valid_len()",
            depth=2,
        )
        view = dict(self.kv.cache)
        view["pos"] = jnp.max(view["pos"])
        return view

    # -- request lifecycle ---------------------------------------------------

    def submit(self, req: Request) -> None:
        """Enqueue a request.

        Raises ``ValueError`` for prompts longer than ``max_len`` (they
        could never be admitted — the pre-PR-4 engine spun on them until
        ``max_iters``) and :class:`QueueFull` under backpressure.
        """
        if len(req.prompt) > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt length {len(req.prompt)} "
                f"exceeds engine max_len={self.max_len}; it can never be "
                f"admitted — truncate the prompt or raise max_len"
            )
        try:
            self.scheduler.submit(req, self.clock())
        except QueueFull:
            self.metrics.request_rejected()
            if self.tracer is not None:
                self.tracer.event("reject", cat="request", rid=req.rid,
                                  reason="queue_full")
            raise
        self.metrics.request_submitted()
        if self.tracer is not None:
            # opens the request span; the request is now in its
            # ``queued`` phase until admission
            self.tracer.request_submit(req.rid, prompt_len=len(req.prompt))

    def step(self) -> list[Request]:
        """One engine iteration: expire + (maybe preempt) + admit + chunked
        prefill advance + one decode step.  Returns requests completed this
        step."""
        tr = self.tracer
        with (tr.span("step") if tr is not None else _NO_SPAN) as sp:
            t0 = self.clock()
            with (tr.span("step.admit") if tr is not None
                  else _NO_SPAN) as sa:
                admitted = self._admit(t0)
                if tr is not None:
                    sa.update(admitted=len(admitted),
                              queue=len(self.scheduler))
            finished: list[Request] = []
            if admitted:
                misses = admitted
                if self.prefix is not None:
                    # prefix hits splice their cached segments and join the
                    # chunked-prefill continuation with only the private
                    # tail left to run; misses take the normal prefill
                    # paths below
                    misses = []
                    for r in admitted:
                        m = self._prefix_match(r)
                        if m is not None:
                            self._admit_prefix_hit(r, m)
                        else:
                            misses.append(r)
                if self.async_prefill:
                    # EVERY miss prefills through the async chunk chain —
                    # even single-chunk prompts get their splice+sample
                    # hidden behind the intervening decode step
                    chunked = list(misses)
                elif self.prefill_chunk:
                    chunked = [r for r in misses
                               if len(self._pending_tokens(r))
                               > self.prefill_chunk]
                else:
                    chunked = []
                chunked_ids = {id(r) for r in chunked}
                plain = [r for r in misses if id(r) not in chunked_ids]
                if plain:
                    finished.extend(self._prefill(plain))
                for r in chunked:
                    # alloc now (the admission decision spent this slot);
                    # the first chunk splices in the advance pass below
                    slot = self.kv.alloc()
                    self._prefilling[slot] = [r, 0]
                    if tr is not None:
                        tr.request_annotate(r.rid, slot=slot)
            if self._prefilling:
                finished.extend(self._advance_chunked())
            # an instant finish (eos / max_new_tokens=1 at prefill) can
            # punch a hole in the active prefix; decode needs it contiguous
            self._defrag_moves = 0
            self._compact()
            decode_batch, decode_s = 0, 0.0
            if self.active:
                done, decode_batch, decode_s = self._decode()
                finished.extend(done)
            self._compact()
            t1 = self.clock()
            self.metrics.record_step(
                t1, step_s=t1 - t0, decode_s=decode_s,
                decode_batch=decode_batch, n_active=self.kv.n_active,
                queue_depth=len(self.scheduler),
            )
            if tr is not None:
                sp.update(decode_rows=decode_batch, admitted=len(admitted))
                # per-step gauges -> counter tracks in the exported trace
                tr.counter("queue_depth", len(self.scheduler))
                tr.counter("active_slots", self.kv.n_active)
                tr.counter("decode_batch", decode_batch)
        return finished

    def _admit(self, now: float) -> list[Request]:
        """Expire overdue queued requests, preempt for a deadline if the
        scheduler asks, and take this step's admissions from the
        scheduler (stamped with their admission order).  Returns them."""
        tr = self.tracer
        expired = self.scheduler.expire(now)
        for r in expired:
            r.expired = True
            if tr is not None:
                tr.request_finish(r.rid, outcome="expired")
        self.expired.extend(expired)
        if expired:
            self.metrics.requests_expired(len(expired))

        self._maybe_preempt(now)
        if self.scheduler.config.moe_experts > 1:
            # Expert-aware batch shaping: refresh the scheduler's router-
            # skew estimate from this engine's dispatch deltas before it
            # decides how many slots to fill (serving/scheduler.py).
            self.scheduler.observe_expert_load(
                self.metrics.dispatch_delta().get("expert_load", {}))
        admitted = self.scheduler.select(self.kv.n_free, self.kv.n_active,
                                         now)
        for r in admitted:
            r.admit_seq = self._admit_seq
            self._admit_seq += 1
            if tr is not None:
                # queued -> prefill (readmitted victims transition
                # preempted -> prefill through the same call)
                tr.request_phase(r.rid, "prefill", admit_seq=r.admit_seq)
        return admitted

    def run_until_drained(self, max_iters: int = 1000) -> list[Request]:
        done: list[Request] = []
        for _ in range(max_iters):
            done.extend(self.step())
            if (not self.active and not self._prefilling
                    and not self.scheduler.queue):
                break
        return done

    # -- internals -----------------------------------------------------------

    @staticmethod
    def _pending_tokens(r: Request) -> np.ndarray:
        """The tokens a (re-)prefill must splice: the prompt, plus whatever
        was already generated when the slot was evicted — re-prefilling the
        full stream makes eviction invisible to greedy token identity."""
        if r.generated:
            return np.concatenate([np.asarray(r.prompt, np.int32),
                                   np.asarray(r.generated, np.int32)])
        return np.asarray(r.prompt, np.int32)

    def _maybe_preempt(self, now: float) -> None:
        """Slot eviction for deadline scheduling (DESIGN.md §8.2): when the
        ``gemv_aware`` scheduler reports a queued request whose deadline
        would expire before a slot naturally frees, evict the YOUNGEST
        occupant (least work wasted) — a mid-chunked-prefill slot first
        (zero decode work done), else the youngest decoding slot.  The
        victim is requeued and re-prefills — prompt plus generated-so-far
        — on readmission.
        """
        if self.kv.n_free > 0 or (not self.active and not self._prefilling):
            return
        if not self.scheduler.wants_preemption(now):
            return
        if self._prefilling:
            slot = max(self._prefilling,
                       key=lambda s: self._prefilling[s][0].admit_seq)
            r, valid = self._prefilling.pop(slot)
            # async chain in flight: land the issued chunks into the slot
            # first so the prefix insert below sees real KV, not the junk
            # the overlapped decode steps wrote there
            self._await_inflight(slot, valid)
        else:
            slot = max(self.active,
                       key=lambda s: self.active[s].admit_seq)
            r = self.active.pop(slot)
            valid = int(self.kv.kv_valid_len()[slot])
        # File the victim's computed KV into the prefix cache BEFORE the
        # slot is freed: readmission then matches it and re-prefills only
        # the tokens generated after this point (the pre-§12 engine threw
        # the whole stream's prefill away on every eviction).
        self._prefix_insert(slot, self._pending_tokens(r)[:valid])
        self._release_prefix(slot)
        self.kv.free(slot)
        r.slot = -1
        r.evictions += 1
        self.scheduler.requeue(r)
        self.metrics.request_evicted()
        if self.tracer is not None:
            # decode/prefill -> preempted; readmission re-enters prefill
            self.tracer.request_phase(r.rid, "preempted",
                                      evicted_from=slot,
                                      evictions=r.evictions)

    def _prefill(self, admitted: list[Request]) -> list[Request]:
        # Recurrent state (rwkv / parallel mamba) must never see pad
        # tokens, so those families prefill per request; pure-attention
        # families prefill the whole admission wave in ONE right-padded
        # batched forward (pad KVs stay masked by per-slot kv_valid_len).
        if self.cfg.family == "ssm" or self.cfg.parallel_ssm:
            waves = [[r] for r in admitted]
        else:
            waves = [admitted]
        finished = []
        for wave in waves:
            finished.extend(self._prefill_wave(wave))
        return finished

    def _prefill_wave(self, wave: list[Request]) -> list[Request]:
        tr = self.tracer
        with (tr.span("prefill_wave") if tr is not None else _NO_SPAN) as sw:
            slots = [self.kv.alloc() for _ in wave]
            if tr is not None:
                for r, slot in zip(wave, slots):
                    tr.request_annotate(r.rid, slot=slot)
            toks = [self._pending_tokens(r) for r in wave]
            lengths = [len(t) for t in toks]
            Lmax = max(lengths)
            if self.cfg.family == "ssm" or self.cfg.parallel_ssm:
                Lpad = Lmax  # exact: no pads through the recurrence
            else:
                Lpad = max(min(_next_pow2(Lmax), self.max_len), Lmax)
            nb = min(_next_pow2(len(wave)), self.slots)
            tokens = np.zeros((nb, Lpad), np.int32)
            lens = np.ones((nb,), np.int32)
            for i, t in enumerate(toks):
                tokens[i, :lengths[i]] = t
                lens[i] = lengths[i]
            # batch-pad rows reuse the first slot's modality features
            row_idx = slots + [slots[0]] * (nb - len(wave))
            extra = {k: v[jnp.asarray(row_idx)]
                     for k, v in self._extra.items()}
            sub = lm.init_cache(self.cfg, nb, self.max_len,
                                per_slot_pos=True, kv_store=self.kv_store)
            with (tr.span("prefill.launch") if tr is not None
                  else _NO_SPAN), self._mesh_ctx():
                last, sub = self._jit_prefill(
                    self.params, jnp.asarray(tokens), jnp.asarray(lens), sub,
                    extra,
                )
            with (tr.span("kv.splice", rows=len(slots)) if tr is not None
                  else _NO_SPAN):
                self.kv.splice(sub, slots, lengths)
                for slot, t in zip(slots, toks):
                    self._prefix_insert(slot, t)
            with (tr.span("prefill.fetch") if tr is not None else _NO_SPAN):
                last_np = np.asarray(last)
            with (tr.span("prefill.sample", rows=len(wave)) if tr is not None
                  else _NO_SPAN):
                now = self.clock()
                finished = []
                for i, (r, slot) in enumerate(zip(wave, slots)):
                    tok = self._sample(r, last_np[i])
                    if self._activate(r, slot, tok, now):
                        finished.append(r)
            self.metrics.prefill_wave(len(wave), sum(lengths))
            if tr is not None:
                sw.update(requests=len(wave), tokens=sum(lengths))
        return finished

    def _advance_chunked(self) -> list[Request]:
        """Advance every in-flight chunked prefill by ONE chunk (so a long
        prompt costs one bounded splice per engine step instead of stalling
        the whole step); the final chunk samples the first token and moves
        the request into the decode set.

        ``async_prefill`` changes only the *blocking* structure: chunks are
        issued against a chained b=1 sub-cache (no per-chunk splice into
        the slot), fully-issued chains harvest at the START of the next
        step's pass — one splice + one logits fetch per request, after the
        intervening decode step already forced the device work — and the
        issue→harvest window is recorded as a ``cat="overlap"`` span whose
        ``blocked_us`` attr is the host time actually spent waiting.
        Mid-chain recurrent-state checkpoints into the prefix cache are
        skipped in async mode (the slot holds no real KV until harvest);
        the full-prompt insert at harvest still files the boundary that
        matters."""
        finished = []
        # prefix-hit tails ride this seam even when chunking is off
        # (prefill_chunk=None): one un-split chunk covers the whole tail
        chunk_limit = self.prefill_chunk or self.max_len
        tr = self.tracer
        if self.async_prefill:
            finished.extend(self._harvest_ready())
        for slot in sorted(self._prefilling):
            inf = self._inflight.get(slot)
            if inf is not None and inf["final"]:
                continue  # fully issued; harvests next step
            chunk_t0 = tr.now_us() if tr is not None else 0.0
            req, consumed = self._prefilling[slot]
            toks = self._pending_tokens(req)
            chunk = toks[consumed:consumed + chunk_limit]
            c = len(chunk)
            if self.cfg.family == "ssm" or self.cfg.parallel_ssm:
                cpad = c  # exact: no pads through the recurrence
            else:
                # pad rounding must not write past max_len: the per-slot KV
                # update starts at ``consumed``, and an over-long pad would
                # make dynamic_update_slice CLAMP the start index backwards,
                # silently overwriting valid KV from earlier chunks
                cpad = max(min(_next_pow2(c), chunk_limit,
                               self.max_len - consumed), c)
            tokens = np.zeros((1, cpad), np.int32)
            tokens[0, :c] = chunk
            # first chunk starts from a fresh b=1 cache; later chunks
            # continue from the in-flight chain (async) or the slot's own
            # row (sync / prefix-hit tail; pos = tokens landed so far)
            if inf is not None:
                sub = inf["sub"]
            elif consumed == 0:
                sub = lm.init_cache(self.cfg, 1, self.max_len,
                                    per_slot_pos=True,
                                    kv_store=self.kv_store)
            else:
                sub = self.kv.slot_view(slot)
            extra1 = {k: v[slot:slot + 1] for k, v in self._extra.items()}
            with self._mesh_ctx():
                last, sub = self._jit_prefill(
                    self.params, jnp.asarray(tokens),
                    jnp.asarray([c], np.int32), sub, extra1,
                )
            self._prefilling[slot][1] = consumed + c
            self.metrics.prefill_chunk(c)
            if tr is not None:
                tr.add_span("prefill_chunk", chunk_t0, tr.now_us(),
                            track=f"slot{slot}", rid=req.rid, slot=slot,
                            tokens=c, consumed=consumed + c)
            if self.async_prefill:
                from repro.kernels.dispatch import record_overlap

                record_overlap("async_prefill", issued=1)
                # the forward advanced pos by the PADDED chunk length; the
                # chain must carry the true token count (pad KV beyond it
                # stays masked, as in the spliced path)
                sub = dict(sub)
                sub["pos"] = jnp.full_like(sub["pos"], consumed + c)
                self._inflight[slot] = {
                    "sub": sub, "last": last,
                    "chunks": (inf["chunks"] if inf is not None else 0) + 1,
                    "final": consumed + c >= len(toks),
                    "t_final_us": (tr.now_us() if tr is not None else 0.0),
                }
                continue
            self.kv.splice(sub, [slot], [consumed + c])
            if consumed + c < len(toks):
                # State-carrying families can only resume from a snapshot,
                # and edge SPLITS can't create one mid-edge — so chunk
                # boundaries are where their shareable boundaries come
                # from: checkpoint the state each chunk.  Pure-KV families
                # skip this (they match mid-edge anyway).
                if self.prefix is not None and self.prefix.has_state:
                    self._prefix_insert(slot, toks[:consumed + c])
                continue
            del self._prefilling[slot]
            self._prefix_insert(slot, toks)
            req2 = req  # fully spliced: sample the first token, activate
            tok = self._sample(req2, np.asarray(last)[0])
            if self._activate(req2, slot, tok, self.clock()):
                finished.append(req2)
        return finished

    def _harvest_ready(self) -> list[Request]:
        """Async-prefill harvest: splice every fully-issued chain into its
        slot, fetch the first-token logits (the one host block), record
        the overlap span, and activate the request — it joins THIS step's
        decode bucket."""
        from repro.kernels.dispatch import record_overlap

        tr = self.tracer
        finished = []
        for slot in sorted(self._inflight):
            inf = self._inflight[slot]
            if not inf["final"]:
                continue
            req, consumed = self._prefilling[slot]
            t_h0 = tr.now_us() if tr is not None else 0.0
            last_np = np.asarray(inf["last"])   # blocks until chain done
            t_h1 = tr.now_us() if tr is not None else 0.0
            del self._inflight[slot]
            self.kv.splice(inf["sub"], [slot], [consumed])
            record_overlap("async_prefill", awaited=inf["chunks"])
            if tr is not None:
                # the span partitions the issue->harvest window into
                # blocked (host waited here) and hidden (decode ran); the
                # summary's hidden_fraction reduces exactly these attrs
                tr.add_span("async_prefill", inf["t_final_us"], t_h1,
                            cat="overlap", track=f"slot{slot}",
                            rid=req.rid, slot=slot,
                            blocked_us=max(t_h1 - t_h0, 0.0),
                            chunks=inf["chunks"], tokens=consumed)
            del self._prefilling[slot]
            self._prefix_insert(slot, self._pending_tokens(req))
            tok = self._sample(req, last_np[0])
            if self._activate(req, slot, tok, self.clock()):
                finished.append(req)
        return finished

    def _await_inflight(self, slot: int, valid: int) -> None:
        """Blocking: land an async chain's issued chunks into ``slot`` (the
        preemption path — the victim's KV must be real before it is filed
        into the prefix cache and the slot freed)."""
        inf = self._inflight.pop(slot, None)
        if inf is None:
            return
        from repro.kernels.dispatch import record_overlap

        self.kv.splice(inf["sub"], [slot], [valid])
        record_overlap("async_prefill", awaited=inf["chunks"])

    def _activate(self, r: Request, slot: int, tok: int,
                  now: float) -> bool:
        """Shared prefill tail: record the first sampled token and move the
        request into the decode set; returns True on an instant finish."""
        r.generated.append(tok)
        r.slot = slot
        self.active[slot] = r
        self.last_tok[slot, 0] = tok
        if self.tracer is not None:
            # prefill -> decode at the first sampled token
            self.tracer.request_phase(r.rid, "decode", slot=slot)
        self.metrics.first_token(r, now)
        self.metrics.tokens_generated(1)
        if self._should_finish(r, tok):
            self._finish(r, slot, now)
            return True
        return False

    def _decode(self) -> tuple[list[Request], int, float]:
        t0 = self.clock()
        tr = self.tracer
        with (tr.span("decode_step") if tr is not None else _NO_SPAN) as sd:
            n = self.kv.n_active  # compact() keeps alloc'd slots a prefix
            b = min(_next_pow2(n), self.slots)
            if self.gemv_policy is not None:
                # Don't let power-of-two rounding push the batch past the
                # dispatcher's GEMV gate when the actives themselves fit
                # under it (a non-pow2 threshold would otherwise silently
                # defeat the gemv_aware policy); the threshold-sized bucket
                # is one extra jit shape, still bounded.
                thresh = self.gemv_policy.batch_threshold
                if n <= thresh < b:
                    b = thresh
            with (tr.span("kv.slice", bucket=b) if tr is not None
                  else _NO_SPAN):
                # Chunked-prefill rows sit inside the alloc'd prefix the
                # bucket covers; decode must not advance their mid-prompt
                # state, so their rows are snapshotted and restored after
                # the merge (their logits are never sampled — only
                # ``self.active`` rows are).  Async mode drops the
                # snapshot/restore entirely: the slot holds no real KV until
                # the harvest splice fully defines it, so whatever decode
                # writes there is junk-on-junk (per-slot rows are
                # independent).
                snaps = ({} if self.async_prefill else
                         {s: (self.kv.slot_view(s), self._prefilling[s][1])
                          for s in self._prefilling if s < b})
                cache_b = self.kv.slice_prefix(b)
                # a copy: the jit may alias a host argument without one
                # (CPU backend), and the buffer changes after this step
                last_b = np.array(self.last_tok[:b])
                extra_b = {k: v[:b] for k, v in self._extra.items()}
            with (tr.span("decode.launch") if tr is not None
                  else _NO_SPAN), self._mesh_ctx():
                logits, new_cache = self._jit_decode(
                    self.params, last_b, cache_b, extra_b
                )
            with (tr.span("kv.merge") if tr is not None else _NO_SPAN):
                self.kv.merge_prefix(new_cache, b)
                for s, (snap, consumed) in snaps.items():
                    self.kv.splice(snap, [s], [consumed])
            with (tr.span("decode.fetch") if tr is not None else _NO_SPAN):
                logits_np = np.asarray(logits)
            decode_s = self.clock() - t0
            now = self.clock()
            finished = []
            rows = list(self.active.items())
            with (tr.span("decode.sample", rows=len(rows)) if tr is not None
                  else _NO_SPAN) as ss:
                for slot, r in rows:
                    tok = self._sample(r, logits_np[slot])
                    r.generated.append(tok)
                    self.last_tok[slot, 0] = tok
                    self.metrics.tokens_generated(1)
                    if self._should_finish(r, tok):
                        self._finish(r, slot, now)
                        finished.append(r)
                if tr is not None:
                    ss.update(finished=len(finished), host_writes=len(rows))
            if tr is not None:
                # live_positions: the positions the decoded rows' caches
                # hold after this step (their kv_valid_len), counted on the
                # host so the span forces no device sync
                sd.update(bucket=b, active=n,
                          defrag_moves=self._defrag_moves,
                          finished=len(finished), rows=len(rows),
                          live_positions=sum(
                              len(r.prompt) + len(r.generated) - 1
                              for _, r in rows))
        return finished, b, decode_s

    def _sample(self, r: Request, logits_row: np.ndarray) -> int:
        # greedy-vs-stochastic decision lives in sampling.sample_token;
        # the engine only caches the per-request generator.
        if r.sampling is None or r.sampling.temperature <= 0:
            return sample_token(logits_row, r.sampling)
        rng = self._rngs.get(r.rid)
        if rng is None:
            rng = self._rngs[r.rid] = request_rng(r.sampling, r.rid)
        return sample_token(logits_row, r.sampling, rng)

    def _should_finish(self, r: Request, tok: int) -> bool:
        return (
            tok in r.stop_set()
            or len(r.generated) >= r.max_new_tokens
            # cache budget: the next decode step would write past max_len
            or len(r.prompt) + len(r.generated) >= self.max_len
        )

    def _finish(self, r: Request, slot: int, now: float) -> None:
        r.done = True
        self.metrics.request_finished(r, now)
        self._release_prefix(slot)
        self.kv.free(slot)
        del self.active[slot]
        self._rngs.pop(r.rid, None)
        if self.tracer is not None:
            self.tracer.request_finish(r.rid, outcome="finished",
                                       tokens=len(r.generated),
                                       evictions=r.evictions)

    def _compact(self) -> None:
        """Defrag active slots to a contiguous prefix; re-point per-slot
        side state (request map, chunked-prefill map, last tokens,
        modality rows)."""
        tr = self.tracer
        with (tr.span("kv.compact") if tr is not None else _NO_SPAN) as sp:
            moves = self.kv.compact()
            for src, dst in moves.items():
                self._defrag_moves += 1
                if src in self.active:
                    r = self.active.pop(src)
                    r.slot = dst
                    self.active[dst] = r
                else:
                    self._prefilling[dst] = self._prefilling.pop(src)
                    if src in self._inflight:  # async chain follows its slot
                        self._inflight[dst] = self._inflight.pop(src)
                if tr is not None:
                    moved = (self.active.get(dst)
                             or self._prefilling.get(dst, [None])[0])
                    tr.event("defrag_move", src=src, dst=dst,
                             rid=moved.rid if moved is not None else None)
                    if moved is not None:
                        tr.request_annotate(moved.rid, slot=dst)
                self.last_tok[dst] = self.last_tok[src]
                # SWAP modality rows (not copy): the in-flight request keeps
                # its features at dst, and the freed src slot inherits dst's
                # old row — the per-slot feature set stays a permutation, so
                # future occupants never see a duplicated/lost row.
                for k, v in self._extra.items():
                    src_row = v[src]
                    self._extra[k] = v.at[src].set(v[dst]).at[dst].set(
                        src_row)
            if tr is not None:
                sp["moves"] = len(moves)


def _splice_cache(cache, single, slot: int):
    """Deprecated (PR-4): lockstep single-slot cache splice.

    Writes a b=1 cache into batch slot ``slot`` of a scalar-``pos``
    (lockstep) cache, tracking position as the max across slots — only
    correct when every admission wave shares one prompt length.  The slot-
    managed replacement is :meth:`repro.serving.kv_cache.SlotKVCache.splice`
    (batched, per-slot positions).  Warns once per call site.
    """
    from repro.kernels.dispatch import _warn_deprecated_once

    _warn_deprecated_once(
        "serving.engine._splice_cache",
        "serving.engine._splice_cache is deprecated; use "
        "serving.kv_cache.SlotKVCache.splice (slot-managed cache with "
        "per-slot positions)",
        depth=2,
    )

    def f(full, one):
        if full.ndim == 0:  # pos scalar: lockstep position
            return jnp.maximum(full, one)
        # every cache leaf is [L, B, ...]: batch is dim 1
        return jax.lax.dynamic_update_slice_in_dim(
            full, one.astype(full.dtype), slot, axis=1
        )

    return jax.tree.map(f, cache, single)
