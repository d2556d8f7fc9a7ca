"""Serving telemetry: latency histograms + GEMV dispatcher counters
(DESIGN.md §8.3).

The paper's end metric is **per-token decode latency** (§V/§VII); the
metrics layer makes the engine emit it.  Three histograms:

* ``ttft_ms`` — submit-to-first-token (queueing + prefill);
* ``per_token_ms`` — decode-step wall time, one sample per step (every
  active slot advances one token per step, so this IS the per-token decode
  latency distribution);
* ``step_ms`` — every engine iteration, including admission-only ones.

plus throughput counters, a small record per engine step (time, step
latency, decode batch, occupancy, queue depth) and the GEMV dispatcher's
decision counters over the whole run
(``repro.kernels.dispatch.dispatch_stats``: plan-cache program hits,
per-backend kernel picks, gemv-vs-matmul path mix).  Those counters are a
*delta against the engine's start*, so one process can run several
engines/policies and attribute decisions to each (serve_bench relies on
this to show the scheduler's batch shaping moving the mix).  They move
only when a plan is made, at trace time, so they are read for the
document, not copied into every step record.

Everything exports as one schema-versioned JSON document
(:meth:`ServingMetrics.to_dict` / :meth:`to_json`).  Laptop-scale design:
histograms keep raw samples and report exact percentiles up to a bounded
reservoir cap (see :class:`Histogram`); per-step records are bounded by
``MAX_STEP_RECORDS`` with aggregates keeping full fidelity.
"""

from __future__ import annotations

import json
import time

import numpy as np

# --json/JSON-document version: bump when the record layout changes.
# v2: the dispatch counters carry the ``expert_load`` and
# ``program_fallbacks`` sections (ragged MoE serving) and the document
# gains the derived ``expert_balance`` summary when MoE dispatches ran.
# v3: prefix-cache counters (``prefix_hits`` / ``prefix_misses`` /
# ``prefill_tokens_saved``), the hit/miss TTFT split histograms
# (``ttft_hit_ms`` / ``ttft_miss_ms``), and the ``prefix_cache`` summary
# section when any lookup ran.
SCHEMA_VERSION = 3

# Per-step records kept in memory; older entries are dropped (the
# aggregate histograms/counters keep full fidelity).
MAX_STEP_RECORDS = 4096


class Histogram:
    """Bounded-memory histogram: exact percentiles up to ``max_samples``.

    Below the cap every sample is kept raw and percentiles are EXACT (the
    documented laptop-scale behavior — the default cap of 65536 covers
    every bench/CI run this repo performs).  Past the cap, reservoir
    sampling (Vitter's Algorithm R, deterministic seed) keeps a uniform
    sample of the full stream: ``count``/``mean``/``max`` stay exact
    (tracked as scalars), percentiles degrade to unbiased estimates, and
    memory stays O(max_samples) no matter how long the run
    (``summary()["sampled"]`` marks the estimated regime).
    """

    DEFAULT_MAX_SAMPLES = 65536

    def __init__(self, name: str = "", max_samples: int | None = None):
        self.name = name
        self.max_samples = (self.DEFAULT_MAX_SAMPLES if max_samples is None
                            else int(max_samples))
        assert self.max_samples > 0
        self.samples: list[float] = []
        self._n = 0                 # total recorded, >= len(samples)
        self._sum = 0.0
        self._max = float("-inf")
        self._rng = np.random.default_rng(0)

    def record(self, value: float) -> None:
        v = float(value)
        self._n += 1
        self._sum += v
        if v > self._max:
            self._max = v
        if len(self.samples) < self.max_samples:
            self.samples.append(v)
        else:
            # Algorithm R: sample i (1-based self._n) replaces a reservoir
            # slot with probability max_samples / n — uniform over stream.
            j = int(self._rng.integers(self._n))
            if j < self.max_samples:
                self.samples[j] = v

    @property
    def count(self) -> int:
        """Total values recorded (exact, even past the reservoir cap)."""
        return self._n

    def percentile(self, p: float) -> float:
        if not self.samples:
            return float("nan")
        return float(np.percentile(np.asarray(self.samples), p))

    def summary(self) -> dict:
        if not self._n:
            return {"count": 0}
        a = np.asarray(self.samples)
        out = {
            "count": self._n,
            "mean": self._sum / self._n,
            "p50": float(np.percentile(a, 50)),
            "p90": float(np.percentile(a, 90)),
            "p99": float(np.percentile(a, 99)),
            "max": self._max,
        }
        if self._n > a.size:
            out["sampled"] = int(a.size)
        return out


def _dispatch_snapshot() -> dict:
    from repro.kernels.dispatch import dispatch_stats

    return dispatch_stats()


def _diff_counters(cur, base):
    """Recursive int-diff of nested counter dicts (cur - base)."""
    if isinstance(cur, dict):
        base = base or {}
        return {k: _diff_counters(v, base.get(k)) for k, v in cur.items()}
    return cur - (base or 0)


class ServingMetrics:
    """Mutable per-engine telemetry; one instance per :class:`Engine`."""

    def __init__(self, clock=time.monotonic):
        self.clock = clock
        self.start_time = clock()
        self.ttft_ms = Histogram("ttft_ms")
        # TTFT split by prefix-cache outcome: a hit prefills only the
        # private tail, so its TTFT should sit strictly below the miss
        # distribution (the CI smoke leg asserts p50 hit < p50 miss).
        self.ttft_hit_ms = Histogram("ttft_hit_ms")
        self.ttft_miss_ms = Histogram("ttft_miss_ms")
        self.per_token_ms = Histogram("per_token_ms")
        self.step_ms = Histogram("step_ms")
        self.batch_sizes = Histogram("decode_batch")
        self.counters = {
            "submitted": 0, "rejected": 0, "expired": 0, "finished": 0,
            "evicted": 0,
            "tokens_out": 0, "prefill_tokens": 0, "prefill_waves": 0,
            "prefill_chunks": 0,
            "decode_steps": 0, "engine_steps": 0,
            "prefix_hits": 0, "prefix_misses": 0,
            "prefill_tokens_saved": 0,
        }
        self.steps: list[dict] = []
        # Dispatch counters are process-global; everything this engine
        # reports is a delta against its construction-time snapshot.
        self._dispatch_base = _dispatch_snapshot()

    # -- request lifecycle ---------------------------------------------------

    def request_submitted(self) -> None:
        self.counters["submitted"] += 1

    def request_rejected(self) -> None:
        self.counters["rejected"] += 1

    def requests_expired(self, n: int) -> None:
        self.counters["expired"] += n

    def request_evicted(self) -> None:
        """A running slot was preempted for a deadline-imminent request."""
        self.counters["evicted"] += 1

    def prefix_lookup(self, hit: bool, saved_tokens: int = 0) -> None:
        """One prefix-cache admission lookup; ``saved_tokens`` is the
        matched prefill the hit skipped."""
        if hit:
            self.counters["prefix_hits"] += 1
            self.counters["prefill_tokens_saved"] += saved_tokens
        else:
            self.counters["prefix_misses"] += 1

    def first_token(self, req, now: float) -> None:
        """Record TTFT once per request: a preempted request re-prefills on
        readmission, but its first token already streamed out."""
        if req.first_token_time is not None:
            return
        req.first_token_time = now
        ttft = (now - req.submit_time) * 1e3
        self.ttft_ms.record(ttft)
        # hit/miss split keyed by the FIRST admission's cache outcome
        # (None when the engine ran without a prefix cache)
        hit = getattr(req, "prefix_hit", None)
        if hit is True:
            self.ttft_hit_ms.record(ttft)
        elif hit is False:
            self.ttft_miss_ms.record(ttft)

    def request_finished(self, req, now: float) -> None:
        req.finish_time = now
        self.counters["finished"] += 1

    def tokens_generated(self, n: int) -> None:
        self.counters["tokens_out"] += n

    def prefill_wave(self, n_requests: int, n_tokens: int) -> None:
        self.counters["prefill_waves"] += 1
        self.counters["prefill_tokens"] += n_tokens

    def prefill_chunk(self, n_tokens: int) -> None:
        """One chunk of a chunked prefill (one bounded splice per step)."""
        self.counters["prefill_chunks"] += 1
        self.counters["prefill_tokens"] += n_tokens

    # -- per-step record -----------------------------------------------------

    def dispatch_delta(self) -> dict:
        return _diff_counters(_dispatch_snapshot(), self._dispatch_base)

    def record_step(self, now: float, *, step_s: float, decode_batch: int,
                    n_active: int, queue_depth: int,
                    decode_s: float = 0.0) -> None:
        self.counters["engine_steps"] += 1
        self.step_ms.record(step_s * 1e3)
        if decode_batch:
            self.counters["decode_steps"] += 1
            self.per_token_ms.record(decode_s * 1e3)
            self.batch_sizes.record(decode_batch)
        self.steps.append({
            "t": now - self.start_time,
            "step_ms": step_s * 1e3,
            "decode_batch": decode_batch,
            "active": n_active,
            "queue": queue_depth,
        })
        if len(self.steps) > MAX_STEP_RECORDS:
            del self.steps[:len(self.steps) - MAX_STEP_RECORDS]

    # -- export --------------------------------------------------------------

    @staticmethod
    def expert_balance(dispatch: dict) -> dict | None:
        """Derived per-expert load-balance summary from the ``expert_load``
        dispatch counters (None when no MoE dispatch decisions ran).

        ``imbalance`` is the planned per-expert bound over the even split
        — 1.0 is PIMnast-perfect balance; ``padding_waste`` is the
        fraction of expert-buffer slots the legacy capacity path padded
        (the ragged path holds it at 0.0, counter-verified).
        """
        el = dispatch.get("expert_load") or {}
        decisions = int(el.get("decisions", 0) or 0)
        if decisions <= 0:
            return None
        routed = int(el.get("routed_tokens", 0) or 0)
        experts = int(el.get("experts", 0) or 0)
        max_tokens = int(el.get("max_tokens", 0) or 0)
        padded = int(el.get("padded_slots", 0) or 0)
        mean_per_expert = routed / max(experts, 1)
        max_per_expert = max_tokens / decisions
        return {
            "decisions": decisions,
            "mean_tokens_per_expert": mean_per_expert,
            "max_tokens_per_expert": max_per_expert,
            "imbalance": max_per_expert / max(mean_per_expert, 1e-9),
            "padding_waste": padded / max(padded + routed, 1),
        }

    def to_dict(self, *, include_steps: bool = True) -> dict:
        elapsed = max(self.clock() - self.start_time, 1e-9)
        dispatch = self.dispatch_delta()
        doc = {
            "schema": SCHEMA_VERSION,
            "elapsed_s": elapsed,
            "ttft_ms": self.ttft_ms.summary(),
            "per_token_ms": self.per_token_ms.summary(),
            "step_ms": self.step_ms.summary(),
            "decode_batch": self.batch_sizes.summary(),
            "tokens_per_s": self.counters["tokens_out"] / elapsed,
            "counters": dict(self.counters),
            "dispatch": dispatch,
        }
        balance = self.expert_balance(dispatch)
        if balance is not None:
            doc["expert_balance"] = balance
        lookups = (self.counters["prefix_hits"]
                   + self.counters["prefix_misses"])
        if lookups:
            doc["prefix_cache"] = {
                "lookups": lookups,
                "hits": self.counters["prefix_hits"],
                "misses": self.counters["prefix_misses"],
                "hit_rate": self.counters["prefix_hits"] / lookups,
                "prefill_tokens_saved":
                    self.counters["prefill_tokens_saved"],
                "ttft_hit_ms": self.ttft_hit_ms.summary(),
                "ttft_miss_ms": self.ttft_miss_ms.summary(),
            }
        if include_steps:
            doc["steps"] = list(self.steps)
        return doc

    def to_json(self, path: str | None = None, *,
                include_steps: bool = True) -> str:
        doc = self.to_dict(include_steps=include_steps)
        text = json.dumps(doc, indent=1, sort_keys=True)
        if path:
            with open(path, "w") as f:
                f.write(text)
        return text
