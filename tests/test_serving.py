"""Serving subsystem tests (DESIGN.md §8): slot-managed KV cache,
heterogeneous continuous batching, scheduler policies, metrics, sampling,
and the serve-bench document.

The load-bearing acceptance test: one Engine batch serving prompts of
DIFFERENT lengths produces token-identical greedy output to b=1 serial
decoding per request.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_stub import given, settings, st

from repro.configs.registry import ARCHS
from repro.kernels import dispatch
from repro.models import lm
from repro.serving import engine as engine_mod
from repro.serving.engine import Engine, Request
from repro.serving.kv_cache import SlotKVCache
from repro.serving.metrics import Histogram, ServingMetrics
from repro.serving.sampling import SamplingParams, request_rng, sample_token
from repro.serving.scheduler import (
    QueueFull,
    Scheduler,
    SchedulerConfig,
)

KEY = jax.random.PRNGKey(0)
MAX_LEN = 64


@pytest.fixture(scope="module")
def cfg():
    return ARCHS["olmo-1b"].reduced()


@pytest.fixture(scope="module")
def params(cfg):
    return lm.init_lm(KEY, cfg)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, L).astype(np.int32) for L in lengths]


def _serial_greedy(cfg, params, prompt, n_new, max_len=MAX_LEN):
    """b=1 reference: plain forward loop, no engine, no dispatcher."""
    cache = lm.init_cache(cfg, 1, max_len)
    logits, cache, _ = lm.forward(params, cfg, jnp.asarray(prompt[None]),
                                  cache=cache)
    out = [int(jnp.argmax(logits[0, -1]))]
    for _ in range(n_new - 1):
        logits, cache, _ = lm.forward(
            params, cfg, jnp.asarray([[out[-1]]]), cache=cache
        )
        out.append(int(jnp.argmax(logits[0, -1])))
    return out


# --------------------------------------------------------------------------
# Slot KV cache
# --------------------------------------------------------------------------


def test_kv_cache_alloc_free_lowest_first(cfg):
    kv = SlotKVCache(cfg, 4, 16)
    assert [kv.alloc() for _ in range(3)] == [0, 1, 2]
    kv.free(1)
    assert kv.alloc() == 1  # lowest free first
    assert kv.n_active == 3 and kv.n_free == 1
    with pytest.raises(ValueError):
        kv.free(3)  # not active


def test_kv_cache_compact_moves_active_to_prefix(cfg):
    kv = SlotKVCache(cfg, 4, 16)
    for _ in range(4):
        kv.alloc()
    kv.cache = {
        k: (v + jnp.arange(4, dtype=v.dtype).reshape(
            (4,) + (1,) * (v.ndim - 1)) if k == "pos"
            else v + jnp.arange(4, dtype=v.dtype).reshape(
                (1, 4) + (1,) * (v.ndim - 2)))
        for k, v in kv.cache.items()
    }  # make every slot row identifiable
    kv.free(0)
    kv.free(2)
    moves = kv.compact()
    assert moves == {3: 0}  # highest active into lowest hole; 1 stays
    assert kv.active_slots() == (0, 1)
    # the moved row carried its data (slot 3's marker now at row 0)
    assert int(kv.cache["pos"][0]) == 3
    k = kv.cache["k"]
    np.testing.assert_array_equal(np.asarray(k[:, 0]), 3.0 + np.zeros_like(
        np.asarray(k[:, 0])))


def test_kv_cache_splice_sets_per_slot_positions(cfg):
    kv = SlotKVCache(cfg, 4, 16)
    s0, s1 = kv.alloc(), kv.alloc()
    sub = lm.init_cache(cfg, 2, 16, per_slot_pos=True)
    sub = {k: v + 1 if k != "pos" else v for k, v in sub.items()}
    kv.splice(sub, [s0, s1], [5, 9])
    np.testing.assert_array_equal(kv.kv_valid_len(), [5, 9, 0, 0])
    # spliced rows carry the sub-cache data; untouched rows stay zero
    k = np.asarray(kv.cache["k"])
    assert (k[:, :2] == 1.0).all() and (k[:, 2:] == 0.0).all()


# --------------------------------------------------------------------------
# Scheduler
# --------------------------------------------------------------------------


def _req(rid, plen):
    return Request(rid=rid, prompt=np.zeros(plen, np.int32))


def test_scheduler_fcfs_preserves_arrival_order():
    s = Scheduler(SchedulerConfig(policy="fcfs"))
    for i, L in enumerate([9, 2, 7]):
        s.submit(_req(i, L))
    assert [r.rid for r in s.select(2, 0)] == [0, 1]
    assert [r.rid for r in s.queue] == [2]


def test_scheduler_sjf_shortest_prompt_first():
    s = Scheduler(SchedulerConfig(policy="sjf"))
    for i, L in enumerate([9, 2, 7, 2]):
        s.submit(_req(i, L))
    picked = s.select(3, 0)
    assert [r.rid for r in picked] == [1, 3, 2]  # stable on ties


def test_scheduler_gemv_aware_caps_active_slots():
    s = Scheduler(SchedulerConfig(policy="gemv_aware",
                                  gemv_batch_threshold=4))
    for i in range(8):
        s.submit(_req(i, 4))
    assert len(s.select(8, 0)) == 4      # free=8 but threshold caps at 4
    for i in range(8, 10):
        s.submit(_req(i, 4))
    assert len(s.select(8, 3)) == 1      # 3 already decoding
    assert s.select(8, 4) == []          # at the cap: admit nothing


def test_scheduler_backpressure_queue_full():
    s = Scheduler(SchedulerConfig(max_queue=2))
    s.submit(_req(0, 4))
    s.submit(_req(1, 4))
    with pytest.raises(QueueFull):
        s.submit(_req(2, 4))
    assert len(s) == 2


def test_scheduler_expires_deadlined_requests():
    s = Scheduler(SchedulerConfig())
    r0, r1 = _req(0, 4), _req(1, 4)
    r0.deadline = 5.0
    s.submit(r0, now=0.0)
    s.submit(r1, now=0.0)
    assert s.expire(now=1.0) == []
    assert [r.rid for r in s.expire(now=6.0)] == [0]
    assert [r.rid for r in s.queue] == [1]


def test_scheduler_rejects_unknown_policy():
    with pytest.raises(ValueError):
        SchedulerConfig(policy="round_robin")


@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 12), free=st.integers(0, 8),
       seed=st.integers(0, 999),
       policy=st.sampled_from(["fcfs", "sjf", "gemv_aware"]))
def test_scheduler_selection_properties(n, free, seed, policy):
    """Conservation + ordering properties across policies."""
    rng = np.random.default_rng(seed)
    s = Scheduler(SchedulerConfig(policy=policy, gemv_batch_threshold=4))
    for i in range(n):
        s.submit(_req(i, int(rng.integers(1, 32))))
    picked = s.select(free, 0)
    # conservation: nothing lost, nothing duplicated
    assert len(picked) + len(s.queue) == n
    assert len({r.rid for r in picked} | {r.rid for r in s.queue}) == n
    cap = min(free, n) if policy != "gemv_aware" else min(free, n, 4)
    assert len(picked) == cap
    if policy == "fcfs":
        assert [r.rid for r in picked] == sorted(r.rid for r in picked)
    else:  # shortest-first: no picked prompt longer than any left queued
        if picked and s.queue:
            assert max(len(r.prompt) for r in picked) <= min(
                len(r.prompt) for r in s.queue)


# --------------------------------------------------------------------------
# Sampling
# --------------------------------------------------------------------------


def test_sampling_greedy_is_argmax():
    logits = np.array([0.1, 3.0, -1.0, 2.9], np.float32)
    assert sample_token(logits) == 1
    assert sample_token(logits, SamplingParams(temperature=0.0)) == 1


def test_sampling_top_k_one_is_argmax():
    rng = np.random.default_rng(0)
    logits = rng.standard_normal(64).astype(np.float32)
    p = SamplingParams(temperature=1.0, top_k=1)
    for _ in range(5):
        assert sample_token(logits, p, request_rng(p, 0)) == logits.argmax()


def test_sampling_deterministic_per_seed():
    rng_a = request_rng(SamplingParams(seed=7), 3)
    rng_b = request_rng(SamplingParams(seed=7), 3)
    logits = np.random.default_rng(0).standard_normal(128).astype(np.float32)
    p = SamplingParams(temperature=0.8, top_k=16, top_p=0.9, seed=7)
    a = [sample_token(logits, p, rng_a) for _ in range(10)]
    b = [sample_token(logits, p, rng_b) for _ in range(10)]
    assert a == b
    assert request_rng(p, 4).integers(1 << 30) != rng_b.integers(1 << 30) \
        or True  # different rid seeds draw independently (smoke)


def test_sampling_top_p_restricts_support():
    # one dominant token: top_p=0.5 keeps only it
    logits = np.array([10.0, 0.0, 0.0, 0.0], np.float32)
    p = SamplingParams(temperature=1.0, top_p=0.5)
    rng = request_rng(p, 0)
    assert all(sample_token(logits, p, rng) == 0 for _ in range(10))


# --------------------------------------------------------------------------
# Metrics
# --------------------------------------------------------------------------


def test_histogram_exact_percentiles():
    h = Histogram("t")
    for v in range(1, 101):
        h.record(float(v))
    s = h.summary()
    assert s["count"] == 100
    assert s["p50"] == pytest.approx(50.5)
    assert s["max"] == 100.0
    assert Histogram("empty").summary() == {"count": 0}


def test_metrics_document_schema():
    clk = FakeClock()
    m = ServingMetrics(clock=clk)
    m.request_submitted()
    r = Request(rid=0, prompt=np.zeros(4, np.int32))
    r.submit_time = clk()
    clk.advance(0.25)
    m.first_token(r, clk())
    m.tokens_generated(3)
    m.record_step(clk(), step_s=0.1, decode_s=0.08, decode_batch=2,
                  n_active=2, queue_depth=1)
    doc = m.to_dict()
    assert doc["schema"] == 3
    assert doc["ttft_ms"]["p50"] == pytest.approx(250.0)
    assert doc["per_token_ms"]["count"] == 1
    assert doc["counters"]["tokens_out"] == 3
    assert doc["steps"][0]["decode_batch"] == 2
    assert "gemv_path" in doc["dispatch"]
    # JSON-serializable end to end
    m.to_json()


# --------------------------------------------------------------------------
# Engine: heterogeneous continuous batching
# --------------------------------------------------------------------------


def test_engine_mixed_prompt_lengths_token_identical(cfg, params):
    """ACCEPTANCE: one batch of different-length prompts decodes greedy
    token streams identical to b=1 serial decoding per request."""
    prompts = _prompts(cfg, [5, 9, 3, 12, 7])
    eng = Engine(cfg, params, batch_slots=4, max_len=MAX_LEN)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=6))
    done = {r.rid: r for r in eng.run_until_drained()}
    assert len(done) == 5
    for i, p in enumerate(prompts):
        assert done[i].generated == _serial_greedy(cfg, params, p, 6), i


def test_engine_mid_stream_slot_refill(cfg, params):
    """Requests submitted while others are mid-decode join cleanly and
    still match serial decoding (slot reuse + defrag under churn)."""
    prompts = _prompts(cfg, [6, 11, 4, 8], seed=1)
    eng = Engine(cfg, params, batch_slots=2, max_len=MAX_LEN)
    for i in (0, 1):
        eng.submit(Request(rid=i, prompt=prompts[i], max_new_tokens=5))
    done = []
    done.extend(eng.step())
    done.extend(eng.step())
    for i in (2, 3):  # mid-stream arrivals
        eng.submit(Request(rid=i, prompt=prompts[i], max_new_tokens=5))
    done.extend(eng.run_until_drained())
    by_rid = {r.rid: r for r in done}
    assert sorted(by_rid) == [0, 1, 2, 3]
    for i, p in enumerate(prompts):
        assert by_rid[i].generated == _serial_greedy(cfg, params, p, 5), i


def test_engine_eos_early_stop_vs_max_new(cfg, params):
    prompt = _prompts(cfg, [8], seed=2)[0]
    ref = _serial_greedy(cfg, params, prompt, 8)
    eos = ref[2]
    eng = Engine(cfg, params, batch_slots=2, max_len=MAX_LEN)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=8, eos_id=eos))
    eng.submit(Request(rid=1, prompt=prompt, max_new_tokens=8))
    done = {r.rid: r for r in eng.run_until_drained()}
    # truncated at the FIRST occurrence of eos in the greedy stream
    assert done[0].generated == ref[:ref.index(eos) + 1]
    assert len(done[0].generated) < len(ref)
    assert done[1].generated == ref           # ran to max_new_tokens
    assert done[0].done and done[1].done


def test_engine_rejects_oversized_prompt_at_submit(cfg, params):
    """Starvation fix: a prompt longer than max_len used to spin in the
    queue for max_iters; now submit() rejects it with a clear error."""
    eng = Engine(cfg, params, batch_slots=2, max_len=16)
    with pytest.raises(ValueError, match="exceeds engine max_len"):
        eng.submit(Request(rid=0, prompt=np.zeros(17, np.int32)))
    assert len(eng.queue) == 0
    assert eng.run_until_drained(max_iters=3) == []  # nothing queued


def test_engine_deadline_expiry(cfg, params):
    clk = FakeClock()
    eng = Engine(cfg, params, batch_slots=1, max_len=MAX_LEN, clock=clk)
    p = _prompts(cfg, [4, 4], seed=3)
    live = Request(rid=0, prompt=p[0], max_new_tokens=3)
    late = Request(rid=1, prompt=p[1], max_new_tokens=3, deadline=5.0)
    eng.submit(live)
    eng.submit(late)
    clk.advance(10.0)  # the queued deadline passes before admission
    done = eng.run_until_drained()
    assert [r.rid for r in done] == [0]
    assert [r.rid for r in eng.expired] == [1]
    assert late.expired and not late.done
    assert eng.metrics.counters["expired"] == 1


def test_engine_backpressure(cfg, params):
    eng = Engine(cfg, params, batch_slots=1, max_len=MAX_LEN, max_queue=1)
    p = _prompts(cfg, [4, 4], seed=4)
    eng.submit(Request(rid=0, prompt=p[0], max_new_tokens=2))
    with pytest.raises(QueueFull):
        eng.submit(Request(rid=1, prompt=p[1], max_new_tokens=2))
    assert eng.metrics.counters["rejected"] == 1


def test_engine_prepack_matches_unprepacked(cfg, params):
    """Fused-weight prepack (one-time concat at init) must not change
    tokens — same fused matrix, same kernel, no per-step concat."""
    packed = lm.prepack_decode_params(params, cfg)
    assert "wqkv" in packed["layers"]["attn"]
    assert "w_gateup" not in packed["layers"].get("mlp", {}) \
        or cfg.act in ("silu", "geglu")
    prompts = _prompts(cfg, [6, 10], seed=5)
    outs = []
    for prepack in (True, False):
        eng = Engine(cfg, params, batch_slots=2, max_len=MAX_LEN,
                     prepack_weights=prepack)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=5))
        outs.append({r.rid: r.generated for r in eng.run_until_drained()})
    assert outs[0] == outs[1]


def test_engine_metrics_and_serving_telemetry(cfg, params):
    eng = Engine(cfg, params, batch_slots=2, max_len=MAX_LEN)
    for i, p in enumerate(_prompts(cfg, [5, 9, 7], seed=6)):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=4))
    eng.run_until_drained()
    doc = eng.metrics.to_dict()
    assert doc["counters"]["finished"] == 3
    assert doc["counters"]["tokens_out"] == 12
    assert doc["ttft_ms"]["count"] == 3
    assert doc["per_token_ms"]["count"] >= 3
    assert doc["steps"], "per-step records missing"
    # the dispatcher's counters are reported for the whole run, not
    # copied into each step record
    assert "gemv_path" in doc["dispatch"]
    assert "dispatch" not in doc["steps"][-1]


def test_engine_sampling_seeded_and_greedy_compatible(cfg, params):
    prompt = _prompts(cfg, [6], seed=7)[0]
    ref = _serial_greedy(cfg, params, prompt, 5)
    outs = []
    for trial in range(2):
        eng = Engine(cfg, params, batch_slots=2, max_len=MAX_LEN)
        eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=5,
                           sampling=SamplingParams(temperature=0.9,
                                                   top_k=8, seed=11)))
        eng.submit(Request(rid=1, prompt=prompt, max_new_tokens=5,
                           sampling=SamplingParams()))  # temp 0 == greedy
        done = {r.rid: r for r in eng.run_until_drained()}
        assert done[1].generated == ref  # greedy-compatible
        outs.append(done[0].generated)
        assert all(0 <= t < cfg.vocab for t in outs[-1])
    assert outs[0] == outs[1]  # per-request rng: reproducible across runs


# --------------------------------------------------------------------------
# Batch shaping changes the GEMV-vs-matmul dispatch mix (acceptance)
# --------------------------------------------------------------------------


def _run_policy_mix(cfg, params, policy):
    dispatch.clear_plan_cache()
    eng = Engine(cfg, params, batch_slots=4, max_len=MAX_LEN,
                 gemv_batch_threshold=2, scheduler=policy)
    for i, p in enumerate(_prompts(cfg, [4, 6, 5, 7, 4, 6], seed=8)):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=4))
    done = eng.run_until_drained()
    assert len(done) == 6
    return eng.metrics.dispatch_delta()


def test_gemv_aware_holds_gate_at_non_pow2_threshold(cfg, params):
    """Power-of-two bucket rounding must not push the decode batch past a
    non-power-of-two gemv_batch_threshold (the bucket clamps to it)."""
    dispatch.clear_plan_cache()
    eng = Engine(cfg, params, batch_slots=4, max_len=MAX_LEN,
                 gemv_batch_threshold=3, scheduler="gemv_aware")
    for i, p in enumerate(_prompts(cfg, [4, 5, 6, 4, 5], seed=10)):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=3))
    done = eng.run_until_drained()
    assert len(done) == 5
    mix = eng.metrics.dispatch_delta()
    assert mix["matmul_fallback"] == 0  # 3 actives decode at b=3, not b=4
    assert mix["gemv_path"] > 0


def test_scheduler_policy_changes_dispatch_mix(cfg, params):
    """ACCEPTANCE: gemv_aware batch shaping keeps every decode dispatch on
    the GEMV path; fcfs crosses the batch gate into the matmul fallback."""
    fcfs = _run_policy_mix(cfg, params, "fcfs")
    aware = _run_policy_mix(cfg, params, "gemv_aware")
    assert fcfs["matmul_fallback"] > 0
    assert aware["matmul_fallback"] == 0
    assert aware["gemv_path"] > 0
    assert fcfs["kernel_picks"] != aware["kernel_picks"] or \
        fcfs["program_modes"] != aware["program_modes"] or \
        fcfs["matmul_fallback"] != aware["matmul_fallback"]


# --------------------------------------------------------------------------
# Tokenizer-aware stop sets (eos_ids over the single-eos_id shim)
# --------------------------------------------------------------------------


def test_engine_eos_ids_stop_set(cfg, params):
    """A multi-token stop SET truncates at the first member hit, exactly
    like the single-id shim would for that token."""
    prompt = _prompts(cfg, [7], seed=11)[0]
    ref = _serial_greedy(cfg, params, prompt, 8)
    stop = {ref[0], cfg.vocab + 5}  # one live stop token + one never-hit
    eng = Engine(cfg, params, batch_slots=2, max_len=MAX_LEN)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=8,
                       eos_ids=stop))
    eng.submit(Request(rid=1, prompt=prompt, max_new_tokens=8,
                       eos_id=ref[0]))  # shim: same stop, old spelling
    done = {r.rid: r for r in eng.run_until_drained()}
    first = min(i for i, t in enumerate(ref) if t in stop)
    assert done[0].generated == ref[:first + 1]
    assert done[0].generated == done[1].generated
    # empty set = never stop on a token (overrides a set eos_id)
    eng = Engine(cfg, params, batch_slots=1, max_len=MAX_LEN)
    eng.submit(Request(rid=2, prompt=prompt, max_new_tokens=6,
                       eos_id=ref[0], eos_ids=set()))
    done = {r.rid: r for r in eng.run_until_drained()}
    assert done[2].generated == ref[:6]


def test_request_stop_set_shim():
    r = Request(rid=0, prompt=np.zeros(2, np.int32))
    assert r.stop_set() == frozenset()          # eos_id -1: never
    r.eos_id = 7
    assert r.stop_set() == frozenset({7})
    r.eos_ids = {1, 2}
    assert r.stop_set() == frozenset({1, 2})    # set overrides the shim


# --------------------------------------------------------------------------
# Preemption / slot eviction (deadline-imminent queued requests)
# --------------------------------------------------------------------------


def test_engine_preempts_youngest_for_imminent_deadline(cfg, params):
    """With every slot busy and a queued deadline about to pass, the
    gemv_aware scheduler (preempt_margin set) evicts the YOUNGEST running
    slot; the evicted request re-prefills prompt+generated on readmission
    and its final greedy stream is unchanged."""
    clk = FakeClock()
    eng = Engine(cfg, params, batch_slots=2, max_len=MAX_LEN, clock=clk,
                 scheduler=SchedulerConfig(policy="gemv_aware",
                                           gemv_batch_threshold=4,
                                           preempt_margin=5.0))
    # gemv_aware admits shortest-prompt-first, so the SHORTER prompt is
    # the older admission; the longer one is the youngest (the victim)
    prompts = _prompts(cfg, [5, 6, 4], seed=12)
    old = Request(rid=0, prompt=prompts[0], max_new_tokens=10)
    young = Request(rid=1, prompt=prompts[1], max_new_tokens=10)
    eng.submit(old)
    eng.submit(young)
    eng.step()
    eng.step()  # both mid-decode; slots full
    assert young.admit_seq > old.admit_seq
    urgent = Request(rid=2, prompt=prompts[2], max_new_tokens=3,
                     deadline=clk() + 3.0)  # imminent: margin 5 > 3
    eng.submit(urgent)
    done = {r.rid: r for r in eng.run_until_drained()}
    assert eng.metrics.counters["evicted"] == 1
    assert young.evictions == 1 and old.evictions == 0  # youngest evicted
    assert urgent.done and not urgent.expired
    for i, p in enumerate(prompts):
        n = done[i].max_new_tokens
        assert done[i].generated == _serial_greedy(cfg, params, p, n), i


def test_no_preemption_without_margin(cfg, params):
    """Default behavior unchanged: running requests always finish."""
    clk = FakeClock()
    eng = Engine(cfg, params, batch_slots=1, max_len=MAX_LEN, clock=clk,
                 scheduler=SchedulerConfig(policy="gemv_aware",
                                           gemv_batch_threshold=4))
    p = _prompts(cfg, [4, 4], seed=13)
    eng.submit(Request(rid=0, prompt=p[0], max_new_tokens=6))
    eng.step()
    late = Request(rid=1, prompt=p[1], max_new_tokens=2, deadline=5.0)
    eng.submit(late)
    clk.advance(10.0)  # deadline passes while rid 0 still holds the slot
    eng.run_until_drained()
    assert eng.metrics.counters["evicted"] == 0
    assert late.expired  # it expired in the queue instead


def test_scheduler_never_expires_started_requests():
    """An evicted request waiting for readmission (it already streamed
    tokens) must not be expired out of the queue mid-stream."""
    s = Scheduler(SchedulerConfig())
    fresh = _req(0, 4)
    fresh.deadline = 5.0
    evicted = _req(1, 4)
    evicted.deadline = 5.0
    evicted.generated = [42]  # already produced output before eviction
    s.submit(fresh)
    s.submit(evicted)
    assert [r.rid for r in s.expire(now=10.0)] == [0]
    assert [r.rid for r in s.queue] == [1]  # still admissible


def test_sjf_ordering_unchanged_by_preempt_margin():
    """preempt_margin is a gemv_aware knob: sjf keeps pure shortest-first
    ordering even when deadlines are in the imminence window."""
    s = Scheduler(SchedulerConfig(policy="sjf", preempt_margin=100.0))
    short = _req(0, 2)
    urgent = _req(1, 9)
    urgent.deadline = 5.0
    s.submit(short)
    s.submit(urgent)
    assert not s.wants_preemption(now=4.0)          # sjf never preempts
    assert [r.rid for r in s.select(1, 0, now=4.0)] == [0]


def test_engine_preempts_prefilling_slot(cfg, params):
    """A slot mid-chunked-prefill is the cheapest victim (zero decode work
    done): preemption must reach it, and the victim re-prefills cleanly."""
    clk = FakeClock()
    eng = Engine(cfg, params, batch_slots=1, max_len=MAX_LEN, clock=clk,
                 prefill_chunk=4,
                 scheduler=SchedulerConfig(policy="gemv_aware",
                                           gemv_batch_threshold=4,
                                           preempt_margin=5.0))
    prompts = _prompts(cfg, [20, 4], seed=17)
    long_req = Request(rid=0, prompt=prompts[0], max_new_tokens=3)
    eng.submit(long_req)
    eng.step()  # first chunk spliced; the only slot is prefilling
    assert eng._prefilling
    urgent = Request(rid=1, prompt=prompts[1], max_new_tokens=2,
                     deadline=clk() + 3.0)
    eng.submit(urgent)
    done = {r.rid: r for r in eng.run_until_drained()}
    assert eng.metrics.counters["evicted"] == 1
    assert long_req.evictions == 1
    assert urgent.done and not urgent.expired
    for i, p in enumerate(prompts):
        n = done[i].max_new_tokens
        assert done[i].generated == _serial_greedy(cfg, params, p, n), i


def test_scheduler_imminent_first_ordering():
    s = Scheduler(SchedulerConfig(policy="gemv_aware",
                                  gemv_batch_threshold=8,
                                  preempt_margin=2.0))
    short = _req(0, 2)
    urgent = _req(1, 9)
    urgent.deadline = 5.0
    s.submit(short)
    s.submit(urgent)
    assert not s.wants_preemption(now=0.0)   # 0 + 2 < 5: not yet imminent
    assert s.wants_preemption(now=4.0)       # 4 + 2 >= 5: in range
    picked = s.select(1, 0, now=4.0)
    assert [r.rid for r in picked] == [1]    # imminent beats shorter prompt


# --------------------------------------------------------------------------
# Chunked prefill (one bounded splice per step; decode keeps running)
# --------------------------------------------------------------------------


def test_engine_chunked_prefill_token_identity(cfg, params):
    """Prompts longer than prefill_chunk splice chunk-by-chunk across steps
    and still decode token-identically to the unchunked engine."""
    prompts = _prompts(cfg, [30, 5, 25, 3], seed=14)
    outs = []
    for chunk in (None, 8):
        eng = Engine(cfg, params, batch_slots=4, max_len=MAX_LEN,
                     prefill_chunk=chunk)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=5))
        outs.append({r.rid: r.generated for r in eng.run_until_drained()})
    assert outs[0] == outs[1]
    for i, p in enumerate(prompts):
        assert outs[1][i] == _serial_greedy(cfg, params, p, 5), i


def test_engine_chunked_prefill_does_not_stall_decode(cfg, params):
    """While a long prompt prefills chunk-by-chunk, already-active slots
    keep decoding — the long prefill no longer stalls the batch."""
    prompts = _prompts(cfg, [4, 32], seed=15)
    eng = Engine(cfg, params, batch_slots=2, max_len=MAX_LEN,
                 prefill_chunk=4)
    r0 = Request(rid=0, prompt=prompts[0], max_new_tokens=12)
    eng.submit(r0)
    eng.step()  # rid 0 active
    eng.submit(Request(rid=1, prompt=prompts[1], max_new_tokens=2))
    eng.step()  # rid 1 admitted: first chunk spliced, still prefilling
    assert eng._prefilling, "long prompt should prefill across steps"
    before = len(r0.generated)
    finished = []
    while eng._prefilling:
        finished.extend(eng.step())
    assert len(r0.generated) > before, \
        "decode made no progress while the long prompt was prefilling"
    assert eng.metrics.counters["prefill_chunks"] >= 32 // 4
    finished.extend(eng.run_until_drained())
    done = {r.rid: r for r in finished}
    assert done[1].generated == _serial_greedy(cfg, params, prompts[1], 2)
    assert done[0].generated == _serial_greedy(cfg, params, prompts[0], 12)


def test_engine_chunked_prefill_near_max_len(cfg, params):
    """Boundary regression: pow2 pad rounding on the LAST chunk must not
    write past max_len — dynamic_update_slice would clamp the start and
    silently overwrite valid KV from earlier chunks.  Token argmax can be
    insensitive to the corruption on reduced models, so the spliced KV is
    compared directly against the unchunked engine's."""
    max_len = 24
    prompt = _prompts(cfg, [23], seed=18)[0]
    caches, outs = [], []
    for chunk in (None, 9):  # chunked: last chunk consumed=18, c=5 —
        # a naive pow2 pad of 8 would cross max_len - consumed = 6
        eng = Engine(cfg, params, batch_slots=2, max_len=max_len,
                     prefill_chunk=chunk)
        eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=1))
        outs.append({r.rid: r.generated
                     for r in eng.run_until_drained()})
        caches.append(np.asarray(eng.kv.cache["k"])[:, 0, :len(prompt)])
    assert outs[0] == outs[1]
    np.testing.assert_array_equal(caches[0], caches[1])


@pytest.mark.slow
def test_engine_chunked_prefill_rwkv():
    """Chunked prefill through the recurrence (exact chunk sizes, no pads)."""
    cfg = ARCHS["rwkv6-3b"].reduced()
    params = lm.init_lm(KEY, cfg)
    prompts = _prompts(cfg, [19, 4], seed=16)
    eng = Engine(cfg, params, batch_slots=2, max_len=MAX_LEN,
                 prefill_chunk=6)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=3))
    done = {r.rid: r for r in eng.run_until_drained()}
    for i, p in enumerate(prompts):
        assert done[i].generated == _serial_greedy(cfg, params, p, 3), i


# --------------------------------------------------------------------------
# SSM family: per-request prefill path (no pads through the recurrence)
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_engine_mixed_lengths_rwkv():
    cfg = ARCHS["rwkv6-3b"].reduced()
    params = lm.init_lm(KEY, cfg)
    prompts = _prompts(cfg, [5, 9, 3], seed=9)
    eng = Engine(cfg, params, batch_slots=2, max_len=MAX_LEN)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=4))
    done = {r.rid: r for r in eng.run_until_drained()}
    assert len(done) == 3
    for i, p in enumerate(prompts):
        assert done[i].generated == _serial_greedy(cfg, params, p, 4), i


# --------------------------------------------------------------------------
# Engine: the last-token feed of the decode step
# --------------------------------------------------------------------------


def _record_feed(eng):
    """Wrap the engine's jitted decode step and its slot compaction.

    Each decode call records the token input it was given (the object
    itself and a copy), whether that input shares memory with the engine's
    host buffer, and each active slot's ``(rid, last generated token)``.
    Each compaction records ``(rid, dst, decode calls so far)`` for every
    active request it moved."""
    calls, moved = [], []
    jit_decode, compact = eng._jit_decode, eng.kv.compact

    def decode(params, last_tok, cache, extra):
        calls.append((last_tok, np.array(last_tok),
                      np.shares_memory(last_tok, eng.last_tok),
                      {s: (r.rid, r.generated[-1])
                       for s, r in eng.active.items()}))
        return jit_decode(params, last_tok, cache, extra)

    def compact_and_record():
        moves = compact()
        moved.extend((eng.active[src].rid, dst, len(calls))
                     for src, dst in moves.items() if src in eng.active)
        return moves

    eng._jit_decode = decode
    eng.kv.compact = compact_and_record
    return calls, moved


def test_engine_decode_feed_carries_last_tokens(cfg, params):
    """Each decode step's token input holds every active row's last
    generated token, also for a request compaction just moved; the input
    is a copy of the engine's host buffer (later writes to the buffer do
    not reach it); the greedy streams match b=1 serial decoding; and
    ``decode.sample`` records one host write per sampled row."""
    from repro.observability.trace import Tracer, uninstall_tracer

    lengths, max_new = [5, 9, 3, 12, 7, 4], [2, 9, 3, 8, 4, 6]
    prompts = _prompts(cfg, lengths, seed=31)
    tr = Tracer()
    eng = Engine(cfg, params, batch_slots=4, max_len=MAX_LEN, tracer=tr)
    try:
        calls, moved = _record_feed(eng)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=max_new[i]))
        done = {r.rid: r for r in eng.run_until_drained()}
    finally:
        uninstall_tracer(tr)
    assert sorted(done) == list(range(len(prompts)))
    for i, p in enumerate(prompts):
        assert done[i].generated == _serial_greedy(cfg, params, p,
                                                   max_new[i]), i
    assert calls
    for fed, at_call, shared, rows in calls:
        assert not shared, "the decode input is a view of the buffer"
        # the buffer was written after the call; the input kept its values
        np.testing.assert_array_equal(np.asarray(fed), at_call)
        assert rows and all(at_call[s, 0] == tok
                            for s, (_, tok) in rows.items()), rows
    # a request moved by compaction was fed from its new row afterwards
    assert moved, "the run made no compaction with moves"
    assert any(calls[k][3].get(dst, (None,))[0] == rid
               for rid, dst, n in moved for k in range(n, len(calls)))
    samples = [s for s in tr.spans if s.name == "decode.sample"]
    assert len(samples) == len(calls)
    assert all(s.attrs["host_writes"] == s.attrs["rows"] == len(rows)
               for s, (_, _, _, rows) in zip(samples, calls))


def test_engine_decode_compiles_once_per_bucket(cfg, params):
    """One kind of token input in every step: with ``jax.log_compiles``,
    a second pass of the same traffic compiles and traces no decode step,
    and each bucket's decode step compiled once."""
    import logging
    import re

    class Lines(logging.Handler):
        def __init__(self):
            super().__init__(logging.DEBUG)
            self.lines = []

        def emit(self, record):
            self.lines.append(record.getMessage())

    lengths, max_new = [5, 9, 3, 12, 7], [2, 7, 3, 5, 4]
    eng = Engine(cfg, params, batch_slots=4, max_len=MAX_LEN)
    log, h = logging.getLogger("jax"), Lines()
    log.addHandler(h)
    passes = []
    try:
        with jax.log_compiles():
            for rid0 in (0, 100):
                n = len(h.lines)
                for i, p in enumerate(_prompts(cfg, lengths, seed=32)):
                    eng.submit(Request(rid=rid0 + i, prompt=p,
                                       max_new_tokens=max_new[i]))
                eng.run_until_drained()
                passes.append(h.lines[n:])
    finally:
        log.removeHandler(h)
    compiled = [re.search(r"int32\[(\d+),1\]", m).group(1)
                for m in passes[0]
                if m.startswith("Compiling jit(_decode_fn)")]
    assert compiled and len(compiled) == len(set(compiled)), compiled
    assert eng._jit_decode._cache_size() == len(compiled)
    assert not [m for m in passes[1] if "_decode_fn" in m], passes[1]


# --------------------------------------------------------------------------
# Deprecation shims (PR-3 pattern: warn once per call site)
# --------------------------------------------------------------------------


def test_splice_cache_deprecated_warns_once_per_site(cfg):
    cache = lm.init_cache(cfg, 2, 8)
    single = lm.init_cache(cfg, 1, 8)

    def call():  # ONE call site, exercised repeatedly
        return engine_mod._splice_cache(cache, single, 0)

    with pytest.warns(DeprecationWarning, match="_splice_cache"):
        out = call()
    assert out["k"].shape == cache["k"].shape
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # memoized site must stay silent
        for _ in range(2):
            call()


def test_lockstep_cache_view_deprecated(cfg, params):
    eng = Engine(cfg, params, batch_slots=2, max_len=16)
    with pytest.warns(DeprecationWarning, match="lockstep_cache"):
        view = eng.lockstep_cache
    assert view["pos"].ndim == 0  # the old scalar layout
    assert eng.kv.cache["pos"].ndim == 1  # the real cache is per-slot


# --------------------------------------------------------------------------
# serve-bench document
# --------------------------------------------------------------------------


def test_serve_bench_document(tmp_path, cfg, params):
    from repro.serving.bench import TraceConfig, run_serve_trace

    out = str(tmp_path / "serve.json")
    doc = run_serve_trace(
        "olmo-1b", policies=("fcfs", "gemv_aware"), smoke=True,
        trace_config=TraceConfig(n_requests=6, arrival_rate=6.0,
                                 prompt_len_range=(2, 8),
                                 max_new_range=(2, 3)),
        out=out,
    )
    import json

    assert json.load(open(out)) == doc
    assert doc["schema"] == 4
    assert doc["mesh"] is None  # single-host run: no mesh record
    runs = {r["policy"]: r for r in doc["runs"]}
    assert runs["fcfs"]["completed"] == 6
    for r in doc["runs"]:
        assert r["ttft_ms"]["count"] == 6
        assert r["per_token_ms"]["count"] > 0
        assert "gemv_path" in r["dispatch"]
    assert runs["gemv_aware"]["dispatch"]["matmul_fallback"] == 0
    assert runs["fcfs"]["dispatch"]["matmul_fallback"] > 0
