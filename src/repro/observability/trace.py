"""Flight recorder: bounded ring buffers of typed spans/events (DESIGN.md §13).

One :class:`Tracer` per observed engine run.  Three design rules:

1. **Bounded.**  Every buffer is a ``deque(maxlen=...)`` ring; a run that
   outlives its budget drops the *oldest* entries and counts the drops
   (``tracer.dropped``) — tracing never grows without bound and never
   throws at the recording site.
2. **Monotonic.**  All timestamps are µs on the tracer's own clock
   (``time.monotonic`` by default; injectable for tests), zeroed at
   construction, so a trace is self-consistent even across engines with
   different wall-clock bases.
3. **Zero-cost when absent.**  Hot paths guard on
   ``current_tracer() is None`` (or the engine's ``self.tracer is None``)
   — a slot read and an ``is`` check; with no tracer no span, profiler
   annotation or attribute dict is built.  The dispatch hook additionally
   only runs on plan-cache *misses*, so the cached decode hot path never
   sees it at all.

Span tree and the profiler's clock
----------------------------------
:meth:`Tracer.span` (a ``with`` block) is also a profiler annotation:
it enters ``jax.profiler.TraceAnnotation("<cat>.<name>", **attrs)``
around its body (scalar attrs only; attrs the body adds are attached on
exit), so a profiled run shows the engine's spans — ``engine.step``,
``engine.decode_step``, ``engine.kv.merge``, ... — on the host thread
that ran them, stamped on the profiler's own clock, with the device
programs they launched beside them.  The annotation costs next to
nothing while no profiler session is active.  The ring-buffer record is
unchanged: the span still lands in ``tracer.spans`` on the tracer's
clock.

Each span records its enclosing span's name (``Span.parent``) from a
per-thread stack of open ``with`` blocks; :meth:`Tracer.add_span`
(retroactive spans: ``prefill_chunk``, ``async_prefill``,
``prefix_match``) records the innermost open block as its parent but
emits no annotation.  One engine step nests so::

    step                       (decode_rows, admitted)
    ├ step.admit               expire, preempt, select, phase bookkeeping
    ├ prefill_wave             (requests, tokens)
    │ ├ prefill.launch         the jitted prefill call returning
    │ ├ kv.splice              splice + prefix inserts (rows)
    │ ├ prefill.fetch          the first-token logits to the host
    │ └ prefill.sample         sample + activate (rows)
    ├ kv.compact               each defrag pass (moves)
    └ decode_step              (bucket, active, defrag_moves, finished,
      │                         rows, live_positions)
      ├ kv.slice               bucket slice, snapshots, inputs (bucket)
      ├ decode.launch          the jitted decode call returning
      ├ kv.merge               merge + snapshot restores
      ├ decode.fetch           logits to the host: waits on the device
      └ decode.sample          sample, last-token writes, finish (rows,
                               finished)

Request-phase spans cross steps, so they have no parent and stay on the
tracer's clock alone.

Request phase machine
---------------------
A request's lifetime is partitioned into phases — ``queued`` → ``prefill``
→ ``decode`` (→ ``preempted`` → ``prefill`` → ...) — by
:meth:`Tracer.request_submit` / :meth:`Tracer.request_phase` /
:meth:`Tracer.request_finish`.  Each transition closes the previous phase
and opens the next **at the same timestamp**, so per-phase durations sum
to the request's end-to-end latency *exactly*, by construction (the
acceptance bound in ISSUE 9 is 1%; the machine gives 0 up to float
rounding).

Dispatch attribution
--------------------
``kernels/dispatch.py`` records one :class:`DispatchRecord` per fresh
decision: ``(backend, kernel/mode, shape key, predicted_us from the
resolved CostModel, cost_model_source)`` — and, when ``Tracer.timing`` is
set (``serve_bench --trace-timing``), ``block_until_ready`` trial times of
the decision's compiled executable.  :meth:`Tracer.drift_report` reduces
those into ``pred_over_measured`` percentiles per kernel (reusing the
calibration subsystem's median/MAD ``robust_us``) and flags kernels whose
median ratio leaves ``[STALE_LO, STALE_HI]`` — the "calibration has gone
stale" signal.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax

# Summary-document / trace-buffer layout version (export.py stamps it).
SCHEMA_VERSION = 1

# The request phase taxonomy (DESIGN.md §13 table). "preempted" re-enters
# "prefill" on readmission; every other transition is forward-only.
PHASES = ("queued", "prefill", "decode", "preempted")

# A kernel whose median predicted/measured ratio leaves this band is
# flagged stale: the cost model is off by >2x in either direction, which
# is the regime where selection starts picking wrong kernels (the
# calibration CI leg holds fitted models to MAPE <= 0.25, far inside it).
STALE_LO = 0.5
STALE_HI = 2.0


@dataclass(frozen=True)
class Span:
    """One closed interval on a track."""

    name: str
    cat: str          # "phase" | "request" | "engine"
    track: str        # "engine" | "requests" | "slot<N>"
    start_us: float
    dur_us: float
    rid: int | None = None
    attrs: dict = field(default_factory=dict)
    parent: str | None = None   # name of the enclosing span, same thread


@dataclass(frozen=True)
class Event:
    """One instant (point-in-time) record."""

    name: str
    cat: str
    ts_us: float
    attrs: dict = field(default_factory=dict)


@dataclass(frozen=True)
class CounterSample:
    """One gauge sample (queue depth, slot occupancy, decode batch)."""

    name: str
    ts_us: float
    value: float


@dataclass(frozen=True)
class DispatchRecord:
    """One fresh dispatch decision, priced and (optionally) timed."""

    backend: str
    kind: str                  # "single" | "fused" | "grouped" | "ragged"
    kernel: str                # kernel name (single) or program mode
    shape: str                 # GemvKey/ProgramKey.table_key()
    predicted_us: float
    source: str                # "seed" | "calibrated"
    trials_us: tuple[float, ...] | None = None
    attrs: dict = field(default_factory=dict)

    @property
    def measured_us(self) -> float | None:
        """Robust (median/MAD-rejected) trial time; None when untimed."""
        if not self.trials_us:
            return None
        from repro.calibration.measure import robust_us

        return robust_us(self.trials_us)


def _scalars(attrs: dict) -> dict:
    """The attrs a profiler annotation can carry."""
    return {k: v for k, v in attrs.items()
            if isinstance(v, (bool, int, float, str))}


def _percentile(sorted_vals: list[float], p: float) -> float:
    """Linear-interpolated percentile of an already-sorted list."""
    if not sorted_vals:
        return float("nan")
    if len(sorted_vals) == 1:
        return sorted_vals[0]
    idx = (len(sorted_vals) - 1) * p / 100.0
    lo = int(idx)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = idx - lo
    return sorted_vals[lo] * (1.0 - frac) + sorted_vals[hi] * frac


class Tracer:
    """Bounded flight recorder; one instance per observed run.

    Thread-safe: the engine may be stepped from a thread pool and the
    dispatch hook fires from whatever thread planned the shape.  All
    mutation sits under one lock — recording is O(1) appends, so the
    critical sections are tens of nanoseconds.
    """

    def __init__(self, *, clock=time.monotonic, timing: bool = False,
                 max_spans: int = 65536, max_events: int = 16384,
                 max_counters: int = 65536, max_dispatches: int = 8192,
                 max_requests: int = 65536):
        self.clock = clock
        self.t0 = clock()
        # --trace-timing: the dispatch hook times each fresh decision's
        # compiled executable (block_until_ready) in addition to pricing it.
        self.timing = bool(timing)
        self.spans: deque[Span] = deque(maxlen=max_spans)
        self.events: deque[Event] = deque(maxlen=max_events)
        self.counters: deque[CounterSample] = deque(maxlen=max_counters)
        self.dispatches: deque[DispatchRecord] = deque(maxlen=max_dispatches)
        self.requests: deque[dict] = deque(maxlen=max_requests)
        self.dropped = {"spans": 0, "events": 0, "counters": 0,
                        "dispatches": 0, "requests": 0}
        self._open: dict[int, dict] = {}   # rid -> in-flight request state
        self._lock = threading.Lock()
        self._local = threading.local()    # .stack: open span names

    # -- clock ---------------------------------------------------------------

    def now_us(self) -> float:
        return (self.clock() - self.t0) * 1e6

    # -- ring-buffer append (caller holds self._lock) ------------------------

    def _append(self, buf: deque, kind: str, item) -> None:
        if buf.maxlen is not None and len(buf) == buf.maxlen:
            self.dropped[kind] += 1
        buf.append(item)

    # -- generic recording ---------------------------------------------------

    def event(self, name: str, *, cat: str = "engine",
              ts_us: float | None = None, **attrs) -> None:
        t = self.now_us() if ts_us is None else ts_us
        with self._lock:
            self._append(self.events, "events", Event(name, cat, t, attrs))

    def counter(self, name: str, value: float,
                ts_us: float | None = None) -> None:
        t = self.now_us() if ts_us is None else ts_us
        with self._lock:
            self._append(self.counters, "counters",
                         CounterSample(name, t, float(value)))

    def _stack(self) -> list:
        """This thread's open ``span`` blocks, innermost last."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def add_span(self, name: str, start_us: float, end_us: float, *,
                 cat: str = "engine", track: str = "engine",
                 rid: int | None = None, **attrs) -> None:
        stack = self._stack()
        parent = stack[-1] if stack else None
        with self._lock:
            self._append(self.spans, "spans",
                         Span(name, cat, track, start_us,
                              end_us - start_us, rid, attrs, parent))

    @contextmanager
    def span(self, name: str, *, cat: str = "engine",
             track: str = "engine", rid: int | None = None, **attrs):
        """Measure a with-block as one span.  Yields the (mutable) attrs
        dict so the body can attach results (e.g. defrag move counts).

        The block is also the profiler annotation ``<cat>.<name>``
        carrying the scalar attrs (and ``rid``); those the body adds or
        changes are attached when it exits."""
        a = dict(attrs)
        meta = _scalars(a)
        if rid is not None:
            meta["rid"] = rid
        ann = jax.profiler.TraceAnnotation(f"{cat}.{name}", **meta)
        stack = self._stack()
        parent = stack[-1] if stack else None
        stack.append(name)
        ann.__enter__()
        t0 = self.now_us()
        try:
            yield a
        finally:
            t1 = self.now_us()
            late = {k: v for k, v in _scalars(a).items()
                    if k not in meta or meta[k] != v}
            if late:
                ann.set_metadata(**late)
            ann.__exit__(None, None, None)
            stack.pop()
            with self._lock:
                self._append(self.spans, "spans",
                             Span(name, cat, track, t0, t1 - t0, rid, a,
                                  parent))

    # -- request phase machine ----------------------------------------------

    def request_submit(self, rid: int, **attrs) -> None:
        """Open the request span; the request enters the ``queued`` phase."""
        t = self.now_us()
        with self._lock:
            self._open[rid] = {
                "rid": rid, "submit_us": t, "phase": "queued",
                "phase_start_us": t, "phases": {}, "slot": None,
                "preemptions": 0, "attrs": dict(attrs),
            }
            self._append(self.events, "events",
                         Event("submit", "request", t, {"rid": rid, **attrs}))

    def _close_phase(self, st: dict, t: float) -> None:
        """Close the current phase at ``t`` (caller holds the lock)."""
        dur = t - st["phase_start_us"]
        ph = st["phase"]
        st["phases"][ph] = st["phases"].get(ph, 0.0) + dur
        # prefill/decode happen on a slot; queued/preempted off-slot time
        # renders on the per-request track.
        on_slot = st["slot"] is not None and ph in ("prefill", "decode")
        track = f"slot{st['slot']}" if on_slot else "requests"
        span_attrs = {"rid": st["rid"]}
        if st["slot"] is not None:
            span_attrs["slot"] = st["slot"]
        self._append(self.spans, "spans",
                     Span(ph, "phase", track, st["phase_start_us"], dur,
                          st["rid"], span_attrs))

    def request_phase(self, rid: int, phase: str, **attrs) -> None:
        """Transition ``rid`` into ``phase``; closes the previous phase and
        opens the new one at the same instant (durations partition the
        lifetime exactly).  Unknown rids are ignored — a tracer installed
        mid-run must not throw on requests it never saw submitted."""
        t = self.now_us()
        with self._lock:
            st = self._open.get(rid)
            if st is None:
                return
            self._close_phase(st, t)
            st["phase"] = phase
            st["phase_start_us"] = t
            if phase == "preempted":
                st["preemptions"] += 1
                st["slot"] = None
            if "slot" in attrs:
                st["slot"] = attrs["slot"]
            st["attrs"].update(attrs)

    def request_annotate(self, rid: int, **attrs) -> None:
        """Attach attrs (e.g. the slot chosen after admission) to ``rid``'s
        in-flight state without a phase transition."""
        with self._lock:
            st = self._open.get(rid)
            if st is None:
                return
            if "slot" in attrs:
                st["slot"] = attrs["slot"]
            st["attrs"].update(attrs)

    def request_finish(self, rid: int, outcome: str = "finished",
                       **attrs) -> None:
        """Close ``rid``'s span tree; ``outcome`` is "finished" or
        "expired"."""
        t = self.now_us()
        with self._lock:
            st = self._open.pop(rid, None)
            if st is None:
                return
            self._close_phase(st, t)
            total = t - st["submit_us"]
            st["attrs"].update(attrs)
            self._append(self.requests, "requests", {
                "rid": rid, "outcome": outcome,
                "submit_us": st["submit_us"], "finish_us": t,
                "total_us": total, "phases": dict(st["phases"]),
                "preemptions": st["preemptions"],
                "attrs": dict(st["attrs"]),
            })
            self._append(self.spans, "spans",
                         Span(f"request {rid}", "request", "requests",
                              st["submit_us"], total, rid,
                              {"outcome": outcome,
                               "preemptions": st["preemptions"]}))

    @property
    def open_requests(self) -> tuple[int, ...]:
        with self._lock:
            return tuple(self._open)

    # -- dispatch attribution ------------------------------------------------

    def record_dispatch(self, *, backend: str, kind: str, kernel: str,
                        shape: str, predicted_us: float, source: str,
                        trials_us: tuple[float, ...] | None = None,
                        **attrs) -> None:
        with self._lock:
            self._append(self.dispatches, "dispatches",
                         DispatchRecord(backend=backend, kind=kind,
                                        kernel=kernel, shape=shape,
                                        predicted_us=float(predicted_us),
                                        source=source, trials_us=trials_us,
                                        attrs=attrs))

    def drift_report(self) -> dict:
        """Predicted-vs-measured attribution per ``backend:kernel``.

        ``pred_over_measured`` percentiles come from per-record
        ``predicted_us / robust_us(trials)`` ratios; a kernel is ``stale``
        when its median ratio leaves ``[STALE_LO, STALE_HI]``.  Records
        without trials (no ``--trace-timing``) still contribute their
        predicted price and count.
        """
        with self._lock:
            records = list(self.dispatches)
        groups: dict[str, dict] = {}
        for r in records:
            g = groups.setdefault(f"{r.backend}:{r.kernel}", {
                "n": 0, "kind": r.kind, "predicted": [], "pairs": [],
                "sources": set()})
            g["n"] += 1
            g["predicted"].append(r.predicted_us)
            g["sources"].add(r.source)
            m = r.measured_us
            if m is not None and m > 0:
                g["pairs"].append((r.predicted_us, m))
        kernels: dict[str, dict] = {}
        stale: list[str] = []
        n_timed = 0
        for name in sorted(groups):
            g = groups[name]
            entry = {
                "n": g["n"],
                "kind": g["kind"],
                "cost_model_source": sorted(g["sources"]),
                "predicted_us_p50": _percentile(sorted(g["predicted"]), 50),
            }
            if g["pairs"]:
                n_timed += len(g["pairs"])
                meas = sorted(m for _, m in g["pairs"])
                ratios = sorted(p / m for p, m in g["pairs"])
                entry["measured_us_p50"] = _percentile(meas, 50)
                entry["pred_over_measured"] = {
                    "p50": _percentile(ratios, 50),
                    "p90": _percentile(ratios, 90),
                }
                entry["stale"] = not (
                    STALE_LO <= entry["pred_over_measured"]["p50"]
                    <= STALE_HI)
                if entry["stale"]:
                    stale.append(name)
            kernels[name] = entry
        return {"n_dispatches": len(records), "n_timed": n_timed,
                "kernels": kernels, "stale_kernels": stale}


# ---------------------------------------------------------------------------
# Module install slot: the dispatch hook's zero-cost discovery point
# ---------------------------------------------------------------------------

_INSTALL_LOCK = threading.Lock()
_INSTALLED: Tracer | None = None


def install_tracer(tracer: Tracer | None) -> Tracer | None:
    """Make ``tracer`` the process-wide tracer; returns the previous one.

    ``Engine(tracer=...)`` calls this so the dispatch hook (a different
    layer, reached through jit traces) can find the recorder without any
    argument threading.
    """
    global _INSTALLED
    with _INSTALL_LOCK:
        prev, _INSTALLED = _INSTALLED, tracer
        return prev


def uninstall_tracer(tracer: Tracer | None = None) -> Tracer | None:
    """Clear the slot (only if it still holds ``tracer``, when given)."""
    global _INSTALLED
    with _INSTALL_LOCK:
        if tracer is None or _INSTALLED is tracer:
            prev, _INSTALLED = _INSTALLED, None
            return prev
        return None


def current_tracer() -> Tracer | None:
    """The installed tracer, or None.  This is the hot-path guard: a plain
    module-global read, no lock (assignment is atomic)."""
    return _INSTALLED
