"""Drive ``Engine`` with an open-loop schedule on the wall clock and
record what a client sees: when each request was due, when it was
submitted, and when each of its tokens came back.

The engine hands tokens back from ``Engine.step``; a token counts as
delivered when the step that produced it returns.  Nothing here reads the
engine's own clocks or metrics."""

from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np
from repro.serving.scheduler import QueueFull


@dataclass
class Client:
    """One request as its client saw it (seconds on ``clock``)."""

    due: float
    in_window: bool
    request: object
    submitted: float | None = None
    token_times: list = field(default_factory=list)
    error: str | None = None      # why the engine refused the request

    @property
    def done(self) -> bool:
        return bool(self.request.done)


def _annotation(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def drive(engine, dues, make_request, *, origin: float, stop: float,
          clock=time.perf_counter, annotate: bool = False,
          clients: list | None = None, steps: list | None = None
          ) -> list[Client]:
    """Submit each of ``dues`` (``traffic.Due``, ordered by due time) at
    ``origin + due.t`` and step the engine until ``stop``.  Sleeps when
    nothing is in flight.  ``annotate`` wraps the host's work in profiler
    annotations (``bench.submit``, ``bench.engine_step``,
    ``bench.wait_arrival``).  ``steps`` collects, per engine step, its
    start and end and the live positions of each row it decoded (the
    row's prompt and served tokens, the new one included).  Returns one
    ``Client`` per submitted request, appended to ``clients`` when
    given."""
    pending = deque(dues)
    clients = [] if clients is None else clients
    inflight: list[Client] = [c for c in clients if not c.done]
    while True:
        now = clock()
        if now >= stop:
            return clients
        while pending and origin + pending[0].t <= now:
            d = pending.popleft()
            c = Client(due=origin + d.t, in_window=d.in_window,
                       request=make_request(d))
            try:
                with _annotation("bench.submit", annotate):
                    engine.submit(c.request)
            except (ValueError, QueueFull) as e:
                c.error = f"{type(e).__name__}: {e}"
            c.submitted = clock()
            clients.append(c)
            if c.error is None:
                inflight.append(c)
        if not inflight:
            wake = min(origin + pending[0].t, stop) if pending else stop
            with _annotation("bench.wait_arrival", annotate):
                time.sleep(max(0.0, wake - clock()))
            continue
        t0 = clock()
        with _annotation("bench.engine_step", annotate):
            engine.step()
        t = clock()
        still, live = [], []
        for c in inflight:
            n = len(c.request.generated)
            if n > len(c.token_times):
                if n >= 2:  # the newest token came from a decode row
                    live.append(len(c.request.prompt) + n - 1)
                c.token_times.extend([t] * (n - len(c.token_times)))
            if not c.done:
                still.append(c)
        inflight = still
        if steps is not None:
            steps.append((t0, t, live))


def client_metrics(clients: list[Client], start: float, stop: float) -> dict:
    """End-to-end numbers of the window ``[start, stop)``.

    * TTFT: first token minus due time, over every request due in the
      window; one with no first token by ``stop`` enters at ``stop - due``.
    * ITL: every gap between consecutive tokens of one request whose later
      token came back in the window.
    * output tokens/s: tokens that came back in the window over its
      length.
    """
    ttft, gaps, tokens = [], [], 0
    for c in clients:
        if c.in_window:
            first = c.token_times[0] if c.token_times else None
            if first is None or first >= stop:
                ttft.append(stop - c.due)
            else:
                ttft.append(first - c.due)
        tt = c.token_times
        for i, t in enumerate(tt):
            if start <= t < stop:
                tokens += 1
                if i > 0:
                    gaps.append(t - tt[i - 1])
    window = stop - start
    out = {"window_s": window, "output_tokens": tokens,
           "output_tokens_per_s": tokens / window,
           "requests_due": len(ttft), "itl_samples": len(gaps)}
    if ttft:
        out["ttft_p50_ms"] = float(np.percentile(ttft, 50) * 1e3)
        out["ttft_p95_ms"] = float(np.percentile(ttft, 95) * 1e3)
    if gaps:
        out["itl_p50_ms"] = float(np.percentile(gaps, 50) * 1e3)
        out["itl_p95_ms"] = float(np.percentile(gaps, 95) * 1e3)
    return out


def lateness(clients: list[Client]) -> dict:
    """How late the generator submitted requests against their due
    times (seconds): it is not a metric, it shows a starved generator."""
    late = [c.submitted - c.due for c in clients if c.submitted is not None]
    if not late:
        return {"n": 0}
    return {"n": len(late), "p50_s": float(np.percentile(late, 50)),
            "p99_s": float(np.percentile(late, 99)),
            "max_s": float(max(late))}
