"""Open-loop traffic from a mix file (``traffic/<mix>.json``) and a seed.

The schedule is periodic on the wall clock.  One cycle lasts the measured
window and holds ``N = rate * window`` requests.  The inter-arrival gaps,
the prompt lengths and the output lengths of a cycle are each the ``N``
stratified quantiles ``(i + 0.5) / N`` of their distribution, rescaled so
the gaps sum to the cycle (the offered rate is exactly ``rate``), and put
in an order drawn once for the mix (``ORDER_SEED``).  The seed rotates
that order, where the window starts in it, and draws the token ids.  The
window is one whole cycle and the ramp before it replays the end of the
cycle before, so every seed's window holds the same requests, bursts and
all, and what the seed changes is the phase and the tokens: seeds that
changed the work would spread the metrics far wider than the system
does.

Arrival processes (``arrivals.process``):

* ``poisson``: exponential gaps;
* ``gamma``: Gamma gaps with coefficient of variation ``arrivals.cv``
  (shape ``1 / cv**2``; BurstGPT, arXiv:2401.17644, fits shapes below 1).

Lengths (``prompt`` / ``output``): ``lognormal`` with ``median`` and
``sigma`` (of the log), rounded and clipped to ``[min, max]``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np
from scipy import special, stats


@dataclass
class Due:
    """One request of the schedule."""

    index: int            # position in the whole schedule, ramp first
    t: float              # due time, seconds after the ramp starts
    prompt: np.ndarray    # int32 token ids
    max_new_tokens: int
    in_window: bool


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def gap_quantiles(arrivals: dict, n: int, cycle_s: float) -> np.ndarray:
    """``n`` inter-arrival gaps summing to ``cycle_s``."""
    q = _strata(n)
    process = arrivals["process"]
    if process == "poisson":
        g = stats.expon.ppf(q)
    elif process == "gamma":
        shape = 1.0 / float(arrivals["cv"]) ** 2
        g = stats.gamma.ppf(q, shape)
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    return g * (cycle_s / g.sum())


def length_quantiles(dist: dict, n: int) -> np.ndarray:
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = special.ndtri(_strata(n))
    x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


ORDER_SEED = 20240117


def requests_per_cycle(rate: float, cycle_s: float) -> int:
    return max(1, int(round(rate * cycle_s)))


def schedule(mix: dict, rate: float, seed: int, window_s: float,
             vocab: int) -> list[Due]:
    """The ramp's and the window's requests, ordered by due time.  The
    window is ``[ramp_s, ramp_s + window_s)``."""
    n = requests_per_cycle(rate, window_s)
    order = np.random.default_rng(ORDER_SEED)
    shift = int(np.random.default_rng(seed).integers(n))

    def arrange(x):
        return np.roll(x[order.permutation(n)], shift)

    gaps = arrange(gap_quantiles(mix["arrivals"], n, window_s))
    prompts = arrange(length_quantiles(mix["prompt"], n))
    outputs = arrange(length_quantiles(mix["output"], n))
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])  # in [0, cycle)
    ramp_s = float(mix["ramp_s"])
    cycles_before = math.ceil(ramp_s / window_s)
    out: list[Due] = []
    for c in range(-cycles_before, 1):
        for k in range(n):
            t = ramp_s + c * window_s + offsets[k]
            if t < 0.0:
                continue
            out.append((t, k, c == 0))
    out.sort()
    tok_rng = np.random.default_rng([seed, 1])
    return [Due(index=i, t=float(t),
                prompt=tok_rng.integers(0, vocab, int(prompts[k]),
                                        dtype=np.int32),
                max_new_tokens=int(outputs[k]), in_window=w)
            for i, (t, k, w) in enumerate(out)]


def length_grid(dist: dict) -> list[int]:
    """The powers of two a prefill pads prompts of this distribution to
    (the engine pads a wave to the next power of two of its longest
    prompt)."""
    lo, hi = int(dist["min"]), int(dist["max"])
    p = 1 << max(lo - 1, 0).bit_length()
    grid = []
    while True:
        grid.append(p)
        if p >= hi:
            return grid
        p *= 2
