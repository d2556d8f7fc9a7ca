"""The traffic generator: seeded, open loop on the wall clock, and with
the distribution parameters of its mix file."""

import math
import threading
import time

import numpy as np
import pytest
from scipy import stats

import serve
import spec
import traffic

MIXES = ("decode-heavy", "chat-bursty")


def mix(name):
    return spec.load_json(f"{spec.BENCH_DIR}/traffic/{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a = traffic.schedule(mix(name), 5.0, 2 ** 33 + 7, 20.0, 50304)
    b = traffic.schedule(mix(name), 5.0, 2 ** 33 + 7, 20.0, 50304)
    assert [d.t for d in a] == [d.t for d in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [d.max_new_tokens for d in a] == [d.max_new_tokens for d in b]


@pytest.mark.parametrize("name", MIXES)
def test_seeds_reorder_one_multiset(name):
    """Every seed sends the window the same sizes and gaps, in its own
    order."""
    def window(seed):
        ds = [d for d in traffic.schedule(mix(name), 4.0, seed, 30.0, 1000)
              if d.in_window]
        gaps = np.diff([d.t for d in ds])
        return ds, gaps

    a, ga = window(1)
    b, gb = window(2)
    assert sorted(len(d.prompt) for d in a) == sorted(len(d.prompt) for d in b)
    assert sorted(d.max_new_tokens for d in a) == sorted(
        d.max_new_tokens for d in b)
    assert [len(d.prompt) for d in a] != [len(d.prompt) for d in b]
    assert len(a) == len(b) == 120
    # the window is one whole cycle: its gaps and the wrap-around gap are
    # the same multiset for every seed
    assert np.allclose(ga.sum() + (30.0 - a[-1].t + a[0].t - 0),
                       gb.sum() + (30.0 - b[-1].t + b[0].t), atol=1e-9)


@pytest.mark.parametrize("name", MIXES)
def test_window_is_one_cycle_after_the_ramp(name):
    m = mix(name)
    ds = traffic.schedule(m, 3.0, 9, 30.0, 1000)
    win = [d for d in ds if d.in_window]
    ramp = [d for d in ds if not d.in_window]
    assert all(m["ramp_s"] <= d.t < m["ramp_s"] + 30.0 for d in win)
    assert all(0 <= d.t < m["ramp_s"] for d in ramp)
    assert [d.t for d in ds] == sorted(d.t for d in ds)
    assert len(win) == 90  # rate x window


def test_prompt_lengths_follow_the_lognormal():
    d = {"dist": "lognormal", "median": 128, "sigma": 0.8, "min": 1,
         "max": 10 ** 9}
    x = traffic.length_quantiles(d, 20000)
    assert np.median(x) == pytest.approx(128, rel=0.01)
    assert np.std(np.log(x)) == pytest.approx(0.8, rel=0.02)


def test_lengths_are_clipped():
    x = traffic.length_quantiles(mix("decode-heavy")["prompt"], 5000)
    assert x.min() == 32 and x.max() == 512


@pytest.mark.parametrize("process,cv", [("poisson", 1.0), ("gamma", 2.0)])
def test_gaps_have_the_mix_rate_and_variation(process, cv):
    arrivals = {"process": process, "cv": cv}
    g = traffic.gap_quantiles(arrivals, 20000, 2000.0)
    assert g.sum() == pytest.approx(2000.0)        # rate 10/s exactly
    assert np.std(g) / np.mean(g) == pytest.approx(cv, rel=0.05)


def test_gamma_gaps_have_shape_one_over_cv_squared():
    g = traffic.gap_quantiles({"process": "gamma", "cv": 2.0}, 4000, 1.0)
    shape, _, _ = stats.gamma.fit(g / g.mean(), floc=0)
    assert shape == pytest.approx(0.25, rel=0.15)


class SlowEngine:
    """Stands in for ``Engine``: each step takes ``step_s`` and gives every
    admitted request one token."""

    def __init__(self, step_s):
        self.step_s = step_s
        self.live = []

    def submit(self, r):
        self.live.append(r)

    def step(self):
        time.sleep(self.step_s)
        for r in self.live:
            r.generated.append(1)
            if len(r.generated) >= r.max_new_tokens:
                r.done = True
        self.live = [r for r in self.live if not r.done]


class Req:
    def __init__(self, d):
        self.prompt, self.max_new_tokens = d.prompt, d.max_new_tokens
        self.generated, self.done = [], False


@pytest.mark.parametrize("step_s", [0.001, 0.05])
def test_arrivals_follow_the_wall_clock_not_the_engine(step_s):
    """A slow engine does not slow the load: each request is submitted
    at most one step after it was due, however slow the steps are."""
    m = dict(mix("chat-bursty"), ramp_s=0.0)
    dues = traffic.schedule(m, 40.0, 3, 1.0, 100)
    t0 = time.perf_counter()
    clients = serve.drive(SlowEngine(step_s), dues, Req, origin=t0,
                          stop=t0 + 1.0)
    late = [c.submitted - c.due for c in clients]
    slack = step_s + 0.03
    assert (sum(1 for d in dues if d.t < 1.0 - slack) <= len(clients)
            <= sum(1 for d in dues if d.t < 1.0))
    assert max(late) < slack
    assert min(late) >= 0


def test_client_metrics_time_from_the_due_time():
    class R:
        prompt, generated, done = [0], [1, 1], True

    late = serve.Client(due=0.0, in_window=True, request=R(), submitted=0.5,
                        token_times=[1.0, 1.1])
    never = serve.Client(due=1.5, in_window=True, request=R(), submitted=1.5)
    m = serve.client_metrics([late, never], 0.0, 2.0)
    assert m["requests_due"] == 2
    assert m["ttft_p50_ms"] == pytest.approx(750.0)   # 1000 and 500 ms
    assert m["itl_p50_ms"] == pytest.approx(100.0)
    assert m["output_tokens_per_s"] == pytest.approx(1.0)
