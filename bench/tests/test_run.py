"""The harness end to end on the CPU at a small size: the device guard,
a sound run, and runs with the timed path broken underneath, each of
which has to come out not correct."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

import run
import spec
import tiny

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def run_tiny(cell, trace=False, seed=2 ** 31 + 11):
    return run.run_cell(cell, seed, 1.5, trace, CPU, PEAKS,
                        t_start=time.perf_counter(), log=lambda s: None)


def test_no_tpu_no_result():
    """Off a TPU the harness exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", "olmo-1b.decode-heavy", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == run.EXIT_DEVICE
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_unknown_device_kind_is_an_error():
    assert spec.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.load_peaks("TPU v9 imaginary")


def test_program_config_must_match_the_file():
    c = tiny.cell()
    c.config["program"]["overrides"]["d_ff"] = 256
    with pytest.raises(spec.SpecError, match="d_ff"):
        run.program_config(c)


@pytest.mark.parametrize("mix", ["decode-heavy", "chat-bursty"])
def test_sound_run_is_correct(mix):
    r = run_tiny(tiny.cell(mix))
    assert r["correct"], r["check"]
    assert r["compiles_in_window"] == 0
    assert r["attempted"] > 10 and r["failed"] == 0
    assert r["check"]["tokens"] > 10
    names = {x["name"] for x in tiny.cell(mix).end_to_end}
    assert set(r["metrics"]) == names


def _state_unchanged(orig):
    def decode(self, params, last_tok, cache, extra):
        logits, _ = orig(self, params, last_tok, cache, extra)
        return logits, cache
    return decode


def _half_batch(orig):
    def decode(self, params, last_tok, cache, extra):
        logits, new = orig(self, params, last_tok, cache, extra)
        b = logits.shape[0]
        if b > 1:  # the rows past the first half get the first half's
            logits = logits.at[b // 2:].set(logits[:b - b // 2])
        return logits, new
    return decode


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    from repro.serving import engine

    if fault == "token_altered":
        orig = engine.sample_token
        calls = [0]

        def altered(logits, params, rng=None):
            calls[0] += 1
            tok = orig(logits, params) if rng is None else orig(
                logits, params, rng)
            return (tok + 1) % len(logits) if calls[0] % 7 == 0 else tok

        monkeypatch.setattr(engine, "sample_token", altered)
    else:
        wrap = {"state_unchanged": _state_unchanged,
                "half_batch": _half_batch}[fault]
        monkeypatch.setattr(engine.Engine, "_decode_fn",
                            wrap(engine.Engine._decode_fn))
    r = run_tiny(tiny.cell("decode-heavy"))
    assert not r["correct"]
    assert r["limits"]["max_logit_gap"]["value"] > 100 * tiny.LIMIT


def test_traced_run_reports_per_layer_metrics():
    r = run_tiny(tiny.cell("chat-bursty"), trace=True)
    got = set(r["metrics"])
    # on the CPU the trace has no TPU plane: device metrics are absent,
    # never 0
    assert {"model.decode_mfu_pct", "model.decode_hbm_pct"} <= got
    assert not got & {"kv.offstep_device_ms", "kernel.gemv_roofline",
                      "device.idle_pct"}
    assert r["correct"]
    assert {"device_ops", "idle_gaps"} == set(r["breakdown"])


def _fields(opts) -> dict:
    return {k: (dict(v) if k == "advanced_configuration" else v)
            for k in dir(opts) if not k.startswith("_")
            for v in [getattr(opts, k)]}


def test_one_chip_cell_traces_as_before(monkeypatch):
    """A one-chip cell's traced run records its whole window under the
    profiler options every traced run has had: host annotations only, no
    Python tracer, the rest at their defaults."""
    import jax

    parents = jax.profiler.ProfileOptions()
    parents.python_tracer_level = 0
    parents.host_tracer_level = 1
    given = []
    start = jax.profiler.start_trace

    def spy(log_dir, *a, profiler_options=None, **kw):
        given.append(_fields(profiler_options))
        return start(log_dir, *a, profiler_options=profiler_options, **kw)

    monkeypatch.setattr(jax.profiler, "start_trace", spy)
    r = run_tiny(tiny.cell(), trace=True)
    assert given == [_fields(parents)]
    # the whole window of 1.5 s, less the time the profiler takes to start
    assert 1.0 < r["window_s"] < 1.7
