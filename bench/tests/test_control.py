"""The correctness check's control, at a size the CPU holds: the same
bfloat16 program the cells serve, and the float32 reference put in its
place at the precision below (``fp8``: float8_e4m3fn weights per output
channel and activations per token).  The control's widest and mean gaps
must read far above the program's on every seed, so that a limit between
them passes the program and fails the control; the chip readings that
set each cell's limits are in PERF.md."""

import copy

import numpy as np
import pytest

import check
import run
import spec
import tiny
import weights
from repro.serving.engine import Request

D, LAYERS, HEADS, VOCAB = 256, 4, 2, 2048


def bf16_cell():
    c = tiny.cell("decode-heavy")
    cfg = copy.deepcopy(c.config)
    cfg.update(hidden_size=D, intermediate_size=4 * D,
               num_hidden_layers=LAYERS, num_attention_heads=HEADS,
               num_key_value_heads=HEADS, vocab_size=VOCAB,
               torch_dtype="bfloat16")
    cfg["program"]["overrides"] = dict(
        n_layers=LAYERS, d_model=D, n_heads=HEADS, n_kv_heads=HEADS,
        head_dim=D // HEADS, d_ff=4 * D, vocab=VOCAB,
        param_dtype="bfloat16", compute_dtype="bfloat16")
    c.config, c.model = cfg, spec.model_sizes(cfg)
    return c


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_reads_far_above_the_program(seed):
    c = bf16_cell()
    w = weights.make_weights(c.model, seed)
    eng = run.engine_for(c, run.program_config(c), w)
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=rng.integers(0, VOCAB, 40).astype(
        np.int32), max_new_tokens=20) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    while eng.active or eng.queue:
        eng.step()
    ref = spec.reference_module(c.config)
    length = c.traffic["max_len"]
    program = check.served_gaps(ref, w, c.model, reqs, length)
    control = check.control_gaps(ref, w, c.model, reqs, length, "fp8")
    assert program["max_logit_gap"] < 0.05            # bf16 rounding
    assert control["max_logit_gap"] > 5 * max(program["max_logit_gap"],
                                               0.02)
    assert control["mean_logit_gap"] > 3 * program["mean_logit_gap"]
