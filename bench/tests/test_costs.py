"""Operation and byte counts against arithmetic done by hand."""

import numpy as np
import pytest

import costs
import spec
import weights

def init_bytes(m):
    """Bytes of every leaf the benchmark's init draws."""
    return sum(int(np.prod(shape)) * 2
               for shape, _, _ in weights.shapes(m).values())


def sizes(name):
    return spec.model_sizes(spec.load_json(
        f"{spec.BENCH_DIR}/configs/{name}.json"))


def test_olmo_1b_decode_gemv_shapes():
    assert costs.decode_gemvs(sizes("olmo-1b")) == {
        "qkv": (6144, 2048, 16),          # (16 + 2 * 16) heads x 128
        "gate_up": (16384, 2048, 16),     # 2 x 8192
        "down": (2048, 8192, 16),
        "lm_head": (50304, 2048, 1),
    }


def test_olmo_1b_weights():
    m = sizes("olmo-1b")
    # per layer: wo 2048^2 + qkv 6144*2048 + gate/up 16384*2048
    # + down 2048*8192 = 67,108,864; the tied head 50304*2048
    assert costs.params_per_token(m) == 16 * 67_108_864 + 103_022_592
    assert costs.weight_bytes(m) == 2 * 1_176_764_416 == 2_353_528_832
    assert init_bytes(m) == 2_353_528_832
    assert costs.kv_bytes_per_position(m) == 16 * 2 * 16 * 128 * 2


def test_olmo_1b_decode_step():
    m = sizes("olmo-1b")
    flops, nbytes = costs.decode_step(m, [100, 200])
    # 2 x weights x 2 rows + 4 x 16 heads x 128 x 16 layers x 300 positions
    assert flops == 2 * 1_176_764_416 * 2 + 4 * 16 * 128 * 16 * 300
    # weights once + KV read at 300 positions and written at 2
    assert nbytes == 2_353_528_832 + 131_072 * 302
    assert costs.decode_step(m, []) == (0.0, 0.0)


def test_minitron_stage_weights():
    m = sizes("minitron-8b-16L")
    assert costs.decode_gemvs(m) == {
        "qkv": (6144, 4096, 16),          # (32 + 2 * 8) heads x 128
        "up": (16384, 4096, 16),          # relu2: no gate
        "down": (4096, 16384, 16),
        "lm_head": (256000, 4096, 1),
    }
    per_layer = 4096 * 4096 + 6144 * 4096 + 2 * 16384 * 4096
    assert costs.params_per_token(m) == 16 * per_layer + 256000 * 4096
    # + the untied embedding; the layer norms are not counted
    assert costs.weight_bytes(m) == 2 * (16 * per_layer + 2 * 256000 * 4096)
    norms = (16 * 2 + 1) * 2 * 4096 * 2
    assert init_bytes(m) == costs.weight_bytes(m) + norms
