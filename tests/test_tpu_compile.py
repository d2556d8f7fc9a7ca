"""The decode GEMV kernels compile for a TPU v5e chip, at olmo-1b widths.

Interpret-mode tests cannot see what the chip's compiler refuses: a block
that overflows the scoped VMEM limit, a slice off the tiling.  These tests
compile every pim / split-K plan the TPU backend considers for olmo-1b's
decode projections against a *described* v5e chip (nothing runs, the host
stays on the CPU) and check that the Pallas kernel is in the compiled
program as a ``tpu_custom_call`` rather than interpreted.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, so describing it
at collection would break every other test worker.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.backends import get_backend
from repro.kernels.pim_gemv import pim_gemv
from repro.kernels.splitk_gemv import splitk_gemv

# olmo-1b (16L, d_model 2048, 16 heads, d_ff 8192, vocab 50304) decode
# projections as the engine dispatches them: (M, K) of out = x @ W^T.
OLMO_1B_DECODE = {
    "qkv_fused": (6144, 2048),
    "attn_out": (2048, 2048),
    "gateup_fused": (16384, 2048),
    "ffn_down": (2048, 8192),
    "lm_head": (50304, 2048),
}
# batch 1 and the largest decode bucket chip_smoke.py serves (4 slots)
BATCHES = (1, 4)
KERNELS = {"pim": pim_gemv, "splitk": splitk_gemv}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("shape", sorted(OLMO_1B_DECODE))
def test_olmo_1b_decode_plan_compiles_for_v5e(one_chip, shape, kernel,
                                              batch):
    M, K = OLMO_1B_DECODE[shape]
    plans = dict(get_backend("tpu").candidate_plans(M, K, batch, 16))
    assert kernel in plans, (shape, sorted(plans))
    plan = plans[kernel]
    x = jax.ShapeDtypeStruct((batch, K), jnp.bfloat16, sharding=one_chip)
    w_t = jax.ShapeDtypeStruct((K, M), jnp.bfloat16, sharding=one_chip)
    fn = KERNELS[kernel]
    compiled = jax.jit(
        lambda a, b: fn(a, b, plan=plan, interpret=False)
    ).lower(x, w_t).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert f"pimnast_{'gemv' if kernel == 'pim' else 'splitk_gemv'}" in hlo


def test_dispatcher_picks_are_among_the_compiled_plans():
    """The auto pick for every shape above is one of the plans compiled
    there, so a compile test guards what the engine actually runs."""
    tpu = get_backend("tpu")
    for M, K in OLMO_1B_DECODE.values():
        for batch in BATCHES:
            kernel, plan = tpu.select_kernel(M, K, batch)
            assert kernel in KERNELS, (M, K, batch, kernel)
            assert (kernel, plan) in tpu.candidate_plans(M, K, batch, 16)
