"""Quantized GEMV with block scale-factors, dequant inside the kernel.

Implements the paper's GenAI-needs placement (§III-C3, §IV-A3, §VI-D2):
low-precision weights (int8, packed int4) with MX-style per-K-block scale
factors. The scales are blocked ALONGSIDE the weights at tile granularity —
the kernel's scale BlockSpec walks in lockstep with the weight BlockSpec,
which is the TPU analogue of interleaving weights and metadata at memory
interleaving granularity so they share a DRAM row.

  w_q:    [K, M] int8            (or [K//2, M] int8 for packed int4)
  scales: [K // block, M]        per-(K-block, output-column) scales
  x:      [B, K]
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tpu_plan import TPUGemvPlan


def _quant_kernel(x_ref, w_ref, s_ref, out_ref, acc_ref, *, n_k, block):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_blk, m_blk = w_ref.shape
    w = w_ref[...].astype(jnp.float32)
    # Dequant: broadcast each K-block's scale over its `block` rows.
    s = s_ref[...].astype(jnp.float32)                      # [k_blk/block, m]
    w = w.reshape(k_blk // block, block, m_blk) * s[:, None, :]
    w = w.reshape(k_blk, m_blk)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _quant4_kernel(x_ref, w_ref, s_ref, out_ref, acc_ref, *, n_k, block):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kp_blk, m_blk = w_ref.shape           # packed: kp_blk = k_blk // 2
    packed = w_ref[...]
    lo = (jnp.left_shift(packed, 4) >> 4).astype(jnp.float32)
    hi = (packed >> 4).astype(jnp.float32)
    w = jnp.stack([lo, hi], axis=1).reshape(2 * kp_blk, m_blk)
    s = s_ref[...].astype(jnp.float32)
    w = w.reshape((2 * kp_blk) // block, block, m_blk) * s[:, None, :]
    w = w.reshape(2 * kp_blk, m_blk)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("plan", "block", "interpret")
)
def quant_gemv(
    x: jnp.ndarray,
    w_q: jnp.ndarray,
    scales: jnp.ndarray,
    *,
    plan: TPUGemvPlan,
    block: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """int8 weights + [K//block, M] scales -> [B, M]."""
    B, K = x.shape
    K2, M = w_q.shape
    assert K == K2 and scales.shape == (K // block, M)
    assert plan.k_blk % block == 0, (plan, block)

    grid = (plan.n_m, plan.n_k)
    sb = plan.k_blk // block
    return pl.pallas_call(
        functools.partial(_quant_kernel, n_k=plan.n_k, block=block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, plan.k_blk), lambda mi, ki: (0, ki)),
            pl.BlockSpec((plan.k_blk, plan.m_blk), lambda mi, ki: (ki, mi)),
            pl.BlockSpec((sb, plan.m_blk), lambda mi, ki: (ki, mi)),
        ],
        out_specs=pl.BlockSpec((B, plan.m_blk), lambda mi, ki: (0, mi)),
        out_shape=jax.ShapeDtypeStruct((B, M), x.dtype),
        scratch_shapes=[pltpu.VMEM((B, plan.m_blk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="pimnast_quant_gemv",
    )(x, w_q, scales)


@functools.partial(
    jax.jit, static_argnames=("plan", "block", "interpret")
)
def quant4_gemv(
    x: jnp.ndarray,
    w_packed: jnp.ndarray,
    scales: jnp.ndarray,
    *,
    plan: TPUGemvPlan,
    block: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Packed int4 (two nibbles per byte along K) + block scales -> [B, M]."""
    B, K = x.shape
    Kp, M = w_packed.shape
    assert K == 2 * Kp and scales.shape == (K // block, M)
    assert plan.k_blk % block == 0 and plan.k_blk % 2 == 0

    grid = (plan.n_m, plan.n_k)
    sb = plan.k_blk // block
    return pl.pallas_call(
        functools.partial(_quant4_kernel, n_k=plan.n_k, block=block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, plan.k_blk), lambda mi, ki: (0, ki)),
            pl.BlockSpec((plan.k_blk // 2, plan.m_blk),
                         lambda mi, ki: (ki, mi)),
            pl.BlockSpec((sb, plan.m_blk), lambda mi, ki: (ki, mi)),
        ],
        out_specs=pl.BlockSpec((B, plan.m_blk), lambda mi, ki: (0, mi)),
        out_shape=jax.ShapeDtypeStruct((B, M), x.dtype),
        scratch_shapes=[pltpu.VMEM((B, plan.m_blk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="pimnast_quant4_gemv",
    )(x, w_packed, scales)
