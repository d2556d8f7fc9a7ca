"""PIMnast-placed GEMV as a Pallas TPU kernel.

out[B, M] = x[B, K] @ w_t[K, M]   (decode-time GEMV, B small)

Placement mapping (paper §IV -> TPU, DESIGN.md §2.2):

  * W is stored transposed (K-major): within a block the M dimension is the
    minor/lane axis, so every lane owns a different output element — the
    paper's intra-tile column-major layout that avoids cross-SIMD-lane ops.
  * Grid = (n_m, n_k) with K innermost: each "bank" (M-block program) walks
    its K stream contiguously before the next M-block opens — CR-order's
    "process an open DRAM row fully" rule, here maximizing sequential HBM
    reads per block.
  * The f32 accumulator scratch is the PIM register file analogue: it stays
    resident for the whole K walk (output-stationary), so the broadcast x
    block is consumed by every resident output row (CR-degree reuse).

The split-K variant (paper §VI-F) lives in :mod:`repro.kernels.splitk_gemv`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tpu_plan import TPUGemvPlan, vmem_limit_bytes


def _gemv_kernel(x_ref, w_ref, out_ref, acc_ref, *,
                 n_steps: int, depth: int, k_blk: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The grid block spans ``depth`` K sub-tiles; rotating through them here
    # (an unrolled Python loop — static slices, one dot per sub-tile) keeps
    # the kernel busy long enough for the Pallas grid pipeline to stream the
    # NEXT megablock's W/x from HBM behind the compute.  The sub-tiles are
    # accumulated left-to-right into the same resident f32 scratch, so the
    # f32 add order — and therefore the output — is identical at any depth.
    x = x_ref[...]
    w = w_ref[...]
    for j in range(depth):
        acc_ref[...] += jax.lax.dot_general(
            x[:, j * k_blk:(j + 1) * k_blk],
            w[j * k_blk:(j + 1) * k_blk, :],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == n_steps - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def pim_gemv(
    x: jnp.ndarray,
    w_t: jnp.ndarray,
    *,
    plan: TPUGemvPlan,
    interpret: bool = False,
) -> jnp.ndarray:
    """x: [B, K], w_t: [K, M] -> [B, M] with f32 accumulation."""
    B, K = x.shape
    K2, M = w_t.shape
    assert K == K2, (x.shape, w_t.shape)
    assert M % plan.m_blk == 0 and K % plan.k_blk == 0, (plan, M, K)
    depth = plan.pipeline_depth
    assert depth >= 1 and plan.n_k % depth == 0, (plan, depth)
    k_mega = plan.k_blk * depth            # K columns staged per grid step

    grid = (plan.n_m, plan.n_k // depth)
    return pl.pallas_call(
        functools.partial(_gemv_kernel, n_steps=plan.n_k // depth,
                          depth=depth, k_blk=plan.k_blk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, k_mega), lambda mi, ki: (0, ki)),
            pl.BlockSpec((k_mega, plan.m_blk), lambda mi, ki: (ki, mi)),
        ],
        out_specs=pl.BlockSpec((B, plan.m_blk), lambda mi, ki: (0, mi)),
        out_shape=jax.ShapeDtypeStruct((B, M), x.dtype),
        scratch_shapes=[pltpu.VMEM((B, plan.m_blk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes(plan),
        ),
        interpret=interpret,
        name="pimnast_gemv",
    )(x, w_t)
