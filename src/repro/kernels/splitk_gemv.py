"""Split-K GEMV (paper §VI-F) as a Pallas TPU kernel.

For small-M GEMVs the output-stationary kernel has too few M-blocks to fill
the machine; the paper's fix vertically decomposes K into 2^i parts, each
producing a partial output that the host reduces. Here the K-parts are the
OUTER (parallel) grid dimension writing ``degree`` partial rows; the final
reduction is a tiny XLA sum outside the kernel (= the paper's SoC reduce).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tpu_plan import TPUGemvPlan, vmem_limit_bytes


def _splitk_kernel(x_ref, w_ref, out_ref, acc_ref, *,
                   n_steps: int, depth: int, k_blk: int):
    ki = pl.program_id(2)  # K walk WITHIN one split part

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Staged K walk (see pim_gemv._gemv_kernel): the block spans ``depth``
    # sub-tiles, unrolled here so the grid pipeline streams the next
    # megablock while this one computes.  Left-to-right accumulation keeps
    # the partials bit-identical to the depth-1 kernel.
    x = x_ref[0]
    w = w_ref[0]
    for j in range(depth):
        acc_ref[0] += jax.lax.dot_general(
            x[:, j * k_blk:(j + 1) * k_blk],
            w[j * k_blk:(j + 1) * k_blk, :],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == n_steps - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def splitk_gemv(
    x: jnp.ndarray,
    w_t: jnp.ndarray,
    *,
    plan: TPUGemvPlan,
    interpret: bool = False,
) -> jnp.ndarray:
    """x: [B, K], w_t: [K, M] -> [B, M]; K split into ``plan.split_k`` parts."""
    B, K = x.shape
    K2, M = w_t.shape
    assert K == K2
    deg = plan.split_k
    assert deg >= 1 and K % deg == 0, (deg, K)
    kp = K // deg
    assert kp % plan.k_blk == 0 and M % plan.m_blk == 0, (plan, kp, M)
    n_k = kp // plan.k_blk
    depth = plan.pipeline_depth
    assert depth >= 1 and n_k % depth == 0, (plan, n_k, depth)
    k_mega = plan.k_blk * depth

    grid = (deg, plan.n_m, n_k // depth)
    partials = pl.pallas_call(
        functools.partial(_splitk_kernel, n_steps=n_k // depth,
                          depth=depth, k_blk=plan.k_blk),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, B, k_mega),
                lambda si, mi, ki: (si, 0, ki),
            ),
            pl.BlockSpec(
                (1, k_mega, plan.m_blk),
                lambda si, mi, ki: (si, ki, mi),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, B, plan.m_blk), lambda si, mi, ki: (si, 0, mi)
        ),
        out_shape=jax.ShapeDtypeStruct((deg, B, M), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, B, plan.m_blk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes(plan),
        ),
        interpret=interpret,
        name="pimnast_splitk_gemv",
    )(
        x.reshape(B, deg, kp).swapaxes(0, 1),  # [deg, B, kp]
        w_t.reshape(deg, kp, M),
    )
    # Host-side ("SoC") reduction of the split partials.
    return jnp.sum(partials, axis=0).astype(x.dtype)
