"""PIMnast tile-shape planning adapted to the TPU memory hierarchy.

The paper's Algorithm 1 sweeps tile height from tall (column-vector) to wide
(row-vector) until (a) rows distribute evenly over banks and (b) the PIM
register budget holds. The TPU analogue (DESIGN.md §2.2):

    bank            -> grid program (one M-block of outputs)
    register file   -> VMEM working set (W block double-buffer + x + f32 acc)
    even bank dist. -> grid dims divide M and K exactly
    cross-SIMD-lane -> M must sit on the 128-lane axis (m_blk % 128 == 0)
    row locality    -> contiguous (k_blk, m_blk) HBM->VMEM streams, K walked
                       innermost within an M-block (CR-order analogue)
    CR-degree       -> output-stationary accumulation: one resident f32
                       accumulator serves the whole K walk (IV reuse)

``plan_tpu_gemv`` mirrors the sweep: start with the tallest lane-aligned
M-block, halve until it divides M and the VMEM budget fits, then grow K-block
to amortize grid overheads (the "process an open row fully" rule).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

LANES = 128
SUBLANES = 8
DEFAULT_VMEM_BUDGET = 96 * 1024 * 1024  # leave headroom of the ~128MB VMEM
# What Mosaic allocates beyond the buffers ``_fits`` counts: batch rows
# padded to whole sublane tiles and the kernel's own stack.  The planner's
# count alone was measured too small for the 384x2048 lm_head block at
# batch 4 on v5e; 1 MiB over it compiled every olmo-1b decode plan.
VMEM_HEADROOM = 4 * 1024 * 1024


@dataclass(frozen=True)
class TPUGemvPlan:
    m_blk: int
    k_blk: int
    n_m: int
    n_k: int
    vmem_bytes: int
    # split-K degree for the k-parallel variant (0 = output-stationary)
    split_k: int = 1
    # K-stream staging depth: the kernel's grid block spans
    # ``k_blk * pipeline_depth`` columns and the kernel walks the
    # ``pipeline_depth`` sub-tiles itself, so the Pallas grid pipeline
    # streams megablock N+1 from HBM while the kernel is still rotating
    # through megablock N's sub-tiles (csl-experiments' double-buffered
    # broadcast, SNIPPETS.md §2–3).  Depth 1 is exactly the unstaged
    # kernel; the accumulation order is identical at every depth.
    pipeline_depth: int = 1

    @property
    def grid(self) -> tuple[int, int]:
        return (self.n_m, self.n_k // self.pipeline_depth)


def vmem_limit_bytes(plan: TPUGemvPlan) -> int:
    """The scoped-VMEM limit a kernel asks the compiler for: the plan's
    own working set plus headroom.  Without it the compiler applies the
    chip's default scoped limit (16 MiB on v5e), which refuses the
    planner's 2048x2048 bf16 blocks although VMEM holds them."""
    return plan.vmem_bytes + VMEM_HEADROOM


def _fits(
    m_blk: int, k_blk: int, batch: int, w_bytes: int, x_bytes: int,
    budget: int, depth: int = 1,
) -> tuple[bool, int]:
    w = m_blk * k_blk * depth * w_bytes * 2  # double-buffered W stream
    x = batch * k_blk * depth * x_bytes * 2
    acc = batch * m_blk * 4                  # f32 accumulator scratch
    out = batch * m_blk * x_bytes * 2
    total = w + x + acc + out
    return total <= budget, total


def plan_tpu_gemv(
    M: int,
    K: int,
    batch: int = 1,
    *,
    w_bytes: int = 2,
    x_bytes: int = 2,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    max_m_blk: int = 2048,
    max_k_blk: int = 2048,
    pipeline_depth: int = 1,
) -> TPUGemvPlan:
    """Algorithm-1 analogue for BlockSpec selection.

    Sweep m_blk from tall to short (lane-aligned), then pick the largest
    k_blk that divides K and fits VMEM. Falls back to the full dimension when
    smaller than one lane/sublane group (ragged edges are padded by ops.py).
    ``pipeline_depth > 1`` sizes the VMEM working set for the staged K
    stream (``k_blk * depth`` columns resident) and requires the K walk to
    split evenly into depth-sized megablocks.
    """
    if M <= 0 or K <= 0:
        raise ValueError("M and K must be positive")
    if pipeline_depth < 1:
        raise ValueError(f"pipeline_depth must be >= 1, got {pipeline_depth}")
    d = pipeline_depth

    # --- m_blk sweep: tallest lane-aligned block that divides M and fits ---
    m_cands = []
    m = min(max_m_blk, M)
    m = max(LANES, (m // LANES) * LANES) if M >= LANES else M
    while m >= LANES:
        if M % m == 0:
            m_cands.append(m)
        m -= LANES if m <= 1024 else 1024  # coarse-to-fine sweep
    if M % LANES == 0 and LANES not in m_cands and M >= LANES:
        m_cands.append(LANES)
    if not m_cands:
        m_cands = [M]  # ragged small M: single block (padded downstream)

    for m_blk in m_cands:
        # --- k_blk: largest sublane-aligned divisor of K under budget ---
        k = min(max_k_blk, K)
        k = max(SUBLANES, (k // SUBLANES) * SUBLANES) if K >= SUBLANES else K
        while k > SUBLANES:
            ok, total = _fits(m_blk, k, batch, w_bytes, x_bytes, vmem_budget,
                              d)
            if K % (k * d) == 0 and ok:
                return TPUGemvPlan(
                    m_blk=m_blk, k_blk=k,
                    n_m=M // m_blk, n_k=K // k, vmem_bytes=total,
                    pipeline_depth=d,
                )
            k -= SUBLANES
        ok, total = _fits(m_blk, min(K, SUBLANES), batch, w_bytes, x_bytes,
                          vmem_budget, d)
        if ok and K % (min(K, SUBLANES) * d) == 0:
            kb = min(K, SUBLANES)
            return TPUGemvPlan(
                m_blk=m_blk, k_blk=kb, n_m=M // m_blk, n_k=K // kb,
                vmem_bytes=total, pipeline_depth=d,
            )

    # Last resort: whole matrix in one block (tiny GEMVs; depth collapses
    # to 1 — a single K block leaves nothing to stage ahead).
    _, total = _fits(M, K, batch, w_bytes, x_bytes, vmem_budget)
    return TPUGemvPlan(m_blk=M, k_blk=K, n_m=1, n_k=1, vmem_bytes=total)


SPLITK_DEGREES = (8, 4, 2)


def valid_splitk_degree(K: int, degrees=SPLITK_DEGREES) -> int | None:
    """Highest degree that splits K into sublane-aligned parts, else None.

    The single source of the split-K validity rule — shared by the planner,
    the dispatcher's candidate enumeration, and kernel pinning.
    """
    for deg in degrees:
        if K % deg == 0 and (K // deg) % SUBLANES == 0:
            return deg
    return None


def plan_splitk(
    M: int, K: int, batch: int = 1, *, degree: int = 4, **kw
) -> TPUGemvPlan:
    """Split-K plan (paper §VI-F): shard the K walk into ``degree`` parallel
    partials reduced outside the kernel — the choice for small-M GEMVs where
    too few M-blocks exist to fill the grid."""
    if K % degree != 0:
        degree = math.gcd(K, degree)
    base = plan_tpu_gemv(M, K // degree, batch, **kw)
    return TPUGemvPlan(
        m_blk=base.m_blk, k_blk=base.k_blk, n_m=base.n_m,
        n_k=base.n_k, vmem_bytes=base.vmem_bytes, split_k=degree,
        pipeline_depth=base.pipeline_depth,
    )


def with_pipeline_depth(plan: TPUGemvPlan, depth: int, *, batch: int = 1,
                        w_bytes: int = 2, x_bytes: int = 2,
                        vmem_budget: int = DEFAULT_VMEM_BUDGET,
                        ) -> TPUGemvPlan | None:
    """``plan`` restaged at ``depth``, or None when it cannot be.

    A depth-d restaging is valid only when the K walk splits into whole
    megablocks (``n_k % depth == 0``) and the widened ``k_blk * depth``
    working set still fits VMEM — the same two feasibility rules
    :func:`plan_tpu_gemv` applies when planning at depth directly.
    """
    if depth == plan.pipeline_depth:
        return plan
    if depth < 1 or plan.n_k % depth != 0:
        return None
    ok, total = _fits(plan.m_blk, plan.k_blk, batch, w_bytes, x_bytes,
                      vmem_budget, depth)
    if not ok:
        return None
    return TPUGemvPlan(
        m_blk=plan.m_blk, k_blk=plan.k_blk, n_m=plan.n_m, n_k=plan.n_k,
        vmem_bytes=total, split_k=plan.split_k, pipeline_depth=depth,
    )
