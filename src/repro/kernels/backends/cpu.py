"""CPU `GemvBackend`: XLA-native serving, no interpret-mode Pallas, ever.

Interpret-mode Pallas re-executes the kernel body with jnp per grid program
— a validation harness, orders of magnitude slower than XLA on CPU.  PR-1
handled this with a downgrade branch inside ``dispatch_gemv``; the backend
registry makes it structural instead: a CPU host resolves *this* backend,
whose whole kernel set is plain XLA:

* ``ref`` — the transposed-placement dot (still the paper's §IV-A1 layout:
  K-major storage keeps the reduction axis contiguous for streaming reads);
* ``splitk`` — a **pre-chunked split-K reduce**: x and W are reshaped into
  ``degree`` K-chunks at trace time and contracted as one batched einsum
  whose partials are summed outside (paper §VI-F in XLA form).  Chunking
  keeps each partial's working set cache-resident and hands XLA:CPU
  ``degree`` independent contractions to spread over cores, where the single
  naive GEMV runs at ``gemv_efficiency`` of stream bandwidth;
* ``quant`` / ``quant4`` — the block-scale dequant oracles (XLA fuses the
  dequant into the contraction; no separate f32 weight materialization at
  decode batch sizes).

Cost constants are measured-on-host class attributes, not module globals —
a DDR-class memory system (tens of GB/s, negligible launch cost, core count
as the parallelism target) rather than the TPU's HBM numbers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backends.base import (
    DEFAULT_POLICY,
    CostModel,
    DispatchPolicy,
    GemvBackend,
    GemvPlan,
    register_backend,
)
from repro.kernels.ops import PackedWeights
from repro.kernels.tpu_plan import valid_splitk_degree


@jax.jit
def cpu_grouped_gemv(xs: jnp.ndarray, w_t: jnp.ndarray) -> jnp.ndarray:
    """Grouped/expert GEMV: out[E, C, M] = xs[E, C, K] @ w_t[E, K, M].

    One batched einsum for the whole expert group — XLA:CPU parallelizes
    over the E contractions, and the group pays ONE dispatch instead of E
    (the launch-amortization term in the program cost model).  f32
    accumulation, like every kernel on this backend.
    """
    E, C, K = xs.shape
    E2, K2, M = w_t.shape
    assert E == E2 and K == K2, (xs.shape, w_t.shape)
    return jnp.einsum(
        "eck,ekm->ecm", xs.astype(jnp.float32), w_t.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(xs.dtype)


@functools.partial(jax.jit, static_argnames=("degree",))
def cpu_splitk_gemv(
    x: jnp.ndarray, w_t: jnp.ndarray, *, degree: int
) -> jnp.ndarray:
    """Pre-chunked split-K GEMV: out[B, M] = x[B, K] @ w_t[K, M].

    The K axis is split into ``degree`` chunks at trace time; the batched
    einsum contracts every chunk independently (XLA:CPU parallelizes over
    the chunk dimension) and the f32 partials reduce outside — the paper's
    SoC reduction (§VI-F) as a tiny XLA sum.
    """
    B, K = x.shape
    K2, M = w_t.shape
    assert K == K2 and K % degree == 0, (x.shape, w_t.shape, degree)
    kp = K // degree
    xp = x.reshape(B, degree, kp).swapaxes(0, 1).astype(jnp.float32)
    wp = w_t.reshape(degree, kp, M).astype(jnp.float32)
    partials = jnp.einsum(
        "dbk,dkm->dbm", xp, wp, preferred_element_type=jnp.float32
    )
    return jnp.sum(partials, axis=0).astype(x.dtype)


def plan_cpu_splitk(M: int, K: int, batch: int) -> GemvPlan | None:
    """Plan builder: chunk K at the highest valid split degree.

    Reuses the split-K validity rule (degree divides K into sublane-aligned
    parts) so CPU-tuned table entries stay meaningful if replayed on TPU.
    """
    deg = valid_splitk_degree(K)
    if deg is None:
        return None
    return GemvPlan(m_blk=M, k_blk=K // deg, n_m=1, n_k=1, vmem_bytes=0,
                    split_k=deg)


class CpuBackend(GemvBackend):
    """XLA-native GEMV serving for DDR-class hosts."""

    name = "cpu"
    kernels = ("ref", "splitk", "quant", "quant4")
    # GEMV programs: fused multi-head runs as one XLA dot on the
    # concatenated weight (one dispatch, one IV stream); grouped/expert
    # programs run through ``cpu_grouped_gemv`` (batched einsum); ragged
    # programs use the universal XLA ragged executor from the base class
    # (jax.lax.ragged_dot).
    program_modes = ("fused", "grouped", "ragged")
    # Measured on the reference container (single-socket DDR): ~1/16 of the
    # TPU analogue's HBM bandwidth, near-zero dispatch cost, and the core
    # count as the fill target for the chunked reduce.
    cost_model = CostModel(
        bandwidth_gbps=51.2,       # dual-channel DDR5-class stream bandwidth
        gemv_efficiency=0.55,      # single naive dot: one stream, no chunking
        launch_us=1.5,             # XLA:CPU dispatch overhead
        program_us=3.0,            # per-chunk contraction setup
        min_parallel_blocks=8,     # physical cores the chunked reduce feeds
    )

    # -- cost model ---------------------------------------------------------

    def estimate_cost_us(
        self, kernel: str, M: int, K: int, batch: int, *,
        bits: int = 16, x_bytes: int = 2, plan: GemvPlan | None = None,
    ) -> float:
        """Streaming model: the chunked reduce reaches full stream bandwidth
        once its ``degree`` chunks cover the cores; the naive dot gets
        ``gemv_efficiency`` of it.  Chunk setup and the f32 partial
        write+re-read traffic are what keep small GEMVs on ``ref``."""
        cm = self.cost_model
        io = self.io_bytes(M, K, batch, bits=bits, x_bytes=x_bytes)
        elem = batch * M * cm.elem_ns * 1e-3
        if kernel != "splitk" or plan is None:
            return io / (cm.bandwidth_bps * cm.gemv_efficiency) * 1e6 + elem
        deg = plan.split_k
        occupancy = min(1.0, deg / cm.min_parallel_blocks)
        t = io / (cm.bandwidth_bps * occupancy) * 1e6
        t += cm.launch_us + cm.program_us * deg
        t += (cm.splitk_reduce_factor * deg * batch * M * 4
              / cm.bandwidth_bps * 1e6)
        return t + elem

    # -- planning -----------------------------------------------------------

    def candidate_plans(
        self, M: int, K: int, batch: int, bits: int
    ) -> list[tuple[str, GemvPlan | None]]:
        if bits < 16:
            return [("quant" if bits == 8 else "quant4", None)]
        cands: list[tuple[str, GemvPlan | None]] = [("ref", None)]
        plan = plan_cpu_splitk(M, K, batch)
        if plan is not None:
            cands.append(("splitk", plan))
        return cands

    # -- selection ----------------------------------------------------------

    def select_kernel(
        self, M: int, K: int, batch: int, *,
        bits: int = 16, block: int = 32, x_bytes: int = 2,
        policy: DispatchPolicy = DEFAULT_POLICY,
    ) -> tuple[str, GemvPlan | None]:
        if policy.kernel != "auto":
            return self._pinned(M, K, batch, bits, policy)
        if bits < 16:
            # Quantized weights keep the dequantizing contraction (fused by
            # XLA); there is no lower-traffic alternative on this backend.
            return ("quant" if bits == 8 else "quant4"), None
        if batch > policy.batch_threshold:
            return "ref", None  # matmul-shaped: leave it to the XLA dot
        cands = self.candidate_plans(M, K, batch, bits)
        return min(
            cands,
            key=lambda kp: self.estimate_cost_us(
                kp[0], M, K, batch, bits=bits, x_bytes=x_bytes, plan=kp[1]
            ),
        )

    def _pinned(self, M, K, batch, bits, policy):
        name = policy.kernel
        self._check_pin(name, bits)
        if bits < 16:
            # any pin on quantized weights resolves to the dequant path
            return ("quant" if bits == 8 else "quant4"), None
        if name == "splitk":
            plan = plan_cpu_splitk(M, K, batch)
            if plan is not None:
                return "splitk", plan
        return "ref", None

    def coerce_plan(
        self, plan: GemvPlan, M: int, K: int, batch: int,
        pw: PackedWeights, policy: DispatchPolicy,
    ) -> tuple[str, GemvPlan | None]:
        """A TPU-shaped plan carries one transferable decision here: its
        split degree.  Everything else (block shape, grid) is Pallas-only."""
        if pw.bits < 16:
            return ("quant" if pw.bits == 8 else "quant4"), None
        if plan.split_k > 1 and K % plan.split_k == 0:
            return "splitk", GemvPlan(
                m_blk=M, k_blk=K // plan.split_k, n_m=1, n_k=1,
                vmem_bytes=0, split_k=plan.split_k,
            )
        return "ref", None

    # -- execution ----------------------------------------------------------

    def _execute_grouped(self, xs: jnp.ndarray,
                         pw: PackedWeights) -> jnp.ndarray:
        # float stacks take the jitted batched einsum; quantized stacks
        # keep the base dequant contraction (XLA fuses the dequant).
        if pw.bits == 16:
            return cpu_grouped_gemv(xs, pw.w_t)
        return super()._execute_grouped(xs, pw)

    def execute(self, kernel: str, x: jnp.ndarray, pw: PackedWeights,
                plan: GemvPlan | None, interpret: bool) -> jnp.ndarray:
        # ``interpret`` is accepted for signature parity and ignored: every
        # kernel here is XLA-native (the backend's core guarantee).
        if kernel == "splitk":
            return cpu_splitk_gemv(x, pw.w_t, degree=plan.split_k)
        if kernel in ("ref", "quant", "quant4"):
            # quant/quant4 on this backend ARE the dequantizing ref oracles
            # (dispatched by pw.bits, which the selection kept in sync)
            return self._execute_ref(x, pw)
        raise ValueError(f"unknown kernel {kernel!r}")


BACKEND = register_backend(CpuBackend(), platforms=("cpu",))
