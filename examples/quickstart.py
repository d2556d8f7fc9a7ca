"""Quickstart: the paper's technique end to end in two minutes.

1. Plan a PIMnast placement for a GEMV and read the modeled speedup
   (the LPDDR-PIM reproduction).
2. Run the SAME placement idea as a TPU Pallas kernel (native on a TPU,
   interpret mode elsewhere) and check it against the jnp oracle.
3. Peek at the mesh-level placement the planner would use on a pod.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pim_arch import BF16, INT8, RYZEN_LPDDR5X
from repro.core.placement import GEMV
from repro.pim.timing import pim_speedup
from repro.kernels import get_backend, ops


def main():
    cfg = RYZEN_LPDDR5X
    print(f"PIM system: {cfg.tot_bank} banks, peak boost "
          f"{cfg.peak_pim_boost:.1f}x, roofline "
          f"{cfg.roofline_pim_boost:.2f}x\n")

    # -- 1. the paper's placement on an OPT-6.7B FC1 GEMV ------------------
    g = GEMV(16384, 4096, INT8, BF16, name="opt-6.7b/fc1")
    speedup, placement, bd = pim_speedup(g, cfg)
    print(f"GEMV {g.name}: {placement.describe()}")
    print(f"  modeled PIM time {bd.total/1e3:.1f} us, "
          f"speedup over SoC {speedup:.2f}x "
          f"(roofline {cfg.roofline_pim_boost:.2f}x)")
    print(f"  breakdown: mac={bd.t_mac/1e3:.1f}us iv={bd.t_iv/1e3:.2f}us "
          f"turn={bd.t_turn/1e3:.2f}us rows={bd.t_row/1e3:.2f}us\n")

    # -- 2. the TPU analogue: PIMnast-planned Pallas GEMV ------------------
    M, K, B = 1024, 2048, 1
    rng = np.random.default_rng(0)
    w = rng.standard_normal((M, K), dtype=np.float32)
    x = rng.standard_normal((B, K), dtype=np.float32)
    packed = ops.pack_weight(jnp.asarray(w))   # "column-major" placement
    # The TPU backend's selection is what placed_gemv executes: natively
    # on a TPU, and off it through the Pallas interpreter (interpret=True
    # resolves the tpu backend on any host).
    tpu = get_backend("tpu")
    interpret = tpu.default_interpret()
    kernel, plan = tpu.select_kernel(M, K, B)
    desc = (f"m_blk={plan.m_blk} k_blk={plan.k_blk} grid={plan.grid} "
            f"split_k={plan.split_k}" if plan is not None else "XLA ref")
    print(f"TPU kernel plan for {M}x{K}: kernel={kernel} {desc}")
    out = ops.placed_gemv(jnp.asarray(x), packed, interpret=interpret)
    err = float(np.abs(np.asarray(out) - x @ w.T).max())
    print(f"  pallas-vs-oracle max err: {err:.2e}\n")

    # -- 3. quantized decode GEMV (block scale-factors, paper §VI-D2) ------
    pq = ops.quantize_weight(w, bits=8, block=32)
    out_q = ops.placed_gemv(jnp.asarray(x), pq, interpret=interpret)
    rel = float(np.abs(np.asarray(out_q) - x @ w.T).max()
                / np.abs(x @ w.T).max())
    print(f"int8 block-scale GEMV rel err vs float: {rel:.3f}")


if __name__ == "__main__":
    main()
