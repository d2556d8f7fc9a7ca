"""Operations and bytes of a dense decoder's decode step, from shapes.

``m`` is ``spec.model_sizes`` of a configuration.  Weights are counted in
the configuration's dtype, activations and the KV cache in bfloat16 (the
dtype the program serves with)."""

from __future__ import annotations

import numpy as np

ACT_BYTES = 2


def _itemsize(m: dict) -> int:
    return {"bfloat16": 2, "float16": 2}.get(
        m["dtype"], None) or int(np.dtype(m["dtype"]).itemsize)


def decode_gemvs(m: dict) -> dict:
    """``{name: (M, K, count)}``: the projections the decode step runs as
    GEMVs (``out[b, M] = x[b, K] @ W``), ``count`` times per step.  The
    attention output projection is an einsum in the program and is not
    among them."""
    d, H, Hkv, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    L, f = m["n_layers"], m["d_ff"]
    g = {"qkv": ((H + 2 * Hkv) * hd, d, L)}
    if m["gated_ffn"]:
        g["gate_up"] = (2 * f, d, L)
    else:
        g["up"] = (f, d, L)
    g["down"] = (d, f, L)
    g["lm_head"] = (m["vocab"], d, 1)
    return g


def params_per_token(m: dict) -> int:
    """Weights a token's decode touches: every layer's projections and
    the head (the embedding row gather is not counted)."""
    d, H, hd = m["d_model"], m["n_heads"], m["head_dim"]
    total = m["n_layers"] * H * hd * d            # attention output
    for M, K, n in decode_gemvs(m).values():
        total += M * K * n
    return total


def kv_bytes_per_position(m: dict) -> int:
    return m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"] * ACT_BYTES


def decode_step(m: dict, live: list[int]) -> tuple[float, float]:
    """``(flops, bytes)`` one decode step needs for rows attending to
    ``live`` positions each (the new token's included): 2 x weights x rows
    plus ``4 * H * hd`` per layer per attended position; weights read
    once, the live KV read and one new position per row written."""
    rows = len(live)
    if rows == 0:
        return 0.0, 0.0
    H, hd, L = m["n_heads"], m["head_dim"], m["n_layers"]
    pos = float(sum(live))
    flops = 2.0 * params_per_token(m) * rows + 4.0 * H * hd * L * pos
    nbytes = (params_per_token(m) * _itemsize(m)
              + kv_bytes_per_position(m) * (pos + rows))
    return flops, nbytes


def weight_bytes(m: dict) -> int:
    d, V = m["d_model"], m["vocab"]
    n = params_per_token(m) + (0 if m["tie_embeddings"] else V * d)
    return n * _itemsize(m)
