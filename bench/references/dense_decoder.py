"""Plain float32 reference of the dense decoder the program serves.

It imports nothing of the program and reads only the benchmark's own
weights (``weights.py``) and sizes (``spec.model_sizes``).  It computes
the program's equations, which depart from the published models where
the configuration file's ``departures`` say: the token embedding is
scaled by ``sqrt(d_model)``, rotary embedding turns the two halves of
each head by ``theta ** (-i / (head_dim / 2))``, and layer norm uses the
configuration's ``equations.norm_eps``.

Every matrix product runs in float32 at ``Precision.HIGHEST``, one layer
at a time (``lax.scan`` over the stacked layer weights, each cast to
float32 inside the step), and the output head in vocabulary blocks, so
the largest float32 temporary is one layer's weights or one block of the
head.  On a mesh the weights arrive split over it (``weights.shardings``)
and the partitioner splits these products with them: compiled for a
four-chip v5e at Minitron-8B's 32 layers and 48 heads, the program
gathers no weight (its one all-gather is of the ``[T, d]`` activations,
and it reshards the head's blocks among the chips) and holds 1.6 GB of
temporaries a chip beside its 4.1 GB share of the weights.

``precision="int8"`` is the control: every projection, the head
included, runs as an int8 x int8 product (weights per output channel,
activations per token, symmetric absmax scales), which is the step below
the bfloat16 the configurations serve.  ``precision="fp8"`` does the same
in float8_e4m3fn.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
HEAD_BLOCKS = 8


def _int8(a, axis):
    """Symmetric absmax int8 codes (as float32) and scales along ``axis``."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(a / s), -127, 127), s


def _fp8(a, axis):
    """float8_e4m3fn values (as float32) scaled so the absmax along
    ``axis`` is the format's largest, and the scales."""
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32), s


def matmul(x, w, precision: str):
    """``x [T, K] @ w [K, N]`` in float32, or as the int8 control."""
    w = w.astype(jnp.float32)
    if precision == "f32":
        return jnp.dot(x, w, precision=HIGHEST)
    if precision == "int8":
        xq, xs = _int8(x, -1)
        wq, ws = _int8(w, 0)
        return jnp.dot(xq, wq, precision=HIGHEST) * xs * ws
    if precision == "fp8":
        xq, xs = _fp8(x, -1)
        wq, ws = _fp8(w, 0)
        return jnp.dot(xq, wq, precision=HIGHEST) * xs * ws
    raise ValueError(f"unknown precision {precision!r}")


def norm(x, p, m):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + m["norm_eps"])
    if m["norm"] == "layernorm":
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    elif m["norm"] != "nonparametric":
        raise ValueError(f"unknown norm {m['norm']!r}")
    return y


def rope(x, m):
    """``x [T, heads, hd]`` rotated by position (two halves per head)."""
    T, _, hd = x.shape
    rot = int(hd * m["rotary_fraction"])
    half = rot // 2
    inv = m["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def layer(x, p, m, precision):
    T, d = x.shape
    H, Hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    a = p["attn"]
    h = norm(x, p["ln1"], m)
    q = matmul(h, a["wq"].reshape(d, H * hd), precision).reshape(T, H, hd)
    k = matmul(h, a["wk"].reshape(d, Hkv * hd), precision).reshape(T, Hkv, hd)
    v = matmul(h, a["wv"].reshape(d, Hkv * hd), precision).reshape(T, Hkv, hd)
    q, k = rope(q, m), rope(k, m)
    G = H // Hkv
    qg = q.reshape(T, Hkv, G, hd)
    s = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST).reshape(T, H * hd)
    x = x + matmul(o, a["wo"].reshape(H * hd, d), precision)
    h = norm(x, p["ln2"], m)
    mlp = p["mlp"]
    up = matmul(h, mlp["w_up"], precision)
    if m["act"] == "silu":
        u = jax.nn.silu(matmul(h, mlp["w_gate"], precision)) * up
    elif m["act"] == "relu2":
        u = jnp.square(jax.nn.relu(up))
    else:
        raise ValueError(f"unknown activation {m['act']!r}")
    return x + matmul(u, mlp["w_down"], precision)


def head_blocks(w, m):
    """The output head as ``[HEAD_BLOCKS, d, V / HEAD_BLOCKS]``."""
    V, d = m["vocab"], m["d_model"]
    if V % HEAD_BLOCKS:
        raise ValueError(f"vocab {V} does not split into {HEAD_BLOCKS}")
    vb = V // HEAD_BLOCKS
    if m["tie_embeddings"]:
        return w["embed"].reshape(HEAD_BLOCKS, vb, d).transpose(0, 2, 1)
    return w["lm_head"].reshape(d, HEAD_BLOCKS, vb).transpose(1, 0, 2)


def hidden(w, m, tokens, precision):
    """Final normed hidden states ``[T, d]`` (float32) of one sequence."""
    x = w["embed"][tokens].astype(jnp.float32)
    if m["embed_scale"] == "sqrt_d_model":
        x = x * math.sqrt(m["d_model"])
    x, _ = jax.lax.scan(lambda c, p: (layer(c, p, m, precision), None),
                        x, w["layers"])
    return norm(x, w["ln_f"], m)


@functools.partial(jax.jit, static_argnums=(1,))
def gaps(w, m_items, tokens, targets, control_tokens=None):
    """For each position ``t``: ``best[t] - ref[t, targets[t]]`` in the
    float32 reference's logits, with ``best`` its largest logit; and the
    reference's own choice ``argmax``.  ``control_tokens`` (optional) are
    scored the same way."""
    m = dict(m_items)
    x = hidden(w, m, tokens, "f32")
    heads = head_blocks(w, m)
    vb = heads.shape[-1]

    def block(carry, i_h):
        best, arg, tgt, ctl = carry
        i, hb = i_h
        z = matmul(x, hb, "f32")
        zb, za = jnp.max(z, -1), jnp.argmax(z, -1) + i * vb
        arg = jnp.where(zb > best, za, arg)
        best = jnp.maximum(best, zb)

        def pick(ids, acc):
            j = ids - i * vb
            inside = (j >= 0) & (j < vb)
            val = jnp.take_along_axis(z, jnp.clip(j, 0, vb - 1)[:, None],
                                      -1)[:, 0]
            return jnp.where(inside, val, acc)

        tgt = pick(targets, tgt)
        if control_tokens is not None:
            ctl = pick(control_tokens, ctl)
        return (best, arg, tgt, ctl), None

    T = tokens.shape[0]
    init = (jnp.full((T,), -jnp.inf), jnp.zeros((T,), jnp.int32),
            jnp.zeros((T,)), jnp.zeros((T,)))
    (best, arg, tgt, ctl), _ = jax.lax.scan(
        block, init, (jnp.arange(HEAD_BLOCKS), heads))
    return best - tgt, arg, best - ctl


@functools.partial(jax.jit, static_argnums=(1, 3))
def choices(w, m_items, tokens, precision):
    """The token each position's logits put first, under ``precision``."""
    m = dict(m_items)
    x = hidden(w, m, tokens, precision)
    heads = head_blocks(w, m)
    vb = heads.shape[-1]

    def block(carry, i_h):
        best, arg = carry
        i, hb = i_h
        z = matmul(x, hb, precision)
        zb, za = jnp.max(z, -1), jnp.argmax(z, -1) + i * vb
        return (jnp.maximum(best, zb), jnp.where(zb > best, za, arg)), None

    T = tokens.shape[0]
    (_, arg), _ = jax.lax.scan(
        block, (jnp.full((T,), -jnp.inf), jnp.zeros((T,), jnp.int32)),
        (jnp.arange(HEAD_BLOCKS), heads))
    return arg
