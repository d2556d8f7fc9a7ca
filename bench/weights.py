"""Random weights for a dense decoder, made from the seed by the benchmark.

The benchmark owns the weights so that the plain reference never reads an
array the program under test made.  One jitted call draws every leaf on
the default device, in the dtype the configuration serves, with the
counter-based ``rbg`` generator (threefry over 2-10 GB of weights costs
tens of seconds of set-up on a v5e).  The tree has the layout the
program's dense decoder consumes: stacked ``[L, ...]`` layer leaves under
``layers``, ``embed`` ``[V, d]``, ``lm_head`` ``[d, V]`` when untied.
Each matrix is normal with standard deviation ``1/sqrt(fan_in)``, the
embedding ``EMBED_SCALE`` and the query and key projections ``QK_SCALE``
times that (see below); layernorm scales are ``1 + 0.1 N(0, 1)`` and
biases ``0.1 N(0, 1)``, so a path that drops them reads differently from
one that applies them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


# Two scales make greedy decoding depend on the context, so that the
# correctness check can see a broken KV cache.  With every matrix at
# 1/sqrt(fan_in), the program's sqrt(d_model)-scaled embedding dominates
# the residual stream and a tied head puts the input token first at every
# position (a d_model 1024, 8-layer float32 model: 100% of choices copy
# the input token, and replacing half of the context changes none).  The
# embedding is drawn EMBED_SCALE times narrower, and the query and key
# projections QK_SCALE times wider, so attention scores spread by
# QK_SCALE**2 standard deviations and each head attends sharply, as a
# trained model's do (same model: 49% of choices copy the input, 66%
# change when half of the context is replaced).
QK_SCALE = 2.0
EMBED_SCALE = 0.3


def seed_key(seed: int):
    """An ``rbg`` key from a seed of any size up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.key(seed & 0xFFFFFFFF, impl="rbg")
    return jax.random.fold_in(key, seed >> 32)


def shapes(c: dict) -> dict:
    """``{path: (shape, fan_in or None, kind)}`` for every leaf; ``kind``
    is ``matrix``, ``scale`` or ``bias``.  ``c`` is the benchmark's
    configuration (``configs/<name>.json``, ``model`` section)."""
    L, d, H, Hkv = c["n_layers"], c["d_model"], c["n_heads"], c["n_kv_heads"]
    hd, f, V = c["head_dim"], c["d_ff"], c["vocab"]
    out = {
        "embed": ((V, d), d, "embed"),
        "layers/attn/wq": ((L, d, H, hd), d, "query"),
        "layers/attn/wk": ((L, d, Hkv, hd), d, "query"),
        "layers/attn/wv": ((L, d, Hkv, hd), d, "matrix"),
        "layers/attn/wo": ((L, H, hd, d), H * hd, "matrix"),
        "layers/mlp/w_up": ((L, d, f), d, "matrix"),
        "layers/mlp/w_down": ((L, f, d), f, "matrix"),
    }
    if c["gated_ffn"]:
        out["layers/mlp/w_gate"] = ((L, d, f), d, "matrix")
    if not c["tie_embeddings"]:
        out["lm_head"] = ((d, V), d, "matrix")
    if c["norm"] == "layernorm":
        for name in ("layers/ln1", "layers/ln2"):
            out[f"{name}/scale"] = ((L, d), None, "scale")
            out[f"{name}/bias"] = ((L, d), None, "bias")
        out["ln_f/scale"] = ((d,), None, "scale")
        out["ln_f/bias"] = ((d,), None, "bias")
    return out


def _tree(c: dict, leaves: dict) -> dict:
    """Nest ``{path: array}`` into the program's params tree (norm groups
    that hold no parameter are empty dicts)."""
    tree: dict = {"ln_f": {}, "layers": {"ln1": {}, "ln2": {}}}
    for path, arr in leaves.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = arr
    return tree


def _std(fan_in, kind) -> tuple[float, float]:
    """(mean, standard deviation) of a leaf's normal draws."""
    return {"matrix": (0.0, 1.0 / math.sqrt(fan_in or 1)),
            "query": (0.0, QK_SCALE / math.sqrt(fan_in or 1)),
            "embed": (0.0, EMBED_SCALE / math.sqrt(fan_in or 1)),
            "scale": (1.0, 0.1), "bias": (0.0, 0.1)}[kind]


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, spec: tuple, dtype: str):
    leaves = {}
    for i, (path, shape, mean, std) in enumerate(spec):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        leaves[path] = (mean + std * z).astype(dtype)
    return leaves


def draw_spec(c: dict) -> tuple:
    return tuple((p, s) + _std(fi, k)
                 for p, (s, fi, k) in sorted(shapes(c).items()))


def make_weights(c: dict, seed: int) -> dict:
    """All weights of configuration ``c`` from ``seed``, in one jitted
    call on the default device."""
    return _tree(c, _make(seed_key(seed), draw_spec(c), c["dtype"]))
