"""Random weights for a dense decoder, made from the seed by the benchmark.

The benchmark owns the weights so that the plain reference never reads an
array the program under test made.  One jitted call draws every leaf, in
the dtype the configuration serves, with the counter-based ``rbg``
generator (threefry over 2-10 GB of weights costs tens of seconds of
set-up on a v5e).  The tree has the layout the program's dense decoder
consumes: stacked ``[L, ...]`` layer leaves under ``layers``, ``embed``
``[V, d]``, ``lm_head`` ``[d, V]`` when untied.  Each matrix is normal
with standard deviation ``1/sqrt(fan_in)``, the embedding ``EMBED_SCALE``
and the query and key projections ``QK_SCALE`` times that (see below);
layernorm scales are ``1 + 0.1 N(0, 1)`` and biases ``0.1 N(0, 1)``, so a
path that drops them reads differently from one that applies them.

The draw runs on the default device or, given a mesh, on every chip of
it, each chip drawing only its own shard of ``shardings``, so that no chip
ever holds a whole matrix; the program then places the weights with its
own planner.  On a mesh the same seed draws other values than on one chip
(a shard's draw is keyed by the seed and the shard's index).  That does
no harm: the reference reads the very arrays the engine was given, and no
cell compares runs across meshes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec


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


def _normal(key, i: int, shape: tuple, mean: float, std: float, dtype: str):
    z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
    return (mean + std * z).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, spec: tuple, dtype: str):
    return {path: _normal(key, i, shape, mean, std, dtype)
            for i, (path, shape, mean, std) in enumerate(spec)}


def draw_spec(c: dict) -> tuple:
    return tuple((p, s) + _std(fi, k)
                 for p, (s, fi, k) in sorted(shapes(c).items()))


def shardings(c: dict, mesh) -> dict:
    """``{path: NamedSharding}`` on ``mesh``: each matrix split over the
    ``model`` axis on its largest dimension that the axis divides (never
    the leading layer axis of a ``layers/*`` leaf, so that every layer is
    split alike), the norms' vectors replicated, nothing split over
    ``data``."""
    n = mesh.shape["model"]
    out = {}
    for path, (shape, fan_in, _) in shapes(c).items():
        spec = [None] * len(shape)
        if fan_in is not None:
            first = 1 if path.startswith("layers/") else 0
            dims = [i for i in range(first, len(shape)) if shape[i] % n == 0]
            if not dims:
                raise ValueError(f"no dimension of {path} {shape} splits "
                                 f"over {n} chips")
            spec[max(dims, key=lambda i: shape[i])] = "model"
        out[path] = NamedSharding(mesh, PartitionSpec(*spec))
    return out


def make_weights(c: dict, seed: int, mesh=None) -> dict:
    """All weights of configuration ``c`` from ``seed``, in one jitted
    call: on the default device, or on ``mesh`` with each chip drawing
    only its own shard of ``shardings(c, mesh)`` (the ``rbg`` generator
    is not partitioned: drawn whole and then split, a 32-layer 4096-wide
    ``w_up`` would put 8.6 GB of random bits on every chip)."""
    spec = draw_spec(c)
    key = seed_key(seed)
    if mesh is None:
        return _tree(c, _make(key, spec, c["dtype"]))
    specs = {p: s.spec for p, s in shardings(c, mesh).items()}
    n = mesh.shape["model"]

    def shard(key):
        here = jax.random.fold_in(key, jax.lax.axis_index("model"))
        out = {}
        for i, (path, shape, mean, std) in enumerate(spec):
            split = tuple(specs[path])
            local = tuple(d // n if a else d for d, a in zip(shape, split))
            out[path] = _normal(here if any(split) else key, i, local, mean,
                                std, c["dtype"])
        return out

    draw = jax.jit(jax.shard_map(shard, mesh=mesh, in_specs=PartitionSpec(),
                                 out_specs=specs))
    return _tree(c, draw(key))
