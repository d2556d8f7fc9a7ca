"""A small cell of the benchmark, cut from the olmo-1b cell's files, that
the CPU runs in seconds: the same configuration keys, mix keys and
metrics, at small sizes."""

from __future__ import annotations

import copy

import spec

SIZES = {"hidden_size": 64, "intermediate_size": 128,
         "num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 4, "vocab_size": 256,
         "torch_dtype": "float32"}
# f32 program against the f32 reference: a wrong token reads O(1)
LIMIT = 1e-3
OVERRIDES = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
             "head_dim": 16, "d_ff": 128, "vocab": 256,
             "param_dtype": "float32", "compute_dtype": "float32"}


def cell(traffic_name: str = "decode-heavy", *, rate: float = 20.0,
         limit: float | None = LIMIT, config: str = "olmo-1b",
         root: str = spec.ROOT,
         mesh: tuple[int, int] | None = None) -> spec.Cell:
    """The small cell; with ``mesh`` (data, model), its configuration
    states that mesh and the cell asks for as many chips."""
    try:
        base = spec.load_cell(f"{config}.{traffic_name}", root)
    except spec.SpecError:  # a mix no cell of the benchmark uses
        base = spec.load_cell(f"{config}.decode-heavy", root)
        base.traffic = spec.load_json(
            f"{root}/bench/traffic/{traffic_name}.json")
        base.traffic_name = traffic_name
    cfg = copy.deepcopy(base.config)
    cfg.update(SIZES)
    cfg.pop("head_dim", None)
    cfg["program"] = {"arch": cfg["program"]["arch"],
                      "overrides": dict(OVERRIDES)}
    if config.startswith("minitron"):
        cfg["num_key_value_heads"] = 2
        cfg["program"]["overrides"]["n_kv_heads"] = 2
    if mesh is not None:
        cfg["mesh"] = {"data": mesh[0], "model": mesh[1]}
    mix = copy.deepcopy(base.traffic)
    mix.update(prompt=dict(mix["prompt"], median=12, min=8, max=32),
               output=dict(mix["output"], median=12, min=6, max=24),
               max_len=64, batch_slots=4, ramp_s=0.5, check_requests=8)
    return spec.Cell(
        name=base.name, chips=mesh[0] * mesh[1] if mesh else 1,
        config_name=base.config_name, config=cfg,
        model=spec.model_sizes(cfg), traffic_name=base.traffic_name,
        traffic=mix,
        cell_file={"rate_per_s": rate, "limits": {"max_logit_gap": limit}},
        end_to_end=base.end_to_end, per_layer=base.per_layer, root=root,
        mesh_shape=mesh)
