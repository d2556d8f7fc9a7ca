"""What a cell is made of, read from ``BENCHMARK.json`` and the files it
names: the configuration (``configs/<config>.json``), the traffic mix
(``traffic/<traffic>.json``), the per-layer metric readers
(``layer_metrics/<metric>.py``) and the table of peaks (``peaks.json``).
Nothing here is specific to one cell; a new cell is new files."""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """The benchmark's files do not describe the cell asked for."""


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing file {path}") from e


def model_sizes(c: dict) -> dict:
    """The sizes and equations the benchmark computes with, from a
    configuration file's published keys."""
    eq = c["equations"]
    H = c["num_attention_heads"]
    return {
        "n_layers": c["num_hidden_layers"],
        "d_model": c["hidden_size"],
        "n_heads": H,
        "n_kv_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim") or c["hidden_size"] // H,
        "d_ff": c["intermediate_size"],
        "vocab": c["vocab_size"],
        "act": c["hidden_act"],
        "gated_ffn": c["hidden_act"] == "silu",
        "tie_embeddings": c["tie_word_embeddings"],
        "rope_theta": float(c["rope_theta"]),
        "rotary_fraction": float(eq["rotary_fraction"]),
        "norm": eq["norm"],
        "norm_eps": float(eq["norm_eps"]),
        "embed_scale": eq["embed_scale"],
        "dtype": c["torch_dtype"],
    }


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # configs/<config>.json as written
    model: dict           # model_sizes(config)
    traffic_name: str
    traffic: dict         # traffic/<traffic>.json as written
    cell_file: dict       # cells/<cell>.json: rate, limits, cuts
    end_to_end: list      # BENCHMARK.json metrics this cell reports
    per_layer: list
    root: str = ROOT
    # (data, model): the configuration's ``mesh``; None on one chip
    mesh_shape: tuple[int, int] | None = None

    @property
    def slots(self) -> int:
        """KV slots: the mix's, unless the cell cuts them to fit."""
        return int(self.cell_file.get("batch_slots",
                                      self.traffic["batch_slots"]))


def mesh_shape(config: dict, chips: int) -> tuple[int, int] | None:
    """A configuration without ``mesh`` runs on one chip; one with
    ``"mesh": {"data": D, "model": M}`` on exactly ``D * M`` chips."""
    if "mesh" not in config:
        if chips != 1:
            raise SpecError(f"the cell asks for {chips} chips, but its "
                            f"configuration states no mesh")
        return None
    try:
        shape = (int(config["mesh"]["data"]), int(config["mesh"]["model"]))
    except (KeyError, TypeError, ValueError) as e:
        raise SpecError(f"mesh wants {{\"data\": D, \"model\": M}}, not "
                        f"{config['mesh']!r}") from e
    if min(shape) < 1 or shape[0] * shape[1] != chips:
        raise SpecError(f"the configuration's mesh {shape} (data, model) "
                        f"does not cover the cell's {chips} chips")
    return shape


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; BENCHMARK.json has "
                        f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    bench_dir = os.path.join(root, "bench")
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     f"{w['traffic']}.json"))
    cell_file = load_json(os.path.join(bench_dir, "cells", f"{name}.json"))
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=config, model=model_sizes(config),
        traffic_name=w["traffic"], traffic=traffic, cell_file=cell_file,
        root=root, mesh_shape=mesh_shape(config, int(w["chips"])),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def load_peaks(device_kind: str, root: str = ROOT) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    peaks = load_json(os.path.join(root, "bench", "peaks.json"))["devices"]
    if device_kind not in peaks:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"bench/peaks.json ({sorted(peaks)}); no default")
    return peaks[device_kind]


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise SpecError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_metric_reader(metric: str, root: str = ROOT):
    """The ``read(ctx)`` function of ``layer_metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "layer_metrics", f"{metric}.py")
    if not os.path.exists(path):
        raise SpecError(f"no reader {path} for per-layer metric {metric!r}")
    return load_module(path, f"layer_metric_{metric.replace('.', '_')}").read


def reference_module(config: dict, root: str = ROOT):
    """``references/<reference>.py``: the configuration's plain float32
    reference."""
    path = os.path.join(root, "bench", "references",
                        f"{config['reference']}.py")
    return load_module(path, f"reference_{config['reference']}")
