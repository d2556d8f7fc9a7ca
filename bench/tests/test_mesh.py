"""A cell whose configuration states a mesh runs on that many chips: the
layout is checked against the cell's chips, the weights are drawn split
over the mesh, and the engine is given it.  The run on four devices uses
the CPU's virtual devices in a process of its own (``mesh_run.py``)."""

import hashlib
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType

import spec
import tiny
import weights
from test_discovery import checkout_with_additions

TESTS = os.path.dirname(os.path.abspath(__file__))


def abstract_mesh(data: int, model: int):
    return AbstractMesh((data, model), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)


def test_four_chip_cell_runs_on_its_mesh():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.join(TESTS, "mesh_run.py")],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"], r["check"]
    assert r["attempted"] > 10 and r["failed"] == 0
    assert r["engine_mesh"] == {"data": 1, "model": 4}
    # no second copy of the weights through the window; the check draws
    # the engine's weights again from the seed
    assert not r["kept"] and r["checked"] == r["given"]
    matrices = [k for k in r["weights"] if k.startswith("layers/")]
    assert len(matrices) == 7
    for path in matrices + ["embed"]:
        assert r["weights"][path] == {"devices": 4, "part_only": True}, path


@pytest.mark.parametrize("mesh,chips,ok", [
    (None, 1, True), ((1, 4), 4, True), ((2, 2), 4, True),
    (None, 4, False), ((1, 2), 4, False), ((1, 4), 1, False),
    ((-1, -4), 4, False), ("1x4", 4, False)])
def test_mesh_must_cover_the_cells_chips(mesh, chips, ok):
    config = {} if mesh is None else {"mesh": mesh if isinstance(mesh, str)
                                      else {"data": mesh[0],
                                            "model": mesh[1]}}
    if ok:
        assert spec.mesh_shape(config, chips) == mesh
    else:
        with pytest.raises(spec.SpecError):
            spec.mesh_shape(config, chips)


def _four_chip_checkout(tmp_path, mesh):
    """A checkout with one more configuration (olmo-1b's, with ``mesh``)
    and a four-chip cell of it, added as files and entries only."""
    root, before = checkout_with_additions(tmp_path)
    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                      "olmo-1b.json"))
    if mesh is not None:
        cfg["mesh"] = mesh
    with open(os.path.join(root, "bench/configs/olmo-1b-x4.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "bench/cells/olmo-1b-x4.decode-heavy.json"),
              "w") as f:
        json.dump({"rate_per_s": 1.0, "limits": {"max_logit_gap": 1.0}}, f)
    bench = spec.load_benchmark(root)
    bench["configs"].append(dict(bench["configs"][0], name="olmo-1b-x4",
                                 file="bench/configs/olmo-1b-x4.json"))
    bench["workloads"].append({"name": "olmo-1b-x4.decode-heavy",
                               "config": "olmo-1b-x4",
                               "traffic": "decode-heavy", "chips": 4,
                               "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, before


def test_four_chip_cell_is_added_by_new_files_only(tmp_path):
    root, before = _four_chip_checkout(tmp_path, {"data": 1, "model": 4})
    cell = spec.load_cell("olmo-1b-x4.decode-heavy", root)
    assert cell.chips == 4 and cell.mesh_shape == (1, 4)
    assert spec.load_cell("olmo-1b.decode-heavy", root).mesh_shape is None
    for p, data in before.items():
        assert (tmp_path / "checkout" / "bench" / p).read_bytes() == data


def test_four_chip_cell_without_a_mesh_is_refused(tmp_path):
    root, _ = _four_chip_checkout(tmp_path, None)
    with pytest.raises(spec.SpecError, match="no mesh"):
        spec.load_cell("olmo-1b-x4.decode-heavy", root)


@pytest.mark.parametrize("config", ["olmo-1b", "minitron-8b-16L"])
def test_every_matrix_is_split_and_no_layer_axis(config):
    m = spec.model_sizes(spec.load_json(os.path.join(
        spec.BENCH_DIR, "configs", f"{config}.json")))
    shapes = weights.shapes(m)
    for path, sharding in weights.shardings(m, abstract_mesh(1, 4)).items():
        shape, fan_in, _ = shapes[path]
        split = [i for i, a in enumerate(sharding.spec) if a is not None]
        if fan_in is None:                      # a norm's vector
            assert split == [], path
            continue
        assert [sharding.spec[i] for i in split] == ["model"], path
        (i,) = split
        assert not (path.startswith("layers/") and i == 0), path
        assert shape[i] % 4 == 0
        first = 1 if path.startswith("layers/") else 0
        assert shape[i] == max(d for d in shape[first:] if d % 4 == 0)


def test_one_chip_draw_is_unchanged():
    """Without a mesh the draw is what it was before meshes were drawn:
    the checksums of each leaf were recorded then."""
    rec = spec.load_json(os.path.join(TESTS, "data", "one_chip_draw.json"))
    for dtype, want in rec["sha256"].items():
        w = weights.make_weights(dict(tiny.cell().model, dtype=dtype),
                                 rec["seed"])
        got = {"/".join(str(k.key) for k in path):
               hashlib.sha256(np.asarray(x).tobytes()).hexdigest()
               for path, x in jax.tree_util.tree_leaves_with_path(w)}
        assert got == want, dtype
