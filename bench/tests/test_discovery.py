"""A configuration, a traffic mix, a cell and a per-layer metric are added
by adding files and ``BENCHMARK.json`` entries: the harness finds each by
its name, and no file of the harness changes."""

import json
import os
import shutil
import time

import spec
import tiny
import run

NEW_METRIC = '''"""Test metric: the traced window's length in seconds."""


def read(ctx):
    return ctx.window_s()
'''


def checkout_with_additions(tmp_path):
    """A copy of the benchmark with a new mix, cell, configuration and
    metric added as files; returns its root and the files of the harness
    (name -> bytes) before the additions."""
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: (root / "bench" / p).read_bytes()
              for p in ("run.py", "spec.py", "serve.py", "traffic.py",
                        "reduce.py", "check.py", "costs.py", "weights.py")}
    bench = spec.load_benchmark()
    mix = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                      "chat-bursty.json"))
    mix["arrivals"] = {"process": "poisson"}
    (root / "bench/traffic/chat-steady.json").write_text(json.dumps(mix))
    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                      "olmo-1b.json"))
    (root / "bench/configs/olmo-1b-copy.json").write_text(json.dumps(cfg))
    (root / "bench/cells/olmo-1b-copy.chat-steady.json").write_text(
        json.dumps({"rate_per_s": 1.0, "limits": {"max_logit_gap": 1.0}}))
    (root / "bench/layer_metrics/test.window_s.py").write_text(NEW_METRIC)
    bench["configs"].append(dict(bench["configs"][0], name="olmo-1b-copy",
                                 file="bench/configs/olmo-1b-copy.json"))
    bench["workloads"].append({"name": "olmo-1b-copy.chat-steady",
                               "config": "olmo-1b-copy",
                               "traffic": "chat-steady", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "test.window_s", "unit": "s",
                               "better": "lower", "source": "device_trace",
                               "layer": "device", "moves": "itl_p50_ms",
                               "workloads": ["olmo-1b-copy.chat-steady"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root), before


def test_new_files_are_found_by_name(tmp_path):
    root, before = checkout_with_additions(tmp_path)
    cell = spec.load_cell("olmo-1b-copy.chat-steady", root)
    assert cell.traffic["arrivals"] == {"process": "poisson"}
    assert cell.config_name == "olmo-1b-copy"
    assert cell.cell_file["rate_per_s"] == 1.0
    assert "test.window_s" in {m["name"] for m in cell.per_layer}
    # the cells that were there do not get the new metric
    old = spec.load_cell("olmo-1b.decode-heavy", root)
    assert "test.window_s" not in {m["name"] for m in old.per_layer}
    for p, data in before.items():
        assert (tmp_path / "checkout" / "bench" / p).read_bytes() == data


def test_new_metric_is_reported_by_a_traced_run(tmp_path):
    root, _ = checkout_with_additions(tmp_path)
    cell = tiny.cell("chat-steady", config="olmo-1b-copy", root=root)
    r = run.run_cell(cell, 7, 1.0, True, {"platform": "cpu", "kind": "cpu",
                                          "count": 1},
                     {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
                     t_start=time.perf_counter(), log=lambda s: None)
    assert abs(r["metrics"]["test.window_s"]["value"] - 1.0) < 0.5
    assert r["correct"]
