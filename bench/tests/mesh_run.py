"""One run of the small cell on a (1, 4) mesh of the CPU's virtual
devices, for ``test_mesh.py``, which starts it with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.  Prints one JSON
line: whether the run was correct, the mesh the engine was given, for
each leaf of the drawn weights its devices and whether each holds a part
of it only, whether the harness kept its own copy beside the engine's,
and checksums of the weights the engine got and the check read."""

import hashlib
import json
import os
import sys
import time

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
sys.path[:0] = [TESTS, BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import run  # noqa: E402
import tiny  # noqa: E402

CPU4 = {"platform": "cpu", "kind": "cpu", "count": 4}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def digest(tree) -> str:
    h = hashlib.sha256()
    for x in jax.tree_util.tree_leaves(tree):
        h.update(np.asarray(x).tobytes())
    return h.hexdigest()


def main() -> None:
    seen = {}
    engine_for = run.engine_for

    def spy(cell, cfg, params, tracer=None, mesh=None):
        eng = engine_for(cell, cfg, params, tracer, mesh)
        seen["engine_mesh"] = None if eng.mesh is None else dict(
            eng.mesh.shape)
        seen["weights"] = {
            "/".join(str(k.key) for k in path): {
                "devices": len(x.sharding.device_set),
                "part_only": all(np.prod(s.data.shape) < x.size
                                 for s in x.addressable_shards)}
            for path, x in jax.tree_util.tree_leaves_with_path(params)}
        seen["given"] = digest(params)
        return eng

    check_weights = run.check_weights

    def spy_check(cell, seed, w):
        seen["kept"] = w is not None
        out = check_weights(cell, seed, w)
        seen["checked"] = digest(out)
        return out

    run.engine_for = spy
    run.check_weights = spy_check
    r = run.run_cell(tiny.cell(mesh=(1, 4)), 2 ** 31 + 23, 1.5, False, CPU4,
                     PEAKS, t_start=time.perf_counter(), log=lambda s: None)
    print(json.dumps(dict(seen, correct=r["correct"], check=r["check"],
                          attempted=r["attempted"], failed=r["failed"])))


if __name__ == "__main__":
    main()
