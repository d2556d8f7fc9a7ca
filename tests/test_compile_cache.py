"""The entry points' persistent compilation cache lands where it should:
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` at the root
the entry point names.  Each case runs in a fresh process, because the
cache is process-wide JAX state."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = textwrap.dedent("""
    import json, os, sys
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache(sys.argv[1])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
    print(json.dumps({"path": path, "entries": sorted(os.listdir(path))}))
""")


@pytest.mark.parametrize("from_env", [True, False],
                         ids=["env_var", "checkout_default"])
def test_compile_cache_lands_where_configured(tmp_path, from_env):
    root = tmp_path / "checkout"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    expected = str(root / ".jax_cache")
    if from_env:
        expected = str(tmp_path / "shared_cache")
        env["JAX_COMPILATION_CACHE_DIR"] = expected
    out = subprocess.run([sys.executable, "-c", PROBE, str(root)],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc["path"] == expected
    assert any(e.endswith("-cache") for e in doc["entries"]), doc
    if from_env:
        assert not (root / ".jax_cache").exists()
