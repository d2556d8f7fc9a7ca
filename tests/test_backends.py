"""GemvBackend registry: resolution and override, per-backend kernel sets,
cost-model monotonicity, autotune-table namespacing, the CPU backend's
no-interpret-Pallas guarantee, and thread-safe dispatch."""

import json
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch, ops
from repro.kernels.backends import (
    AutotuneTable,
    CostModel,
    GemvBackend,
    available_backends,
    backend_for_platform,
    get_backend,
    register_backend,
    resolve_backend,
)
from repro.kernels.backends.cpu import cpu_splitk_gemv, plan_cpu_splitk
from repro.kernels.backends.gpu import plan_triton_gemv
from repro.kernels.dispatch import DispatchPolicy

RNG = np.random.default_rng(11)


@pytest.fixture(autouse=True)
def _fresh_caches():
    dispatch.clear_plan_cache()
    dispatch.clear_autotune_table()
    yield
    dispatch.clear_plan_cache()
    dispatch.clear_autotune_table()


def _mk(M, K, B):
    w = RNG.standard_normal((M, K)).astype(np.float32)
    x = RNG.standard_normal((B, K)).astype(np.float32)
    return w, x


# --------------------------------------------------------------------------
# Registry + resolution
# --------------------------------------------------------------------------


def test_registry_ships_three_backends():
    assert {"cpu", "gpu", "tpu"} <= set(available_backends())
    for name in ("cpu", "gpu", "tpu"):
        b = get_backend(name)
        assert b.name == name
        assert "ref" in b.kernels
        assert isinstance(b.cost_model, CostModel)


def test_unknown_backend_is_an_error():
    with pytest.raises(ValueError, match="unknown GEMV backend"):
        get_backend("npu")
    with pytest.raises(ValueError, match="unknown GEMV backend"):
        resolve_backend(DispatchPolicy(backend="npu"))


def test_resolution_order():
    # explicit backend override wins over everything, incl. interpret
    assert resolve_backend(
        DispatchPolicy(backend="cpu", interpret=True)).name == "cpu"
    assert resolve_backend(DispatchPolicy(backend="gpu")).name == "gpu"
    # explicit interpret opt-in -> the TPU validation harness
    assert resolve_backend(DispatchPolicy(interpret=True)).name == "tpu"
    # otherwise the platform decides (this container is CPU)
    assert resolve_backend(DispatchPolicy()).name == "cpu"
    assert resolve_backend(None).name == "cpu"


def test_platform_mapping_covers_gpu_spellings():
    for platform in ("gpu", "cuda", "rocm"):
        assert backend_for_platform(platform).name == "gpu"
    assert backend_for_platform("tpu").name == "tpu"
    # an unclaimed platform is an error, never a silent cpu fallback
    with pytest.raises(ValueError, match="no GEMV backend claims"):
        backend_for_platform("weird-accelerator")


def test_register_backend_rejects_anonymous_and_allows_custom():
    with pytest.raises(ValueError, match="non-empty name"):
        register_backend(GemvBackend())

    class _Toy(GemvBackend):
        name = "toy-test"
        kernels = ("ref",)

        def select_kernel(self, M, K, batch, **kw):
            return "ref", None

        def execute(self, kernel, x, pw, plan, interpret):
            from repro.kernels import ref
            return ref.gemv_ref(pw.w_t, x)

    register_backend(_Toy())
    assert get_backend("toy-test").name == "toy-test"
    w, x = _mk(64, 32, 1)
    out = dispatch.dispatch_gemv(
        jnp.asarray(x), jnp.asarray(w),
        policy=DispatchPolicy(backend="toy-test"))
    np.testing.assert_allclose(np.asarray(out), x @ w.T, rtol=1e-4,
                               atol=1e-3)


# --------------------------------------------------------------------------
# CPU backend: forced anywhere, never interpret-mode Pallas
# --------------------------------------------------------------------------


CPU_SHAPES = [(6912, 1152, 1, 16), (1152, 6912, 1, 16), (300, 250, 1, 16),
              (2048, 8192, 4, 16), (2048, 2048, 1, 8), (2048, 2048, 1, 4),
              (6912, 1152, 32, 16)]


@pytest.mark.parametrize("M,K,B,bits", CPU_SHAPES)
def test_cpu_auto_picks_are_always_xla(M, K, B, bits):
    """`backend="cpu"` auto picks come from the XLA-native kernel set —
    structurally incapable of interpret-mode Pallas."""
    cpu = get_backend("cpu")
    kernel, plan = cpu.select_kernel(
        M, K, B, bits=bits, policy=DispatchPolicy(backend="cpu"))
    assert kernel in cpu.kernels
    assert kernel in ("ref", "splitk", "quant", "quant4")


def test_cpu_backend_forced_dispatch_matches_oracle():
    w, x = _mk(1152, 6912, 1)
    out = dispatch.dispatch_gemv(
        jnp.asarray(x), jnp.asarray(w),
        policy=DispatchPolicy(backend="cpu"))
    np.testing.assert_allclose(np.asarray(out), x @ w.T, rtol=1e-4,
                               atol=1e-3)
    # tall-K small-M lands on the pre-chunked split-K reduce
    kernel, plan = get_backend("cpu").select_kernel(1152, 6912, 1)
    assert kernel == "splitk" and plan.split_k > 1


def test_cpu_splitk_kernel_matches_oracle():
    w, x = _mk(512, 2048, 3)
    for deg in (2, 4, 8):
        out = cpu_splitk_gemv(jnp.asarray(x), jnp.asarray(w.T), degree=deg)
        np.testing.assert_allclose(np.asarray(out), x @ w.T, rtol=1e-4,
                                   atol=1e-3)


def test_cpu_splitk_plan_builder():
    plan = plan_cpu_splitk(512, 2048, 1)
    assert plan.split_k > 1 and plan.k_blk * plan.split_k == 2048
    assert plan_cpu_splitk(512, 7, 1) is None  # indivisible K: no chunking


def test_cpu_tiny_gemv_stays_on_ref():
    # chunk-setup overhead dominates: the model must keep tiny GEMVs whole
    kernel, _ = get_backend("cpu").select_kernel(128, 64, 1)
    assert kernel == "ref"


# --------------------------------------------------------------------------
# GPU backend: capability-gated Triton
# --------------------------------------------------------------------------


def test_gpu_without_triton_falls_back_to_ref():
    """On this CPU container the capability check fails: auto and pinned
    picks degrade to ref instead of raising at lowering time."""
    gpu = get_backend("gpu")
    k, plan = gpu.select_kernel(262144, 1152, 1)  # lm_head-sized
    assert (k, plan) == ("ref", None)
    k, plan = gpu.select_kernel(
        262144, 1152, 1, policy=DispatchPolicy(backend="gpu",
                                               kernel="triton"))
    assert (k, plan) == ("ref", None)
    w, x = _mk(512, 256, 1)
    out = dispatch.dispatch_gemv(
        jnp.asarray(x), jnp.asarray(w), policy=DispatchPolicy(backend="gpu"))
    np.testing.assert_allclose(np.asarray(out), x @ w.T, rtol=1e-4,
                               atol=1e-3)


def test_gpu_interpret_optin_runs_triton_kernel():
    """interpret=True satisfies the capability check (jnp semantics of the
    same kernel body) — the CPU-hosted validation of the Triton path."""
    gpu = get_backend("gpu")
    pol = DispatchPolicy(backend="gpu", kernel="triton", interpret=True)
    k, plan = gpu.select_kernel(1024, 512, 2, policy=pol)
    assert k == "triton" and plan is not None
    w, x = _mk(1024, 512, 2)
    out = dispatch.dispatch_gemv(jnp.asarray(x), jnp.asarray(w), policy=pol)
    np.testing.assert_allclose(np.asarray(out), x @ w.T, rtol=1e-4,
                               atol=1e-3)


def test_gpu_auto_picks_triton_only_when_grid_fills():
    """The SM-occupancy term: LM-head-sized M fills the grid -> triton;
    mid-sized M underfills -> ref (the library matmul)."""
    gpu = get_backend("gpu")
    pol = DispatchPolicy(backend="gpu", interpret=True)
    k_big, plan = gpu.select_kernel(262144, 1152, 1, policy=pol)
    assert k_big == "triton" and plan.n_m >= gpu.cost_model.min_parallel_blocks
    k_mid, _ = gpu.select_kernel(2048, 2048, 1, policy=pol)
    assert k_mid == "ref"


def test_gpu_plan_builder_pow2_blocks():
    plan = plan_triton_gemv(6912, 1152, 1)
    assert plan.m_blk & (plan.m_blk - 1) == 0 and 6912 % plan.m_blk == 0
    assert plan.k_blk & (plan.k_blk - 1) == 0 and plan.n_k * plan.k_blk == 1152
    assert plan_triton_gemv(300, 1152, 1) is None  # no >=64 pow2 M divisor


# --------------------------------------------------------------------------
# Cost-model monotonicity (per backend)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["cpu", "gpu", "tpu"])
def test_ref_cost_monotonic_in_shape(name):
    """More bytes must never be modeled cheaper: ref cost grows with each
    of M, K, and batch on every backend."""
    b = get_backend(name)
    base = b.estimate_cost_us("ref", 1024, 1024, 1)
    assert b.estimate_cost_us("ref", 2048, 1024, 1) > base
    assert b.estimate_cost_us("ref", 1024, 2048, 1) > base
    assert b.estimate_cost_us("ref", 1024, 1024, 4) > base
    # and scaling every dim together dominates scaling one
    assert b.estimate_cost_us("ref", 2048, 2048, 4) > \
        b.estimate_cost_us("ref", 2048, 1024, 1)


@pytest.mark.parametrize("name,kernel,planner", [
    ("cpu", "splitk", lambda M, K: plan_cpu_splitk(M, K, 1)),
    ("gpu", "triton", lambda M, K: plan_triton_gemv(M, K, 1)),
])
def test_planned_cost_monotonic_in_weight_bytes(name, kernel, planner):
    b = get_backend(name)
    small = b.estimate_cost_us(kernel, 1024, 2048, 1,
                               plan=planner(1024, 2048))
    big = b.estimate_cost_us(kernel, 4096, 8192, 1,
                             plan=planner(4096, 8192))
    assert big > small


def test_backend_default_interpret_is_per_backend():
    """policy.interpret=None must not force interpret mode off-TPU for the
    native backends: only the TPU backend is the interpret harness (so a
    real GPU host runs its picked Triton kernel lowered, not interpreted)."""
    assert get_backend("tpu").default_interpret() is True   # CPU host
    assert get_backend("cpu").default_interpret() is False
    assert get_backend("gpu").default_interpret() is False


def test_cost_models_are_frozen_and_distinct():
    seen = {}
    for name in ("cpu", "gpu", "tpu"):
        cm = get_backend(name).cost_model
        with pytest.raises(Exception):  # frozen dataclass
            cm.bandwidth_gbps = 1.0
        seen[name] = cm.bandwidth_gbps
    assert len(set(seen.values())) == 3  # per-memory-system constants


# --------------------------------------------------------------------------
# Autotune: per-backend namespaces in one JSON file
# --------------------------------------------------------------------------


def test_two_backends_one_table_roundtrip(tmp_path):
    """Acceptance: tables written by two different backends merge into one
    JSON file without key collisions (save -> load -> merge round-trip)."""
    table_path = str(tmp_path / "fleet.json")
    w, x = _mk(256, 512, 1)
    for backend in ("cpu", "tpu"):
        pol = DispatchPolicy(backend=backend, autotune=True,
                             table_path=table_path, interpret=True)
        out = dispatch.dispatch_gemv(jnp.asarray(x), jnp.asarray(w),
                                     policy=pol)
        np.testing.assert_allclose(np.asarray(out), x @ w.T, rtol=1e-4,
                                   atol=1e-3)
    doc = json.load(open(table_path))
    assert set(doc["tables"]) == {"cpu", "tpu"}
    # same shape key in both namespaces — namespacing is what prevents the
    # collision a flat table would have
    (cpu_key,) = doc["tables"]["cpu"]
    (tpu_key,) = doc["tables"]["tpu"]
    assert cpu_key == tpu_key
    assert doc["tables"]["cpu"][cpu_key]["kernel"] in ("ref", "splitk")
    assert doc["tables"]["tpu"][tpu_key]["kernel"] in ("ref", "pim",
                                                       "splitk")

    # fresh process: load once, both backends reuse their own entries
    dispatch.clear_plan_cache()
    dispatch.clear_autotune_table()
    parsed = dispatch.load_autotune_table(table_path)
    assert set(parsed) == {"cpu", "tpu"}
    for backend in ("cpu", "tpu"):
        entry = dispatch._AUTOTUNE_TABLE.get(backend, cpu_key)
        assert entry == doc["tables"][backend][cpu_key]


def test_table_save_merges_namespaces_not_files(tmp_path):
    """A CPU tuner must not erase a TPU tuner's entries for other shapes."""
    table_path = str(tmp_path / "t.json")
    t1 = AutotuneTable()
    t1.put("tpu", "shapeA", {"kernel": "pim", "us": 1.0})
    t1.save(table_path)
    t2 = AutotuneTable()   # a different process
    t2.put("cpu", "shapeB", {"kernel": "splitk", "us": 2.0})
    t2.put("tpu", "shapeC", {"kernel": "ref", "us": 3.0})
    t2.save(table_path)
    merged = json.load(open(table_path))["tables"]
    assert set(merged) == {"cpu", "tpu"}
    assert set(merged["tpu"]) == {"shapeA", "shapeC"}
    assert set(merged["cpu"]) == {"shapeB"}


def test_autotuned_cpu_entries_never_name_pallas_kernels(tmp_path):
    """Acceptance: the CPU backend's *measured* winners are XLA kernels too
    (autotune times its own candidate set, not the TPU's)."""
    pol = DispatchPolicy(backend="cpu", autotune=True,
                         table_path=str(tmp_path / "t.json"))
    w, x = _mk(512, 1024, 1)
    dispatch.dispatch_gemv(jnp.asarray(x), jnp.asarray(w), policy=pol)
    snap = dispatch._AUTOTUNE_TABLE.snapshot()
    assert set(snap) == {"cpu"}
    for entry in snap["cpu"].values():
        assert entry["kernel"] in ("ref", "splitk", "quant", "quant4")


# --------------------------------------------------------------------------
# Thread safety (Engine stepped from a thread pool)
# --------------------------------------------------------------------------


def test_concurrent_dispatch_keeps_cache_stats_consistent():
    """N threads x M dispatches over a handful of shapes: with the lock, no
    lost updates — hits + misses == total resolutions, and every resolved
    decision is present in the cache."""
    shapes = [(1024, 512), (512, 1024), (2048, 256), (256, 2048)]
    weights = {s: ops.pack_weight(jnp.asarray(
        RNG.standard_normal(s).astype(np.float32))) for s in shapes}
    xs = {s: jnp.asarray(RNG.standard_normal((1, s[1])).astype(np.float32))
          for s in shapes}
    pol = DispatchPolicy(backend="cpu")
    reps, errors = 8, []

    def worker():
        try:
            for _ in range(reps):
                for s in shapes:
                    dispatch.dispatch_gemv(xs[s], weights[s], policy=pol)
        except Exception as e:  # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    stats = dispatch.plan_cache_stats()
    assert stats["hits"] + stats["misses"] == 8 * reps * len(shapes)
    # every shape resolved exactly one cached decision
    assert stats["misses"] >= len(shapes)


def test_concurrent_autotune_table_puts_do_not_lose_entries():
    table = AutotuneTable()

    def worker(tid):
        for i in range(50):
            table.put(f"ns{tid % 3}", f"k{tid}_{i}",
                      {"kernel": "ref", "us": float(i)})

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = table.snapshot()
    assert sum(len(v) for v in snap.values()) == 6 * 50
