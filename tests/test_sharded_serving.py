"""Sharded serving tests (DESIGN.md §9): the slot-managed engine on a
device mesh.

In-process tests cover the pure pieces — the ShardedPlan even-distribution
test and the serving cache placement rules.  Engine execution tests run in
SUBPROCESSES with ``--xla_force_host_platform_device_count=8`` so the main
test process keeps the single real CPU device (the dry-run isolation rule,
same pattern as test_distributed.py).

The load-bearing acceptance test: a (1, N)-mesh engine produces
token-identical greedy output to the single-host engine on mixed prompt
lengths, while ``dispatch_stats()`` shows kernels picked from per-shard
(M/N or K/N) shapes — and the per-shard decision counts sum to exactly the
unsharded run's counters.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.distributed import sharding as shd
from repro.kernels.backends import DispatchPolicy, ProgramKey, ShardedPlan
from repro.kernels.dispatch import _shard_program_key

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout: int = 900) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    tail = out.stdout.strip().splitlines()[-1]
    return json.loads(tail)


# --------------------------------------------------------------------------
# ShardedPlan: Algorithm 1's even-distribution test at the mesh level
# --------------------------------------------------------------------------


def test_sharded_plan_row_placement_first():
    sp = ShardedPlan.place(256, 192, 4)
    assert sp.axis == "M" and sp.shard_shape(256, 192) == (64, 192)


def test_sharded_plan_splitk_fallback():
    # M=250 does not divide 4; K=192 does -> split-K placement
    sp = ShardedPlan.place(250, 192, 4)
    assert sp.axis == "K" and sp.shard_shape(250, 192) == (250, 48)


def test_sharded_plan_replicated_when_nothing_divides():
    sp = ShardedPlan.place(250, 190, 4)
    assert sp.axis == "replicated"
    assert sp.shard_shape(250, 190) == (250, 190)
    assert ShardedPlan.place(256, 192, 1).axis == "replicated"


def test_shard_program_key_prefers_experts_then_rows():
    pol = DispatchPolicy(model_shards=4)
    grouped = ProgramKey(kind="grouped", Ms=(128,), K=64, batch=2, group=8,
                         bits=16, block=32, dtype="float32", backend="cpu")
    key, axis = _shard_program_key(grouped, pol)
    assert axis == "E" and key.group == 2 and key.Ms == (128,)
    fused = ProgramKey(kind="fused", Ms=(64, 64, 64), K=96, batch=1,
                       group=3, bits=16, block=32, dtype="float32",
                       backend="cpu")
    key, axis = _shard_program_key(fused, pol)
    assert axis == "M" and key.Ms == (16, 16, 16) and key.K == 96
    odd = ProgramKey(kind="fused", Ms=(30, 30), K=96, batch=1, group=2,
                     bits=16, block=32, dtype="float32", backend="cpu")
    key, axis = _shard_program_key(odd, pol)
    assert axis == "K" and key.K == 24 and key.Ms == (30, 30)


@pytest.mark.parametrize("axis", ["M", "K", "replicated"])
def test_tpu_kernels_run_per_shard_on_mesh(axis):
    """Mosaic kernels cannot be partitioned by GSPMD, so on a mesh each
    TPU kernel runs inside shard_map on its placement's shard: output rows
    (M), split-K partials summed over 'model' (K), or the whole GEMV
    (replicated).  Interpret mode here; every result must equal x @ W."""
    res = run_sub(f"""
import json
import numpy as np
import jax
from repro.distributed.axes import activation_mesh
from repro.kernels import dispatch
from repro.kernels.backends import get_backend
from repro.kernels.dispatch import DispatchPolicy, _execute_per_shard
from repro.kernels.ops import PackedWeights
from repro.launch.mesh import make_mesh

mesh = make_mesh((1, 4), ("data", "model"))
rng = np.random.default_rng(0)
x = rng.standard_normal((2, 1024)).astype(np.float32)
w = rng.standard_normal((1024, 512)).astype(np.float32)
pol = DispatchPolicy(interpret=True, model_shards=4)
tpu = get_backend("tpu")
err = {{}}
for kernel in ("pim", "splitk"):
    f = jax.jit(lambda a, b: _execute_per_shard(
        tpu, kernel, a, PackedWeights(w_t=b), "{axis}", pol, True, mesh))
    err[kernel] = float(np.abs(np.asarray(f(x, w)) - x @ w).max())
# the dispatcher takes the per-shard path on its own under a mesh
with activation_mesh(mesh):
    out = jax.jit(lambda a, b: dispatch.dispatch_gemv(
        a, dispatch.from_transposed(b),
        policy=DispatchPolicy(interpret=True, model_shards=4,
                              kernel="pim")))(x, w)
err["dispatch"] = float(np.abs(np.asarray(out) - x @ w).max())
print(json.dumps(err))
""", devices=4)
    assert max(res.values()) < 1e-3, res


# --------------------------------------------------------------------------
# Serving cache placement rules (pure functions of shapes + mesh)
# --------------------------------------------------------------------------


class FakeMesh:
    def __init__(self, shape: dict):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_serve_cache_kv_shards_heads_only():
    mesh = FakeMesh({"data": 2, "model": 4})
    spec = shd.serve_cache_spec(mesh, None, (4, 8, 64, 8, 16), "k")
    # heads on model; batch (defrag axis) and sequence NEVER sharded
    assert tuple(spec) == (None, None, None, "model", None)
    # kv heads that don't divide: fully replicated, no sequence fallback
    spec = shd.serve_cache_spec(mesh, None, (4, 8, 64, 1, 16), "k")
    assert all(s is None for s in spec)


def test_serve_cache_pos_replicated():
    mesh = FakeMesh({"data": 2, "model": 4})
    assert all(s is None
               for s in shd.serve_cache_spec(mesh, None, (8,), "pos"))
    # recurrent state: channel dim on model
    spec = shd.serve_cache_spec(mesh, None, (4, 8, 2, 32, 32), "rwkv_s")
    assert "model" in tuple(spec) and spec[1] is None


# --------------------------------------------------------------------------
# Engine execution on a mesh (subprocess, forced host devices)
# --------------------------------------------------------------------------

_SERVE_BOTH = """
import json
import numpy as np
import jax
from repro.configs.registry import ARCHS
from repro.kernels import dispatch
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.serving.engine import Engine, Request

def serve(arch, mesh_shape, lengths, max_new=4, slots=4, max_len=64):
    cfg = ARCHS[arch].reduced()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, L).astype(np.int32)
               for L in lengths]
    mesh = (make_mesh(mesh_shape, ("data", "model"))
            if mesh_shape else None)
    dispatch.clear_plan_cache()
    eng = Engine(cfg, params, batch_slots=slots, max_len=max_len,
                 mesh=mesh)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=max_new))
    done = {r.rid: r.generated for r in eng.run_until_drained()}
    assert len(done) == len(prompts), (arch, sorted(done))
    return done, dispatch.dispatch_stats()
"""


def test_sharded_engine_token_identity_and_pershard_stats():
    """ACCEPTANCE: (1,2)-mesh greedy decode == single-host greedy decode,
    and the sharded run's kernels were picked from per-shard shapes whose
    decision counts sum to the unsharded counters."""
    r = run_sub(_SERVE_BOTH + textwrap.dedent("""
    lengths = [5, 9, 3, 12, 7]
    single, s_stats = serve("olmo-1b", None, lengths)
    sharded, m_stats = serve("olmo-1b", (1, 2), lengths)
    print(json.dumps({
        "identical": single == sharded,
        "s_picks": s_stats["kernel_picks"],
        "m_picks": m_stats["kernel_picks"],
        "s_modes": s_stats["program_modes"],
        "m_modes": m_stats["program_modes"],
        "s_gemv": [s_stats["gemv_path"], s_stats["matmul_fallback"]],
        "m_gemv": [m_stats["gemv_path"], m_stats["matmul_fallback"]],
        "axes": m_stats["sharded_axes"],
        "shard_picks": m_stats["shard_picks"],
        "s_axes": s_stats["sharded_axes"],
    }))
    """))
    assert r["identical"], "sharded decode diverged from single-host"
    # the single-host run never reasons per-shard
    assert r["s_axes"] == {}
    # the sharded path reasoned about HALVED shapes: every shard_pick key
    # carries the per-shard geometry tag ".../2"
    assert r["shard_picks"], "no per-shard selections recorded"
    assert all(k.endswith("/2") for k in r["shard_picks"])
    assert r["axes"].get("M", 0) > 0  # row placement found (M divides)
    # per-shard dispatch stats sum to the unsharded counters: same decision
    # counts, same batch-gate split — sharding changed the shapes selection
    # reasons about, not how many decisions were made
    assert sum(r["m_picks"].values()) == sum(r["s_picks"].values())
    assert sum(r["m_modes"].values()) == sum(r["s_modes"].values())
    assert r["m_gemv"] == r["s_gemv"]
    assert sum(r["axes"].values()) == (
        sum(r["m_picks"].values()) + sum(r["m_modes"].values()))


def test_sharded_defrag_keeps_prefix_and_shardings():
    """Sharded defrag: actives stay a contiguous prefix, per-slot positions
    travel with their rows, and every cache leaf keeps its ORIGINAL
    placement through splice + free + compact (defrag never reshards)."""
    r = run_sub("""
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.configs.registry import ARCHS
    from repro.launch.mesh import make_mesh
    from repro.models import lm
    from repro.serving.kv_cache import SlotKVCache

    cfg = ARCHS["olmo-1b"].reduced()
    mesh = make_mesh((1, 2), ("data", "model"))
    kv = SlotKVCache(cfg, 4, 16, mesh=mesh)
    specs_before = {k: str(v.sharding.spec) for k, v in kv.cache.items()}
    slots = [kv.alloc() for _ in range(4)]
    sub = lm.init_cache(cfg, 4, 16, per_slot_pos=True)
    sub = {k: v + 1 if k != "pos" else v for k, v in sub.items()}
    kv.splice(sub, slots, [3, 5, 7, 9])
    kv.free(0); kv.free(2)
    moves = kv.compact()
    specs_after = {k: str(v.sharding.spec) for k, v in kv.cache.items()}
    print(json.dumps({
        "moves": {str(k): v for k, v in moves.items()},
        "active": list(kv.active_slots()),
        "pos": np.asarray(kv.cache["pos"]).tolist(),
        "specs_same": specs_before == specs_after,
        "k_spec": specs_before["k"],
    }))
    """)
    assert r["moves"] == {"3": 0}
    assert r["active"] == [0, 1]
    assert r["pos"][:2] == [9, 5]  # slot 3's position rode along to slot 0
    assert r["specs_same"], "defrag changed a cache leaf's sharding"
    assert "model" in r["k_spec"]  # KV really is sharded on heads


def test_sharded_decode_feed_carries_last_tokens():
    """Sharded mode takes the same host token feed: on a (1, 2) mesh each
    decode input is a copy of the host buffer whose active rows hold each
    request's last token (also after a compaction move), its replicated
    in_sharding places it, the greedy streams equal the single-host
    engine's, and ``decode.sample`` records one host write per row."""
    r = run_sub(_SERVE_BOTH + textwrap.dedent("""
    from repro.observability.trace import Tracer, uninstall_tracer

    lengths, max_new = [5, 9, 3, 12, 7, 4], [2, 9, 3, 8, 4, 6]
    cfg = ARCHS["olmo-1b"].reduced()
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, L).astype(np.int32)
               for L in lengths]

    def run(mesh_shape, tracer=None):
        mesh = (make_mesh(mesh_shape, ("data", "model"))
                if mesh_shape else None)
        eng = Engine(cfg, params, batch_slots=4, max_len=64, mesh=mesh,
                     tracer=tracer)
        calls, moved = [], []
        jit_decode, compact = eng._jit_decode, eng.kv.compact

        def decode(params, last_tok, cache, extra):
            calls.append((last_tok, np.array(last_tok),
                          bool(np.shares_memory(last_tok, eng.last_tok)),
                          {s: r.generated[-1]
                           for s, r in eng.active.items()}))
            return jit_decode(params, last_tok, cache, extra)

        def compact_and_record():
            moves = compact()
            moved.extend(src for src in moves if src in eng.active)
            return moves

        eng._jit_decode, eng.kv.compact = decode, compact_and_record
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=max_new[i]))
        done = {r.rid: r.generated for r in eng.run_until_drained()}
        rows_ok = all(at[s, 0] == tok for _, at, _, rows in calls
                      for s, tok in rows.items())
        kept = all(np.array_equal(np.asarray(fed), at)
                   for fed, at, _, _ in calls)
        return done, {"calls": len(calls), "moved": len(moved),
                      "rows_ok": rows_ok, "kept": kept,
                      "shared": any(c[2] for c in calls),
                      "rows": [len(c[3]) for c in calls]}

    single, _ = run(None)
    tr = Tracer()
    try:
        sharded, feed = run((1, 2), tr)
    finally:
        uninstall_tracer(tr)
    samples = [s.attrs for s in tr.spans if s.name == "decode.sample"]
    print(json.dumps(dict(
        feed, identical=single == sharded, n=len(sharded),
        host_writes=[a["host_writes"] for a in samples],
        sample_rows=[a["rows"] for a in samples])))
    """))
    assert r["identical"], "sharded decode diverged from single-host"
    assert r["n"] == 6 and r["calls"] > 0 and r["moved"] > 0
    assert r["rows_ok"] and r["kept"] and not r["shared"]
    assert r["host_writes"] == r["sample_rows"] == r["rows"]


@pytest.mark.slow
def test_sharded_engine_all_families_token_identity():
    """Tentpole acceptance: every registered model family decodes
    token-identically on a (1, 2) mesh vs single-host (greedy, mixed
    prompt lengths)."""
    archs = ["olmo-1b", "gemma3-1b", "deepseek-moe-16b", "rwkv6-3b",
             "hymba-1.5b", "whisper-small", "llama-3.2-vision-11b"]
    r = run_sub(_SERVE_BOTH + textwrap.dedent(f"""
    results = {{}}
    for arch in {archs!r}:
        single, _ = serve(arch, None, [5, 9, 3], max_new=3, slots=2)
        sharded, stats = serve(arch, (1, 2), [5, 9, 3], max_new=3, slots=2)
        results[arch] = {{
            "identical": single == sharded,
            "axes": stats["sharded_axes"],
        }}
    print(json.dumps(results))
    """), timeout=1800)
    bad = [a for a, v in r.items() if not v["identical"]]
    assert not bad, f"sharded decode diverged for {bad}"
    # every family's dispatcher reasoned about the mesh axis
    assert all(v["axes"] for v in r.values()), r


@pytest.mark.slow
def test_sharded_engine_2x2_mesh_token_identity():
    """A (2,2) mesh (data axis present) still decodes token-identically —
    serving state replicates over 'data'; params may FSDP-shard on it."""
    r = run_sub(_SERVE_BOTH + textwrap.dedent("""
    lengths = [6, 11, 4, 8]
    single, _ = serve("olmo-1b", None, lengths, max_new=5)
    sharded, stats = serve("olmo-1b", (2, 2), lengths, max_new=5)
    print(json.dumps({"identical": single == sharded,
                      "axes": stats["sharded_axes"]}))
    """))
    assert r["identical"]
    assert r["axes"]


@pytest.mark.slow
def test_serve_bench_mesh_document():
    """serve_bench --mesh: schema-4 document records the mesh and per-shard
    dispatch stats for every run."""
    r = run_sub("""
    import json
    from repro.serving.bench import TraceConfig, run_serve_trace

    doc = run_serve_trace(
        "olmo-1b", policies=("fcfs", "gemv_aware"), smoke=True,
        mesh_shape=(1, 4),
        trace_config=TraceConfig(n_requests=6, arrival_rate=6.0,
                                 prompt_len_range=(2, 8),
                                 max_new_range=(2, 3)),
    )
    runs = {r["policy"]: r for r in doc["runs"]}
    print(json.dumps({
        "schema": doc["schema"],
        "mesh": doc["mesh"],
        "run_mesh": runs["fcfs"]["mesh"],
        "axes": runs["fcfs"]["dispatch"]["sharded_axes"],
        "aware_fallback": runs["gemv_aware"]["dispatch"]["matmul_fallback"],
        "completed": [r["completed"] for r in doc["runs"]],
    }))
    """)
    assert r["schema"] == 4
    assert r["mesh"] == {"data": 1, "model": 4}
    assert r["run_mesh"] == {"data": 1, "model": 4}
    assert r["axes"], "no per-shard stats in the mesh run"
    assert r["aware_fallback"] == 0  # batch shaping still holds when sharded
    assert r["completed"] == [6, 6]
