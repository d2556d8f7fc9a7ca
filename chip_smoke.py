#!/usr/bin/env python3
"""Chip smoke test: serve olmo-1b at its published width through ``Engine``.

    python chip_smoke.py               # one TPU chip
    python chip_smoke.py --mesh 1x4    # four chips: sharded Engine vs one chip

One chip: olmo-1b (16 layers, d_model 2048, 16 heads, d_ff 8192, vocab
50304, bf16, random weights from ``--seed``) serves four requests with
prompts of 32-256 tokens through ``Engine`` (fcfs scheduler, 4 slots,
max_len 1024), then checks

  a. the jitted decode step holds the ``pimnast_*`` Pallas kernels as
     ``tpu_custom_call``s: they lowered natively, neither interpreted nor
     replaced by the XLA reference;
  b. decode-step logits of the Pallas dispatch path and of the XLA path
     (``gemv_policy=None``) agree within ``LOGIT_RTOL``;
  c. every logit is finite and every request ends with its token budget.

``--mesh 1x4`` runs only the mesh phase: the same requests through a
sharded ``Engine`` on a (data=1, model=4) mesh and through a one-device
``Engine`` in this process; it compares their prefill and first decode
logits, reports greedy-token agreement, and fails unless the weights are
spread over the four devices.

Both phases run in this one process and start no other.  Anything that
fails exits non-zero; so does a first device that is not a TPU (this
script never falls back to the CPU).  The last line of standard output is
``{"ok": true, "device": {...}}`` and is printed only on success.
Compiled programs are cached in ``$JAX_COMPILATION_CACHE_DIR`` when set,
else in ``.jax_cache/`` here.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "olmo-1b"
# the published olmo-1b widths this script must see (arXiv:2402.00838)
PUBLISHED = dict(n_layers=16, d_model=2048, n_heads=16, n_kv_heads=16,
                 d_ff=8192, vocab=50304, param_dtype="bfloat16")
SLOTS = 4
MAX_LEN = 1024
PROMPT_LENS = (32, 96, 160, 256)
NEW_TOKENS = 16
CHECK_PROMPT_LEN = 64
# Logit agreement between two bf16 paths.  Both accumulate every
# projection in f32 and round its output to bf16; they differ only in the
# order the f32 partial sums are added (block sizes, split-K partials,
# per-shard splits), which can move each rounded output by one bf16 ulp
# (2^-8 relative).  Sixteen layers of residual updates plus the lm_head
# let such one-ulp differences add up to a few percent of the largest
# logit; a kernel that reads a wrong tile or skips a block is off by
# O(1).  So the bound is on max|a - b| / max|b|.
LOGIT_RTOL = 5e-2


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    log(f"  ok: {what}")


def tree_bytes(tree) -> int:
    import jax

    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def requests(vocab: int, seed: int, prompt_lens=PROMPT_LENS,
             new_tokens: int = NEW_TOKENS):
    import numpy as np

    from repro.serving.engine import Request

    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab, n).astype(np.int32),
                    max_new_tokens=new_tokens)
            for i, n in enumerate(prompt_lens)]


def serve(eng, reqs) -> dict[int, list[int]]:
    """Drain ``reqs`` through ``eng``; check (c)'s token budget."""
    for r in reqs:
        eng.submit(r)
    done = eng.run_until_drained(max_iters=10_000)
    check(len(done) == len(reqs) and all(r.done for r in done),
          f"all {len(reqs)} requests completed")
    check(all(len(r.generated) == r.max_new_tokens for r in done),
          f"every request generated its {reqs[0].max_new_tokens} tokens")
    vocab = eng.cfg.vocab
    check(all(0 <= t < vocab for r in done for t in r.generated),
          "every token id is inside the vocabulary")
    return {r.rid: list(r.generated) for r in done}


def step_logits(cfg, params, policy, prompts, *, mesh=None,
                cache_shardings=None):
    """(prefill last-token logits, first decode-step logits) for a batch of
    equal-length prompts, through ``build_serve_fns`` with ``policy``."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from repro.distributed.axes import activation_mesh
    from repro.models import lm
    from repro.serving.engine import build_serve_fns, greedy

    prefill, decode = build_serve_fns(cfg, MAX_LEN, policy)
    cache = lm.init_cache(cfg, len(prompts), MAX_LEN, per_slot_pos=True)
    tokens = jnp.asarray(prompts)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        cache = jax.device_put(cache, cache_shardings)
        tokens = jax.device_put(tokens, NamedSharding(mesh, P()))
    ctx = (activation_mesh(mesh) if mesh is not None
           else contextlib.nullcontext())
    with ctx:
        first, cache = jax.jit(prefill)(params, tokens, cache, {})
        nxt = greedy(first)[:, None]
        second, _ = jax.jit(decode)(params, nxt, cache, {})
    return jax.device_get(first), jax.device_get(second)


def compare(name: str, got, ref) -> float:
    """(b)/(c): finite logits and max|got - ref| / max|ref| <= LOGIT_RTOL."""
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(bool(np.isfinite(got).all() and np.isfinite(ref).all()),
          f"{name}: all logits finite")
    rel = float(np.abs(got - ref).max() / np.abs(ref).max())
    same = float((got.argmax(-1) == ref.argmax(-1)).mean())
    log(f"  {name}: max|diff|/max|ref| = {rel!r} (limit {LOGIT_RTOL}), "
        f"argmax agreement {same!r}, max|ref| = "
        f"{float(np.abs(ref).max())!r}")
    check(rel <= LOGIT_RTOL, f"{name}: logits agree within {LOGIT_RTOL}")
    return rel


def check_pallas_lowering(cfg, eng) -> float:
    """(a): compile the engine's decode step at its largest bucket and
    find the Pallas kernels in it; returns the compile seconds."""
    import jax
    import jax.numpy as jnp

    from repro.serving.engine import build_serve_fns

    _, decode = build_serve_fns(cfg, MAX_LEN, eng.gemv_policy)
    t0 = time.perf_counter()
    compiled = jax.jit(decode).lower(
        eng.params, jnp.zeros((SLOTS, 1), jnp.int32), eng.kv.cache,
        {}).compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    calls = hlo.count('custom_call_target="tpu_custom_call"')
    names = {n: hlo.count(n) for n in ("pimnast_gemv", "pimnast_splitk_gemv",
                                       "pimnast_quant")}
    log(f"  decode step HLO: {calls} tpu_custom_call(s), kernel name "
        f"mentions {names}")
    check(calls > 0 and names["pimnast_gemv"] + names["pimnast_splitk_gemv"]
          > 0, "decode step lowers pimnast_* kernels as tpu_custom_call")
    return compile_s


def one_chip(cfg, params, seed: int) -> None:
    import jax
    import numpy as np

    from repro.kernels import dispatch
    from repro.kernels.backends import resolve_backend
    from repro.serving.engine import Engine

    log("phase: one chip")
    dispatch.clear_plan_cache()
    eng = Engine(cfg, params, batch_slots=SLOTS, max_len=MAX_LEN,
                 scheduler="fcfs")
    check(resolve_backend(eng.gemv_policy).name == "tpu",
          "the engine's decode GEMVs resolve the tpu backend")
    log(f"  bytes: params (with decode prepack) {tree_bytes(eng.params)}, "
        f"KV cache {tree_bytes(eng.kv.cache)}")
    compile_s = check_pallas_lowering(cfg, eng)
    log(f"  set-up: decode step compile {compile_s!r} s")

    reqs = requests(cfg.vocab, seed)
    t0 = time.perf_counter()
    served = serve(eng, reqs)
    serve_s = time.perf_counter() - t0
    log(f"  served {len(served)} requests, "
        f"{sum(map(len, served.values()))} tokens; host clock "
        f"{serve_s!r} s including the engine's own compilation")
    stats = dispatch.dispatch_stats()
    log(f"  dispatcher picks {stats['kernel_picks']} program modes "
        f"{stats['program_modes']} gemv_path {stats['gemv_path']} "
        f"matmul_fallback {stats['matmul_fallback']}")

    rng = np.random.default_rng(seed + 1)
    prompts = rng.integers(0, cfg.vocab, (SLOTS, CHECK_PROMPT_LEN)
                           ).astype(np.int32)
    pf_p, dec_p = step_logits(cfg, eng.params, eng.gemv_policy, prompts)
    pf_x, dec_x = step_logits(cfg, params, None, prompts)
    compare("prefill logits (both einsum paths)", pf_p, pf_x)
    compare("decode-step logits, Pallas dispatch vs XLA", dec_p, dec_x)
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"  peak_bytes_in_use {peak}")


def mesh_phase(cfg, params, seed: int, shape: tuple[int, int]) -> None:
    import jax
    import numpy as np

    from repro.launch.mesh import make_mesh
    from repro.serving.engine import Engine

    log(f"phase: mesh {shape[0]}x{shape[1]} vs one device")
    n = shape[0] * shape[1]
    check(len(jax.devices()) >= n, f"{n} devices for the mesh")
    mesh = make_mesh(shape, ("data", "model"))
    sharded = Engine(cfg, params, batch_slots=SLOTS, max_len=MAX_LEN,
                     scheduler="fcfs", mesh=mesh)
    per_dev: dict = {}
    for leaf in jax.tree.leaves(sharded.params):
        for s in leaf.addressable_shards:
            per_dev[s.device] = per_dev.get(s.device, 0) + s.data.nbytes
    total = tree_bytes(sharded.params)
    log(f"  sharded params: {total} bytes logical; per device "
        f"{[per_dev.get(d, 0) for d in mesh.devices.flat]}")
    for d in mesh.devices.flat:
        log(f"  device {d.id}: bytes_in_use "
            f"{(d.memory_stats() or {}).get('bytes_in_use')}")
    check(all(per_dev.get(d, 0) >= total / (2 * n) for d in mesh.devices.flat)
          and max(per_dev.values()) < total,
          f"weights spread over all {n} devices")
    single = Engine(cfg, params, batch_slots=SLOTS, max_len=MAX_LEN,
                    scheduler="fcfs")

    rng = np.random.default_rng(seed + 1)
    prompts = rng.integers(0, cfg.vocab, (SLOTS, CHECK_PROMPT_LEN)
                           ).astype(np.int32)
    pf_m, dec_m = step_logits(cfg, sharded.params, sharded.gemv_policy,
                              prompts, mesh=mesh,
                              cache_shardings=sharded.kv.shardings)
    pf_1, dec_1 = step_logits(cfg, single.params, single.gemv_policy,
                              prompts)
    compare("prefill logits, mesh vs one device", pf_m, pf_1)
    compare("decode-step logits, mesh vs one device", dec_m, dec_1)

    tok_m = serve(sharded, requests(cfg.vocab, seed))
    tok_1 = serve(single, requests(cfg.vocab, seed))
    pairs = [(a, b) for rid in tok_1 for a, b in zip(tok_m[rid], tok_1[rid])]
    agree = sum(a == b for a, b in pairs) / len(pairs)
    first = sum(tok_m[rid][0] == tok_1[rid][0] for rid in tok_1)
    log(f"  greedy tokens: {agree!r} of {len(pairs)} agree; first tokens "
        f"{first}/{len(tok_1)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="run only the mesh phase on a (data, model) mesh, "
                         "e.g. 1x4")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is "
              f"platform {dev.platform!r} ({dev.device_kind}); not "
              f"falling back", file=sys.stderr)
        return 2
    log(f"device: platform {dev.platform} kind {dev.device_kind} "
        f"count {len(devices)}")
    try:
        sys.path.insert(0, os.path.join(ROOT, "src"))
        from repro.configs.registry import get_config
        from repro.kernels.backends import get_backend, resolve_backend
        from repro.launch.compile_cache import enable_compile_cache
        from repro.models import lm
    except ImportError as e:
        print(f"chip_smoke: the repository is not next to this script: "
              f"{e}", file=sys.stderr)
        return 2

    try:
        log(f"compile cache: {enable_compile_cache(ROOT)}")
        check(resolve_backend().name == "tpu",
              "dispatch resolves the tpu backend on this platform")
        check(get_backend("tpu").default_interpret() is False,
              "Pallas kernels lower natively (default_interpret() is False)")
        cfg = get_config(ARCH)
        widths = {k: getattr(cfg, k) for k in PUBLISHED}
        log(f"config {cfg.name}: {widths} (source {cfg.source})")
        check(widths == PUBLISHED, f"{ARCH} at its published widths")
        t0 = time.perf_counter()
        params = jax.block_until_ready(lm.init_lm_on_device(cfg, args.seed))
        log(f"  set-up: weights made on device in {time.perf_counter() - t0!r}"
            f" s; {tree_bytes(params)} bytes of parameters")
        if args.mesh:
            from repro.launch.mesh import parse_mesh_arg

            mesh_phase(cfg, params, args.seed, parse_mesh_arg(args.mesh))
        else:
            one_chip(cfg, params, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
