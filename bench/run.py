"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) is a model configuration
(``bench/configs/<config>.json``) served through the program's ``Engine``
under a traffic mix (``bench/traffic/<mix>.json``) at the rate in
``bench/cells/<cell>.json``.  One run:

1. checks that JAX sees a TPU whose kind is in ``bench/peaks.json`` and as
   many chips as the cell asks for, else exits 3 with no result;
2. set-up: makes the weights from ``--seed`` on the device, builds the
   engine, warms up every program shape the mix's lengths can reach, then
   drives the mix's ramp (``ramp_s``) until the slots are in steady use.
   A configuration that states a ``mesh`` runs on a (data, model) mesh of
   the cell's chips: the weights are drawn split over it and the engine
   is given it;
3. drives the mix open loop on the wall clock for ``--seconds``, and with
   ``--trace 1`` records the profiler trace and the engine's ``Tracer``
   spans over that window;
4. frees the engine and scores a sample of the finished requests' served
   tokens against the configuration's float32 reference (``check.py``);
5. prints the numbers compared beside their limits as the last lines of
   standard error, and one JSON result as the last line of standard
   output: the cell's end-to-end metrics (``--trace 0``) or its per-layer
   metrics (``--trace 1``).

Compiled programs are kept in ``$JAX_COMPILATION_CACHE_DIR`` when set,
else in ``.jax_cache/`` at the checkout's root.
"""

from __future__ import annotations

T_START = __import__("time").perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import spec  # noqa: E402

EXIT_SPEC, EXIT_DEVICE = 2, 3

# ModelConfig fields the configuration file's sizes must match.
_NORMS = {"nonparametric": "nonparametric_ln", "layernorm": "layernorm"}


class CompileCounter:
    """Counts JAX traces, compiles and persistent-cache loads while armed
    (a monitoring listener; there is no way to remove one, so it stays
    registered and is disarmed instead)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        self._event(event)

    def _event(self, event, **kw):
        if self.armed and event in self.EVENTS:
            self.count += 1


def program_config(cell: spec.Cell):
    """The program's ``ModelConfig`` for the cell, checked against every
    size in the configuration file."""
    from repro.configs.registry import get_config

    prog = cell.config["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **prog["overrides"])
    m = cell.model
    want = {"n_layers": m["n_layers"], "d_model": m["d_model"],
            "n_heads": m["n_heads"], "n_kv_heads": m["n_kv_heads"],
            "hd": m["head_dim"], "d_ff": m["d_ff"], "vocab": m["vocab"],
            "act": m["act"], "tie_embeddings": m["tie_embeddings"],
            "norm_type": _NORMS[m["norm"]], "param_dtype": m["dtype"],
            "rope_theta": m["rope_theta"], "family": "dense",
            "attn_pattern": "full"}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise spec.SpecError(f"program config differs from "
                             f"{cell.config_name}.json (program, file): {bad}")
    return cfg


def engine_for(cell: spec.Cell, cfg, params, tracer=None, mesh=None):
    from repro.serving.engine import Engine

    mix = cell.traffic
    return Engine(cfg, params, batch_slots=cell.slots, max_len=mix["max_len"],
                  scheduler=mix["engine"]["scheduler"], tracer=tracer,
                  mesh=mesh)


def warm_up(eng, cell: spec.Cell, vocab: int) -> int:
    """Run every program shape and eager-op shape the mix can reach
    through the engine's public path, with throwaway requests: a prefill
    wave of each power-of-two size at each padded length, waves of every
    size, decode at every bucket, and compactions of every move count.
    Returns the number of throwaway requests."""
    import numpy as np

    from repro.serving.engine import Request

    import traffic

    rng = np.random.default_rng(0)
    slots, mix = cell.slots, cell.traffic
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    rid = [-1]

    def wave(lengths, new_tokens):
        for L, n in zip(lengths, new_tokens):
            eng.submit(Request(rid=rid[0], prompt=rng.integers(
                0, vocab, L).astype(np.int32), max_new_tokens=n))
            rid[0] -= 1
        while eng.active or eng.queue:
            eng.step()

    pow2 = [n for n in (1, 2, 4, 8, 16, 32, 64) if n <= slots]
    for L in traffic.length_grid(mix["prompt"]):
        L = max(min(L, hi), lo)
        for n in pow2:
            wave([L] * n, [1] * n)
    for n in range(1, slots + 1):
        wave([lo] * n, [1] * n)
    for n in (1,) + tuple(p // 2 + 1 for p in pow2[1:]):
        wave([lo] * n, [3] * n)
    for k in range(1, slots // 2 + 1):
        wave([lo] * slots, [2] * k + [4] * (slots - k))
    return -rid[0] - 1


def device_info(chips: int) -> dict:
    """The first ``chips`` devices as JAX reports them; a non-TPU or too
    few chips raises ``DeviceError``."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise DeviceError(f"JAX's first device is {d.platform!r} "
                          f"({d.device_kind}), not a TPU; not falling back")
    if len(devs) < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX sees "
                          f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


class DeviceError(Exception):
    pass


def peak_memory(chips: int) -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def cell_mesh(cell: spec.Cell):
    """The (data, model) mesh the cell's configuration states, or
    ``None`` for a one-chip configuration."""
    if cell.mesh_shape is None:
        return None
    from repro.launch.mesh import make_mesh

    return make_mesh(cell.mesh_shape, ("data", "model"))


def set_up(cell: spec.Cell, seed: int, *, tracer=None, t_start: float,
           log=lambda s: None):
    """Weights from ``seed`` on the device (split over the cell's mesh, if
    it has one), the engine, and its warm-up.  Returns
    ``(weights, engine)``; on a mesh the weights are ``None``, since the
    engine placed its own copy and a second would double each chip's
    weights (``check_weights`` draws them again for the check)."""
    import jax

    import weights

    cfg = program_config(cell)
    mesh = cell_mesh(cell)
    w = jax.block_until_ready(weights.make_weights(cell.model, seed, mesh))
    log(f"bench: weights made at {time.perf_counter() - t_start:.2f} s")
    eng = engine_for(cell, cfg, w, tracer, mesh)
    if mesh is not None:
        w = None
    n_warm = warm_up(eng, cell, cell.model["vocab"])
    log(f"bench: {n_warm} warm-up requests done at "
        f"{time.perf_counter() - t_start:.2f} s")
    return w, eng


def check_weights(cell: spec.Cell, seed: int, w):
    """The weights the engine was given: ``w``, or on a mesh the same
    draw from ``seed`` again."""
    if w is not None:
        return w
    import weights

    return weights.make_weights(cell.model, seed, cell_mesh(cell))


def serve_mix(eng, cell: spec.Cell, rate: float, seed: int, seconds: float,
              *, on_open=lambda: None, on_close=lambda: None,
              annotate: bool = False) -> dict:
    """Drive the mix at ``rate``: the ramp, then the window of
    ``seconds``.  ``on_open`` / ``on_close`` run at the window's edges.
    Returns the clients, the window's edges and the step records."""
    import serve
    import traffic
    from repro.serving.engine import Request

    mix = cell.traffic
    dues = traffic.schedule(mix, rate, seed, seconds, cell.model["vocab"])
    ramp_s = float(mix["ramp_s"])

    def make_request(d):
        return Request(rid=d.index, prompt=d.prompt,
                       max_new_tokens=d.max_new_tokens)

    steps: list = []
    clock = time.perf_counter
    origin = clock()
    start, stop = origin + ramp_s, origin + ramp_s + seconds
    clients = serve.drive(eng, [d for d in dues if d.t < ramp_s],
                          make_request, origin=origin, stop=start,
                          clock=clock)
    ramp_end = clock()
    on_open()
    opened = clock()
    serve.drive(eng, [d for d in dues if d.t >= ramp_s], make_request,
                origin=origin, stop=stop, clock=clock, annotate=annotate,
                clients=clients, steps=steps)
    closed = clock()
    on_close()
    return {"clients": clients, "start": start, "stop": stop,
            "ramp_end": ramp_end, "steps": steps,
            "window": {"planned_start_s": start - origin,
                       "opened_late_s": opened - start,
                       "closed_late_s": closed - stop}}


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             device: dict, peaks: dict, *, t_start: float,
             log=lambda s: print(s, file=sys.stderr, flush=True),
             after_check=None, keep_trace: str | None = None) -> dict:
    """One run of ``cell`` (module docstring, steps 2-4).  Returns the
    result line's fields and the comparisons (``limits``).
    ``after_check(reference, weights, requests, length)`` runs after the
    check,
    on the same sample (``readings.py`` reads the control there).
    ``keep_trace`` names a directory to keep the traced run's records in
    (``reduce.save_records``)."""
    import jax

    import check
    import serve

    counter = CompileCounter()
    tracer = None
    if trace:
        from repro.observability.trace import Tracer

        tracer = Tracer(timing=False, clock=time.perf_counter)
    w, eng = set_up(cell, seed, tracer=tracer, t_start=t_start, log=log)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None

    opened_us = [0.0]

    def on_open():
        if trace:
            opened_us[0] = tracer.now_us()
            # host: TraceMe annotations only, no Python function tracing
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench.window_start",
                                              tracer_us=tracer.now_us()):
                pass
        counter.armed = True

    def on_close():
        counter.armed = False
        if trace:
            with jax.profiler.TraceAnnotation("bench.window_end",
                                              tracer_us=tracer.now_us()):
                pass
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            log(f"bench: trace written in {time.perf_counter() - t0:.2f} s")

    run = serve_mix(eng, cell, float(cell.cell_file["rate_per_s"]), seed,
                    seconds, on_open=on_open, on_close=on_close,
                    annotate=trace)
    clients, start, stop = run["clients"], run["start"], run["stop"]
    setup_s = run["ramp_end"] - t_start
    memory_peak = peak_memory(cell.chips)
    result = {"window": run["window"], "compiles_in_window": counter.count,
              "lateness": serve.lateness(clients)}
    e2e = serve.client_metrics(clients, start, stop)
    result["client"] = e2e
    if trace:
        import shutil

        import reduce

        t0 = time.perf_counter()
        ctx = reduce.Context.build(
            trace_dir=trace_dir, tracer=tracer, steps=run["steps"],
            model=cell.model, peaks=peaks, chips=cell.chips)
        if keep_trace:
            reduce.save_records(keep_trace, trace_dir, tracer, run["steps"],
                                since_us=opened_us[0])
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = {}
        for metric in cell.per_layer:
            value = spec.layer_metric_reader(metric["name"],
                                             cell.root)(ctx)
            if value is not None:
                metrics[metric["name"]] = {"value": float(value),
                                           "unit": metric["unit"]}
        result["breakdown"] = ctx.breakdown()
        result["busy_s"], result["window_s"] = ctx.busy_s(), ctx.window_s()
        log(f"bench: trace read in {time.perf_counter() - t0:.2f} s")
    else:
        values = dict(e2e, setup_s=setup_s)
        metrics = {x["name"]: {"value": values[x["name"]],
                               "unit": x["unit"]}
                   for x in cell.end_to_end if x["name"] in values}
    result["metrics"] = metrics
    result["memory_peak_bytes"] = memory_peak
    in_window = [c for c in clients if c.in_window]
    result["attempted"] = len(in_window)
    result["failed"] = sum(1 for c in in_window if c.error is not None)
    result["finished"] = sum(1 for c in clients if c.done)

    # correctness: free the program's state, then run the reference
    picked = check.sample(clients, int(cell.traffic["check_requests"]), seed)
    reqs = [c.request for c in picked]
    del eng, clients, picked, run
    gc.collect()
    ref = spec.reference_module(cell.config, cell.root)
    t0 = time.perf_counter()
    w = check_weights(cell, seed, w)
    length = int(cell.traffic["max_len"])
    scored = (check.served_gaps(ref, w, cell.model, reqs, length)
              if reqs else None)
    result["reference_s"] = time.perf_counter() - t0
    if after_check is not None:
        after_check(ref, w, reqs, length)
    limits = cell.cell_file["limits"]
    result["check"] = scored
    result["limits"] = {
        name: {"value": scored.get(name) if scored else None,
               "limit": limit} for name, limit in limits.items()}
    result["correct"] = bool(limits) and all(
        x["value"] is not None and x["limit"] is not None
        and x["value"] <= x["limit"] for x in result["limits"].values())
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload, ROOT)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_SPEC
    try:
        device = device_info(cell.chips)
        peaks = spec.load_peaks(device["kind"], ROOT)
    except (DeviceError, spec.SpecError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_DEVICE
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"bench: the program is not in this checkout: {e}",
              file=sys.stderr)
        return EXIT_SPEC
    print(f"bench: compile cache {enable_compile_cache(ROOT)}",
          file=sys.stderr)
    try:
        r = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                     device, peaks, t_start=T_START)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_SPEC
    device = dict(device, memory_peak_bytes=r["memory_peak_bytes"])
    if args.trace:
        device.update(busy_s=r["busy_s"], window_s=r["window_s"])
    info = {k: r[k] for k in ("window", "compiles_in_window", "lateness",
                              "client", "check", "reference_s",
                              "finished")}
    print("bench: run " + json.dumps(info), file=sys.stderr)
    print(json.dumps({"generator_lateness_s": r["lateness"],
                      "compiles_in_window": r["compiles_in_window"]}))
    line = {"correct": r["correct"], "attempted": r["attempted"],
            "failed": r["failed"], "metrics": r["metrics"],
            "device": device}
    if args.trace:
        line["breakdown"] = r["breakdown"]
    line["limits"] = r["limits"]
    for name, x in r["limits"].items():
        print(f"bench: {name} {x['value']!r} limit {x['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
