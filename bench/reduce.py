"""From a traced run to what the per-layer metrics read.

A ``--trace 1`` run leaves three records of its window:

* the profiler trace (``.xplane.pb``): on each chip's plane the program
  executions (line ``XLA Modules``, named ``jit_<function>(<hash>)``) and
  the operations inside them (line ``XLA Ops``, named by their HLO text:
  ``%name = <shape> <opcode>(<operands>)``), and the benchmark's host
  annotations (``bench.*``) on the host plane;
* the engine's ``Tracer`` spans (request phases, ``prefill_wave``,
  ``decode_step``) on ``time.perf_counter``;
* the benchmark's own step records (``serve.drive``), on the same clock.

``bench.window_start`` and ``bench.window_end`` are annotations that
carry the ``Tracer``'s clock reading, so every record is put on the
profiler's clock (nanoseconds) here.  ``Context`` holds the result; each
``layer_metrics/<metric>.py`` reads one number from it, or ``None`` when
the window holds nothing it can read.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# The engine's jitted step programs (``Engine._prefill_fn`` and
# ``Engine._decode_fn`` under ``jax.jit``), by the names the trace gives.
STEP_PROGRAMS = ("jit__prefill_fn", "jit__decode_fn")
GEMV_KERNEL = re.compile(r"pimnast_")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


CONTAINERS = re.compile(r"^%(while|conditional|call)[.\s]")
SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")
DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "s8": 1, "u8": 1,
               "f8e4m3fn": 1, "pred": 1}


def program_name(module: str) -> str:
    """``jit__decode_fn(1652...)`` -> ``jit__decode_fn``."""
    return module.split("(")[0]


def is_step_program(module: str) -> bool:
    return program_name(module) in STEP_PROGRAMS


def is_gemv_kernel(op) -> bool:
    return bool(GEMV_KERNEL.search(op.name.split(" = ")[0]))


def instruction(op_name: str) -> str:
    """``%pimnast_gemv.9 = bf16[8,16384]... custom-call(...)`` ->
    ``%pimnast_gemv.9``."""
    return op_name.split(" = ")[0]


def shapes_of(op_name: str) -> list[tuple[str, tuple[int, ...], bool]]:
    """``(dtype, dims, in_vmem)`` of the result and then each operand of
    an HLO op's text; ``in_vmem`` when the layout names memory space 1
    (``S(1)``), the chip's on-core memory."""
    _, _, rhs = op_name.partition(" = ")
    out = []
    for m in SHAPE.finditer(rhs):
        dims = tuple(int(x) for x in m.group(2).split(",") if x)
        tail = rhs[m.end():m.end() + 40]
        layout = tail[:tail.find("}") + 1] if tail.startswith("{") else ""
        out.append((m.group(1), dims, "S(1)" in layout))
    return out


def gemv_least_ns(op_name: str, peaks: dict) -> tuple[float, tuple]:
    """Least time (ns) of one ``pimnast_*`` kernel call on the chip, from
    its own shapes, and its weight operand's dims.  The kernel's
    result, input ``x [.., b, k]`` and weights ``w [.., k, m]`` are read
    from its HLO text; operations ``2 * b * size(w)``; bytes those of
    result, input and weights.  The larger of bytes over peak bandwidth
    and operations over peak FLOP/s."""
    (odt, od, _), (xdt, xd, _), (wdt, wd, _) = shapes_of(op_name)[:3]
    size = lambda d: int(np.prod(d)) if d else 1
    flops = 2.0 * xd[-2] * size(wd)
    nbytes = sum(size(d) * DTYPE_BYTES.get(t, 4)
                 for t, d in ((odt, od), (xdt, xd), (wdt, wd)))
    least = max(flops / peaks["bf16_flops"],
                nbytes / peaks["hbm_bytes_per_s"])
    return least * 1e9, wd


def stages_weights(op, weights: set) -> bool:
    """Whether ``op`` is a copy or slice whose result is a kernel's weight
    operand (same element count and innermost dim, or the same dims in
    another order): XLA materialises each layer's weight slice, or a
    transposed head, before the kernel reads it, and may place it in the
    chip's on-core memory, where the kernel's own time no longer holds
    the read from HBM."""
    head = instruction(op.name)
    if not ("copy" in head or "dynamic-slice" in head):
        return False
    res = shapes_of(op.name)
    if not res:
        return False
    dims = tuple(d for d in res[0][1] if d != 1)
    n = int(np.prod(dims)) if dims else 1
    return any(n == int(np.prod(wd))
               and (dims[-1:] == wd[-1:] or sorted(dims) == sorted(wd))
               for wd in weights)


@dataclass
class Interval:
    name: str
    start: float          # ns on the profiler's clock
    end: float
    attrs: dict = field(default_factory=dict)


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` pairs clipped to
    ``[lo, hi]``."""
    spans = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                   if e > lo and s < hi)
    total, cur_s, cur_e = 0.0, None, None
    for s, e in spans:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The holes ``[lo, hi]`` minus the union of ``intervals``."""
    spans = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                   if e > lo and s < hi)
    out, t = [], lo
    for s, e in spans:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _stats(event) -> dict:
    try:
        return {k: v for k, v in event.stats}
    except (TypeError, ValueError):
        return {}


def read_xplane(path: str):
    """``(device_ops, device_modules, host)``: per device index, its
    operations and program executions, and the host's ``bench.*``
    annotations, all as ``Interval`` on the profiler's clock."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    ops, modules, host = defaultdict(list), defaultdict(list), []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None and line.name in (OPS_LINE, MODULES_LINE):
                dest = (ops if line.name == OPS_LINE else modules)[
                    int(m.group(1))]
                for e in line.events:
                    dest.append(Interval(e.name, e.start_ns,
                                         e.start_ns + e.duration_ns))
            elif m is None and plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append(Interval(e.name, e.start_ns,
                                             e.start_ns + e.duration_ns,
                                             _stats(e)))
    for dev in ops:
        assign_programs(ops[dev], modules.get(dev, []))
    return dict(ops), dict(modules), host


def assign_programs(ops: list, modules: list) -> None:
    """Set ``attrs["module"]`` of each op to the program execution whose
    interval holds its start (a chip runs one program at a time)."""
    ops.sort(key=lambda o: o.start)
    modules = sorted(modules, key=lambda m: m.start)
    j = 0
    for o in ops:
        while j < len(modules) and modules[j].end < o.start:
            j += 1
        inside = j < len(modules) and modules[j].start <= o.start
        o.attrs["module"] = modules[j].name if inside else ""


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {paths}")
    return paths[0]


@dataclass
class Context:
    """Everything a per-layer metric may read, on the profiler's clock."""

    ops: dict             # device index -> [Interval] device operations
    modules: dict         # device index -> [Interval] program executions
    host: list            # bench.* annotations
    spans: list           # Tracer spans as Interval (attrs: rid, cat, ...)
    steps: list           # (start, end, live positions of decoded rows)
    window: tuple         # (start, end)
    model: dict
    peaks: dict
    chips: int = 1

    @classmethod
    def build(cls, *, trace_dir: str, tracer, steps, model, peaks,
              chips: int = 1) -> "Context":
        ops, modules, host = read_xplane(find_xplane(trace_dir))
        return cls.from_records(ops=ops, modules=modules, host=host,
                                tracer_t0=tracer.t0,
                                tracer_spans=list(tracer.spans),
                                steps=steps, model=model, peaks=peaks,
                                chips=chips)

    @classmethod
    def from_records(cls, *, ops, modules, host, tracer_t0, tracer_spans,
                     steps, model, peaks, chips=1) -> "Context":
        """Align the ``Tracer`` clock (``perf_counter`` seconds, zero at
        ``tracer_t0``) to the profiler's by the two window marks."""
        marks = {h.name: h for h in host
                 if h.name in ("bench.window_start", "bench.window_end")}
        if len(marks) != 2:
            raise RuntimeError(f"trace lacks the window marks: {sorted(marks)}")
        ws, we = marks["bench.window_start"], marks["bench.window_end"]
        offset = np.mean([ws.start - float(ws.attrs["tracer_us"]) * 1e3,
                          we.start - float(we.attrs["tracer_us"]) * 1e3])

        def from_perf(t: float) -> float:
            return (t - tracer_t0) * 1e9 + offset

        spans = [Interval(s.name, s.start_us * 1e3 + offset,
                          (s.start_us + s.dur_us) * 1e3 + offset,
                          dict(s.attrs, rid=s.rid, cat=s.cat))
                 for s in tracer_spans]
        steps = [(from_perf(a), from_perf(b), live) for a, b, live in steps]
        return cls(ops=ops, modules=modules, host=host, spans=spans,
                   steps=steps, window=(ws.start, we.start), model=model,
                   peaks=peaks, chips=chips)

    # -- shared reductions ---------------------------------------------------

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def in_window(self, iv) -> bool:
        return self.window[0] <= iv.start and iv.end <= self.window[1]

    def device_ops(self, device: int = 0) -> list:
        return [o for o in self.ops.get(device, [])
                if o.end > self.window[0] and o.start < self.window[1]]

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        lo, hi = self.window
        per = [union_ns([(o.start, o.end) for o in self.device_ops(d)],
                        lo, hi) for d in range(self.chips)]
        return float(np.mean(per)) / 1e9

    def engine_spans(self, name: str) -> list:
        return [s for s in self.spans
                if s.name == name and s.attrs.get("cat") == "engine"
                and self.in_window(s)]

    def program_of(self, op) -> str:
        return program_name(op.attrs.get("module", ""))

    def device_modules(self, device: int = 0) -> list:
        return [m for m in self.modules.get(device, [])
                if m.end > self.window[0] and m.start < self.window[1]]

    def clipped_ns(self, iv) -> float:
        return min(iv.end, self.window[1]) - max(iv.start, self.window[0])

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (by program and
        operation name), and the device's idle time by what the host was
        doing (the innermost host span open at each gap's middle)."""
        lo, hi = self.window
        by_op: dict = defaultdict(float)
        for o in self.device_ops(0):
            if not CONTAINERS.match(o.name):
                name = f"{self.program_of(o)}/{instruction(o.name)}"
                by_op[name] += self.clipped_ns(o) / 1e9
        host = ([s for s in self.spans if s.attrs.get("cat") == "engine"]
                + self.host)
        host.sort(key=lambda s: s.start)
        starts = np.array([s.start for s in host])
        longest = max((s.end - s.start for s in host), default=0.0)
        idle: dict = defaultdict(float)
        for a, b in gaps_ns([(o.start, o.end) for o in self.device_ops(0)],
                            lo, hi):
            mid = (a + b) / 2
            name = "untraced host work"
            for s in reversed(host[:int(np.searchsorted(starts, mid))]):
                if s.start < mid - longest:
                    break
                if s.end >= mid:
                    name = s.name
                    break
            idle[name] += (b - a) / 1e9
        rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in rank(by_op)],
                "idle_gaps": [[k, v] for k, v in rank(idle)]}


def save_records(out_dir: str, trace_dir: str, tracer, steps,
                 since_us: float = 0.0) -> None:
    """Keep a traced run's records: the ``.xplane.pb`` and, as JSON, the
    ``Tracer``'s spans that end after ``since_us``, its zero and the step
    records (``load_records`` reads them back)."""
    import json
    import shutil

    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(find_xplane(trace_dir), os.path.join(out_dir,
                                                     "trace.xplane.pb"))
    spans = [[s.name, s.cat, s.start_us, s.dur_us, s.rid,
              {k: v for k, v in s.attrs.items()
               if isinstance(v, (int, float, str, bool))}]
             for s in tracer.spans if s.start_us + s.dur_us >= since_us]
    with open(os.path.join(out_dir, "records.json"), "w") as f:
        json.dump({"tracer_t0": tracer.t0, "spans": spans,
                   "steps": steps}, f)


def load_records(out_dir: str, *, model, peaks, chips=1) -> Context:
    """A ``Context`` from records that ``save_records`` kept."""
    import json
    from types import SimpleNamespace

    with open(os.path.join(out_dir, "records.json")) as f:
        rec = json.load(f)
    name = next(n for n in ("trace.xplane.pb", "trace.xplane.pb.gz")
                if os.path.exists(os.path.join(out_dir, n)))
    ops, modules, host = read_xplane(os.path.join(out_dir, name))
    spans = [SimpleNamespace(name=n, cat=c, start_us=s, dur_us=d, rid=r,
                             attrs=a) for n, c, s, d, r, a in rec["spans"]]
    return Context.from_records(
        ops=ops, modules=modules, host=host, tracer_t0=rec["tracer_t0"],
        tracer_spans=spans, steps=[tuple(x) for x in rec["steps"]],
        model=model, peaks=peaks, chips=chips)
