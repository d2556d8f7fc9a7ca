"""The program's own spans in a profiler trace, the host dispatches made
under them, and the span that launched each device program.

With the engine's ``Tracer`` installed, each ``Tracer.span`` block is
also a profiler annotation named ``<cat>.<name>`` (``engine.step``,
``engine.decode_step``, ``engine.kv.merge``, ...; the tree is in the
program's ``observability/trace.py``) on the Python thread that ran it,
stamped on the profiler's clock and carrying the span's scalar attrs.
The same thread's line holds JAX's own host events: ``PjitFunction(<f>)``
for each call of a jitted function or eager operation (a call shows as
two nested events of that name) and ``DevicePut`` for each transfer to
the device.

A device program execution (line ``XLA Modules``) carries a ``run_id``,
and so does the host event ``DoEnqueueProgram`` that enqueued it.  That
event runs on the calling thread, or on a runtime thread
(``pjrt-tpu-tasks``, ``tfrt-non-blocking-queue``) inside an event whose
flow id (``_c``) names the event that handed it the work (``_p``).  The
calling thread's C++ events sit on a line of their own, and its
``PJRT_LoadedExecutable_Execute`` links back the same way to the
``PJRT_LoadedExecutable_Execute linkage`` event of the call on the
Python line.  Following the links from each enqueue back to the Python
line gives the execution the time it was launched there, and so the
innermost program span open then.  The engine runs on one thread: the
line that makes the most calls is taken as its.

``read`` parses a trace file into a ``Program``; the functions below
reduce it (with the device operations and the window of a
``reduce.Context``) to per-layer numbers.
"""

from __future__ import annotations

import bisect
import gzip
import re
from collections import defaultdict
from dataclasses import dataclass

import costs
import reduce

PREFIX = "engine."
CALL = re.compile(r"^PjitFunction\((.*)\)$")
# The engine's jitted step functions, by the name their calls carry.
STEP_FUNCTIONS = ("_prefill_fn", "_decode_fn")
ENQUEUE = "DoEnqueueProgram"
LINKAGE = "PJRT_LoadedExecutable_Execute linkage"
# Attribution-based readers read nothing below this share of the
# window's module time traced back to a launching call.
MIN_ATTRIBUTED = 0.99
MAX_HOPS = 8


@dataclass
class Program:
    """A trace's program spans and launches, on the profiler's clock."""

    spans: list               # Interval per annotation (attrs: its stats)
    calls: list               # Interval per PjitFunction call (name: f)
    puts: list                # Interval per DevicePut
    modules: dict             # device -> [Interval] executions; attrs:
    # run_id, and launch_ns and span (innermost annotation open at
    # launch, "" if none) when the launch was traced back to the caller

    def named(self, name: str, window) -> list:
        lo, hi = window
        return [s for s in self.spans
                if s.name == name and lo <= s.start and s.end <= hi]


def _load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _nest(events: list) -> list:
    """Sort one thread's ``(name, start, end, stats)`` events by start
    (outer first) and give each the index of its enclosing event, or -1."""
    events.sort(key=lambda e: (e[1], -e[2]))
    parent, stack = [], []
    for i, (_, s, e, _) in enumerate(events):
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        parent.append(stack[-1] if stack else -1)
        stack.append(i)
    return parent


def _flow_ids(events, parent, i) -> list:
    """``_c`` flow ids of event ``i`` and its enclosing events, innermost
    first."""
    out = []
    while i >= 0:
        c = events[i][3].get("_c")
        if c is not None:
            out.append(c)
        i = parent[i]
    return out


def read(path: str) -> Program:
    """Parse a ``.xplane.pb`` (or ``.xplane.pb.gz``)."""
    pd = _load(path)
    modules: dict = defaultdict(list)
    lines: dict = {}
    for plane in pd.planes:
        m = reduce.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None and line.name == reduce.MODULES_LINE:
                for e in line.events:
                    modules[int(m.group(1))].append(reduce.Interval(
                        e.name, e.start_ns, e.start_ns + e.duration_ns,
                        {"run_id": reduce._stats(e).get("run_id")}))
            elif m is None and plane.name.startswith("/host:"):
                lines[(plane.name, line.name)] = [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns,
                     reduce._stats(e)) for e in line.events]
    # the Python thread: the line that makes the calls
    main = max(lines, default=None,
               key=lambda k: sum(e[0] == LINKAGE for e in lines[k]))
    if main is None:
        return Program([], [], [], dict(modules))
    nested = {k: (ev, _nest(ev)) for k, ev in lines.items()}

    # the Python thread's annotations, calls and transfers
    ev, parent = nested[main]
    spans, calls, puts = [], [], []
    for i, (name, s, e, st) in enumerate(ev):
        if name.startswith(PREFIX):
            spans.append(reduce.Interval(name, s, e, st))
        elif name == "DevicePut":
            puts.append(reduce.Interval(name, s, e))
        else:
            f = CALL.match(name)
            if f and not (parent[i] >= 0 and ev[parent[i]][0] == name):
                calls.append(reduce.Interval(f.group(1), s, e))

    # flows: producer id -> (line, index)
    producer = {}
    for k, (ev, _) in nested.items():
        for i, x in enumerate(ev):
            if "_p" in x[3]:
                producer[x[3]["_p"]] = (k, i)

    def launch_ns(k, i, hops=0):
        """Time of the Python-line event that the flows from event ``i``
        of line ``k`` lead back to."""
        ev, parent = nested[k]
        if k == main:
            return ev[i][1]
        if hops >= MAX_HOPS:
            return None
        for c in _flow_ids(ev, parent, i):
            p = producer.get(c)
            if p is not None:
                return launch_ns(*p, hops + 1)
        return None

    launched = {}
    for k, (ev, _) in nested.items():
        for i, x in enumerate(ev):
            if x[0] == ENQUEUE and "run_id" in x[3]:
                t = launch_ns(k, i)
                if t is not None:
                    launched[x[3]["run_id"]] = t

    owner = _innermost(spans)
    for dev in modules:
        for mod in modules[dev]:
            t = launched.get(mod.attrs["run_id"])
            if t is not None:
                mod.attrs["launch_ns"] = t
                mod.attrs["span"] = owner(t)
    return Program(spans, calls, puts, dict(modules))


def _innermost(spans: list):
    """A function from a time to the name of the innermost of ``spans``
    (properly nested, one thread) open then, or ""."""
    items = [(s.name, s.start, s.end, {}) for s in spans]
    parent = _nest(items)
    starts = [x[1] for x in items]

    def at(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        while i >= 0 and items[i][2] < t:
            i = parent[i]
        return items[i][0] if i >= 0 else ""

    return at


def _within(intervals: list):
    """A test of whether a time lies in one of ``intervals`` (sorted,
    disjoint)."""
    starts = [s.start for s in intervals]

    def inside(t: float) -> bool:
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t <= intervals[i].end

    return inside


# -- reductions --------------------------------------------------------------


def attributed_share(prog: Program, window, device: int = 0) -> float:
    """Share of the window's module time whose launch was traced back to
    the calling thread."""
    lo, hi = window
    total = done = 0.0
    for m in prog.modules.get(device, []):
        if m.end > lo and m.start < hi:
            ns = min(m.end, hi) - max(m.start, lo)
            total += ns
            done += ns if "launch_ns" in m.attrs else 0.0
    return done / total if total else 0.0


def idle_ms_inside(spans: list, ops: list) -> float | None:
    """Mean device idle time (ms) inside each of ``spans``: its length
    less the union of the device operations within it."""
    if not spans:
        return None
    pairs = sorted((o.start, o.end) for o in ops)
    starts = [a for a, _ in pairs]
    longest = max((b - a for a, b in pairs), default=0.0)

    def near(s):
        """The operations that can overlap span ``s``: none that starts
        more than the longest operation before it."""
        return pairs[bisect.bisect_left(starts, s.start - longest):
                     bisect.bisect_left(starts, s.end)]

    idle = sum(s.end - s.start - reduce.union_ns(near(s), s.start, s.end)
               for s in spans)
    return idle / len(spans) / 1e6


def decode_idle_ms(prog: Program, ctx) -> float | None:
    """``engine.decode_idle_ms`` from the ``engine.decode_step``
    annotations."""
    return idle_ms_inside(prog.named("engine.decode_step", ctx.window),
                          ctx.device_ops(0))


def dispatches_per_step(prog: Program, window) -> float | None:
    """Calls of jitted functions and eager operations made inside
    ``engine.step`` annotations, less the step programs' own, per
    ``engine.decode_step``."""
    steps = prog.named("engine.step", window)
    decodes = len(prog.named("engine.decode_step", window))
    if not steps or not decodes:
        return None
    inside = _within(steps)
    n = sum(1 for c in prog.calls if c.name not in STEP_FUNCTIONS
            and inside(c.start))
    return n / decodes


def launched(prog: Program, window, device: int = 0) -> dict:
    """Per innermost launching span: device time (ms, clipped to the
    window) and number of the program executions it launched, and the
    calls and transfers made directly inside it."""
    lo, hi = window
    out: dict = defaultdict(lambda: {"device_ms": 0.0, "executions": 0,
                                     "calls": 0, "puts": 0})
    for m in prog.modules.get(device, []):
        if m.end > lo and m.start < hi and "span" in m.attrs:
            x = out[m.attrs["span"] or "(no span)"]
            x["device_ms"] += (min(m.end, hi) - max(m.start, lo)) / 1e6
            x["executions"] += 1
    owner = _innermost(prog.spans)
    for kind, evs in (("calls", prog.calls), ("puts", prog.puts)):
        for c in evs:
            if lo <= c.start < hi:
                out[owner(c.start) or "(no span)"][kind] += 1
    return dict(out)


def kv_launched_device_ms(prog: Program, window) -> float | None:
    """Device time of the executions launched inside ``engine.kv.*``
    spans, per ``engine.decode_step``; nothing when less than
    ``MIN_ATTRIBUTED`` of the module time is attributed."""
    decodes = len(prog.named("engine.decode_step", window))
    if not decodes or attributed_share(prog, window) < MIN_ATTRIBUTED:
        return None
    ms = sum(x["device_ms"] for name, x in launched(prog, window).items()
             if name.startswith(PREFIX + "kv."))
    return ms / decodes


def decode_program_hbm_pct(model: dict, peaks: dict, steps: list,
                           programs: list, chips: int) -> float | None:
    """The decode program's share of the peak memory bandwidth of
    ``chips`` chips: the bytes the decode ``steps`` need
    (``costs.decode_step`` from each step's own ``rows`` and
    ``live_positions`` attrs), over the device time of the ``programs``
    executions (one chip's) that ran inside those steps times ``chips``
    times one chip's peak, in percent."""
    steps = sorted((s for s in steps if "live_positions" in s.attrs),
                   key=lambda s: s.start)
    nbytes = 0.0
    for s in steps:
        rows = int(s.attrs["rows"])
        # a step's cost depends on its rows and their summed positions
        live = [int(s.attrs["live_positions"])] + [0] * (rows - 1)
        nbytes += costs.decode_step(model, live)[1] if rows else 0.0
    inside = _within(steps)
    ns = sum(p.end - p.start for p in programs
             if inside((p.start + p.end) / 2))
    if not ns or not nbytes:
        return None
    return 100.0 * nbytes / (ns / 1e9 * chips * peaks["hbm_bytes_per_s"])


def decode_program_hbm_pct_launched(prog: Program, ctx) -> float | None:
    """``decode_program_hbm_pct`` over the executions attributed to
    ``engine.decode.launch``; nothing below ``MIN_ATTRIBUTED``."""
    if attributed_share(prog, ctx.window) < MIN_ATTRIBUTED:
        return None
    programs = [m for m in prog.modules.get(0, [])
                if m.attrs.get("span") == PREFIX + "decode.launch"]
    return decode_program_hbm_pct(
        ctx.model, ctx.peaks, prog.named("engine.decode_step", ctx.window),
        programs, ctx.chips)


def alignment_residual_us(prog: Program, ctx) -> list:
    """|start of each in-window ``decode_step`` span of the ``Tracer``
    (put on the profiler's clock by the two window marks) - start of the
    annotation of the same step| in µs, step by step."""
    tracer = sorted(ctx.engine_spans("decode_step"), key=lambda s: s.start)
    ann = sorted(prog.named("engine.decode_step", ctx.window),
                 key=lambda s: s.start)
    if len(tracer) != len(ann):
        return []
    return [abs(a.start - b.start) / 1e3 for a, b in zip(tracer, ann)]
