"""One chip's device milliseconds per decode step spent in programs other
than the engine's jitted prefill and decode steps: the eager slices,
merges, splices, compactions and per-slot token writes around them (the
KV cache layer's copies).  From the profiler trace: the summed device
time of the first chip's program executions that are not step programs
(``reduce.STEP_PROGRAMS``), over the number of ``decode_step`` spans in
the window."""

import reduce


def read(ctx):
    steps = len(ctx.engine_spans("decode_step"))
    modules = ctx.device_modules(0)
    if not steps or not modules:
        return None
    ns = sum(ctx.clipped_ns(m) for m in modules
             if not reduce.is_step_program(m.name))
    return ns / 1e6 / steps
