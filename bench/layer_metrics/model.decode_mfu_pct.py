"""The whole decode step's share of the peak bf16 FLOP/s of the cell's
chips: the operations the window's decode steps need
(``costs.decode_step``: two per weight per decoded row, plus attention
over each row's live positions), over the summed ``decode_step`` span
time of the engine's ``Tracer`` times the cell's chips times one chip's
peak, in percent."""

import costs


def read(ctx):
    lo, hi = ctx.window
    flops = sum(costs.decode_step(ctx.model, live)[0]
                for a, b, live in ctx.steps if lo <= a and b <= hi)
    secs = sum(s.end - s.start for s in ctx.engine_spans("decode_step")) / 1e9
    if not secs or not flops:
        return None
    return 100.0 * flops / (secs * ctx.chips * ctx.peaks["bf16_flops"])
