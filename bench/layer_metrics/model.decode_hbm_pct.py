"""The decode step's share of the peak memory bandwidth of the cell's
chips: the bytes the window's decode steps need (``costs.decode_step``:
the whole model's weights once per step, each row's live KV read and its
new KV written), over the summed ``decode_step`` span time of the
engine's ``Tracer`` times the cell's chips times one chip's peak
bandwidth, in percent."""

import costs


def read(ctx):
    lo, hi = ctx.window
    nbytes = sum(costs.decode_step(ctx.model, live)[1]
                 for a, b, live in ctx.steps if lo <= a and b <= hi)
    secs = sum(s.end - s.start for s in ctx.engine_spans("decode_step")) / 1e9
    if not secs or not nbytes:
        return None
    return 100.0 * nbytes / (secs * ctx.chips * ctx.peaks["hbm_bytes_per_s"])
