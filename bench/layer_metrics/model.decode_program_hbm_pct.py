"""The decode program's own share of the peak memory bandwidth of the
cell's chips: the bytes the window's decode steps need
(``costs.decode_step`` from each ``decode_step`` span's own ``rows`` and
``live_positions``: the whole model's weights once, each row's live KV
read and its new KV written), over the device time of the jitted decode
program's executions on the first chip inside those spans (one per step,
launched by its ``decode.launch``; on a mesh every chip runs the same
program) times the cell's chips times one chip's peak bandwidth, in
percent.  ``model.decode_hbm_pct`` divides by the whole span, host
work included; this divides by the program alone."""

import program_spans
import reduce


def read(ctx):
    programs = [m for m in ctx.device_modules(0)
                if reduce.program_name(m.name) == "jit__decode_fn"]
    return program_spans.decode_program_hbm_pct(
        ctx.model, ctx.peaks, ctx.engine_spans("decode_step"), programs,
        ctx.chips)
