"""One chip's device idle milliseconds per decode step inside the
engine's ``decode_step`` span: for each of the window's ``decode_step``
spans of the engine's ``Tracer``, its length less the union of the first
chip's device operations within it, averaged over the spans
(``program_spans.idle_ms_inside``).  The host work of the step that
keeps the chip waiting: slicing and merging the bucket, dispatch,
sampling."""

import program_spans


def read(ctx):
    ops = ctx.device_ops(0)
    if not ops:
        return None
    return program_spans.idle_ms_inside(ctx.engine_spans("decode_step"), ops)
