"""Host-clock milliseconds the engine spends per thousand prompt tokens
it prefills: the ``prefill_wave`` spans of the engine's ``Tracer`` in the
window, their summed duration over their summed (unpadded) tokens."""


def read(ctx):
    waves = ctx.engine_spans("prefill_wave")
    tokens = sum(int(s.attrs.get("tokens", 0)) for s in waves)
    if not tokens:
        return None
    ms = sum(s.end - s.start for s in waves) / 1e6
    return ms / (tokens / 1000.0)
