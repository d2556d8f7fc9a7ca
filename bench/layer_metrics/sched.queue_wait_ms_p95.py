"""95th percentile of the time requests waited in the scheduler's queue:
the ``queued`` phase spans of the engine's ``Tracer`` (submit to
admission) that closed in the window, in milliseconds."""

import numpy as np


def read(ctx):
    lo, hi = ctx.window
    waits = [s.end - s.start for s in ctx.spans
             if s.name == "queued" and s.attrs.get("cat") == "phase"
             and lo <= s.end <= hi]
    if not waits:
        return None
    return float(np.percentile(waits, 95)) / 1e6
