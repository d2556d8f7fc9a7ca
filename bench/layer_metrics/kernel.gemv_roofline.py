"""The decode GEMVs' share of their roofline on one chip: the least time
of every ``pimnast_*`` kernel call in the window's decode programs on
the first chip (from the call's own shapes in the trace, which on a mesh
are that chip's shard: the larger of its bytes over one chip's peak
bandwidth and its operations over one chip's peak FLOP/s,
``reduce.gemv_least_ns``) over that chip's device time of those calls
and of the copies and slices that stage their weights
(``reduce.stages_weights``), in percent.  The staging
is counted because XLA may place a layer's weight slice in on-core
memory, and the kernel then reads fast memory while the read from HBM is
the slice's."""

import reduce


def read(ctx):
    ops = [o for o in ctx.device_ops(0)
           if ctx.program_of(o) == "jit__decode_fn"]
    kernels = [o for o in ops if reduce.is_gemv_kernel(o)]
    if not kernels:
        return None
    # every step repeats the same few operations: parse each text once
    parsed = {}
    least, weights = 0.0, set()
    for k in kernels:
        if k.name not in parsed:
            parsed[k.name] = reduce.gemv_least_ns(k.name, ctx.peaks)
        ns, wd = parsed[k.name]
        least += ns
        weights.add(wd)
    staging = {}
    for o in ops:
        if o.name not in staging:
            staging[o.name] = (not reduce.is_gemv_kernel(o)
                               and reduce.stages_weights(o, weights))
    spent = sum(ctx.clipped_ns(o) for o in kernels)
    spent += sum(ctx.clipped_ns(o) for o in ops if staging[o.name])
    return 100.0 * least / spent
