"""The decode GEMVs' share of their roofline: the least time of every
``pimnast_*`` kernel call in the window's decode programs (from the
call's own shapes in the trace: the larger of its bytes over peak
bandwidth and its operations over peak FLOP/s, ``reduce.gemv_least_ns``)
over the device time of those calls and of the copies and slices that
stage their weights (``reduce.stages_weights``), in percent.  The staging
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
    least, weights = 0.0, set()
    for k in kernels:
        ns, wd = reduce.gemv_least_ns(k.name, ctx.peaks)
        least += ns
        weights.add(wd)
    spent = sum(ctx.clipped_ns(o) for o in kernels)
    spent += sum(ctx.clipped_ns(o) for o in ops
                 if not reduce.is_gemv_kernel(o)
                 and reduce.stages_weights(o, weights))
    return 100.0 * least / spent
