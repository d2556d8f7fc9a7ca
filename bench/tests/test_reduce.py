"""The reduction from a traced run to the per-layer metrics, on hand-made
intervals and on a small trace recorded on the chip."""

import collections
import gzip
import os

import pytest

import reduce
import spec


def test_union_merges_overlaps_and_clips():
    iv = [(0, 10), (5, 15), (20, 30), (29, 31), (40, 50)]
    assert reduce.union_ns(iv, 0, 100) == 15 + 11 + 10
    assert reduce.union_ns(iv, 8, 45) == 7 + 11 + 5
    assert reduce.union_ns([], 0, 10) == 0


def test_gaps_are_the_complement_of_the_union():
    iv = [(5, 15), (0, 10), (20, 30)]
    assert reduce.gaps_ns(iv, 0, 40) == [(15, 20), (30, 40)]
    assert reduce.gaps_ns(iv, -5, 25) == [(-5, 0), (15, 20)]
    lo, hi = -5, 40
    gaps = reduce.gaps_ns(iv, lo, hi)
    assert sum(b - a for a, b in gaps) + reduce.union_ns(iv, lo, hi) == \
        hi - lo


def test_step_programs_and_kernels_by_name():
    assert reduce.is_step_program("jit__decode_fn")
    assert reduce.is_step_program("jit__prefill_fn")
    assert not reduce.is_step_program("jit__merge_fn")
    assert not reduce.is_step_program("jit_dynamic_update_slice")
    op = reduce.Interval("pimnast_gemv.3", 0, 1)
    assert reduce.is_gemv_kernel(op)
    assert not reduce.is_gemv_kernel(reduce.Interval("fusion.12", 0, 1))


# A traced window of olmo-1b.decode-heavy on one TPU v5 lite: 0.55 s,
# 13 decode steps at bucket 8, recorded by ``run.run_cell(...,
# keep_trace=...)``.
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "olmo-1b.decode-heavy")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def ctx():
    m = spec.load_cell("olmo-1b.decode-heavy").model
    return reduce.load_records(RECORDED, model=m, peaks=PEAKS)


@pytest.fixture(scope="module")
def raw():
    """The recorded device events read straight from the file, without
    the reduction: ``[(line, name, start_ns, end_ns)]``."""
    from jax.profiler import ProfileData

    with gzip.open(os.path.join(RECORDED, "trace.xplane.pb.gz")) as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    plane = pd.find_plane_with_name("/device:TPU:0")
    return [(line.name, e.name, e.start_ns, e.start_ns + e.duration_ns)
            for line in plane.lines for e in line.events]


def test_recorded_window_and_spans(ctx):
    assert ctx.window_s() == pytest.approx(0.553456144)
    assert len(ctx.engine_spans("decode_step")) == 13
    assert all(int(s.attrs["bucket"]) == 8
               for s in ctx.engine_spans("decode_step"))
    # the step records and the Tracer's spans land on the same clock
    for (a, b, _), s in zip(ctx.steps, ctx.engine_spans("decode_step")):
        assert a <= s.start and s.end <= b


def test_idle_is_the_complement_of_the_busy_union(ctx, raw):
    lo, hi = ctx.window
    ops = [(a, b) for line, _, a, b in raw if line == "XLA Ops"]
    busy = reduce.union_ns(ops, lo, hi) / 1e9
    assert ctx.busy_s() == pytest.approx(busy)
    idle = spec.layer_metric_reader("device.idle_pct")(ctx)
    assert idle == pytest.approx(100 * (1 - busy / ctx.window_s()))
    assert 30 < idle < 50


def test_offstep_device_time_is_the_other_programs(ctx, raw):
    lo, hi = ctx.window
    other = sum(min(b, hi) - max(a, lo) for line, name, a, b in raw
                if line == "XLA Modules" and b > lo and a < hi
                and not name.startswith(("jit__decode_fn(",
                                         "jit__prefill_fn(")))
    got = spec.layer_metric_reader("kv.offstep_device_ms")(ctx)
    assert got == pytest.approx(other / 1e6 / 13)
    assert 5 < got < 8      # the whole-cache merge: two 2.15 GB copies


def test_every_gemv_kernel_runs_inside_the_decode_program(ctx):
    kernels = [o for o in ctx.device_ops(0) if reduce.is_gemv_kernel(o)]
    assert {ctx.program_of(o) for o in kernels} == {"jit__decode_fn"}
    names = collections.Counter(reduce.instruction(o.name) for o in kernels)
    # per step: fused QKV and ffn_down (split-K) and gate+up (pim) in each
    # of 16 layers, and the lm_head once
    assert sorted(names.values())[-3:] == [207, 208, 208]
    assert names["%pimnast_gemv.8"] == 13


def test_gemv_roofline_counts_the_weight_staging(ctx):
    kernels = [o for o in ctx.device_ops(0) if reduce.is_gemv_kernel(o)]
    weights = {reduce.gemv_least_ns(k.name, PEAKS)[1] for k in kernels}
    assert weights == {(8, 256, 6144), (8, 1024, 2048), (2048, 16384),
                       (2048, 50304)}
    staged = collections.Counter(
        reduce.shapes_of(o.name)[0][1] for o in ctx.device_ops(0)
        if ctx.program_of(o) == "jit__decode_fn"
        and reduce.stages_weights(o, weights))
    # each layer's fused QKV, gate+up and down slice, and the tied head's
    # transpose: one per kernel call
    assert staged == {(1, 2048, 6144): 207, (2048, 16384): 208,
                      (1, 8192, 2048): 208, (50304, 2048): 13}
    share = spec.layer_metric_reader("kernel.gemv_roofline")(ctx)
    assert 10 < share < 100


def test_gemv_least_time_from_the_kernel_text():
    op = ("%pimnast_gemv.8 = bf16[8,50304]{1,0:T(8,128)(2,1)} custom-call("
          "bf16[8,2048]{1,0:T(8,128)(2,1)S(1)} %fusion.51, "
          "bf16[2048,50304]{1,0:T(8,128)(2,1)} %bitcast.5)")
    ns, wd = reduce.gemv_least_ns(op, PEAKS)
    assert wd == (2048, 50304)
    nbytes = 2 * (8 * 50304 + 8 * 2048 + 2048 * 50304)
    assert ns == pytest.approx(nbytes / 819e9 * 1e9)
    assert [s[2] for s in reduce.shapes_of(op)] == [False, True, False]


def test_breakdown_names_programs_and_host_spans(ctx):
    b = ctx.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all("/%" in name for name, _ in b["device_ops"])
    assert not any("%while" in name for name, _ in b["device_ops"])
    assert b["device_ops"][0][0] == "jit_dynamic_update_slice/%copy.1"
    idle = sum(s for _, s in b["idle_gaps"])
    assert idle == pytest.approx(ctx.window_s() - ctx.busy_s())


# What each reader gave on the recorded traces before the shares were read
# against every chip of the cell; a one-chip Context must still give it.
ONE_CHIP = {
    "olmo-1b.decode-heavy": {
        "device.idle_pct": 40.58843513353426,
        "engine.decode_idle_ms": 17.26273538461656,
        "kernel.gemv_roofline": 38.19701734084294,
        "kv.offstep_device_ms": 6.599396461538461,
        "model.decode_hbm_pct": 7.9152947063497034,
        "model.decode_mfu_pct": 0.23028431261264357},
    "olmo-1b.decode-heavy.spans": {
        "device.idle_pct": 42.21192682495225,
        "engine.decode_idle_ms": 18.2078652500089,
        "kernel.gemv_roofline": 38.21305441466566,
        "kv.offstep_device_ms": 6.59843875,
        "model.decode_hbm_pct": 7.377615969039433,
        "model.decode_mfu_pct": 0.22286891106664597,
        "model.decode_program_hbm_pct": 17.12295823194941},
}
WHOLE_MODEL_SHARES = ("model.decode_hbm_pct", "model.decode_mfu_pct",
                      "model.decode_program_hbm_pct")


def _recorded(name, chips):
    m = spec.load_cell("olmo-1b.decode-heavy").model
    return reduce.load_records(os.path.join(os.path.dirname(RECORDED), name),
                               model=m, peaks=PEAKS, chips=chips)


@pytest.mark.parametrize("name", sorted(ONE_CHIP))
def test_one_chip_readings_are_unchanged(name):
    ctx = _recorded(name, 1)
    for metric, want in ONE_CHIP[name].items():
        assert spec.layer_metric_reader(metric)(ctx) == want, metric


def test_whole_model_shares_are_read_against_every_chip():
    """The model's work over four chips' peak reads a quarter of it over
    one chip's; a kernel's share stays one chip's shard against one
    chip's peak."""
    one, four = (_recorded("olmo-1b.decode-heavy.spans", n) for n in (1, 4))
    for metric in WHOLE_MODEL_SHARES:
        read = spec.layer_metric_reader(metric)
        assert read(four) == pytest.approx(read(one) / 4, rel=1e-12), metric
    for metric in ("kernel.gemv_roofline", "kv.offstep_device_ms",
                   "engine.decode_idle_ms"):
        read = spec.layer_metric_reader(metric)
        assert read(four) == read(one), metric
