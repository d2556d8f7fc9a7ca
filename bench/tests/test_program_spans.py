"""The program's own spans in a profiler trace, and the device programs
attributed to the span that launched them, on two traces recorded on the
chip: one with the engine's annotations (``.spans``) and one without."""

import math
import os

import pytest

import program_spans
import reduce
import spec

DATA = os.path.join(os.path.dirname(__file__), "data")
# 0.55 s of olmo-1b.decode-heavy on one TPU v5 lite above its knee, with
# the engine's Tracer spans also profiler annotations; recorded by
# ``run.run_cell(..., keep_trace=...)`` and gzipped
ANNOTATED = os.path.join(DATA, "olmo-1b.decode-heavy.spans")
# the earlier recording, made before the spans were annotations
PLAIN = os.path.join(DATA, "olmo-1b.decode-heavy")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
DECODE_CHILDREN = ["engine.kv.slice", "engine.decode.launch",
                   "engine.kv.merge", "engine.decode.fetch",
                   "engine.decode.sample"]


def _load(where):
    m = spec.load_cell("olmo-1b.decode-heavy").model
    ctx = reduce.load_records(where, model=m, peaks=PEAKS)
    return ctx, program_spans.read(os.path.join(where,
                                                "trace.xplane.pb.gz"))


@pytest.fixture(scope="module")
def annotated():
    return _load(ANNOTATED)


@pytest.fixture(scope="module")
def plain():
    return _load(PLAIN)


def test_annotations_carry_the_decode_step_tree(annotated):
    ctx, prog = annotated
    steps = prog.named("engine.decode_step", ctx.window)
    assert len(steps) >= 8
    assert len(steps) == len(ctx.engine_spans("decode_step"))
    for s in steps:
        kids = sorted((c for c in prog.spans
                       if s.start <= c.start and c.end <= s.end
                       and c.name.startswith(("engine.kv.",
                                              "engine.decode."))),
                      key=lambda c: c.start)
        assert [c.name for c in kids] == DECODE_CHILDREN
        assert {"rows", "live_positions", "bucket"} <= set(s.attrs)
        assert kids[0].attrs["bucket"] == s.attrs["bucket"]


def test_each_call_counted_once(plain):
    """A call of a jitted function shows as two nested events of one name;
    each call launches one program execution."""
    ctx, prog = plain
    assert len(prog.calls) == len(prog.modules[0]) == 1027
    assert sum(c.name == "_decode_fn" for c in prog.calls) == 13


@pytest.mark.parametrize("which", ["annotated", "plain"])
def test_flow_attribution_covers_the_module_time(which, request):
    ctx, prog = request.getfixturevalue(which)
    assert program_spans.attributed_share(prog, ctx.window) >= 0.99


def test_decode_programs_are_launched_by_decode_launch(annotated):
    ctx, prog = annotated
    lo, hi = ctx.window
    decode = [m for m in prog.modules[0] if lo <= m.start and m.end <= hi
              and reduce.program_name(m.name) == "jit__decode_fn"]
    assert decode
    assert {m.attrs["span"] for m in decode} == {"engine.decode.launch"}
    launched = program_spans.launched(prog, ctx.window)
    assert launched["engine.decode.launch"]["executions"] >= len(decode)
    assert launched["engine.decode.launch"]["calls"] == len(
        prog.named("engine.decode.launch", ctx.window))


def test_new_readers_read_finite_values(annotated):
    ctx, prog = annotated
    values = {
        "engine.decode_idle_ms":
            spec.layer_metric_reader("engine.decode_idle_ms")(ctx),
        "model.decode_program_hbm_pct":
            spec.layer_metric_reader("model.decode_program_hbm_pct")(ctx),
        "engine.dispatches_per_step":
            program_spans.dispatches_per_step(prog, ctx.window),
        "kv.launched_device_ms":
            program_spans.kv_launched_device_ms(prog, ctx.window),
    }
    assert all(v is not None and math.isfinite(v) for v in values.values()), \
        values
    assert 0 < values["model.decode_program_hbm_pct"] < 100
    # the KV layer's launches are a part of what runs outside the steps
    offstep = spec.layer_metric_reader("kv.offstep_device_ms")(ctx)
    assert 0 < values["kv.launched_device_ms"] <= offstep


def test_decode_idle_fits_in_the_window_idle(annotated):
    ctx, prog = annotated
    steps = len(ctx.engine_spans("decode_step"))
    idle = spec.layer_metric_reader("engine.decode_idle_ms")(ctx)
    assert idle * steps <= (ctx.window_s() - ctx.busy_s()) * 1e3
    by_ann = program_spans.decode_idle_ms(prog, ctx)
    assert by_ann * steps <= (ctx.window_s() - ctx.busy_s()) * 1e3


def test_tracer_spans_and_annotations_read_alike(annotated):
    """The Tracer's spans, put on the profiler's clock by the two window
    marks, read what the annotations of the same blocks read."""
    ctx, prog = annotated
    res = program_spans.alignment_residual_us(prog, ctx)
    assert res and max(res) < 500
    idle = spec.layer_metric_reader("engine.decode_idle_ms")(ctx)
    assert idle == pytest.approx(program_spans.decode_idle_ms(prog, ctx),
                                 rel=0.05)
    hbm = spec.layer_metric_reader("model.decode_program_hbm_pct")(ctx)
    assert hbm == pytest.approx(
        program_spans.decode_program_hbm_pct_launched(prog, ctx), rel=1e-9)


def test_a_trace_without_annotations(plain):
    """What the readers give on a program whose spans are not annotations
    and whose decode steps carry no live positions."""
    ctx, prog = plain
    assert prog.spans == []
    assert program_spans.dispatches_per_step(prog, ctx.window) is None
    assert program_spans.kv_launched_device_ms(prog, ctx.window) is None
    assert spec.layer_metric_reader("model.decode_program_hbm_pct")(
        ctx) is None
    idle = spec.layer_metric_reader("engine.decode_idle_ms")(ctx)
    assert idle is not None and 0 < idle < 42.4
