"""The reduction from a trace to the per-layer metrics: on hand-made
events, and on traces recorded on a TPU v5e."""
import os

import pytest

from chipbench import harness, shapes, trace

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(HERE, "tests", "data")


def op(name, start, dur):
    return trace.Op(name, float(start), float(dur))


def planes(ops, host, span=(100.0, 1100.0)):
    return [("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", [])]),
            ("/host:CPU", [("python3", host + [op(trace.CALL_SPAN,
                                                    span[0],
                                                    span[1] - span[0])])])]


def test_busy_is_the_union_of_intervals():
    ops = [op("a", 0, 10), op("b", 5, 10), op("c", 30, 5), op("d", 31, 1)]
    assert trace.busy_ns(ops) == 15 + 5


def test_reading_keeps_the_call_span():
    ops = [op("before", 0, 50), op("in", 200, 100), op("after", 1200, 5)]
    r = trace.reading_from_planes(planes(ops, []))
    assert [o.name for o in r.ops] == ["in"]
    assert r.window_s == 1000 / 1e9 and r.busy_s == 100 / 1e9


def test_idle_gaps_name_the_innermost_host_event():
    ops = [op("x", 100, 100), op("y", 700, 400)]
    host = [op("outer", 100, 1000), op("inner", 150, 600)]
    r = trace.reading_from_planes(planes(ops, host))
    gaps = trace.idle_gaps(r)
    assert gaps[0] == ["inner", 500 / 1e9]
    assert trace.device_ops(r)[0] == ["y", 400 / 1e9]


DBB = ("%_dbb_gemm_impl.67 = bf16[32,8192]{1,0:T(8,128)(2,1)S(1)} "
       "custom-call(bf16[32,2048]{1,0:T(8,128)(2,1)S(1)} %fusion.44, "
       "bf16[1024,8192]{1,0:T(8,128)(2,1)} %g, s32[256,8192]{1,0} %m), "
       "custom_call_target=\"tpu_custom_call\"")
PAGED = ("%closed_call.25 = bf16[32,16,8,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
         "custom-call(s32[32,32]{1,0:T(8,128)S(1)} %t, s32[32]{0:T(128)} "
         "%l, s32[32]{0:T(128)} %s, bf16[32,16,8,128]{3,2,1,0} %q), "
         "custom_call_target=\"tpu_custom_call\"")


def test_shapes_of_kernel_ops():
    assert shapes.dbb_gemm_mkn(DBB, 8, 4) == (32, 2048, 8192)
    assert shapes.dbb_gemm_mkn(DBB, 8, 2) is None
    assert shapes.dbb_gemm_mkn(PAGED, 8, 4) is None
    assert shapes.paged_decode(PAGED) == (32, 32)
    assert shapes.paged_decode(DBB) is None
    assert shapes.short(DBB) == "%_dbb_gemm_impl.67 = bf16[32,8192] " \
        "custom-call"


RECORDED = sorted(f for f in os.listdir(DATA) if f.endswith(".json.gz")) \
    if os.path.isdir(DATA) else []


@pytest.mark.parametrize("path", RECORDED)
def test_recorded_trace_gives_the_recorded_numbers(path):
    """A small call traced on a TPU v5e (four requests of the cell's mix,
    eight tokens each at most; ``record_trace.py``) read again gives the
    numbers computed from it when it was recorded."""
    reading, rec = trace.load(os.path.join(DATA, path))
    cell = harness.load_cell(rec["cell"])
    cell.device_kind = rec["device_kind"]
    run = trace.make_run(cell, reading, rec["prompt_lens"],
                         rec["served_lens"], rec["stats"])
    assert trace.metrics_of(cell, run) == rec["metrics"]
    assert {"device_ops": trace.device_ops(reading),
            "idle_gaps": trace.idle_gaps(reading)} == rec["breakdown"]
    assert rec["metrics"], "the recorded trace holds no metric"
