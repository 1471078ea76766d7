"""The serving loop's spans and the kernels' stable names, on a call
traced on a TPU v5e (``record_trace.py``, ``tests/data/*.spans.json.gz``),
and the span reduction of ``host_work_idle_share`` on hand-made events."""
import os
import re

import pytest

from chipbench import harness, shapes, trace

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(HERE, "tests", "data")
RECORDED = sorted(f for f in os.listdir(DATA)
                  if f.endswith(".spans.json.gz"))
HLO_NAME = re.compile(r"^%([A-Za-z_][\w-]*?)(?:\.\d+)? = ")
KERNELS = {"dbb_gemm_skinny", "dbb_gemm_tiled", "paged_decode"}


def _op_name(text: str) -> str:
    m = HLO_NAME.match(text)
    return m.group(1) if m else ""


@pytest.fixture(scope="module", params=RECORDED)
def recorded(request):
    reading, rec = trace.load(os.path.join(DATA, request.param))
    return harness.load_cell(rec["cell"]), reading


def test_recordings_exist():
    assert RECORDED


def test_kernel_ops_carry_their_stable_names(recorded):
    """Every DBB GEMM and paged decode op, found by its operands, is named
    after its kernel, and no other op carries those names."""
    cell, reading = recorded
    dbb = cell.cfg["dbb"]
    found = set()
    for o in reading.ops:
        mkn = shapes.dbb_gemm_mkn(o.name, dbb["block"], dbb["nnz"])
        if mkn is not None:
            want = ("dbb_gemm_skinny" if mkn[0] <= 32 else "dbb_gemm_tiled")
        elif shapes.paged_decode(o.name) is not None:
            want = "paged_decode"
        else:
            assert _op_name(o.name) not in KERNELS, shapes.short(o.name)
            continue
        assert _op_name(o.name) == want, shapes.short(o.name)
        found.add(want)
    assert found == KERNELS


def test_packed_prefill_attention_is_named(recorded):
    _, reading = recorded
    assert any(_op_name(o.name) == "flash_prefill_packed"
               for o in reading.ops)


def test_serve_spans_nest(recorded):
    """One ``serve.call`` inside the benchmark's span; every
    ``serve.iter`` inside it; every ``serve.host.*`` and ``serve.sync.*``
    span inside an iteration."""
    _, reading = recorded
    host = trace.caller(reading)[1]
    calls = [o for o in host if o.name == "serve.call"]
    iters = [o for o in host if o.name == "serve.iter"]
    leaves = [o for o in host if o.name.startswith(("serve.host.",
                                                     "serve.sync."))]
    assert len(calls) == 1 and iters and leaves
    lo, hi = reading.span
    assert lo <= calls[0].start_ns and calls[0].end_ns <= hi

    def inside(a, b):
        return b.start_ns <= a.start_ns and a.end_ns <= b.end_ns

    assert all(inside(i, calls[0]) for i in iters)
    for o in leaves:
        assert any(inside(o, i) for i in iters), o
    assert {o.name for o in leaves} >= {
        "serve.host.assign", "serve.host.pack", "serve.host.dispatch_prefill",
        "serve.host.install", "serve.host.dispatch_decode",
        "serve.host.retire", "serve.sync.first_token", "serve.sync.decode"}


def _op(name, start, dur):
    return trace.Op(name, float(start), float(dur))


def test_host_work_idle_share_on_hand_made_events():
    """Idle device time [300, 500) and [800, 1000) of a 1000 ns call: a
    host span covers 150 ns of the first gap and 100 ns of the second, a
    sync span the rest of the first gap."""
    ops = [_op("a", 0, 300), _op("b", 500, 300)]
    host = [_op(trace.CALL_SPAN, 0, 1000),
            _op("serve.host.retire", 250, 200),       # idle 300-450
            _op("serve.sync.decode", 450, 100),       # idle 450-500
            _op("serve.host.assign", 900, 200)]       # idle 900-1000
    reading = trace.Reading(ops=ops, modules=[], host={"main": host},
                            span=(0.0, 1000.0), chips=1)
    run = trace.Run(cfg={}, mix={}, peaks={}, reading=reading,
                    prompt_lens=[], served_lens=[], stats={})
    reader = harness.load_cell("olmo-1b-dbb.chat").metric_reader(
        "host_work_idle_share")
    assert reader.read(run) == pytest.approx(100.0 * 250 / 1000)
    no_host = dict(reading.host, main=[o for o in host
                                       if not o.name.startswith(
                                           "serve.host.")])
    run.reading = trace.Reading(ops=ops, modules=[], host=no_host,
                                span=(0.0, 1000.0), chips=1)
    assert reader.read(run) is None


def _counter_run(stats, ops=(_op("a", 0, 10),)):
    reading = trace.Reading(ops=list(ops), modules=[], host={},
                            span=(0.0, 100.0), chips=1)
    return trace.Run(cfg={}, mix={"engine": {"max_batch": 4}}, peaks={},
                     reading=reading, prompt_lens=[], served_lens=[],
                     stats=stats)


def test_counter_readers_on_hand_made_stats():
    cell = harness.load_cell("olmo-1b-dbb.chat")
    occupancy = cell.metric_reader("decode_occupancy")
    queue = cell.metric_reader("queue_wait_p95_s")
    stats = {"decode_steps": 10, "decode_row_steps": 30,
             "decode_surplus_row_steps": 4,
             "assign_s": [0.0, 1.0, 2.0, float("nan"), 3.0],
             "done_s": [1.0, 2.0, 3.0, float("nan"), 4.0]}
    assert occupancy.read(_counter_run(stats)) == pytest.approx(75.0)
    assert queue.read(_counter_run(stats)) == pytest.approx(2.85)
    # what a reader reads is absent, or the trace holds no device op
    assert occupancy.read(_counter_run({})) is None
    assert queue.read(_counter_run({})) is None
    assert occupancy.read(_counter_run(stats, ops=())) is None
    assert queue.read(_counter_run(stats, ops=())) is None
