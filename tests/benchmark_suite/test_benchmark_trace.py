"""The trace reduction: interval arithmetic on hand-made spans, and the
whole reduction on a small trace recorded on the chip."""

from pathlib import Path

import pytest

from benchmark.lib import trace

RECORDED = Path(__file__).resolve().parents[2] / "benchmark" / "recorded" / "recorded.xplane.pb.gz"


def test_union_counts_overlap_once():
    spans = [(0.0, 1.0, "a"), (0.5, 1.5, "b"), (2.0, 3.0, "c"), (2.2, 2.4, "d")]
    assert trace.union_seconds(spans) == pytest.approx(2.5)


def test_idle_gaps_are_what_no_span_covers():
    spans = [(1.0, 2.0, "a"), (1.5, 2.5, "b"), (4.0, 5.0, "c")]
    assert trace.idle_gaps(spans, 0.0, 6.0) == [(0.0, 1.0), (2.5, 4.0), (5.0, 6.0)]
    assert trace.idle_gaps(spans, 1.2, 4.5) == [(2.5, 4.0)]


def test_clip_cuts_spans_to_the_window():
    assert trace.clip([(0.0, 2.0, "a"), (3.0, 4.0, "b")], 1.0, 3.5) == [(1.0, 2.0, "a"), (3.0, 3.5, "b")]


@pytest.mark.parametrize("name,want", [
    ("fusion.123", "fusion"), ("jit_step(12345)", "jit_step"), ("copy", "copy"), ("custom-call.4.1", "custom-call"),
    ("%fusion.246 = bf16[16,14336]{1,0:T(8,128)(2,1)S(1)} fusion(f32[14336]{0} %bitcast.285)", "fusion"),
    ("%while.41 = (s32[]{:T(128)}, bf16[16,1,4096]) while(%tuple.3)", "while"),
])
def test_strip_id(name, want):
    assert trace.strip_id(name) == want


def test_top_ops_and_gaps_are_named_by_program_and_host_span():
    dev = trace.DeviceTrace(
        ops=[(0.0, 1.0, "fusion.1"), (1.0, 1.5, "fusion.2"), (3.0, 4.0, "copy.9")],
        modules=[(0.0, 1.5, "jit_step(1)"), (3.0, 4.0, "jit_join(2)")],
    )
    tr = trace.Trace(devices={0: dev}, host=[(1.4, 3.1, "PjitFunction(join)"), (0.0, 4.0, "bench:window")])
    assert trace.top_device_ops(dev, 0.0, 4.0) == [["jit_step/fusion", 1.5], ["jit_join/copy", 1.0]]
    gaps = trace.top_idle_gaps(tr, dev, 0.0, 4.0)
    assert gaps == [["jit_step>jit_join|pjit:join", pytest.approx(1.5)]]
    nested = trace.DeviceTrace(ops=[(0.0, 2.0, "%while.1 = (s32[]) while(%t)"), (0.0, 1.0, "fusion.1"), (1.0, 2.0, "fusion.2")],
                               modules=[(0.0, 2.0, "jit_decode(7)")])
    assert trace.top_device_ops(nested, 0.0, 2.0) == [["jit_decode/fusion", 2.0]]
    assert trace.module_runs(dev, "step", 0.0, 4.0) == [(0.0, 1.5, "jit_step(1)")]
    assert trace.module_runs(dev, "step", 0.5, 4.0) == []


@pytest.mark.skipif(not RECORDED.is_file(), reason="no recorded trace in this checkout")
def test_reduction_of_a_trace_recorded_on_the_chip():
    tr = trace.load(RECORDED)
    assert tr.devices, "the recorded trace has a TPU plane"
    dev = max(tr.devices.values(), key=lambda d: len(d.ops))
    assert dev.ops and dev.modules
    t0, t1 = dev.ops[0][0], max(b for _, b, _ in dev.ops)
    busy = trace.union_seconds(trace.clip(dev.ops, t0, t1))
    assert 0 < busy <= (t1 - t0) * (1 + 1e-9)
    idle = sum(b - a for a, b in trace.idle_gaps(dev.ops, t0, t1))
    assert busy + idle == pytest.approx(t1 - t0, rel=1e-6)
    top = trace.top_device_ops(dev, t0, t1)
    assert 1 <= len(top) <= 10 and top == sorted(top, key=lambda kv: -kv[1])
    assert sum(v for _, v in top) <= busy * (1 + 1e-6)
    assert all("/" in k for k, _ in top) and top[0][0] == "jit_decode/fusion"
    window = next(s for s in tr.host if s[2] == "bench:window")
    runs = trace.module_runs(dev, "jit_decode", window[0], window[1])
    assert len(runs) == 2 and sum(b - a for a, b, _ in runs) == pytest.approx(0.4836, abs=1e-3)
    gaps = trace.top_idle_gaps(tr, dev, window[0], window[1])
    assert 1 <= len(gaps) <= 10 and gaps[0][0].endswith("|pjit:dynamic_slice")
