"""The ``program_span`` readers: ``span_ms`` on hand-made span lists (self
time, per-slice and per-request sums, window clipping), the reader of the
program's ring, ``idle_named`` on a hand-made trace, and a ``--dry`` run that
prints every ``program_span`` metric under its ``dry.`` name."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark.lib import spans as SP
from benchmark.lib import trace
from benchmark.readers import Context, idle_named, span_ms

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SPAN_METRICS = [m["name"] for m in BENCH["per_layer"] if m["source"] == "program_span"]


def params_of(name):
    return json.loads((ROOT / "benchmark" / "layer_metrics" / f"{name}.json").read_text())["params"]


def S(name, t0, t1, span_id, parent=None, trace_id=None, tid=1, **attrs):
    return SP.S(name, t0, t1, tid, span_id, parent, trace_id, attrs)


# two passes of the loop on thread 1; the second ran no slice. A request's
# phases hang under its root on no thread of the loop's (tid 7).
LOOP = [
    S("sched.iter", 10.000, 10.400, 1),
    S("sched.reap", 10.000, 10.001, 2, 1),
    S("sched.slice", 10.001, 10.301, 3, 1, rows=4),
    S("session.slice.dispatch", 10.001, 10.003, 4, 3),
    S("session.slice.wait", 10.003, 10.281, 5, 3),
    S("session.slice.fetch", 10.281, 10.286, 6, 3),
    S("session.slice.account", 10.286, 10.301, 7, 3),
    S("sched.egress", 10.301, 10.311, 8, 1),
    S("sched.join", 10.311, 10.381, 9, 1),
    # re-entered under the request's root (attach): parent 100, but inside sched.join in time
    S("session.join.prefill", 10.312, 10.340, 10, 100, "aaaa"),
    S("session.join.commit", 10.340, 10.380, 11, 100, "aaaa"),
    S("session.join.install", 10.345, 10.378, 12, 11, "aaaa"),
    S("sched.admit", 10.381, 10.395, 13, 1),
    S("sched.iter", 10.400, 10.460, 20),
    S("sched.join", 10.401, 10.455, 21, 20),
    S("session.join.commit", 10.420, 10.450, 22, 101, "bbbb"),
    S("sched.iter", 10.460, 10.900, 30),
    S("sched.slice", 10.461, 10.861, 31, 30, rows=5),
    S("session.slice.fetch", 10.841, 10.845, 32, 31),
    S("session.slice.account", 10.845, 10.861, 33, 31),
]
REQUESTS = [
    S("request", 9.800, 12.0, 100, None, "aaaa", tid=7),
    S("queue", 9.800, 10.100, 110, 100, "aaaa", tid=7),
    S("join.wait", 10.100, 10.312, 111, 100, "aaaa", tid=7),
    S("join.prefill", 10.312, 10.340, 112, 100, "aaaa", tid=7),
    S("join.wait", 10.340, 10.700, 113, 100, "aaaa", tid=7),
    S("join.prefill", 10.700, 10.730, 114, 100, "aaaa", tid=7),
    S("join.commit", 10.730, 10.770, 115, 100, "aaaa", tid=7),
    S("egress.first", 10.770, 10.772, 116, 100, "aaaa", tid=7),
    S("queue", 10.000, 10.050, 120, 101, "bbbb", tid=7),
    S("join.wait", 10.050, 10.400, 121, 101, "bbbb", tid=7),
    # submitted before the window: its queue span is not in the list, its waits do not count
    S("join.wait", 10.010, 10.020, 131, 102, "cccc", tid=7),
]
ALL = sorted(LOOP + REQUESTS, key=lambda s: (s.t0, -s.t1))


def test_self_time_takes_the_union_of_what_ran_inside():
    # pass 1: 400 ms less the slice (300) less the join spans outside it (prefill 28 + commit 40:
    # the install nests in the commit and is not taken off twice) = 32 ms; pass 3: 440 - 400 = 40 ms;
    # pass 2 ran no slice and is not a sample
    got = span_ms.measure(ALL, params_of("sched.gap_self_ms_p50"))
    assert got == pytest.approx((32.0 + 40.0) / 2)
    it = ALL[[s.span_id for s in ALL].index(1)]
    inner = SP.inside(it, ALL, ["sched.slice", "session."])
    assert {s.span_id for s in inner} == {3, 4, 5, 6, 7, 10, 11, 12}
    assert SP.covered_seconds(it, inner) == pytest.approx(0.368)


def test_per_parent_sums_the_two_tails_of_one_slice():
    got = span_ms.measure(ALL, params_of("session.slice_tail_ms_p50"))
    assert got == pytest.approx((20.0 + 20.0) / 2)
    assert span_ms.measure(ALL, params_of("session.join_commit_ms_p50")) == pytest.approx(35.0)


def test_per_request_sums_waits_of_requests_submitted_in_the_window():
    p = params_of("sched.join_wait_p95_ms")
    # aaaa: 212 + 360 = 572 ms; bbbb: 350 ms; cccc was submitted before the window
    assert span_ms.measure(ALL, {**p, "stat": "p50"}) == pytest.approx((572.0 + 350.0) / 2)
    assert span_ms.measure(ALL, p) == pytest.approx(350.0 + 0.95 * 222.0)
    assert span_ms.measure(ALL, params_of("sched.queue_wait_p95_ms")) == pytest.approx(50.0 + 0.95 * 250.0)


def test_nothing_to_read_is_none_never_zero():
    old_program = [S("queue", 1.0, 2.0, 1), S("decode", 2.0, 3.0, 2), S("prefill", 1.5, 2.0, 3)]
    for name in SPAN_METRICS:
        if name != "sched.queue_wait_p95_ms":
            assert span_ms.measure(old_program, params_of(name)) is None, name
    assert span_ms.measure([], params_of("sched.queue_wait_p95_ms")) is None
    with pytest.raises(ValueError):
        span_ms.measure(ALL, {"names": ["queue"], "stat": "p50", "per": "cell"})


def test_the_ring_is_clipped_to_spans_whole_inside_the_window():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu import obs
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.trace import TRACER

    was = obs.enabled()
    obs.enable()
    try:
        root = TRACER.root("request", trace_id="dddddddddddddddd")
        TRACER.add_span("queue", 5000.0, 5000.5, parent=root)
        TRACER.add_span("queue", 5000.9, 5001.2, parent=root)  # ends after the window
        TRACER.add_span("queue", 4999.9, 5000.2, parent=root)  # starts before it
        TRACER.add_span("join.wait", 5000.5, 5000.75, attrs={"k": 1}, parent=root)
    finally:
        (obs.enable if was else obs.disable)()
    got = SP.finished(5000.0, 5001.0)
    assert [(s.name, s.t0, s.t1) for s in got] == [("queue", 5000.0, 5000.5), ("join.wait", 5000.5, 5000.75)]
    assert got[0].trace_id == "dddddddddddddddd" and got[0].parent_id == root.span_id
    assert got[1].attrs == {"k": 1} and isinstance(got[1].tid, int)
    ctx = Context.build(cfg={}, mix={}, cell={}, chip=None, trace=None, records=[], slices=[], slice_steps=16,
                        compiles=0, t0=5000.0, t1=5000.3, window_t1=5001.0)
    # the whole window, not its traced part
    assert span_ms.read(ctx, {"names": ["queue"], "stat": "p50"}) == pytest.approx(500.0)
    assert span_ms.read(ctx, {"names": ["join.wait"], "stat": "p95", "per": "request"}) == pytest.approx(250.0)


def test_idle_named_is_the_share_of_long_gaps_a_phase_span_covers():
    dev = trace.DeviceTrace(
        ops=[(0.000, 0.100, "fusion.1"), (0.110, 0.200, "fusion.2"), (0.2004, 0.300, "fusion.3"),
             (0.330, 0.400, "fusion.4"), (0.440, 0.500, "fusion.5")],
        modules=[(0.0, 0.5, "jit_decode(1)")],
    )
    host = [
        (0.0, 0.5, "bench:window"), (0.05, 0.45, "sched.iter"),
        (0.099, 0.109, "session.slice.fetch"),  # covers 9 of the 10 ms gap
        (0.300, 0.312, "sched.egress"),  # covers 12 of the 30 ms gap: not most of it
        (0.402, 0.439, "session.join.commit"),  # covers 37 of the 40 ms gap
    ]
    tr = trace.Trace(devices={0: dev}, host=sorted(host))
    ctx = Context.build(cfg={}, mix={}, cell={}, chip=None, trace=tr, records=[], slices=[], slice_steps=16,
                        compiles=0, t0=100.0, t1=100.5, window_t1=140.0)
    p = params_of("device.idle_named_pct")
    # gaps of 1 ms or more: 10 + 30 + 40 ms (the 0.4 ms one is too short to look up); named: 10 + 40
    assert idle_named.read(ctx, p) == pytest.approx(100.0 * 50 / 80)
    # a pass cut by the trace's start is not in the trace (the profiler keeps only what opened and
    # closed inside the session): the gaps under it cannot be named and are left out, as are those
    # after the last span's end
    cut = trace.Trace(devices={0: dev}, host=sorted(h for h in host if h[2] != "sched.iter" and h[0] > 0.2))
    ctx_cut = Context.build(cfg={}, mix={}, cell={}, chip=None, trace=cut, records=[], slices=[],
                            slice_steps=16, compiles=0, t0=100.0, t1=100.5, window_t1=140.0)
    # between 0.300 and 0.439: the 30 ms gap, unnamed, and 39 of the 40 ms one, named
    assert idle_named.read(ctx_cut, p) == pytest.approx(100.0 * 39 / 69)
    # sched.iter covers every gap and names none
    only_iter = trace.Trace(devices={0: dev}, host=[(0.0, 0.5, "bench:window"), (0.05, 0.45, "sched.iter")])
    ctx2 = Context.build(cfg={}, mix={}, cell={}, chip=None, trace=only_iter, records=[], slices=[],
                         slice_steps=16, compiles=0, t0=100.0, t1=100.5, window_t1=140.0)
    assert idle_named.read(ctx2, p) is None  # an older program: no phase span on the host plane
    gaps = trace.top_idle_gaps(tr, dev, 0.0, 0.5)
    assert [g[0] for g in gaps][:3] == ["jit_decode>jit_decode|session.join.commit",
                                        "jit_decode>jit_decode|sched.iter",
                                        "jit_decode>jit_decode|session.slice.fetch"]


def test_span_names_survive_the_trace_reducers_id_stripping():
    names = {s.name for s in ALL} | {"sched.sweep", "sched.open", "open"}
    for name in names:
        assert trace.strip_id(name) == name


def test_dry_run_prints_every_program_span_metric():
    assert len(SPAN_METRICS) == 5
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload", "mistral-7b.chat-closed",
         "--seed", str(2**31 + 11), "--seconds", "3", "--trace", "1", "--dry"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    for name in SPAN_METRICS:
        assert line["metrics"][f"dry.{name}"]["value"] > 0, name
    assert line["correct"] is True
    # on the CPU there is no TPU plane: the device_trace ones stay silent
    assert not [k for k in line["metrics"] if k.startswith(("dry.step.", "dry.device."))]
