"""The per-layer readers on a hand-made trace and log: device metrics take
their time and their runs from the trace, counters cover the whole window."""

import json
from pathlib import Path

import pytest

from benchmark.lib import shapes, trace
from benchmark.lib.stats import Record
from benchmark.readers import Context, decode_step_roofline, slice_period_p50, slice_rows_mean, step_mfu

ROOT = Path(__file__).resolve().parents[2]
CFG = json.loads((ROOT / "benchmark" / "configs" / "mistral-7b.json").read_text())
CHIP = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
T0 = 100.0  # host clock at the start of the traced part; the trace's span starts at 0.5


def record(index, prompt_tokens, events, total, t_done=None):
    return Record(index=index, prompt_tokens=prompt_tokens, output_tokens=total, t_due=events[0][0] - 1,
                  t_submit=events[0][0] - 1, events=list(events), tokens=[7] * total, t_done=t_done)


def context(modules, records, slices=()):
    dev = trace.DeviceTrace(ops=[(a, b, "fusion.1") for a, b, _ in modules], modules=list(modules))
    tr = trace.Trace(devices={0: dev}, host=[(0.5, 3.5, "bench:window")])
    return Context.build(cfg=CFG, mix={}, cell={}, chip=CHIP, trace=tr, records=records, slices=list(slices),
                         slice_steps=16, compiles=0, t0=T0, t1=T0 + 3.0, window_t1=T0 + 10.0)


RECORDS = [
    # decoding through the whole run: 17 tokens had, 23 to come
    record(0, 130, [(99.0, 1), (99.9, 16)], 40),
    # retires in the run: 33 had, 4 to come
    record(1, 200, [(99.5, 1), (99.95, 16), (100.3, 16)], 37),
    # joins after the run: the prefill run at 1.21-1.25 is its prompt
    record(2, 180, [(100.8, 1)], 30),
    # ended before the traced part
    record(3, 150, [(98.0, 1), (98.5, 16)], 17, t_done=98.6),
]
MODULES = [(1.0, 1.2, "jit_decode(77)"), (1.21, 1.25, "jit_prefill(78)"), (3.4, 3.6, "jit_decode(77)")]


def test_step_mfu_takes_time_and_runs_from_the_trace():
    ctx = context(MODULES, RECORDS)
    assert ctx.host_time(1.1) == pytest.approx(100.6)
    assert ctx.slice_work(100.6) == [(147, 16), (233, 4)]
    flops = (16 * shapes.decode_token_flops(CFG, 147 + 8) + 4 * shapes.decode_token_flops(CFG, 233 + 2)
             + shapes.prefill_flops(CFG, 180))
    want = 100.0 * flops / 3.0 / 197e12
    got = step_mfu.read(ctx, {"decode": "^jit_decode", "prefill": "^jit_prefill"})
    assert got == pytest.approx(want, rel=1e-12) and 0 < got < 100
    # the run that ends after the span is not whole inside it and counts for nothing;
    # with no decode run in the trace there is nothing to read, never 0
    assert step_mfu.read(context(MODULES[2:], RECORDS), {"decode": "^jit_decode", "prefill": "^jit_prefill"}) is None
    # the host's clock does not enter: the same trace and log, the window's end moved, read the same
    moved = context(MODULES, RECORDS)
    moved.t1 += 5.0
    assert step_mfu.read(moved, {"decode": "^jit_decode", "prefill": "^jit_prefill"}) == got


def test_hbm_roofline_counts_needed_bytes_over_the_runs_device_time():
    ctx = context(MODULES, RECORDS)
    rows = 20 / 16
    tokens = ((147 + 8) * 16 + (233 + 2) * 4) / 16
    want = 100.0 * 16 * shapes.decode_step_bytes(CFG, rows, tokens) / 819e9 / 0.2
    got = decode_step_roofline.read(ctx, {"module": "^jit_decode"})
    assert got == pytest.approx(want, rel=1e-9)
    # 16 steps of the 7.25 GB weight stream in 0.2 s: 12.5 ms a step against 8.9 ms of bytes
    assert 65 < got < 80
    assert decode_step_roofline.read(context([], RECORDS), {"module": "^jit_decode"}) is None


def test_counters_cover_the_whole_window_not_the_traced_part():
    slices = [(99.9, 0.3, 5), (100.5, 0.3, 4), (104.0, 0.5, 8), (109.9, 0.4, 6), (110.2, 0.3, 2)]
    ctx = context(MODULES, RECORDS, slices)
    assert [s[0] for s in ctx.slices] == [100.5, 104.0, 109.9]
    assert slice_rows_mean.read(ctx, {}) == pytest.approx((0.3 * 4 + 0.5 * 8 + 0.4 * 6) / 1.2)
    assert slice_period_p50.read(ctx, {}) == pytest.approx(400.0)
