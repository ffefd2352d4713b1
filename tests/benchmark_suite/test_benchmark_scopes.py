"""From device operation to ``jax.named_scope``: the wire-format reader on a
hand-made xplane, the reduction by scope, and ``scope_ms_per_step`` and
``idle_named`` on a small trace recorded on the chip with the program's
spans and scopes in it (``benchmark/recorded/spans.xplane.pb.gz``)."""

import gzip
import json
from pathlib import Path

import pytest

from benchmark.lib import scopes, trace
from benchmark.readers import Context, idle_named, scope_ms_per_step

ROOT = Path(__file__).resolve().parents[2]
RECORDED = ROOT / "benchmark" / "recorded" / "spans.xplane.pb.gz"
STEP_METRICS = ["step.attn_ms_per_step", "step.mlp_ms_per_step", "step.head_ms_per_step", "step.other_ms_per_step"]


def params_of(name):
    return json.loads((ROOT / "benchmark" / "layer_metrics" / f"{name}.json").read_text())["params"]


# -- a protobuf writer small enough to check the reader against ------------------

def varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(no, value):
    if isinstance(value, int):
        return varint(no << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(no << 3 | 2) + varint(len(value)) + value


def entry(key, message):
    return field(1, key) + field(2, message)


def event(meta, offset_ps, duration_ps):
    return field(1, meta) + field(2, offset_ps) + field(3, duration_ps)


def plane(name, lines, event_meta, stat_meta):
    out = field(2, name)
    for line_name, t_ns, events in lines:
        out += field(3, field(2, line_name) + field(3, t_ns) + b"".join(field(4, e) for e in events))
    for mid, (ev_name, stats) in event_meta.items():
        body = field(1, mid) + field(2, ev_name) + b"".join(field(5, s) for s in stats)
        out += field(4, entry(mid, body))
    for sid, stat_name in stat_meta.items():
        out += field(5, entry(sid, field(1, sid) + field(2, stat_name)))
    return out


def small_xspace():
    tf_op, other, kept_once = 7, 8, 9
    stat_meta = {tf_op: "tf_op", other: "hlo_category", kept_once: "jit(decode)/while/body/mlp/dot_general:"}
    event_meta = {
        1: ("jit_decode(123)", []),
        2: ("%while.4 = (s32[]) while(%t)", [field(1, tf_op) + field(5, "jit(decode)/while:")]),
        3: ("%fusion.7 = bf16[16,64] fusion(%a)", [field(1, other) + field(5, "x"),
                                                  field(1, tf_op) + field(5, "jit(decode)/while/body/attn.core/attn.kv_gather/gather:")]),
        4: ("%fusion.8 = bf16[16,64] fusion(%b)", [field(1, tf_op) + field(7, kept_once)]),  # by reference
        5: ("%copy.2 = bf16[16,64] copy(%c)", []),  # the compiler's own: no op_name
        6: ("jit_prefill(9)", []),
        7: ("%fusion.1 = f32[8] fusion(%d)", [field(1, tf_op) + field(5, "jit(prefill)/head/dot_general:")]),
    }
    ms = 10**9  # picoseconds
    ops = [event(2, 0, 10 * ms), event(3, 0, 4 * ms), event(4, 4 * ms, 3 * ms), event(5, 7 * ms, 3 * ms),
           event(7, 12 * ms, 2 * ms),
           event(3, 20 * ms, 5 * ms), event(5, 25 * ms, 5 * ms)]
    modules = [event(1, 0, 10 * ms), event(6, 12 * ms, 2 * ms), event(1, 20 * ms, 10 * ms)]
    tpu = plane("/device:TPU:0", [("XLA Ops", 5_000_000_000, ops), ("XLA Modules", 5_000_000_000, modules),
                                  ("Steps", 5_000_000_000, [event(1, 0, ms)])], event_meta, stat_meta)
    host = plane("/host:CPU", [("python", 5_000_000_000, [event(1, 0, 40 * ms)])], {1: ("bench:window", [])}, {})
    return field(1, host) + field(1, tpu)


def test_wire_reader_takes_runs_operations_and_op_names(tmp_path):
    path = tmp_path / "small.xplane.pb.gz"
    path.write_bytes(gzip.compress(small_xspace()))
    dev = scopes.load(path)
    assert [(round(a - 5.0, 6), round(b - 5.0, 6), n) for a, b, n in dev.modules] == [
        (0.0, 0.010, "jit_decode(123)"), (0.012, 0.014, "jit_prefill(9)"), (0.020, 0.030, "jit_decode(123)")]
    assert len(dev.ops) == 7 and dev.ops == sorted(dev.ops)
    assert dev.meta[3] == ("%fusion.7 = bf16[16,64] fusion(%a)", "jit(decode)/while/body/attn.core/attn.kv_gather/gather:")
    assert dev.meta[4][1] == "jit(decode)/while/body/mlp/dot_general:"
    assert dev.meta[5][1] == ""
    table = scopes.reduce(dev, "^jit_decode", 5.0, 5.040)
    # the while only wraps the others and counts for nothing; the innermost scope names an operation
    assert table.runs == 2 and table.total_seconds == pytest.approx(0.020)
    assert {k: round(v, 6) for k, v in table.by_scope.items()} == {"attn.kv_gather": 0.009, "mlp": 0.003, None: 0.008}
    assert table.unscoped_by_kind == {"copy": pytest.approx(0.008)}
    assert table.scoped_seconds == pytest.approx(0.012)
    # a run that ends after the window is not whole inside it
    assert scopes.reduce(dev, "^jit_decode", 5.0, 5.025).runs == 1
    assert scopes.reduce(dev, "^jit_prefill", 5.0, 5.040).by_scope == {"head": pytest.approx(0.002)}


@pytest.mark.parametrize("op_name,want", [
    ("jit(decode)/while/body/while/body/attn.core/dot_general:", "attn.core"),
    ("jit(decode)/while/body/while/body/attn.core/attn.kv_gather/gather:", "attn.kv_gather"),
    ("jit(decode)/while/body/sample/sample/argmax:", "sample"),
    ("jit(prefill)/while/body/closed_call/attn.core/pallas_prefill_attention/pallas_call:", "attn.core"),
    ("jit(decode)/while/body/carry/select_n:", "carry"),
    ("jit(decode)/while:", None), ("", None), ("jit(_threefry_split)/add:", None),
])
def test_scope_of(op_name, want):
    assert scopes.scope_of(op_name) == want


def place(tmp_path, monkeypatch, raw):
    """The trace where ``run.py`` leaves it for the readers."""
    d = tmp_path / "trace" / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(raw)
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path / "trace")
    scopes._LOADED.clear()
    scopes._TABLES.clear()


def ctx_of(tr, slice_steps=16):
    return Context.build(cfg={}, mix={}, cell={}, chip=None, trace=tr, records=[], slices=[],
                         slice_steps=slice_steps, compiles=0, t0=100.0, t1=100.04, window_t1=140.0)


def test_the_four_step_metrics_add_up_to_the_step(tmp_path, monkeypatch):
    place(tmp_path, monkeypatch, small_xspace())
    dev = trace.DeviceTrace(ops=[(5.0, 5.03, "fusion.1")], modules=[(5.0, 5.03, "jit_decode(123)")])
    ctx = ctx_of(trace.Trace(devices={0: dev}, host=[(5.0, 5.04, "bench:window")]), slice_steps=2)
    got = {n: scope_ms_per_step.read(ctx, params_of(n)) for n in STEP_METRICS}
    # 2 runs x 2 steps; attn 9 ms, mlp 3 ms, head none, the rest 8 ms of copies
    assert got == {"step.attn_ms_per_step": pytest.approx(2.25), "step.mlp_ms_per_step": pytest.approx(0.75),
                   "step.head_ms_per_step": pytest.approx(0.0), "step.other_ms_per_step": pytest.approx(2.0)}
    assert sum(got.values()) == pytest.approx(20.0 / 4)


def test_an_older_program_without_scopes_reads_nothing(tmp_path, monkeypatch):
    raw = small_xspace().replace(b"attn.core/attn.kv_gather", b"xxxx.core/xxxx.kv_gather").replace(b"/mlp/", b"/xxx/")
    place(tmp_path, monkeypatch, raw)
    dev = trace.DeviceTrace(ops=[(5.0, 5.03, "fusion.1")], modules=[(5.0, 5.03, "jit_decode(123)")])
    ctx = ctx_of(trace.Trace(devices={0: dev}, host=[(5.0, 5.04, "bench:window")]))
    assert [scope_ms_per_step.read(ctx, params_of(n)) for n in STEP_METRICS] == [None] * 4
    # and with no trace on disk at all
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path / "nowhere")
    assert scope_ms_per_step.read(ctx, params_of(STEP_METRICS[0])) is None


@pytest.mark.skipif(not RECORDED.is_file(), reason="no recorded trace in this checkout")
def test_readers_on_a_trace_recorded_on_the_chip(tmp_path, monkeypatch):
    place(tmp_path, monkeypatch, gzip.decompress(RECORDED.read_bytes()))
    tr = trace.load(RECORDED)
    ctx = ctx_of(tr)
    assert ctx.device is not None and ctx.trace_t1 > ctx.trace_t0
    runs = ctx.program_runs("^jit_decode")
    assert len(runs) >= 2
    got = {n: scope_ms_per_step.read(ctx, params_of(n)) for n in STEP_METRICS}
    assert all(v is not None and v >= 0 for v in got.values())
    # the scopes account for the step: the four add up to the runs' device time as the modules line has it
    step_ms = 1e3 * sum(b - a for a, b, _ in runs) / (len(runs) * 16)
    assert sum(got.values()) == pytest.approx(step_ms, rel=0.01)
    # mistral: the weight stream is the MLP's; attention over 8 short rows is the smaller part
    assert got["step.mlp_ms_per_step"] > got["step.attn_ms_per_step"] > got["step.head_ms_per_step"] > 0
    table = scopes.for_window("^jit_decode", ctx.trace_t0, ctx.trace_t1)
    assert {"attn.norm_qkv", "attn.core", "attn.out", "mlp", "head", "sample"} <= set(table.by_scope)
    # the wire reader and ProfileData agree on the clock
    dev = scopes._LOADED[next(iter(scopes._LOADED))]
    assert dev.modules[0][0] == pytest.approx(ctx.device.modules[0][0], abs=1e-6)
    assert len(dev.ops) == len(ctx.device.ops)
    # the prefill chunk's flash kernel shows under its own name
    assert any(n.startswith("%pallas_prefill_attention") for n, _ in dev.meta.values())
    # the program's phase spans lie on the host plane and name the idle gaps
    named = idle_named.read(ctx, params_of("device.idle_named_pct"))
    assert named is not None and named >= 90.0
    gaps = trace.top_idle_gaps(tr, ctx.device, ctx.trace_t0, ctx.trace_t1)
    assert gaps and not any(g[0].endswith(("|host", "|sched.iter")) for g in gaps[:5])
    assert gaps[0][0].endswith("|session.join.install")
