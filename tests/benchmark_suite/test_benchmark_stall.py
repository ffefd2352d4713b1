"""The reader of the loop's own account of a window (``span_attr_rate``) on
hand-made spans, the four metric files that name it (PR 36: files and reader,
no ``per_layer`` entry yet; a fifth over ``sched.iter``'s ``run_delay_s`` waits
for a benchmark machine whose kernel has ``schedstat``), and a ``--dry`` run whose ``sched.iter`` spans
carry what the reader reads."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark.lib import spans as SP
from benchmark.readers import span_attr_rate

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
METRICS = ["sched.stall_ms_per_s", "sched.stall_named_pct", "host.gc_ms_per_s",
           "host.process_stall_ms_per_s"]
# what the held-back fifth would hold: the reader reads any numeric attribute
RUN_DELAY = {"names": ["sched.iter"], "attr": "run_delay_s"}


def params_of(name):
    if isinstance(name, dict):
        return name
    return json.loads((ROOT / "benchmark" / "layer_metrics" / f"{name}.json").read_text())["params"]


def S(name, t0, t1, span_id, parent=None, **attrs):
    return SP.S(name, t0, t1, 1, span_id, parent, None, attrs)


# a window of 10 s: four passes, two of them long, one collection, one process stall
WINDOW = [
    S("sched.iter", 100.0, 100.2, 1, gc_s=0.0, run_delay_s=0.001, cpu_s=0.01),
    S("sched.iter", 100.2, 100.52, 2, gc_s=0.118, run_delay_s=0.0, cpu_s=0.13),
    S("gc", 100.3, 100.418, 3, 2, generation=2, collected=12),
    S("stall", 100.2, 100.52, 4, 2, cause="gc", excess_s=0.12, phase="wait"),
    S("sched.iter", 100.52, 100.72, 5, gc_s=0.002, run_delay_s=None, cpu_s=0.01),
    S("sched.iter", 100.72, 101.0, 6, gc_s=0.0, run_delay_s=0.003, cpu_s=0.02),
    S("stall", 100.72, 101.0, 7, 6, cause="unknown", excess_s=0.08, phase="join"),
    S("stall.process", 103.0, 103.6, 8, held_by="process not scheduled", cpu_s=0.0),
]
OLDER = [S("sched.iter", 100.0, 100.2, 1, rows=4), S("sched.slice", 100.0, 100.18, 2, 1, rows=4)]


@pytest.mark.parametrize(
    "name, spans, want",
    [
        ("sched.stall_ms_per_s", WINDOW, 1e3 * (0.12 + 0.08) / 10),
        ("sched.stall_named_pct", WINDOW, 100 * 0.12 / 0.20),
        ("host.gc_ms_per_s", WINDOW, 1e3 * 0.120 / 10),
        # a pass whose platform lacks the counter (None) adds nothing
        (RUN_DELAY, WINDOW, 1e3 * 0.004 / 10),
        # the spans' own seconds where no attribute is named
        ("host.process_stall_ms_per_s", WINDOW, 1e3 * 0.6 / 10),
        # a program that writes the deltas and had no long pass: 0, and nothing unnamed
        ("sched.stall_ms_per_s", WINDOW[:1], 0.0),
        ("sched.stall_named_pct", WINDOW[:1], 100.0),
        ("host.process_stall_ms_per_s", WINDOW[:1], 0.0),
        # an older program (no deltas on its passes, no such spans): nothing to read
        ("sched.stall_ms_per_s", OLDER, None),
        ("sched.stall_named_pct", OLDER, None),
        ("host.gc_ms_per_s", OLDER, None),
        (RUN_DELAY, OLDER, None),
        ("host.process_stall_ms_per_s", OLDER, None),
        ("host.gc_ms_per_s", [], None),
    ],
)
def test_span_attr_rate_on_hand_made_spans(name, spans, want):
    got = span_attr_rate.measure(spans, params_of(name), 10.0)
    assert got is None if want is None else got == pytest.approx(want)


def test_where_leaves_spans_out_and_a_bool_is_no_number():
    spans = [S("x", 0, 1, 1, v=2.0, kind="a"), S("x", 1, 2, 2, v=3.0, kind="b"), S("x", 2, 3, 3, v=True, kind="a")]
    base = {"names": ["x"], "attr": "v"}
    assert span_attr_rate.measure(spans, base, 1.0) == pytest.approx(5000.0)
    assert span_attr_rate.measure(spans, {**base, "where": {"attr": "kind", "is_not": "b"}}, 1.0) == pytest.approx(2000.0)
    assert span_attr_rate.measure(spans, {**base, "where": {"attr": "kind", "is_not": "a"}, "as": "share_pct"},
                                  1.0) == pytest.approx(60.0)
    assert span_attr_rate.measure(spans, base, 0.0) is None


@pytest.mark.parametrize("name", METRICS)
def test_the_metric_files_load_and_name_the_reader(name):
    spec = json.loads((ROOT / "benchmark" / "layer_metrics" / f"{name}.json").read_text())
    assert spec["reader"] == "span_attr_rate" and set(spec) == {"reader", "params"}
    assert set(spec["params"]) <= {"names", "attr", "where", "as", "witness"}
    # files and reader only: the entries are the next benchmark PR's to append (PERF.md §7 (0))
    assert name not in {m["name"] for m in BENCH["per_layer"]}


DRY = """
import json, sys
sys.path.insert(0, {root!r})
import benchmark.run as run
from benchmark.lib import spans
from benchmark.readers import span_attr_rate
result = run.run_cell(run.parse(["--workload", "mistral-7b.chat-closed", "--seed", "3600000007",
                                 "--seconds", "2", "--trace", "0", "--dry"]))
ring = spans.finished(0.0, float("inf"))
iters = [s for s in ring if s.name == "sched.iter"]
out = {{"correct": result["correct"], "iters": len(iters),
       "with_deltas": sum(1 for s in iters if {{"cpu_s", "thread_cpu_s", "run_delay_s", "throttled_s", "gc_s", "gc_n",
                                               "nivcsw", "majflt"}} <= set(s.attrs)),
       "stalls": [s.attrs for s in ring if s.name == "stall"]}}
window = max(s.t1 for s in iters) - min(s.t0 for s in iters)
for name in {metrics!r}:
    params = json.load(open({root!r} + "/benchmark/layer_metrics/" + name + ".json"))["params"]
    out[name] = span_attr_rate.measure(ring, params, window)
print(json.dumps(out), flush=True)
import os
os._exit(0)
"""


def test_a_dry_run_leaves_attributes_the_reader_reads():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT), "TPU_LLM_OBS": "1"}
    env.pop("BENCH_RUN", None)
    proc = subprocess.run([sys.executable, "-c", DRY.format(root=str(ROOT), metrics=METRICS)],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    # every pass of the loop, set-up's included, carries the eight deltas
    assert out["iters"] > 20 and out["with_deltas"] == out["iters"]
    for name in METRICS:
        assert out[name] is not None and out[name] >= 0.0, (name, out)
    assert out["sched.stall_named_pct"] <= 100.0
    for attrs in out["stalls"]:
        assert {"cause", "excess_s", "phase"} <= set(attrs) and attrs["excess_s"] > 0.05
