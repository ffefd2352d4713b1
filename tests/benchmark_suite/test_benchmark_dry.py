"""The whole command at tiny sizes on the CPU (``--dry``): both arrival
kinds, a scratch copy that gains a configuration, a mix, a cell and a
per-layer metric as new files only, and the timed path broken underneath."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cli(root, *args, expect=0):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, str(Path(root) / "benchmark" / "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == expect, proc.stderr[-3000:]
    return proc


def last_line(proc):
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", ["0", "1"])
def test_dry_closed_loop_ends_in_the_contracts_line(trace):
    proc = run_cli(ROOT, "--workload", "phi3-mini.decode-closed", "--seed", str(2**31 + 7),
                   "--seconds", "2", "--trace", trace, "--dry")
    line = last_line(proc)
    assert KEYS <= set(line) and list(line)[-1] == "check"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1
    assert all(name.startswith("dry.") for name in line["metrics"])
    # the TTFT tail swings too far from seed to seed for a bound: it is read
    # per layer, under the cell's own name
    want = {"dry.tokens_per_s", "dry.stream_gap_p95_ms", "dry.setup_s"} if trace == "0" else \
        {"dry.sched.live_rows_mean", "dry.session.slice_period_p50_ms", "dry.session.window_compiles",
         "dry.ttft_p95_ms.decode-closed"}
    assert want <= set(line["metrics"])
    assert "dry.ttft_p95_ms" not in line["metrics"]
    if trace == "1":
        # device readers find no TPU plane on the CPU and stay silent
        assert not {"dry.step.hbm_roofline", "dry.step.mfu", "dry.device.idle_pct"} & set(line["metrics"])
    assert "check logit_gap_max=" in proc.stderr.strip().splitlines()[-5]
    for n in line["check"].values():
        assert {"value", "limit"} <= set(n)


def test_off_the_chip_a_real_run_fails_and_prints_no_result():
    proc = run_cli(ROOT, "--workload", "phi3-mini.decode-closed", "--seed", "1", "--seconds", "1",
                   "--trace", "0", expect=1)
    assert proc.stdout.strip() == "" and "no accelerator" in proc.stderr


@pytest.fixture()
def scratch(tmp_path):
    """BENCHMARK.json and benchmark/ alone, plus new files: a configuration,
    an open-loop and a bursty mix's cells, and a per-layer metric with a
    reader of its own. No existing file is edited except BENCHMARK.json,
    which only gains entries."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".jax_cache", ".out", "__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*") if p.is_file()}
    b = tmp_path / "benchmark"
    cfg = json.loads((b / "configs" / "mistral-7b.json").read_text())
    cfg.update(model="newmodel:1b", source="https://example.org/new/config.json")
    (b / "configs" / "new-model.json").write_text(json.dumps(cfg))
    (b / "layer_metrics" / "client.requests_done.json").write_text(
        json.dumps({"reader": "requests_done", "params": {}}))
    # a counter, read over the whole window as the harness's own are: an open loop's sessions drain and reopen,
    # and on a loaded CPU a traced part of 2 s could hold nothing but their compiles
    (b / "readers" / "requests_done.py").write_text(
        "def read(ctx, params):\n"
        "    return float(sum(1 for r in ctx.records if r.t_done and ctx.t0 <= r.t_done < ctx.window_t1))\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "new-model", "source": cfg["source"],
                             "file": "benchmark/configs/new-model.json", "reduced": [], "why": "test"})
    for mix in ("dry-open", "dry-bursty"):
        bench["workloads"].append({"name": f"new-model.{mix}", "config": "new-model", "traffic": mix,
                                   "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "client.requests_done", "unit": "count", "better": "higher",
                               "source": "program_counter", "layer": "client", "moves": "tokens_per_s",
                               "workloads": ["new-model.dry-open", "new-model.dry-bursty"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    yield tmp_path
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


@pytest.mark.parametrize("mix", ["dry-open", "dry-bursty"])
def test_new_cells_are_data_and_open_loop_runs(scratch, mix):
    proc = run_cli(scratch, "--workload", f"new-model.{mix}", "--seed", "21", "--seconds", "3",
                   "--trace", "0", "--dry")
    line = last_line(proc)
    assert line["correct"] is True and line["attempted"] >= 8 and line["failed"] == 0
    assert line["metrics"]["dry.tokens_per_s"]["value"] > 0
    assert "generator lateness" in proc.stderr
    traced = last_line(run_cli(scratch, "--workload", f"new-model.{mix}", "--seed", "22", "--seconds", "4",
                               "--trace", "1", "--dry"))
    assert traced["metrics"]["dry.client.requests_done"]["value"] > 0
    assert "dry.client.requests_done" not in line["metrics"]


def test_without_the_program_the_command_fails_and_prints_no_result(scratch):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "phi3-mini.decode-closed", "--seed", "1",
         "--seconds", "1", "--trace", "0", "--dry"],
        cwd=scratch, env={**env, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def _dry_args(seed):
    from benchmark import run

    return run.parse(["--workload", "mistral-7b.chat-closed", "--seed", str(seed), "--seconds", "2",
                      "--trace", "0", "--dry"])


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    """Skips the look for a chip (``--dry``) and drives the rest of a run
    in this process, with the session's token hand-off to the host broken:
    every slice's first sampled token of every row comes out one higher."""
    from benchmark import run
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine import jax_engine

    sound = run.run_cell(_dry_args(31))
    assert sound["correct"] is True
    real = jax_engine._to_host_list

    def broken(x):
        out = real(x)
        if out and isinstance(out[0], list) and len(out[0]) == 16:
            out = [[row[0] + 1] + row[1:] for row in out]
        return out

    monkeypatch.setattr(jax_engine, "_to_host_list", broken)
    faulty = run.run_cell(_dry_args(31))
    assert faulty["correct"] is False
    gap = faulty["check"]["logit_gap_max"]
    assert gap["value"] > gap["limit"] > sound["check"]["logit_gap_max"]["value"]


def test_the_control_in_the_programs_place_is_not_correct():
    """``--control``: the whole run as ever, and then the int4 reference's
    tokens judged where the served ones would be. The last line has to say
    ``correct: false`` by the logit gap and by nothing else."""
    proc = run_cli(ROOT, "--workload", "mistral-7b.chat-closed", "--seed", "33", "--seconds", "2",
                   "--trace", "0", "--dry", "--control")
    line = last_line(proc)
    assert line["correct"] is False
    over = {k for k, n in line["check"].items() if not n.get("at_least") and n["value"] > n["limit"]}
    assert over == {"logit_gap_max"}
    assert line["check"]["tokens_compared"]["value"] > 0


def test_a_request_cut_short_is_not_correct(monkeypatch):
    """The other fault a served cell can have: an answer that says the
    wrong thing by ending early."""
    from benchmark import run
    from benchmark.lib import loadgen

    real = loadgen.Load._consume

    def short(self, rec, stream):
        real(self, rec, stream)
        if rec.index % 3 == 0 and len(rec.tokens) > 2 and rec.tokens[-2] != 2:
            rec.tokens = rec.tokens[:-1]

    monkeypatch.setattr(loadgen.Load, "_consume", short)
    faulty = run.run_cell(_dry_args(32))
    assert faulty["correct"] is False and faulty["check"]["wrong_length"]["value"] > 0
