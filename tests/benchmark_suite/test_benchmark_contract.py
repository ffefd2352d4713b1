"""BENCHMARK.json keeps to the contract's shapes, and every name in it
leads to a file of its own."""

import json
import re
from pathlib import Path

import pytest

from benchmark.lib import family

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
# what ``reduced`` may never name: a width, or the experts a token takes. A count of layers or of heads is
# no width, though its name may hold one's (``num_hidden_layers``: the cut in depth every sized model makes)
WIDTH = re.compile(r"_dim|_rank|hidden|intermediate|head|per_tok|top_?k")
COUNT = re.compile(r"^(num|n)_\w*(layers|heads)$")


def names_a_width(key):
    return bool(WIDTH.search(key)) and not COUNT.match(key)

SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            yield group, entry["name"]
    for cell in BENCH["workloads"]:
        yield "traffic", cell["traffic"]
        yield "config", cell["config"]


@pytest.mark.parametrize("group,name", sorted(set(_names())))
def test_name_uses_allowed_characters(group, name):
    assert NAME.match(name), (group, name)


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    allowed = {"name", "unit", "better", "source", "workloads"}
    if "bound" in metric:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
        allowed |= {"bound"}
    else:
        allowed |= {"layer", "moves"}
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
    assert set(metric) <= allowed


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    four = sum(1 for c in BENCH["workloads"] if c["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    used = {c["config"] for c in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    pairs = [(c["config"], c["traffic"]) for c in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("path", BENCH["paths"])
def test_path_is_a_directory_of_the_benchmarks_own(path):
    assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", path) and not path.startswith("/") and ".." not in path
    assert (ROOT / path).is_dir()
    if path.startswith("tests/"):
        # what tier-1 collects from here are the benchmark's tests and nothing else
        # (a ``fixture_*.py`` is a file a test copies into a scratch benchmark: pytest does not collect it)
        assert all(f.name.startswith(("test_benchmark_", "fixture_")) or f.name == "conftest.py"
                   for f in (ROOT / path).glob("*.py"))


def test_the_command_names_no_file_outside_the_paths():
    assert BENCH["command"][0] == "python3" and len(BENCH["command"]) <= 32
    files = [w for w in BENCH["command"][1:] if "/" in w or w.endswith(".py")]
    assert files and all(any(w.startswith(p + "/") for p in BENCH["paths"]) for w in files)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_finds_its_files(cell):
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200
    assert (ROOT / "benchmark" / "configs" / f"{cell['config']}.json").is_file()
    mix = json.loads((ROOT / "benchmark" / "traffic" / f"{cell['traffic']}.json").read_text())
    assert mix["arrival"] in ("closed", "open")


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_holds_what_is_run(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    path = ROOT / config["file"]
    assert path.is_file() and config["file"].startswith("benchmark/")
    cfg = json.loads(path.read_text())
    assert cfg["source"] == config["source"]
    # the source's keys that the configuration's family (``family``; absent: dense) builds the model from
    required = family.load(cfg).REQUIRED_KEYS
    assert len(required) >= 5 and all(NAME.match(key) for key in required)
    for key in required:
        assert key in cfg, key
    for key in config["reduced"]:
        assert not names_a_width(key), key
    assert cfg["check"]["max_logit_gap"] > 0


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_of_its_own(metric):
    spec = json.loads((ROOT / "benchmark" / "layer_metrics" / f"{metric['name']}.json").read_text())
    reader = ROOT / "benchmark" / "readers" / f"{spec['reader']}.py"
    assert reader.is_file()
    assert "def read(ctx, params)" in reader.read_text()
    cells = {c["name"] for c in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells


def test_roofline_and_mfu_names():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert any(re.search(r"(^|[._])mfu($|[._])", n) for n in names)
    for m in BENCH["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_the_default_family_asks_for_the_dense_decoders_keys():
    assert family.load({}).REQUIRED_KEYS == (
        "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "vocab_size", "rms_norm_eps", "rope_theta")


@pytest.mark.parametrize("key,refused", [
    ("num_hidden_layers", False), ("num_layers", False), ("vocab_size", False), ("n_routed_experts", False),
    ("num_key_value_heads", False), ("head_dim", True), ("kv_lora_rank", True), ("hidden_size", True),
    ("ffn_hidden_size", True), ("intermediate_size", True), ("qk_rope_head_dim", True),
    ("num_experts_per_tok", True), ("moe_topk", True),
])
def test_reduced_may_name_no_width(key, refused):
    assert names_a_width(key) is refused
