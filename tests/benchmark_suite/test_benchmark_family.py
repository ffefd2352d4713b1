"""A configuration brings its own model: the harness takes the program's
config, the reference and the byte count from the configuration's family
(``benchmark/lib/family.py``). The dense family is the default and gives
what the harness gave before; a fixture family, added to a scratch copy as
new files only, runs the expert layer the program already has."""

import importlib.util
import json
import re
import shutil
import sys
import types
from pathlib import Path

import numpy as np
import pytest

from test_benchmark_dry import last_line, run_cli
from test_benchmark_readers import MODULES, RECORDS
from test_benchmark_readers import context as readers_context

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = ROOT / "benchmark" / "configs"
FIXTURE = Path(__file__).with_name("fixture_experts_family.py")
CELLS = ("fixture", "fixture-denseref", "fixture-nofamily")
FIXTURE_CFG = {
    "source": "https://example.org/fixture/config.json", "model": "fixture-experts:tiny",
    "family": "fixture_experts", "hidden_size": 64, "expert_ffn_size": 128, "num_experts": 4,
    "experts_per_token": 2, "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 16, "vocab_size": 512, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "max_position_embeddings": 4096,
}
# the dense reference in the fixture's place, the program still built with experts
DENSE_REFERENCE = '''\
from . import dense
from .fixture_experts import *  # noqa: F401,F403


def _as_dense(cfg):
    return {**cfg, "intermediate_size": cfg["expert_ffn_size"]}


def make_weights(cfg, seed, bits=8):
    return dense.make_weights(_as_dense(cfg), seed, bits)


def served_logits(cfg, weights, token_rows, spans):
    return dense.served_logits(_as_dense(cfg), weights, token_rows, spans)
'''


@pytest.fixture(scope="module")
def scratch(tmp_path_factory):
    """BENCHMARK.json and benchmark/ alone, plus new files: two families,
    three configurations (the fixture family; the dense reference in its
    place; the ``family`` key dropped) and a cell each under the topics
    mix. BENCHMARK.json only gains entries, and a cell joins a per-layer
    metric by being appended to its list."""
    root = tmp_path_factory.mktemp("family")
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns(".jax_cache", ".out", "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*") if p.is_file()}
    b = root / "benchmark"
    shutil.copy(FIXTURE, b / "families" / "fixture_experts.py")
    (b / "families" / "fixture_dense_ref.py").write_text(DENSE_REFERENCE)
    base = json.loads((b / "configs" / "mistral-7b.json").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name, fam in zip(CELLS, ("fixture_experts", "fixture_dense_ref", None)):
        # float32, so that the router's top-2 choice is the reference's (see the fixture's docstring)
        cfg = {**FIXTURE_CFG, "family": fam, "engine": {**base["engine"], "dtype": "float32"},
               "check": base["check"]}
        if fam is None:
            del cfg["family"]
        (b / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        bench["configs"].append({"name": name, "source": cfg["source"], "file": f"benchmark/configs/{name}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": f"{name}.dry-topics", "config": name, "traffic": "dry-topics",
                                   "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] in ("sched.live_rows_mean", "step.hbm_roofline", "step.mfu"):
            m["workloads"].append("fixture.dry-topics")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    yield root
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


@pytest.fixture()
def fixture_family(monkeypatch):
    """The fixture family importable in this process under the name a
    scratch copy gives it."""
    spec = importlib.util.spec_from_file_location("benchmark.families.fixture_experts", FIXTURE)
    module = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, spec.name, module)
    spec.loader.exec_module(module)
    return module


def over_limit(line):
    return {k for k, n in line["check"].items() if not n.get("at_least") and n["value"] > n["limit"]}


def test_a_family_added_as_new_files_serves_and_proves_correct(scratch):
    line = last_line(run_cli(scratch, "--workload", "fixture.dry-topics", "--seed", str(2**31 + 41),
                             "--seconds", "2", "--trace", "1", "--dry"))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    gap = line["check"]["logit_gap_max"]
    assert gap["value"] < gap["limit"] / 5 and line["check"]["tokens_compared"]["value"] > 50
    # the cell reports the metrics it was appended to; the device's stay silent off the chip
    assert set(line["metrics"]) == {"dry.sched.live_rows_mean"}


def test_the_dense_reference_in_the_familys_place_is_not_correct(scratch):
    line = last_line(run_cli(scratch, "--workload", "fixture-denseref.dry-topics", "--seed", str(2**31 + 41),
                             "--seconds", "2", "--trace", "0", "--dry"))
    assert line["correct"] is False and line["failed"] == 0
    assert over_limit(line) == {"logit_gap_max"}


def test_without_its_family_key_the_configuration_fails_in_program_config(scratch):
    proc = run_cli(scratch, "--workload", "fixture-nofamily.dry-topics", "--seed", "43", "--seconds", "2",
                   "--trace", "0", "--dry", expect=1)
    assert proc.stdout.strip() == ""
    assert "program_config" in proc.stderr and "KeyError: 'intermediate_size'" in proc.stderr


# -- the dense family gives what the harness gave before --------------------------

DENSE_FIELDS = {
    "phi3-mini": dict(name="phi3:3.8b", vocab_size=32064, d_model=3072, n_layers=32, n_heads=32, n_kv_heads=32,
                      d_head=96, d_ff=8192, rope_theta=10000.0, norm_eps=1e-05, activation="silu",
                      tie_embeddings=False, qkv_bias=False, max_seq_len=4096),
    "mistral-7b": dict(name="mistral:7b", vocab_size=32768, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
                       d_head=128, d_ff=14336, rope_theta=1000000.0, norm_eps=1e-05, activation="silu",
                       tie_embeddings=False, qkv_bias=False, max_seq_len=32768),
    # the optional keys absent: MHA, head size from the hidden size, SwiGLU, untied, no biases
    "bare": dict(name="bare", vocab_size=1000, d_model=96, n_layers=3, n_heads=6, n_kv_heads=6, d_head=16,
                 d_ff=256, rope_theta=500000.0, norm_eps=1e-06, activation="silu", tie_embeddings=False,
                 qkv_bias=False, max_seq_len=2048),
    # and present with other values than the defaults
    "full": dict(name="full", vocab_size=1000, d_model=96, n_layers=3, n_heads=6, n_kv_heads=2, d_head=32,
                 d_ff=256, rope_theta=500000.0, norm_eps=1e-06, activation="gelu", tie_embeddings=True,
                 qkv_bias=True, max_seq_len=2048),
}
BARE = dict(model="bare", vocab_size=1000, hidden_size=96, num_hidden_layers=3, num_attention_heads=6,
            intermediate_size=256, rope_theta=5e5, rms_norm_eps=1e-6, max_position_embeddings=2048)
FULL = dict(BARE, model="full", num_key_value_heads=2, head_dim=32, hidden_act="gelu", tie_word_embeddings=True,
            attention_bias=True)


@pytest.mark.parametrize("name", sorted(DENSE_FIELDS))
def test_dense_program_config_field_for_field(name):
    from benchmark.families import dense
    from benchmark.lib import family, system

    cfg = {"bare": BARE, "full": FULL}.get(name) or json.loads((CONFIGS / f"{name}.json").read_text())
    assert "family" not in cfg and family.load(cfg) is dense
    fields = dense.program_config(cfg)
    assert fields == DENSE_FIELDS[name] and len(fields) == 14
    assert {k: type(v) for k, v in fields.items()} == {k: type(v) for k, v in DENSE_FIELDS[name].items()}
    built = system.model_config(cfg)
    assert all(getattr(built, k) == v for k, v in fields.items())
    assert built.n_experts == 0  # the dense builder passes no expert field


def test_dense_served_logits_are_the_references_own():
    """Moved from ``check.served_logits``: all rows in one call, padded to
    a multiple of 128, sliced at the asked positions."""
    import jax.numpy as jnp

    from benchmark.families import dense
    from benchmark.lib import reference

    cfg = dict(BARE, num_key_value_heads=2)
    w = dense.make_weights(cfg, 7, 8)
    rng = np.random.RandomState(3)
    rows = [list(rng.randint(3, 259, n)) for n in (150, 131, 40)]
    spans = [(129, 21), (100, 31), (0, 40)]
    toks = np.zeros((3, 256), np.int32)
    for i, r in enumerate(rows):
        toks[i, :len(r)] = r
    full = np.asarray(reference.logits(cfg, w, jnp.asarray(toks)))
    got = dense.served_logits(cfg, w, rows, spans)
    assert [g.shape for g in got] == [(21, 1000), (31, 1000), (40, 1000)]
    for i, (first, n) in enumerate(spans):
        assert np.array_equal(np.asarray(got[i]), full[i, first:first + n])


# -- the loader --------------------------------------------------------------------

def test_load_names_what_a_family_lacks(monkeypatch):
    from benchmark.lib import family

    lacking = types.ModuleType("benchmark.families.lacking")
    lacking.__file__ = "lacking.py"
    for name in family.CONTRACT:
        if name not in ("served_logits", "kv_bytes_per_token"):
            setattr(lacking, name, object())
    monkeypatch.setitem(sys.modules, lacking.__name__, lacking)
    with pytest.raises(ImportError, match=r"'lacking' \(lacking\.py\) lacks served_logits, kv_bytes_per_token$"):
        family.load({"family": "lacking"})


@pytest.mark.parametrize("name", ["", "Dense", "..lib.reference", "os.path", "a/b", "1st"])
def test_load_takes_a_modules_name_and_nothing_else(name):
    from benchmark.lib import family

    with pytest.raises(ValueError):
        family.load({"family": name})


def test_load_of_an_unknown_family_names_it():
    from benchmark.lib import family

    with pytest.raises(ModuleNotFoundError, match="benchmark.families.no_such_family"):
        family.load({"family": "no_such_family"})


def test_the_fixture_family_meets_the_contract(fixture_family):
    from benchmark.lib import family

    assert family.load(FIXTURE_CFG) is fixture_family
    assert set(fixture_family.REQUIRED_KEYS) <= set(FIXTURE_CFG)
    fields = fixture_family.program_config(FIXTURE_CFG)
    assert fields["n_experts"] == 4 and fields["top_k_experts"] == 2 and fields["d_ff"] == 128


# -- the readers count through the family ---------------------------------------

def context(cfg):
    ctx = readers_context(MODULES, RECORDS)
    ctx.cfg = cfg
    return ctx


def test_step_readers_count_with_the_configurations_family(fixture_family):
    """The same trace and log under two configurations: each reader takes
    its bytes and FLOPs from the family the configuration names."""
    from benchmark.lib import shapes
    from benchmark.readers import decode_step_roofline, step_mfu

    dense_cfg = dict(FIXTURE_CFG, intermediate_size=128)
    del dense_cfg["family"]
    rows, tokens = 20 / 16, ((147 + 8) * 16 + (233 + 2) * 4) / 16
    for cfg, count in ((FIXTURE_CFG, fixture_family), (dense_cfg, shapes)):
        want = 100.0 * 16 * count.decode_step_bytes(cfg, rows, tokens) / 819e9 / 0.2
        assert decode_step_roofline.read(context(cfg), {"module": "^jit_decode"}) == pytest.approx(want, rel=1e-12)
        flops = (16 * count.decode_token_flops(cfg, 147 + 8) + 4 * count.decode_token_flops(cfg, 233 + 2)
                 + count.prefill_flops(cfg, 180))
        got = step_mfu.read(context(cfg), {"decode": "^jit_decode", "prefill": "^jit_prefill"})
        assert got == pytest.approx(100.0 * flops / 3.0 / 197e12, rel=1e-12)
    # the fixture reads the experts that 1.25 rows touch (2.3 of 4 a layer), the dense count one FFN
    touched = fixture_family.experts_touched(FIXTURE_CFG, rows)
    assert 2.0 < touched < 2.5
    assert (fixture_family.decode_step_bytes(FIXTURE_CFG, rows, tokens) - shapes.decode_step_bytes(dense_cfg, rows, tokens)
            == pytest.approx(2 * (touched - 1) * 3 * 64 * 128 + 2 * 2 * 64 * 4))
    # two experts a token against one FFN, and the router
    assert (fixture_family.decode_token_flops(FIXTURE_CFG, 100) - shapes.decode_token_flops(dense_cfg, 100)
            == 2 * 2 * (3 * 64 * 128 + 64 * 4))


def test_fixture_weight_bytes_by_hand(fixture_family):
    attention = 2 * 4 * 64 * 64
    experts = 2 * 4 * 3 * 64 * 128
    assert fixture_family.weight_bytes(FIXTURE_CFG) == attention + experts + 2 * (2 * 64 * 4) + 2 * 64 * 512
    assert fixture_family.kv_bytes_per_token(FIXTURE_CFG) == 2 * 2 * 4 * 16 * 2


# -- only the dense family knows the dense keys -----------------------------------

OWN = {"lib/reference.py", "lib/shapes.py", "lib/family.py"}  # the definitions, and the contract's description
THROUGH_THE_FAMILY = {"lib/check.py", "lib/system.py", "readers/decode_step_roofline.py", "readers/step_mfu.py"}
HARNESS = sorted({str(p.relative_to(ROOT / "benchmark")) for d in ("lib", "readers")
                  for p in (ROOT / "benchmark" / d).glob("*.py")} - OWN)


@pytest.mark.parametrize("path", HARNESS)
def test_harness_reaches_the_model_through_the_family(path):
    """No module of the harness names a dense key or imports the dense
    reference or the dense counts: a docstring may, the code may not."""
    text = (ROOT / "benchmark" / path).read_text()
    code = re.sub(r'"""(.|\n)*?"""', "", text)
    code = "\n".join(line for line in code.splitlines() if not line.lstrip().startswith("#"))
    assert not re.search(r"intermediate_size|num_key_value_heads|head_dim", code)
    imports = [line for line in code.splitlines() if re.match(r"\s*(from|import)\s", line)]
    assert not [line for line in imports if re.search(r"\b(reference|shapes)\b", line)]
    assert ("family.load(" in code) == (path in THROUGH_THE_FAMILY)
