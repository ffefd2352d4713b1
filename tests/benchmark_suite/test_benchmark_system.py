"""The one file that imports the program (``benchmark/lib/system.py``) hands
the program's ``JaxEngine`` every ``engine`` key a configuration file states,
and refuses one it does not know by name."""

import json
from pathlib import Path

import jax.numpy as jnp
import pytest

from benchmark.lib import system

ROOT = Path(__file__).resolve().parents[2]
FILES = sorted((ROOT / "benchmark" / "configs").glob("*.json"))
SEED = 2**31 + 39
TINY = dict(model="tiny", hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, intermediate_size=128, vocab_size=512, rms_norm_eps=1e-5, rope_theta=1e4,
            max_position_embeddings=512,
            engine={"quantize": "int8", "paged_kv": True, "decode_attention": "auto", "dtype": "float32",
                    "page_size": 128, "scheduler": "continuous"})


class Built(Exception):
    """Raised by the stand-in engine once it holds its arguments."""


def given_to_the_engine(monkeypatch, cfg):
    """The keyword arguments ``System`` builds the program's engine with."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine import jax_engine

    got = {}

    def engine(**kwargs):
        got.update(kwargs)
        raise Built

    monkeypatch.setattr(jax_engine, "JaxEngine", engine)
    with pytest.raises(Built):
        system.System(cfg, SEED)
    return got


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_the_committed_files_build_the_engine_as_before(monkeypatch, path):
    cfg = json.loads(path.read_text())
    got = given_to_the_engine(monkeypatch, cfg)
    mc = got["registry"][cfg["model"]]
    # what the harness handed over before it knew ``speculative``: seven keys of the group, the model, the seed
    assert got == {"registry": {cfg["model"]: mc}, "dtype": jnp.dtype(jnp.bfloat16), "decode_attention": "auto",
                   "quantize": "int8", "kv_quantize": None, "paged_kv": True, "page_size": 128,
                   "prefix_share": False, "seed": SEED}
    assert mc == system.model_config(cfg)


def test_a_file_that_states_speculation_builds_a_speculating_engine():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import JaxEngine
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.speculative import DraftSpec

    cfg = {**TINY, "engine": {**TINY["engine"], "speculative": ["ngram", 2], "spec_accept_floor": 0.25}}
    mc = system.model_config(cfg)
    kwargs = system.engine_kwargs(cfg, mc, SEED)
    assert kwargs["speculative"] == {"tiny": ("ngram", 2)} and kwargs["spec_accept_floor"] == 0.25
    engine = JaxEngine(**kwargs)
    assert engine.speculative == {"tiny": DraftSpec("ngram", None, 2)} and engine.spec_accept_floor == 0.25
    plain = system.engine_kwargs(TINY, mc, SEED)
    assert not {"speculative", "spec_accept_floor"} & set(plain)


@pytest.mark.parametrize("key", ["speculate", "max_batch"])
def test_an_engine_key_the_harness_does_not_know_ends_the_run_by_name(key):
    cfg = {**TINY, "engine": {**TINY["engine"], key: 1}}
    with pytest.raises(ValueError, match=key):
        system.engine_kwargs(cfg, system.model_config(cfg), SEED)


def test_only_the_continuous_scheduler_is_driven():
    cfg = {**TINY, "engine": {**TINY["engine"], "scheduler": "batch"}}
    with pytest.raises(ValueError, match="continuous"):
        system.engine_kwargs(cfg, system.model_config(cfg), SEED)
