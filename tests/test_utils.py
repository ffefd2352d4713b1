"""Dotenv loader."""

import os
from pathlib import Path

import pytest

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.env import (
    load_dotenv,
    parse_dotenv,
)


def test_parse_dotenv():
    text = """
# comment
SERVER_IP=10.0.0.5
export QUOTED="hello world"
SINGLE='x'
EMPTY=
BROKEN LINE
"""
    values = parse_dotenv(text)
    assert values == {
        "SERVER_IP": "10.0.0.5",
        "QUOTED": "hello world",
        "SINGLE": "x",
        "EMPTY": "",
    }


def test_load_dotenv_respects_existing(tmp_path, monkeypatch):
    env_file = tmp_path / ".env"
    env_file.write_text("TEST_DOTENV_VAR=from_file\n")
    monkeypatch.setenv("TEST_DOTENV_VAR", "preexisting")
    load_dotenv(env_file)
    assert os.environ["TEST_DOTENV_VAR"] == "preexisting"
    load_dotenv(env_file, override=True)
    assert os.environ["TEST_DOTENV_VAR"] == "from_file"


def test_load_dotenv_missing_file(tmp_path):
    assert load_dotenv(tmp_path / "nope.env") == {}


# -- memory budget / weight estimation ---------------------------------------


def test_estimate_weight_bytes_matches_actual_quantized_params():
    """The fail-fast estimate must track what quantize_params actually
    allocates (within a couple of %, scales included)."""
    import jax
    import jax.numpy as jnp

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import (
        params_nbytes,
        quantize_params,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import (
        init_params,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.memory import (
        estimate_weight_bytes,
    )

    # gemma ties embeddings; llama3.1/mistral don't — the untied case
    # exercises the lm_head's own per-row scale vector in the estimate
    # (ADVICE round-2: it was previously counted once, not twice).
    for base in ("qwen2:1.5b", "gemma:2b", "llama3.1:8b", "mistral:7b"):
        cfg = get_model_config(base).tiny()
        params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        for mode in (None, "int8", "int4"):
            actual = params_nbytes(
                quantize_params(params, mode=mode) if mode else params
            )
            est = estimate_weight_bytes(cfg, mode, dtype_bytes=4)
            assert abs(est - actual) / actual < 0.03, (base, mode, est, actual)


def test_load_model_fails_fast_when_over_budget(monkeypatch):
    import jax.numpy as jnp

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.memory import (
        ModelMemoryError,
    )

    monkeypatch.setenv("TPU_MEMORY_BUDGET_BYTES", "1000")
    engine = JaxEngine(
        registry={"tiny": get_model_config("qwen2:1.5b").tiny()},
        dtype=jnp.float32,
    )
    with pytest.raises(ModelMemoryError) as exc_info:
        engine.load_model("tiny")
    msg = str(exc_info.value)
    # actionable: both numbers, a remedy, and the override knob
    assert "GiB" in msg and "quantize" in msg and "TPU_MEMORY_BUDGET_BYTES" in msg
    assert "tiny" not in engine._models


def test_memory_budget_unknown_on_cpu(monkeypatch):
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.memory import (
        device_memory_budget,
    )

    monkeypatch.delenv("TPU_MEMORY_BUDGET_BYTES", raising=False)
    assert device_memory_budget() is None  # tests run on CPU devices


# -- persistent compilation cache --------------------------------------------


def test_compilation_cache_dir_comes_from_the_environment(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself and
    enable_compilation_cache() sets no other directory."""
    import jax

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
    assert enable_compilation_cache() == tmp_path / "outside"
    assert jax.config.jax_compilation_cache_dir == before


def test_compilation_cache_defaults_to_one_dir_in_the_checkout(
    monkeypatch, tmp_path
):
    import jax

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils import (
        compile_cache,
    )

    repo_root = Path(__file__).resolve().parent.parent
    assert compile_cache.DEFAULT_CACHE_DIR == repo_root / ".jax_cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "DEFAULT_CACHE_DIR", tmp_path / "fixed")
    before = jax.config.jax_compilation_cache_dir
    try:
        used = compile_cache.enable_compilation_cache()
        assert used == tmp_path / "fixed" and used.is_dir()
        assert jax.config.jax_compilation_cache_dir == str(used)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_load_model_budget_counts_resident_models(monkeypatch):
    import jax.numpy as jnp

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.memory import (
        ModelMemoryError,
        estimate_weight_bytes,
    )

    cfg_a = get_model_config("qwen2:1.5b").tiny()
    cfg_b = get_model_config("gemma:2b").tiny()
    one = estimate_weight_bytes(cfg_a, None, 4)
    # budget fits one resident model plus half of the second — the second
    # load must fail BECAUSE of the resident one
    monkeypatch.setenv("TPU_MEMORY_BUDGET_BYTES", str(int(1.5 * one)))
    engine = JaxEngine(
        registry={"a": cfg_a, "b": cfg_b}, dtype=jnp.float32
    )
    engine.load_model("a")
    with pytest.raises(ModelMemoryError, match="already resident"):
        engine.load_model("b")
    engine.unload_all()
    engine.load_model("b")  # fits alone once the first is unloaded


# -- decode bytes-per-step accounting (the energy model's HBM term) ----------


def test_decode_read_bytes_match_measured_traffic():
    """The bytes accounting must reproduce docs/PERF.md's measured decode
    traffic for qwen2:1.5b int8: ~1.31 GB transformer body + 233 MB
    logits head + ~9 MB KV at short context ⇒ ~1.55 GB/step."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.memory import (
        estimate_decode_read_bytes_per_step,
    )

    cfg = get_model_config("qwen2:1.5b")
    b = estimate_decode_read_bytes_per_step(cfg, "int8", 320)
    assert 1.45e9 < b < 1.65e9
    # bf16 doubles the matmul stream (PERF.md: 2.62 GB body)
    b16 = estimate_decode_read_bytes_per_step(cfg, None, 320)
    assert 2.7e9 < b16 < 3.3e9
    # int4 halves the matmul body relative to int8 (logits head stays int8)
    b4 = estimate_decode_read_bytes_per_step(cfg, "int4", 320)
    assert b4 < 0.75 * b
    # KV term grows linearly with context: qwen2's GQA cache is
    # 2·28·2·128·2 B = 28.7 KB per position
    delta = estimate_decode_read_bytes_per_step(
        cfg, "int8", 1320
    ) - estimate_decode_read_bytes_per_step(cfg, "int8", 320)
    assert delta == pytest.approx(1000 * 2 * 28 * 2 * 128 * 2)


def test_decode_read_bytes_kv_quantize_halves_cache_term():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.memory import (
        estimate_decode_read_bytes_per_step,
    )

    # phi3 is the KV-heavy family (32 full-width heads, PERF.md): at 2k
    # context its cache stream dominates, so int8 KV must cut the step's
    # bytes by roughly the cache half (minus the f32 position scales)
    cfg = get_model_config("phi3:3.8b")
    full = estimate_decode_read_bytes_per_step(cfg, "int8", 2048)
    kvq = estimate_decode_read_bytes_per_step(
        cfg, "int8", 2048, kv_quantize="int8"
    )
    kv_bf16 = 2 * 32 * 32 * 96 * 2048 * 2
    assert full - kvq == pytest.approx(
        kv_bf16 / 2 - 2 * 32 * 32 * 2048 * 4, rel=0.01
    )


def test_decode_read_bytes_moe_streams_active_experts_only():
    """Per decode step only the routed top-k experts leave HBM — an
    8-expert Mixtral layer streams 2 experts' MLPs, not 8 (matching
    flops_per_token's active-expert accounting)."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.memory import (
        estimate_decode_read_bytes_per_step,
        estimate_weight_bytes,
    )

    cfg = get_model_config("mixtral:8x7b")
    per_step = estimate_decode_read_bytes_per_step(cfg, "int8", 128)
    resident = estimate_weight_bytes(cfg, "int8")
    # streamed bytes are far below residency (2 of 8 experts active) ...
    assert per_step < 0.45 * resident
    # ... but still dominated by the two active experts' MLPs
    active_mlp = cfg.n_layers * 3 * cfg.d_model * cfg.d_ff * 2
    assert per_step > active_mlp


def test_cache_keys_carry_metadata_on_accelerators_only(monkeypatch):
    """A trace must name operations by the scopes of the tree that runs,
    so on an accelerator the persistent cache is keyed on the programs'
    metadata too; on the CPU the key stays JAX's default."""
    import jax

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils import compile_cache

    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    try:
        jax.config.update(flag, False)
        compile_cache.key_cache_on_metadata()
        assert getattr(jax.config, flag) is False  # the tests run on the CPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        compile_cache.key_cache_on_metadata()
        assert getattr(jax.config, flag) is True
    finally:
        jax.config.update(flag, was)
