"""The plain reference makes the program's weights bit for bit from the
seed, follows the program's forward pass, and its int4 control fails the
output check's limit (kept here at a size a test run can hold)."""

import json
from pathlib import Path

import numpy as np
import pytest

CONFIGS = Path(__file__).resolve().parents[2] / "benchmark" / "configs"
SMALL = dict(model="small", hidden_size=256, num_hidden_layers=4, num_attention_heads=8,
             num_key_value_heads=2, head_dim=32, intermediate_size=512, vocab_size=2048,
             rms_norm_eps=1e-5, rope_theta=1e6, max_position_embeddings=512)
SEEDS = (3, 2**31 + 5, 4_000_000_019)


@pytest.fixture(scope="module")
def program():
    """The program's engine params and forward at the small size, per seed."""
    import jax.numpy as jnp

    from benchmark.lib import system
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import JaxEngine
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import forward, logits_for

    mc = system.model_config(SMALL)

    def make(seed):
        eng = JaxEngine(registry={mc.name: mc}, quantize="int8", paged_kv=True, seed=seed)
        eng.load_model(mc.name)
        params = eng._models[mc.name].params

        def logits(tokens):
            n, s = tokens.shape
            cache = jnp.zeros((mc.n_layers, n, mc.n_kv_heads, s, mc.d_head), jnp.bfloat16)
            hidden, _, _ = forward(params, mc, jnp.asarray(tokens), jnp.int32(0), cache, cache)
            return np.asarray(logits_for(params, mc, hidden))

        return params, logits

    return make


def tokens_for(seed, n=3, s=192):
    return np.random.RandomState(seed % 2**31).randint(3, 259, (n, s)).astype(np.int32)


def gap(ref_logits, chosen):
    best = ref_logits.max(-1)
    return best - np.take_along_axis(ref_logits, chosen[..., None], -1)[..., 0]


@pytest.mark.parametrize("seed", SEEDS)
def test_weights_equal_the_programs(program, seed):
    from benchmark.lib import reference

    params, _ = program(seed)
    mine = reference.make_weights(SMALL, seed)
    for name, leaf in mine.items():
        assert np.array_equal(np.asarray(leaf["q"]), np.asarray(params[name]["q"])), name
        assert np.array_equal(np.asarray(leaf["s"]), np.asarray(params[name]["s"])), name


@pytest.mark.parametrize("seed", SEEDS)
def test_program_passes_and_int4_control_fails_the_limit(program, seed):
    import jax.numpy as jnp

    from benchmark.lib import reference

    # the dense files' limit: the reference this rehearsal model stands for (another family tests its own)
    limit = min(
        cfg["check"]["max_logit_gap"]
        for cfg in (json.loads(p.read_text()) for p in CONFIGS.glob("*.json"))
        if cfg.get("family", "dense") == "dense"
    )
    toks = tokens_for(seed)
    ref = np.asarray(reference.logits(SMALL, reference.make_weights(SMALL, seed), jnp.asarray(toks)))
    _, served = program(seed)
    program_gap = gap(ref, served(toks).argmax(-1)).max()
    ctl = np.asarray(reference.logits(SMALL, reference.make_weights(SMALL, seed, bits=4), jnp.asarray(toks)))
    control_gap = gap(ref, ctl.argmax(-1)).max()
    assert program_gap < limit < control_gap, (program_gap, limit, control_gap)
    assert control_gap > 3 * program_gap


def test_padding_after_a_row_changes_nothing_before_it():
    import jax.numpy as jnp

    from benchmark.lib import reference

    w = reference.make_weights(SMALL, 1)
    toks = tokens_for(1, n=1, s=128)
    short = np.asarray(reference.logits(SMALL, w, jnp.asarray(toks[:, :128])))
    padded = np.zeros((1, 256), np.int32)
    padded[:, :128] = toks
    long = np.asarray(reference.logits(SMALL, w, jnp.asarray(padded)))
    assert np.allclose(short, long[:, :128], atol=1e-4)


def test_bits_other_than_8_or_4_are_refused():
    from benchmark.lib import reference

    with pytest.raises(ValueError):
        reference.make_weights(SMALL, 0, bits=2)
