"""The grouped expert kernel (ops/pallas_moe.py, interpret mode on the
CPU) against the loop of ``_moe_parts`` on the same leaves: the loop is
what runs where the kernel does not fit, and a sharded engine's switch
(``unpartitioned_kernels_disabled``) selects it here for the same leaves."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import GenerationRequest
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import JaxEngine
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import ModelConfig, get_model_config
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import (
    unpartitioned_kernels_disabled,
    quantize_params,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import (
    Transformer,
    _moe_parts,
    expert_layer_leaves,
    forward,
    init_params,
    logits_for,
    moe_block_rows,
    moe_impl,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_moe import (
    grouped_expert_ffn,
    grouped_ffn_supported,
)

# aligned widths (lane tiles), everything else small: 8 held experts of a
# 12-wide router with 4 identity experts, top-3, two layers
CFG = ModelConfig(
    name="grouped-tiny", vocab_size=512, d_model=256, n_layers=2, n_heads=4, n_kv_heads=1,
    d_head=24, d_ff=128, rope_theta=1e7, norm_eps=1e-5, max_seq_len=1024,
    attention="latent", q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, mla_scale_q_lora=True, mla_scale_kv_lora=True, blocks_per_layer=2, d_ff_expert=128,
    n_experts=8, router_width=12, n_zero_experts=4, top_k_experts=3, routed_scaling_factor=6.0,
    renormalize_topk=False, router_bias=True,
)


def _experts(cfg, kind):
    """The expert layer's leaves: int8 codes + scales, or plain bfloat16."""
    params = init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    if kind == "int8":
        params = quantize_params(params)
    experts = {k: params[k] for k in expert_layer_leaves(cfg)}
    if kind == "bf16":
        experts = {k: (v if k.startswith("router") else v.astype(jnp.bfloat16)) for k, v in experts.items()}
    return experts


def _tokens(shape, dtype=jnp.bfloat16, seed=4):
    return jax.random.normal(jax.random.PRNGKey(seed), (*shape, CFG.d_model), dtype=jnp.float32).astype(dtype)


def _both(cfg, h, experts, li, mask=None):
    """(kernel path, loop path) of ``_moe_parts`` under jit."""
    fn = jax.jit(lambda h, e, li, m: _moe_parts(cfg, h, e, li, m))
    assert moe_impl(cfg, h.dtype, h.shape[0] * h.shape[1], experts) == "pallas-grouped"
    got = fn(h, experts, li, mask)
    with unpartitioned_kernels_disabled():
        assert moe_impl(cfg, h.dtype, h.shape[0] * h.shape[1], experts) == "xla-loop"
        want = jax.jit(lambda h, e, li, m: _moe_parts(cfg, h, e, li, m))(h, experts, li, mask)
    return got, want


def _agree(got, want, touched_at_most=None):
    """Same routed part (the sums inside a matmul differ in order, no more),
    same identity part, the five counts equal, blocks >= touched."""
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    counts = [int(c) for c in got[2]]
    assert counts == [int(c) for c in want[2]] and len(counts) == 5
    assert counts[4] >= counts[3]
    if touched_at_most is not None:
        assert counts[3] <= touched_at_most
    return counts


@pytest.mark.parametrize("kind", ["int8", "bf16"])
@pytest.mark.parametrize("shape,rows", [((2, 9), 8), ((32, 1), 8), ((1, 64), 16)])
def test_kernel_equals_loop(kind, shape, rows):
    assert moe_block_rows(CFG, shape[0] * shape[1]) == rows
    got, want = _both(CFG, _tokens(shape), _experts(CFG, kind), jnp.int32(1))
    assert _agree(got, want)[0] > 0 and float(jnp.abs(got[0]).max()) > 0.1


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_an_expert_with_more_pairs_than_rows_and_one_with_none(dtype):
    """Every token's first choice is expert 3 (20 pairs: three blocks of 8
    rows on one expert), nobody chooses expert 5."""
    experts = _experts(CFG, "int8")
    bias = jnp.zeros_like(experts["router_bias"]).at[:, 3].set(10.0).at[:, 5].set(-10.0)
    got, want = _both(CFG, _tokens((20, 1), dtype), {**experts, "router_bias": bias}, jnp.int32(0))
    counts = _agree(got, want, touched_at_most=7)
    assert counts[4] >= counts[3] + 2  # expert 3 alone takes three blocks


def test_no_live_token_is_zero_and_no_block():
    h = _tokens((4, 3))
    got, want = _both(CFG, h, _experts(CFG, "int8"), jnp.int32(1), jnp.zeros(h.shape[:2], dtype=bool))
    assert _agree(got, want) == [0, 0, 0, 0, 0]
    assert not np.asarray(got[0]).any() and not np.asarray(got[1]).any()


def test_masked_rows_route_nowhere():
    h = _tokens((6, 4))
    mask = jnp.zeros(h.shape[:2], dtype=bool).at[1, :3].set(True).at[4, 1:].set(True)
    got, want = _both(CFG, h, _experts(CFG, "int8"), jnp.int32(0), mask)
    counts = _agree(got, want)
    assert sum(counts[:3]) == 6 * CFG.top_k_experts
    assert not np.asarray(got[0])[~np.asarray(mask)].any()


@pytest.mark.parametrize("first", [2, 6])
def test_a_held_slice_with_absent_experts(first):
    """This chip holds experts ``first`` and ``first + 1`` of the 8 routed
    ones: the others' pairs are absent, and nothing is read for them."""
    cfg = dataclasses.replace(CFG, n_experts=2, first_expert=first)
    experts = {k: (jax.tree_util.tree_map(lambda a: a[:, first : first + 2], v) if k.startswith("we_") else v)
               for k, v in _experts(CFG, "int8").items()}
    counts = _agree(*_both(cfg, _tokens((3, 11)), experts, jnp.int32(1)), touched_at_most=2)
    assert counts[2] > 0 and counts[0] > 0


@pytest.mark.parametrize("kind", ["int8", "bf16"])
def test_the_layer_index_traced_in_a_scan(kind):
    experts, h = _experts(CFG, kind), _tokens((2, 5))

    def stack(experts):
        def layer(c, li):
            routed, identity, counts = _moe_parts(CFG, c, experts, li)
            return c, (routed, identity, counts)
        return jax.lax.scan(layer, h, jnp.arange(CFG.n_layers))[1]

    got = jax.jit(stack)(experts)
    with unpartitioned_kernels_disabled():
        want = jax.jit(stack)(experts)
    for li in range(CFG.n_layers):
        _agree([g[li] for g in got], [w[li] for w in want])
    assert float(jnp.abs(got[0][0] - got[0][1]).max()) > 0.1  # two layers, two sets of experts


def test_the_kernel_alone_adds_weighted_rows_to_their_tokens():
    """Two real blocks of four given: tokens 1 and 2 on expert 2, token 1
    again on expert 0; the blocks past the count are not computed."""
    experts = _experts(CFG, "int8")
    leaves = tuple(experts[k] for k in ("we_gate", "we_up", "we_down"))
    h = _tokens((5,), jnp.bfloat16)
    slot_token = jnp.zeros((32,), jnp.int32).at[0].set(1).at[1].set(2).at[8].set(1).at[16].set(4)
    slot_weight = jnp.zeros((32,), jnp.float32).at[0].set(0.5).at[1].set(2.0).at[8].set(1.0).at[16].set(9.0)
    out = grouped_expert_ffn(h, *leaves, jnp.int32(1), jnp.array([2, 0, 7, 7]), jnp.int32(2), slot_token, slot_weight)

    def ffn(x, e):
        def dot(a, leaf):
            return jnp.dot(a, leaf["q"][1, e].astype(a.dtype), preferred_element_type=jnp.float32) * leaf["s"][1, e]
        return dot((jax.nn.silu(dot(x, leaves[0])) * dot(x, leaves[1])).astype(x.dtype), leaves[2])

    want = jnp.zeros((5, CFG.d_model)).at[1].set(0.5 * ffn(h[1:2], 2)[0] + ffn(h[1:2], 0)[0]).at[2].set(2.0 * ffn(h[2:3], 2)[0])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert not np.asarray(out)[[0, 3, 4]].any()  # token 4's slot lies past the real blocks


@pytest.mark.parametrize("what,fits", [
    ("int8", True), ("bf16", True), ("float32", False), ("int4", False), ("unaligned", False),
    ("rows-4", False), ("too-many-tokens", False),
])
def test_what_the_kernel_takes(what, fits):
    cfg = dataclasses.replace(CFG, d_model=192) if what == "unaligned" else CFG
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    if what in ("int8", "int4"):
        params = quantize_params(params, mode=what)
    leaves = [params[k] for k in ("we_gate", "we_up", "we_down")]
    if what == "bf16":
        leaves = [a.astype(jnp.bfloat16) for a in leaves]
    rows = 4 if what == "rows-4" else 8
    tokens = 1 << 20 if what == "too-many-tokens" else 32
    assert grouped_ffn_supported(jnp.bfloat16, tokens, rows, *leaves) is fits
    if what in ("int8", "float32", "int4", "unaligned"):
        experts = {k: params[k] for k in expert_layer_leaves(cfg)}
        assert moe_impl(cfg, jnp.bfloat16, 32, experts) == ("pallas-grouped" if fits else "xla-loop")


def test_forward_on_int8_experts_runs_the_kernel_and_keeps_the_logits():
    cfg = dataclasses.replace(
        get_model_config("mixtral:8x7b").tiny(), d_model=256, n_heads=4, n_kv_heads=2, d_head=64, d_ff=128, n_experts=8,
    )
    params = quantize_params(Transformer.initialise(cfg, seed=1, dtype=jnp.float32).params)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0, cfg.vocab_size)
    cache = jnp.zeros((cfg.n_layers, 2, cfg.n_kv_heads, 12, cfg.d_head), jnp.float32)

    def logits(params):
        stats = {}
        hidden, _, _ = forward(params, cfg, tokens, jnp.int32(0), cache, cache, None, stats=stats)
        return logits_for(params, cfg, hidden), stats["moe"]

    assert moe_impl(cfg, jnp.float32, 24, params) == "pallas-grouped"
    got, counts = jax.jit(logits)(params)
    with unpartitioned_kernels_disabled():
        assert moe_impl(cfg, jnp.float32, 24, params) == "xla-loop"
        want, want_counts = jax.jit(logits)(params)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert counts.tolist() == want_counts.tolist() and counts[0] == 24 * 2 * cfg.n_layers and counts[4] >= counts[3]


@pytest.mark.parametrize("width,impl", [(256, "pallas-grouped"), (64, "xla-loop")])
def test_a_session_says_which_and_counts_blocks(width, impl):
    cfg = dataclasses.replace(CFG, d_model=width, d_ff_expert=width // 2, n_zero_experts=0, router_width=0)
    eng = JaxEngine(registry={cfg.name: cfg}, dtype=jnp.float32, paged_kv=True, quantize="int8", seed=3)
    reqs = [GenerationRequest(cfg.name, "abc " * (9 + i), max_new_tokens=6) for i in range(2)]
    sess = eng.decode_open(reqs, reserve_rows=4, slice_steps=4)
    try:
        assert sess.debug_state()["moe"] == {"impl": impl, "block_rows": 8}
        while sess.active:
            sess.step()
            s = sess.last_slice_moe
            assert s["moe_blocks"] >= s["moe_touched"] > 0
            assert s["moe_held"] == s["moe_tokens"] * cfg.n_layers * cfg.top_k_experts
    finally:
        sess.close()
