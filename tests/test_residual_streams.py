"""A stack that is not one scan of one-stream layers (ISSUE 32): several
residual streams mixed by Sinkhorn-projected maps around every sublayer, a
dense prefix before expert layers (two runs of ONE layer body), a sigmoid
router beside a shared expert, YaRN-scaled rotary angles; at a small size on
the CPU in float32. The comparison with the plain reference lives with the
benchmark (tests/benchmark_suite/test_benchmark_xing4.py)."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
    GenerationRequest,
    UnsupportedMechanism,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import JaxEngine
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
    FFN_DENSE,
    FFN_DENSE_THEN_EXPERTS,
    FFN_EXPERTS,
    FFN_EXPERTS_BESIDE_DENSE,
    ModelConfig,
    RopeScaling,
    get_model_config,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import quantize_leaf
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import (
    NON_LAYER_LEAVES,
    Transformer,
    _attention_block,
    _gated_ffn,
    _hc_map,
    _hc_write,
    _moe_parts,
    _moe_route,
    expert_layer_leaves,
    forward,
    init_params,
    logits_for,
    run_blocks,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.norms import rms_norm
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.rope import (
    rope_angles,
    rope_score_scale,
    yarn_ramp_bounds,
)

YARN = RopeScaling(factor=4.0, original_max_position=32, beta_fast=32, beta_slow=1, mscale=1.0, mscale_all_dim=1.0)
# 2 leading dense layers + 2 expert layers, 4 streams
TINY = ModelConfig(
    name="streams:tiny", vocab_size=512, d_model=64, n_layers=4, n_heads=4, n_kv_heads=1, d_head=24, d_ff=128,
    attention="latent", q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_dense_layers=2, d_ff_expert=32, n_experts=8, top_k_experts=3, n_shared_experts=1, router_scoring="sigmoid",
    routed_scaling_factor=2.0, router_bias=True, residual_streams=4, rope_scaling=YARN, max_seq_len=1024,
)


@pytest.fixture(scope="module")
def tiny():
    params = init_params(TINY, jax.random.PRNGKey(5), jnp.float32)
    # a bias that moves choices: the recipe's is a hundredth of the scores' spread
    params["router_bias"] = params["router_bias"] * 30.0
    return params


def _logits(cfg, params, tokens):
    k0, v0 = Transformer(cfg=cfg, params=params).init_cache(tokens.shape[0], tokens.shape[1], jnp.float32)
    stats = {}
    hidden, kc, _ = forward(params, cfg, tokens, jnp.int32(0), k0, v0, stats=stats)
    return logits_for(params, cfg, hidden), kc, stats


TOKENS = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 3, 259)


# -- the config says in one place what a layer's FFN is ---------------------------

def test_ffn_kind_and_the_runs_of_layers():
    assert TINY.ffn_kind == FFN_DENSE_THEN_EXPERTS and TINY.layer_runs == ((True, 0, 2), (False, 2, 2))
    assert TINY.n_expert_layers == 2 and TINY.cache_layers == 4 and TINY.experts_held == 9
    assert TINY.active_experts_per_token == 3 + 1
    assert get_model_config("mistral:7b").ffn_kind == FFN_DENSE
    assert get_model_config("mistral:7b").layer_runs == ((True, 0, 32),)
    mixtral = get_model_config("mixtral:8x7b")
    assert mixtral.ffn_kind == FFN_EXPERTS and mixtral.layer_runs == ((False, 0, 32),) and mixtral.n_expert_layers == 32
    beside = dataclasses.replace(TINY, n_dense_layers=0, n_shared_experts=0, residual_streams=1)
    assert beside.ffn_kind == FFN_EXPERTS_BESIDE_DENSE and len(beside.layer_runs) == 1


@pytest.mark.parametrize("change,why", [
    ({"n_dense_layers": 4}, "expert layers after them"),
    ({"n_dense_layers": 2, "n_experts": 0, "n_shared_experts": 0, "d_ff_expert": 0, "router_bias": False},
     "expert layers after them"),
    ({"blocks_per_layer": 2}, "one attention block"),
    ({"n_dense_layers": 0}, "shared experts"),
    ({"router_scoring": "tanh"}, "router_scoring"),
    ({"residual_streams": 0}, "residual_streams"),
])
def test_a_config_that_makes_no_stack_is_refused(change, why):
    with pytest.raises(ValueError, match=why):
        dataclasses.replace(TINY, **change)


def test_the_counts_are_per_kind_of_layer():
    d = 64
    attn = d * 32 + 32 * 4 * 24 + d * 24 + 16 * 4 * 32 + 4 * 16 * d
    assert TINY.layer_matmul_params(9, dense=True) == attn + 3 * d * 128
    assert TINY.layer_matmul_params(9) == attn + d * 8 + 9 * 3 * d * 32
    assert TINY.stack_matmul_params(9) == 2 * (attn + 3 * d * 128) + 2 * (attn + d * 8 + 9 * 3 * d * 32)
    assert TINY.hc_maps == 8 and TINY.hc_map_outputs == 24 and TINY.hc_params == 8 * (4 * d * 24 + 3 + 24)
    assert TINY.n_dense_ffn_layers == 2 and get_model_config("mixtral:8x7b").n_dense_ffn_layers == 0
    norms = 2 * d * 4 + d
    assert TINY.params_count == 2 * 512 * d + TINY.stack_matmul_params(9) + TINY.hc_params + norms
    plain = get_model_config("phi3:3.8b")
    assert plain.hc_params == 0 and plain.stack_matmul_params(0) == 32 * plain.layer_matmul_params(0)


# -- the stack: leaves as long as their run, one cache entry a layer ------------------

def test_leaves_are_as_long_as_their_run_of_layers(tiny):
    lengths = {k: jax.tree_util.tree_leaves(v)[0].shape[0] for k, v in tiny.items() if k not in NON_LAYER_LEAVES}
    for name in ("attn_norm", "w_qa", "w_qb", "w_kva", "w_kvb", "wo", "mlp_norm", "hc_attn_phi", "hc_mlp_bias"):
        assert lengths[name] == 4, name
    for name in ("w_gate", "w_up", "w_down", "we_gate", "we_up", "we_down", "ws_gate", "ws_down", "router",
                 "router_bias"):
        assert lengths[name] == 2, name
    assert tiny["we_gate"].shape == (2, 8, 64, 32) and tiny["ws_up"].shape == (2, 64, 32)
    assert tiny["hc_attn_phi"].shape == (4, 256, 24) and tiny["hc_attn_phi"].dtype == jnp.float32
    assert set(expert_layer_leaves(TINY)) == {"we_gate", "we_up", "we_down", "router", "router_bias"}
    k0, v0 = Transformer(cfg=TINY, params=tiny).init_cache(1, 32, jnp.float32)
    assert k0.shape == (4, 1, 1, 32, 24) and v0.shape[-1] == 0


def test_expert_leaves_are_made_a_layer_at_a_time_and_quantized_as_a_whole_leaf_would_be():
    quantized = jax.jit(lambda k: init_params(TINY, k, jnp.float32, post=lambda n, l: quantize_leaf(n, l, "int8")))(
        jax.random.PRNGKey(5))
    plain = init_params(TINY, jax.random.PRNGKey(5), jnp.float32)
    for name in ("we_gate", "we_down", "ws_up"):
        whole = quantize_leaf(name, plain[name], "int8")
        assert quantized[name]["q"].dtype == jnp.int8 and quantized[name]["s"].shape == whole["s"].shape
        np.testing.assert_array_equal(np.asarray(quantized[name]["q"]), np.asarray(whole["q"]), err_msg=name)
    for name in ("hc_attn_phi", "hc_mlp_alpha", "router", "router_bias", "attn_norm"):
        assert not isinstance(quantized[name], dict), name
    # layer i of an expert leaf has a key of its own
    assert float(jnp.max(jnp.abs(plain["we_gate"][0] - plain["we_gate"][1]))) > 0.1


@pytest.mark.parametrize("std,gain", [(0.02, 1.0), (2.0, 0.25)])
def test_the_stand_in_numbers_move_the_embedding_and_the_routed_down_projection_alone(std, gain):
    """``init_embed_std`` and ``init_routed_gain``: at their defaults every
    leaf is the array the recipe made before they existed; elsewhere only
    ``embed`` and ``we_down`` move, by exactly those factors."""
    key = jax.random.PRNGKey(9)
    plain = init_params(TINY, key, jnp.float32)
    moved = init_params(dataclasses.replace(TINY, init_embed_std=std, init_routed_gain=gain), key, jnp.float32)
    keys = jax.random.split(key, 12)
    np.testing.assert_array_equal(
        np.asarray(plain["embed"]),
        np.asarray(jax.random.normal(keys[0], (TINY.vocab_size, TINY.d_model), dtype=jnp.float32) * 0.02))
    down = jax.random.normal(
        jax.random.fold_in(jax.random.fold_in(keys[11], 2), 1), (8, 32, 64), dtype=jnp.float32) / math.sqrt(32)
    # made inside ``lax.map``: the compiled division is an ulp from the eager one
    np.testing.assert_allclose(np.asarray(plain["we_down"][1]), np.asarray(down), rtol=1e-6)
    for name in plain:
        if name not in ("embed", "we_down"):
            np.testing.assert_array_equal(np.asarray(plain[name]), np.asarray(moved[name]), err_msg=name)
    np.testing.assert_allclose(np.asarray(moved["embed"]), np.asarray(plain["embed"]) * (std / 0.02), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(moved["we_down"]), np.asarray(plain["we_down"]) * gain, rtol=1e-6)


def test_a_stack_split_by_hand_is_the_stack(tiny):
    """Layer 0 alone (a dense model of one layer) and then layers 1-3 (one
    dense layer before two expert layers) give what the four give: every
    layer reads the layer before it, and its own entry of the cache."""
    whole, cache, stats = _logits(TINY, tiny, TOKENS)
    assert stats["moe"].tolist()[:3] == [2 * 24 * 2 * 3, 0, 0]  # tokens x EXPERT layers x top-k; none zero or absent

    def part(lo, hi, dense_lo, dense_hi, keep_experts):
        out = {}
        for k, v in tiny.items():
            if k in NON_LAYER_LEAVES:
                continue
            if k in ("w_gate", "w_up", "w_down"):
                out[k] = v[dense_lo:dense_hi]
            elif k.startswith(("we_", "ws_", "router")):
                if keep_experts:
                    out[k] = v
            else:
                out[k] = v[lo:hi]
        return out

    first = dataclasses.replace(TINY, n_layers=1, n_dense_layers=0, n_experts=0, n_shared_experts=0, d_ff_expert=0,
                                router_bias=False)
    rest = dataclasses.replace(TINY, n_layers=3, n_dense_layers=1)
    b, s = TOKENS.shape
    x = jnp.broadcast_to(tiny["embed"][TOKENS][:, :, None, :], (b, s, 4, 64))
    cos, sin = rope_angles(jnp.broadcast_to(jnp.arange(s), (b, s)), 8, TINY.rope_theta, YARN)
    k0, v0 = Transformer(cfg=TINY, params=tiny).init_cache(b, s, jnp.float32)
    x, k_a, _ = run_blocks(part(0, 1, 0, 1, False), first, x, jnp.int32(0), k0[:1], v0[:1], cos, sin)
    x, k_b, _ = run_blocks(part(1, 4, 1, 2, True), rest, x, jnp.int32(0), k0[1:], v0[1:], cos, sin)
    hidden = rms_norm(jnp.sum(x, axis=2), tiny["final_norm"], TINY.norm_eps)
    np.testing.assert_allclose(np.asarray(logits_for(tiny, TINY, hidden)), np.asarray(whole), atol=1e-5)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([k_a, k_b])), np.asarray(cache), atol=1e-5)


def test_batched_decode_over_the_carry_is_prefill(tiny):
    """The carry-resident variant (one offset a row) runs both runs of layers too."""
    whole, _, _ = _logits(TINY, tiny, TOKENS)
    k0, v0 = Transformer(cfg=TINY, params=tiny).init_cache(2, 24, jnp.float32)
    _, kc, vc = forward(tiny, TINY, TOKENS[:, :23], jnp.int32(0), k0, v0)
    stats = {}
    hidden, _, _ = forward(tiny, TINY, TOKENS[:, 23:], jnp.array([23, 23]), kc, vc,
                           token_mask=jnp.array([[True], [False]]), stats=stats)
    np.testing.assert_allclose(np.asarray(logits_for(tiny, TINY, hidden[0, 0])), np.asarray(whole[0, 23]), atol=1e-5)
    assert stats["moe"].tolist()[:3] == [1 * 2 * 3, 0, 0]  # the masked row routes nowhere


# -- the residual map ------------------------------------------------------------------

def _map_leaves(params, layer=0, sub="attn"):
    return {k: params[f"hc_{sub}_{k}"][layer] for k in ("phi", "alpha", "bias")}


def _matrix(h_res):
    return jnp.stack([jnp.stack(row, axis=-1) for row in h_res], axis=-2)  # [B,S,n,n]


def test_the_mixing_matrix_is_doubly_stochastic(tiny):
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 7, 4, 64))
    h_pre, h_post, h_res = _hc_map(TINY, x, _map_leaves(tiny))
    m = _matrix(h_res)
    assert m.shape == (2, 7, 4, 4) and float(jnp.min(m)) >= 0.0
    assert float(jnp.max(jnp.abs(jnp.sum(m, axis=-1) - 1.0))) <= 2.5e-6  # rows: 1 / (1 + hc_eps), and rounding
    # columns to the iteration's accuracy: under the stand-in gains (a_res 1, logits of deviation ~1 around
    # 3 I) the worst token's column is 6e-3 off after 20 turns; 200 turns bring it under 1e-5
    assert float(jnp.max(jnp.abs(jnp.sum(m, axis=-2) - 1.0))) <= 2e-2
    longer = _matrix(_hc_map(dataclasses.replace(TINY, hc_sinkhorn_iters=200), x, _map_leaves(tiny))[2])
    assert float(jnp.max(jnp.abs(jnp.sum(longer, axis=-2) - 1.0))) <= 1e-5
    assert float(jnp.min(h_pre)) > 0 and float(jnp.max(h_pre)) < 1 and float(jnp.max(h_post)) < 2
    assert float(jnp.std(m[..., 0, 0])) > 1e-3  # the map reads its input
    # the streams' sum passes through the mixing untouched
    mixed = _hc_write(x, jnp.zeros_like(h_post), h_res, jnp.zeros((2, 7, 64)))
    np.testing.assert_allclose(np.asarray(jnp.sum(mixed, axis=2)), np.asarray(jnp.sum(x, axis=2)), atol=5e-2)
    mixed = _hc_write(x, jnp.zeros_like(h_post), _hc_map(dataclasses.replace(TINY, hc_sinkhorn_iters=200), x,
                                                        _map_leaves(tiny))[2], jnp.zeros((2, 7, 64)))
    np.testing.assert_allclose(np.asarray(jnp.sum(mixed, axis=2)), np.asarray(jnp.sum(x, axis=2)), atol=1e-4)


@pytest.mark.parametrize("logit", [100.0, -100.0])
def test_the_clamp_keeps_extreme_logits_finite(tiny, logit):
    hc = _map_leaves(tiny)
    hc = {**hc, "alpha": jnp.zeros((3,)), "bias": jnp.full((24,), logit)}
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 3, 4, 64))
    _, _, h_res = _hc_map(TINY, x, hc)
    m = _matrix(h_res)
    assert bool(jnp.all(jnp.isfinite(m)))
    np.testing.assert_allclose(np.asarray(m), 0.25, atol=1e-5)  # every logit alike: the even mixing


def test_the_logits_see_the_data_dependent_half_of_the_map(tiny):
    whole, _, _ = _logits(TINY, tiny, TOKENS)
    still = {k: (jnp.zeros_like(v) if k.endswith("_alpha") else v) for k, v in tiny.items()}
    moved = float(jnp.max(jnp.abs(_logits(TINY, still, TOKENS)[0] - whole)))
    assert moved > 1e-2  # the comparison's tolerance is 1e-4: zeroing the gains is a fault it sees


def test_one_stream_is_the_plain_residual_bit_for_bit():
    """A dense model's scan of layers, written out by hand as ``x + F(norm(x))``,
    is what the one layer body gives when there is one stream."""
    cfg = get_model_config("mistral:7b").tiny()
    params = init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    assert not any(k.startswith("hc_") for k in params)
    tokens = TOKENS[:1]
    k0, v0 = Transformer(cfg=cfg, params=params).init_cache(1, 24, jnp.float32)
    hidden, kc, vc = forward(params, cfg, tokens, jnp.int32(0), k0, v0)
    cos, sin = rope_angles(jnp.broadcast_to(jnp.arange(24), (1, 24)), cfg.d_head, cfg.rope_theta)

    def layer(x, xs):  # the scan of one residual stream, as it always was
        lw, k_l, v_l = xs
        h = rms_norm(x, lw["attn_norm"], cfg.norm_eps)
        out, k_l, v_l = _attention_block(cfg, h, lw, k_l, v_l, jnp.int32(0), cos, sin, None)
        x = x + out
        h = rms_norm(x, lw["mlp_norm"], cfg.norm_eps)
        return x + _gated_ffn(cfg, h, lw["w_gate"], lw["w_up"], lw["w_down"]), (k_l, v_l)

    stacked = {k: v for k, v in params.items() if k not in NON_LAYER_LEAVES}
    x, (k_hand, _) = jax.lax.scan(layer, params["embed"][tokens], (stacked, k0, v0))
    np.testing.assert_array_equal(np.asarray(k_hand), np.asarray(kc))
    np.testing.assert_array_equal(np.asarray(rms_norm(x, params["final_norm"], cfg.norm_eps)), np.asarray(hidden))
    text = jax.jit(lambda p, t, k, v: forward(p, cfg, t, jnp.int32(0), k, v)).lower(params, tokens, k0, v0).as_text(
        debug_info=True)
    assert "hc." not in text


# -- the router and the shared expert ------------------------------------------------------

def test_sigmoid_router_weights_sum_to_the_factor_and_the_bias_moves_the_choice_only(tiny):
    h = jax.random.normal(jax.random.PRNGKey(4), (40, 64))
    layer = {"router": tiny["router"][0], "router_bias": tiny["router_bias"][0]}
    top_i, top_w = _moe_route(TINY, h, layer)
    np.testing.assert_allclose(np.asarray(jnp.sum(top_w, axis=-1)), 2.0, rtol=1e-6)
    scores = jax.nn.sigmoid(h @ layer["router"])
    picked = jnp.take_along_axis(scores, top_i, axis=-1)
    np.testing.assert_allclose(np.asarray(top_w), np.asarray(2.0 * picked / picked.sum(-1, keepdims=True)), rtol=1e-6)
    # without the bias some tokens choose otherwise, and expert 5 with a bias of 10 is chosen by all
    unbiased, _ = _moe_route(TINY, h, {**layer, "router_bias": jnp.zeros((8,))})
    assert bool(jnp.any(jnp.sort(unbiased, -1) != jnp.sort(top_i, -1)))
    forced, w = _moe_route(TINY, h, {**layer, "router_bias": jnp.zeros((8,)).at[5].set(10.0)})
    assert bool(jnp.all(jnp.any(forced == 5, axis=-1)))
    np.testing.assert_allclose(np.asarray(jnp.sum(w, axis=-1)), 2.0, rtol=1e-6)


def test_softmax_routers_are_what_they_were(tiny):
    h = jax.random.normal(jax.random.PRNGKey(4), (10, 64))
    cfg = dataclasses.replace(TINY, router_scoring="softmax", router_bias=False, routed_scaling_factor=1.0)
    top_i, top_w = _moe_route(cfg, h, {"router": tiny["router"][0]})
    probs = jax.nn.softmax(h @ tiny["router"][0], axis=-1)
    want_w, want_i = jax.lax.top_k(probs, 3)
    np.testing.assert_array_equal(np.asarray(top_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(top_w), np.asarray(want_w / jnp.sum(want_w, -1, keepdims=True)))


def test_the_shared_experts_part_is_the_same_whatever_is_chosen(tiny):
    """With the routed experts' output weights zeroed, what is left of the
    layer does not move with the router's bias; with the shared expert's
    zeroed too, the FFN of an expert layer adds nothing."""
    no_routed = {**tiny, "we_down": jnp.zeros_like(tiny["we_down"])}
    a, _, _ = _logits(TINY, no_routed, TOKENS)
    b, _, _ = _logits(TINY, {**no_routed, "router_bias": -no_routed["router_bias"]}, TOKENS)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    whole, _, _ = _logits(TINY, tiny, TOKENS)
    assert float(jnp.max(jnp.abs(a - whole))) > 1e-3
    no_shared = {**tiny, "ws_down": jnp.zeros_like(tiny["ws_down"])}
    without = {k: v for k, v in tiny.items() if not k.startswith("ws_")}
    got, _, _ = _logits(dataclasses.replace(TINY, n_shared_experts=0), without, TOKENS)
    np.testing.assert_allclose(np.asarray(_logits(TINY, no_shared, TOKENS)[0]), np.asarray(got), atol=1e-6)


def test_grouped_dispatch_is_every_expert_for_every_token_and_shares_add_up(tiny):
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 9, 64))
    experts = {k: tiny[k] for k in expert_layer_leaves(TINY)}
    li = jnp.int32(1)
    routed, identity, counts = _moe_parts(TINY, h, experts, li)
    assert counts.tolist()[:3] == [18 * 3, 0, 0] and float(jnp.max(jnp.abs(identity))) == 0.0
    hf = h.reshape(18, 64)
    top_i, top_w = _moe_route(TINY, hf, {"router": tiny["router"][1], "router_bias": tiny["router_bias"][1]})
    want = jnp.zeros((18, 64))
    for e in range(8):
        weight = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        y = (jax.nn.silu(hf @ tiny["we_gate"][1, e]) * (hf @ tiny["we_up"][1, e])) @ tiny["we_down"][1, e]
        want = want + weight[:, None] * y
    np.testing.assert_allclose(np.asarray(routed.reshape(18, 64)), np.asarray(want), atol=1e-5)
    # two chips' shares of four experts each give the uncut layer's routed part (the shared expert
    # is every chip's alike and counted once: it is not in ``routed``)
    total = jnp.zeros_like(routed)
    for first in (0, 4):
        share = dataclasses.replace(TINY, n_experts=4, router_width=8, first_expert=first)
        held = {k: (v if k.startswith("router") else v[:, first : first + 4]) for k, v in experts.items()}
        part, _, n = _moe_parts(share, h, held, li)
        total = total + part
        assert n[0] + n[2] == 18 * 3
    np.testing.assert_allclose(np.asarray(total), np.asarray(routed), atol=1e-5)


# -- rotary angles ------------------------------------------------------------------------------

PUBLISHED = RopeScaling(factor=64.0, original_max_position=4096, beta_fast=32, beta_slow=1, mscale=1.0,
                        mscale_all_dim=1.0)


def test_yarn_at_the_published_numbers():
    assert yarn_ramp_bounds(PUBLISHED, 64, 10_000.0) == (10, 23)
    assert rope_score_scale(PUBLISHED) == pytest.approx((0.1 * math.log(64.0) + 1.0) ** 2)
    assert rope_score_scale(PUBLISHED) / math.sqrt(192) == pytest.approx(2.0048 / 13.8564, rel=1e-4)
    positions = jnp.arange(3000)[None, :]
    cos, sin = rope_angles(positions, 64, 10_000.0, PUBLISHED)
    plain_cos, _ = rope_angles(positions, 64, 10_000.0)
    # dimensions below ``low`` keep their frequency, those from ``high`` on turn 64 times slower
    np.testing.assert_array_equal(np.asarray(cos[..., :11]), np.asarray(plain_cos[..., :11]))
    f = 10_000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(np.asarray(cos[0, :, 23:]), np.cos(np.arange(3000)[:, None] * f[23:] / 64), atol=2e-4)
    np.testing.assert_allclose(np.asarray(cos**2 + sin**2), 1.0, atol=1e-6)  # mscale / mscale_all_dim = 1
    assert rope_score_scale(None) == 1.0 and rope_score_scale(dataclasses.replace(PUBLISHED, mscale_all_dim=0)) == 1.0


def test_no_scaling_is_todays_array_exactly():
    positions = jax.random.randint(jax.random.PRNGKey(0), (3, 17), 0, 5000)
    for d_head, theta in ((128, 1e6), (96, 1e4), (64, 1e7)):
        half = d_head // 2
        freqs = jnp.exp(-jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
        angles = positions.astype(jnp.float32)[..., None] * freqs
        for got in (rope_angles(positions, d_head, theta), rope_angles(positions, d_head, theta, None)):
            np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(jnp.cos(angles)))
            np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(jnp.sin(angles)))


def test_a_configuration_files_record_becomes_the_frozen_one():
    cfg = dataclasses.replace(TINY, rope_scaling=dict(factor=4.0, original_max_position=32, mscale_all_dim=1.0))
    assert cfg.rope_scaling == RopeScaling(factor=4.0, original_max_position=32, mscale_all_dim=1.0)
    assert cfg == TINY and hash(cfg) == hash(TINY)  # the same numbers as YARN's: the same (hashable) config


# -- the served path and its refusals -----------------------------------------------------------

def _engine(**kw):
    return JaxEngine(registry={TINY.name: TINY}, dtype=jnp.float32, **kw)


@pytest.mark.parametrize("mechanism,kwargs", [
    ("kv_quantize", {"kv_quantize": "int8"}),
    ("prefix_share", {"paged_kv": True, "prefix_share": True}),
    ("speculative", {"speculative": {TINY.name: ("ngram", 4)}}),
])
def test_load_refuses_by_name(mechanism, kwargs):
    with pytest.raises(UnsupportedMechanism, match=mechanism) as err:
        _engine(**kwargs).load_model(TINY.name)
    assert err.value.mechanism == mechanism and err.value.model == TINY.name


@pytest.mark.parametrize("cfg", [
    TINY,
    # neither is latent: the streams, or the two runs of layers, refuse on their own
    dataclasses.replace(get_model_config("mistral:7b").tiny(), residual_streams=4),
    dataclasses.replace(get_model_config("mixtral:8x7b").tiny(), n_layers=4, n_dense_layers=2),
], ids=["latent", "streams", "two-runs"])
def test_a_mesh_refuses_by_name(cfg):
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.mesh import MeshSpec, build_mesh
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.pp import make_pp_loss
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.sharding import param_specs

    with pytest.raises(UnsupportedMechanism, match="mesh") as err:
        param_specs(cfg, build_mesh(MeshSpec.tp_only(2), jax.devices()[:2]))
    assert err.value.mechanism == "mesh"
    if not cfg.latent:
        with pytest.raises(UnsupportedMechanism, match="mesh"):
            make_pp_loss(cfg, build_mesh(MeshSpec(axes=(("pp", 2),)), jax.devices()[:2]), n_microbatches=2)


def test_the_paged_session_serves_what_forward_serves_and_names_its_stack():
    eng = _engine(paged_kv=True, quantize="int8", seed=3)
    reqs = [GenerationRequest(TINY.name, "abc " * (33 + i), max_new_tokens=10 + 3 * i) for i in range(2)]
    alone = [eng.generate(r).tokens for r in reqs]
    sess = eng.decode_open(reqs, reserve_rows=4, slice_steps=4)
    try:
        state = sess.debug_state()
        assert state["stack"] == {"residual_streams": 4, "layer_runs": [2, 2]}
        assert sess.carry["pool_k"].shape[0] == 4 and sess.carry["side_k"].shape[0] == 4
        with pytest.raises(UnsupportedMechanism, match="preemption"):
            sess.preempt(reqs[0], policy="swap")
        with pytest.raises(UnsupportedMechanism, match="migration"):
            sess.resume_begin(None)
        got = {}
        while sess.active:
            for res in sess.step():
                got[res.request.prompt] = res.tokens
            s = sess.last_slice_moe
            assert s["moe_held"] == s["moe_tokens"] * 2 * 3 and s["moe_zero"] == s["moe_absent"] == 0
    finally:
        sess.close()
    assert [got[r.prompt] for r in reqs] == alone


def test_a_dense_session_names_no_stack():
    cfg = get_model_config("qwen2:1.5b").tiny()
    eng = JaxEngine(registry={cfg.name: cfg}, dtype=jnp.float32, paged_kv=True)
    sess = eng.decode_open([GenerationRequest(cfg.name, "abc " * 20, max_new_tokens=4)], reserve_rows=2, slice_steps=4)
    try:
        assert "stack" not in sess.debug_state()
    finally:
        sess.close()
