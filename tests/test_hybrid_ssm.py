"""A stack of state-space (Mamba-2) and attention layers: the mixer's two
forms against a token-by-token scan, masked positions, attention without a
position embedding and with a configured score scale, the four scalar
multipliers, the router's share of an expert layer beside a shared MLP, the
stack's leaves / cache / state by kind of layer, the recurrent state in a
paged session (install, retirement, admission, refusals), and that a model
without ``layer_types`` compiles to the program it compiled to before."""

import dataclasses
import hashlib
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
    MIXER_ATTENTION,
    MIXER_SSM,
    ModelConfig,
    get_model_config,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.ssm import (
    SSM_LEAVES,
    init_state,
    install_state_row,
    ssm_mixer,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import (
    Transformer,
    forward,
    init_params,
    is_state_cache,
    logits_for,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.trace import TRACER
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_attention import (
    pallas_decode_attention,
)

TYPES = ("mamba", "mamba", "attention", "mamba", "mamba", "attention")
TINY = ModelConfig(
    name="hybrid-tiny", vocab_size=512, d_model=64, n_layers=6, n_heads=4, n_kv_heads=2, d_head=16,
    d_ff=48, d_ff_expert=32, n_experts=8, top_k_experts=3, tie_embeddings=True, norm_eps=1e-5,
    max_seq_len=1024, layer_types=TYPES, ssm_n_heads=8, ssm_d_head=16, ssm_d_state=16,
    ssm_chunk_size=8, embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=1 / 32, logits_scaling=4.0, position_embedding="none",
)
PUBLISHED_TYPES = tuple(
    "attention" if i in (5, 15, 25, 35) else "mamba" for i in range(40)
)


@pytest.fixture(scope="module")
def tiny():
    return TINY, init_params(TINY, jax.random.PRNGKey(3), jnp.float32)


def mixer_layer(params, i):
    return {k: params[k][i] for k in SSM_LEAVES}


def scan_reference(cfg, u, layer):
    """The mixer token by token in numpy: no chunk, no state handed in."""
    h, p, n, k = cfg.ssm_n_heads, cfg.ssm_d_head, cfg.ssm_d_state, cfg.ssm_d_conv
    d_in, c_w = cfg.ssm_d_inner, cfg.ssm_conv_width
    w = {key: np.asarray(val, np.float64) for key, val in layer.items()}
    u = np.asarray(u, np.float64)
    zxbcdt = u @ w["ssm_in"]
    z, xbc, dt = zxbcdt[:, :d_in], zxbcdt[:, d_in : d_in + c_w], zxbcdt[:, d_in + c_w :]
    before = np.concatenate([np.zeros((k - 1, c_w)), xbc])
    silu = lambda a: a / (1 + np.exp(-a))  # noqa: E731
    state, out, states = np.zeros((h, p, n)), [], []
    for t in range(len(u)):
        c = silu(w["ssm_conv_b"] + sum(w["ssm_conv_w"][i] * before[t + i] for i in range(k)))
        x, bm, cm = c[:d_in].reshape(h, p), c[d_in : d_in + n], c[d_in + n :]
        step = np.logaddexp(dt[t] + w["ssm_dt_bias"], 0.0)
        a = np.exp(-step * np.exp(w["ssm_a_log"]))
        state = a[:, None, None] * state + (step[:, None] * x)[:, :, None] * bm[None, None, :]
        y = state @ cm + w["ssm_d"][:, None] * x
        g = y.reshape(-1) * silu(z[t])
        out.append((w["ssm_norm"] * g / np.sqrt(np.mean(g * g) + cfg.norm_eps)) @ w["ssm_out"])
        states.append(state)
    return np.stack(out), states, before


def mixer_steps(cfg, u, layer, st):
    """The mixer one token at a time (ONE compiled step: an eager call a token
    would load a fresh executable for every scan it meets)."""
    step = jax.jit(lambda tok, st: ssm_mixer(cfg, tok, layer, st))
    outs = []
    for t in range(u.shape[1]):
        y, st = step(u[:, t : t + 1], st)
        outs.append(y[:, 0])
    return jnp.stack(outs, axis=1), st


def layer_state(cfg, batch=1):
    return jax.tree_util.tree_map(lambda a: a[0], init_state(cfg, batch, jnp.float32))


# -- 2. the mixer: chunk form = step form = the scan ---------------------------------


@pytest.mark.parametrize("length", [13, 8, 21])
def test_chunk_form_equals_step_form_equals_the_scan(tiny, length):
    cfg, params = tiny
    layer = mixer_layer(params, 1)
    u = jax.random.normal(jax.random.PRNGKey(length), (1, length, cfg.d_model), jnp.float32)
    want, states, before = scan_reference(cfg, u[0], layer)
    whole, st = ssm_mixer(cfg, u, layer, layer_state(cfg))
    np.testing.assert_allclose(np.asarray(whole[0]), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(st["s"][0]), states[-1], atol=2e-6)
    np.testing.assert_allclose(np.asarray(st["conv"][0]), before[length:], atol=1e-6)
    stepped, step_st = mixer_steps(cfg, u, layer, layer_state(cfg))
    np.testing.assert_allclose(np.asarray(stepped[0]), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(step_st["s"]), np.asarray(st["s"]), atol=2e-6)
    np.testing.assert_allclose(np.asarray(step_st["conv"]), np.asarray(st["conv"]), atol=1e-6)


def test_a_chunk_run_as_two_halves_with_the_state_handed_over_equals_the_chunk_whole(tiny):
    cfg, params = tiny
    layer = mixer_layer(params, 0)
    u = jax.random.normal(jax.random.PRNGKey(7), (2, 19, cfg.d_model), jnp.float32)
    whole, st = ssm_mixer(cfg, u, layer, layer_state(cfg, 2))
    first, half = ssm_mixer(cfg, u[:, :11], layer, layer_state(cfg, 2))
    second, st2 = ssm_mixer(cfg, u[:, 11:], layer, half)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([first, second], 1)), np.asarray(whole), atol=2e-5)
    np.testing.assert_allclose(np.asarray(st2["s"]), np.asarray(st["s"]), atol=2e-6)
    np.testing.assert_array_equal(np.asarray(st2["conv"]), np.asarray(st["conv"]))


@pytest.mark.parametrize("real", [1, 2, 9, 16])
def test_padded_positions_leave_the_state_and_the_tail_where_the_last_real_token_left_them(tiny, real):
    cfg, params = tiny
    layer = mixer_layer(params, 2)
    u = jax.random.normal(jax.random.PRNGKey(11), (1, 16, cfg.d_model), jnp.float32)
    start = jax.tree_util.tree_map(
        lambda a: jax.random.normal(jax.random.PRNGKey(5), a.shape, a.dtype), layer_state(cfg)
    )
    want, want_st = ssm_mixer(cfg, u[:, :real], layer, start)
    mask = jnp.arange(16)[None, :] < real
    got, got_st = ssm_mixer(cfg, u, layer, start, token_mask=mask)
    np.testing.assert_allclose(np.asarray(got[:, :real]), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_st["s"]), np.asarray(want_st["s"]), atol=2e-6)
    np.testing.assert_array_equal(np.asarray(got_st["conv"]), np.asarray(want_st["conv"]))
    # nothing real at all (a finished row's decode step): bit-equal to what came in
    _, still = ssm_mixer(cfg, u[:, :1], layer, start, token_mask=jnp.zeros((1, 1), bool))
    for key in ("s", "conv"):
        np.testing.assert_array_equal(np.asarray(still[key]), np.asarray(start[key]))


def test_the_mixer_with_groups_of_heads(tiny):
    cfg = dataclasses.replace(TINY, ssm_n_groups=2)
    params = init_params(cfg, jax.random.PRNGKey(4), jnp.float32)
    layer = mixer_layer(params, 0)
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 12, cfg.d_model), jnp.float32)
    whole, st = ssm_mixer(cfg, u, layer, layer_state(cfg))
    stepped, step_st = mixer_steps(cfg, u, layer, layer_state(cfg))
    np.testing.assert_allclose(np.asarray(stepped), np.asarray(whole), atol=2e-5)
    np.testing.assert_allclose(np.asarray(step_st["s"]), np.asarray(st["s"]), atol=2e-6)


# -- 1 / 3. forward: prefill, decode through cache and state, and the state is seen ----


def full_logits(cfg, params, tokens):
    k0, v0 = Transformer(cfg, params).init_cache(tokens.shape[0], tokens.shape[1], jnp.float32)

    @jax.jit
    def run(params, tokens, k0, v0):
        hidden, _, _ = forward(params, cfg, tokens, jnp.int32(0), k0, v0)
        return logits_for(params, cfg, hidden)

    return run(params, tokens, k0, v0)


def test_decode_through_cache_and_state_equals_one_pass_and_sees_the_state(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 40), 3, 259)
    want = full_logits(cfg, params, tokens)[0]
    k0, v0 = Transformer(cfg, params).init_cache(1, 64, jnp.float32)
    assert is_state_cache(k0) and k0["kv"].shape == (2, 1, 2, 64, 16)
    assert k0["ssm"]["s"].shape == (4, 1, 8, 16, 16) and k0["ssm"]["conv"].shape == (4, 1, 3, 128 + 32)
    padded = jnp.zeros((1, 32), jnp.int32).at[:, :27].set(tokens[:, :27])
    hidden, kc, vc = forward(
        params, cfg, padded, jnp.int32(0), k0, v0, token_mask=jnp.arange(32)[None, :] < 27
    )
    np.testing.assert_allclose(np.asarray(logits_for(params, cfg, hidden[0, :27])), np.asarray(want[:27]), atol=1e-4)

    step = jax.jit(lambda tok, t, kc, vc: forward(params, cfg, tok, t, kc, vc))

    def decode(kc, vc):
        worst = 0.0
        for t in range(27, 40):
            hidden, kc, vc = step(tokens[:, t : t + 1], jnp.int32(t), kc, vc)
            worst = max(worst, float(jnp.max(jnp.abs(logits_for(params, cfg, hidden[0, 0]) - want[t]))))
        return worst

    assert decode(kc, vc) <= 1e-4
    # the comparison sees the state: with S, or the convolution's tail, dropped the logits move
    for leaf in ("s", "conv"):
        dropped = {"kv": kc["kv"], "ssm": {**kc["ssm"], leaf: jnp.zeros_like(kc["ssm"][leaf])}}
        assert decode(dropped, vc) > 1e-3, leaf
    # pads that move the state are seen too
    hidden, kc_bad, vc_bad = forward(params, cfg, padded, jnp.int32(0), k0, v0)
    assert decode(kc_bad, vc_bad) > 1e-3


# -- 4. attention without a position embedding, with a configured scale ---------------


ATTN_ONLY = dataclasses.replace(
    TINY, name="nope-tiny", n_layers=2, layer_types=("attention", "attention"), n_experts=0, d_ff_expert=0,
)


def test_without_a_position_embedding_only_the_order_of_the_mask_is_left():
    """No rotary: a key does not depend on where it sits, and one attention
    layer's output for the last token does not depend on the order of the
    tokens before it. Under rope both do."""
    one = dataclasses.replace(ATTN_ONLY, n_layers=1, layer_types=("attention",))
    rope = dataclasses.replace(one, position_embedding="rope")
    params = init_params(one, jax.random.PRNGKey(1), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 12), 3, 259)
    shuffled = jnp.concatenate([tokens[:, :11][:, ::-1], tokens[:, 11:]], axis=1)
    tf = Transformer(one, params)
    k0, v0 = tf.init_cache(1, 64, jnp.float32)

    def run(cfg, toks, offset=0):
        hidden, kc, _ = forward(params, cfg, toks, jnp.int32(offset), k0, v0)
        return hidden[0, -1], kc

    last, kc = run(one, tokens)
    last_shuffled, _ = run(one, shuffled)
    np.testing.assert_allclose(np.asarray(last_shuffled), np.asarray(last), atol=1e-5)
    _, kc7 = run(one, tokens, 7)
    np.testing.assert_array_equal(np.asarray(kc[:, :, :, :12]), np.asarray(kc7[:, :, :, 7:19]))
    r_last, rkc = run(rope, tokens)
    r_shuffled, _ = run(rope, shuffled)
    _, rkc7 = run(rope, tokens, 7)
    assert float(jnp.max(jnp.abs(r_shuffled - r_last))) > 1e-3
    assert float(jnp.max(jnp.abs(rkc[:, :, :, :12] - rkc7[:, :, :, 7:19]))) > 1e-2


def test_the_score_scale_is_the_configured_multiplier():
    cfg = dataclasses.replace(ATTN_ONLY, attention_multiplier=1 / math.sqrt(16))
    params = init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 9), 3, 259)
    default = dataclasses.replace(cfg, attention_multiplier=0.0)  # one over the root of d_head
    np.testing.assert_allclose(
        np.asarray(full_logits(cfg, params, tokens)), np.asarray(full_logits(default, params, tokens)), atol=1e-5
    )
    other = dataclasses.replace(cfg, attention_multiplier=1 / 32)
    assert float(jnp.max(jnp.abs(full_logits(other, params, tokens) - full_logits(default, params, tokens)))) > 1e-4


def test_rope_and_unit_multipliers_are_todays_arrays_exactly():
    base = get_model_config("mistral:7b").tiny()
    named = dataclasses.replace(
        base, position_embedding="rope", embedding_multiplier=1.0, residual_multiplier=1.0,
        attention_multiplier=0.0, logits_scaling=1.0, layer_types=("attention",) * base.n_layers,
    )
    params = init_params(base, jax.random.PRNGKey(0), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 9), 3, 259)
    np.testing.assert_array_equal(
        np.asarray(full_logits(base, params, tokens)), np.asarray(full_logits(named, params, tokens))
    )


@pytest.mark.parametrize("field,value", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0), ("logits_scaling", 1.0),
])
def test_each_multiplier_reaches_the_logits(tiny, field, value):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 10), 3, 259)
    moved = full_logits(dataclasses.replace(cfg, **{field: value}), params, tokens)
    assert float(jnp.max(jnp.abs(moved - full_logits(cfg, params, tokens)))) > 1e-3


def test_logits_scaling_divides_and_the_embedding_multiplier_multiplies(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 10), 3, 259)
    plain = full_logits(dataclasses.replace(cfg, logits_scaling=1.0), params, tokens)
    np.testing.assert_allclose(np.asarray(full_logits(cfg, params, tokens)), np.asarray(plain) / 4.0, rtol=1e-6)


# -- 5. the router, and this chip's share beside the shared MLP ------------------------


def test_two_shares_and_the_shared_mlp_once_equal_the_uncut_layer(tiny):
    """A one-layer model's stream before the final norm: the uncut layer (all
    8 experts) against the sum of what two chips' shares (experts 0-3, 4-7)
    add, the mixer and the shared MLP counted once."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import NON_LAYER_LEAVES, run_blocks

    cfg, params = tiny
    one = dataclasses.replace(cfg, n_layers=1, layer_types=("mamba",))
    stacked = {k: v[:1] for k, v in params.items() if k not in NON_LAYER_LEAVES}
    x0 = jax.random.normal(jax.random.PRNGKey(9), (2, 24, cfg.d_model), jnp.float32)

    def stream(c, leaves, stats=None):
        k0, v0 = Transformer(c, params).init_cache(2, 24, jnp.float32)
        return run_blocks(leaves, c, x0, jnp.int32(0), k0, v0, None, None, stats=stats)[0]

    whole_stats = {}
    whole = stream(one, stacked, whole_stats)
    assert whole_stats["moe"].tolist()[:3] == [2 * 24 * 1 * 3, 0, 0]
    shares = []
    for first in (0, 4):
        share = dataclasses.replace(one, n_experts=4, router_width=8, first_expert=first)
        leaves = {k: (v[:, first : first + 4] if k.startswith("we_") else v) for k, v in stacked.items()}
        stats = {}
        shares.append(stream(share, leaves, stats))
        held, zero, absent = stats["moe"].tolist()[:3]
        assert held + absent == 2 * 24 * 1 * 3 and zero == 0 and 0 < held < 2 * 24 * 3
    none = dataclasses.replace(one, n_experts=4, router_width=8)
    without = stream(none, {k: (jnp.zeros_like(v[:, :4]) if k.startswith("we_") else v) for k, v in stacked.items()})
    np.testing.assert_allclose(np.asarray(shares[0] + shares[1] - without), np.asarray(whole), atol=1e-5)
    assert float(jnp.max(jnp.abs(whole - without))) > 1e-3  # the experts add something


def test_router_weights_are_the_softmax_over_the_chosen(tiny):
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import _moe_route

    cfg, params = tiny
    h = jax.random.normal(jax.random.PRNGKey(3), (17, cfg.d_model), jnp.float32)
    top_i, top_w = _moe_route(cfg, h, {"router": params["router"][1]})
    logits = np.asarray(h @ params["router"][1])
    order = np.argsort(-logits, axis=-1)[:, :3]
    np.testing.assert_array_equal(np.sort(np.asarray(top_i), -1), np.sort(order, -1))
    chosen = np.take_along_axis(logits, np.asarray(top_i), -1)
    want = np.exp(chosen - chosen.max(-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(top_w), want / want.sum(-1, keepdims=True), atol=1e-6)


# -- 6. the stack -----------------------------------------------------------------------


def test_leaves_cache_and_state_are_as_long_as_the_layers_of_their_kind(tiny):
    cfg, params = tiny
    assert cfg.state_layers == 4 and cfg.attention_layers == 2 == cfg.cache_layers
    assert [cfg.mixer_kind(i) for i in range(6)] == [MIXER_SSM, MIXER_SSM, MIXER_ATTENTION] * 2
    assert [cfg.kind_index(i) for i in range(6)] == [0, 1, 0, 2, 3, 1]
    assert cfg.layer_runs == ((False, 0, 2), (False, 2, 1), (False, 3, 2), (False, 5, 1))
    for name in SSM_LEAVES:
        assert params[name].shape[0] == 4, name
    for name in ("wq", "wk", "wv", "wo"):
        assert params[name].shape[0] == 2, name
    for name in ("attn_norm", "mlp_norm", "w_gate", "w_up", "w_down", "we_gate", "we_up", "we_down", "router"):
        assert params[name].shape[0] == 6, name
    assert params["we_gate"].shape == (6, 8, 64, 32) and params["ssm_in"].shape == (4, 64, 2 * 128 + 2 * 16 + 8)
    assert cfg.state_bytes_per_row(4) == 4 * (8 * 16 * 16 * 4 + 3 * 160 * 4)


def test_layer_runs_of_the_published_layer_types():
    cfg = dataclasses.replace(TINY, n_layers=40, layer_types=PUBLISHED_TYPES)
    assert [count for _, _, count in cfg.layer_runs] == [5, 1, 9, 1, 9, 1, 9, 1, 4]
    assert [cfg.mixer_kind(first) for _, first, _ in cfg.layer_runs] == [MIXER_SSM, MIXER_ATTENTION] * 4 + [MIXER_SSM]
    assert cfg.state_layers == 36 and cfg.cache_layers == 4
    assert get_model_config("mistral:7b").layer_runs == ((True, 0, 32),)


def test_three_layers_followed_by_hand_by_the_next_three_are_the_six(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 12), 3, 259)
    k0, v0 = Transformer(cfg, params).init_cache(1, 12, jnp.float32)
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import NON_LAYER_LEAVES, run_blocks

    # the stream after all six, without the final norm
    stacked = {k: v for k, v in params.items() if k not in NON_LAYER_LEAVES}
    x0 = 12.0 * params["embed"][tokens]
    six, _, _ = run_blocks(stacked, cfg, x0, jnp.int32(0), k0, v0, None, None)

    def half(lo, hi, x):
        c = dataclasses.replace(cfg, n_layers=hi - lo, layer_types=TYPES[lo:hi])
        kinds = [cfg.mixer_kind(i) for i in range(6)]
        ssm_idx = [cfg.kind_index(i) for i in range(lo, hi) if kinds[i] == MIXER_SSM]
        att_idx = [cfg.kind_index(i) for i in range(lo, hi) if kinds[i] == MIXER_ATTENTION]
        part = {}
        for k, v in stacked.items():
            if k in SSM_LEAVES:
                part[k] = v[jnp.asarray(ssm_idx)]
            elif k in ("wq", "wk", "wv", "wo"):
                part[k] = v[jnp.asarray(att_idx)]
            else:
                part[k] = v[lo:hi]
        kc, vc = Transformer(c, params).init_cache(1, 12, jnp.float32)
        return run_blocks(part, c, x, jnp.int32(0), kc, vc, None, None)[0]

    np.testing.assert_allclose(np.asarray(half(3, 6, half(0, 3, x0))), np.asarray(six), atol=1e-5)


# -- 7. the session ---------------------------------------------------------------------


def prompt(i, n):
    return "".join("abcdefgh "[(i * 7 + j * (1 + i % 3)) % 9] for j in range(n))


@pytest.fixture(scope="module")
def engine():
    return JaxEngine(
        registry={TINY.name: TINY}, dtype=jnp.float32, paged_kv=True, seed=3,
        decode_attention=pallas_decode_attention,  # the stacked parts path, as on the chip
    )


def test_a_stacked_paged_session_with_joins_serves_what_generate_serves(engine):
    reqs = [GenerationRequest(TINY.name, prompt(i, 131 + 9 * i), max_new_tokens=14 + 5 * i) for i in range(3)]
    reqs.append(GenerationRequest(TINY.name, prompt(5, 300), max_new_tokens=12))  # two chunks, the last padded
    alone = [engine.generate(r).tokens for r in reqs]
    mark = TRACER.seq()
    sess = engine.decode_open(reqs[:2], reserve_rows=4, slice_steps=8)
    assert sess.stacked and sess.carry["ssm"]["s"].shape == (4, 4, 8, 16, 16)
    got = {}

    def step():
        for res in sess.step():
            got[res.request.prompt] = res.tokens

    step()
    for req in reqs[2:]:
        pending = sess.join_begin(req)
        chunks = 0
        while not sess.join_step(pending):
            chunks += 1
            step()
        assert is_state_cache(pending.k_cache)
        sess.join_commit(pending)
    assert chunks == 1  # the long prompt took two chunks
    state = sess.debug_state()
    assert state["stack"] == {
        "residual_streams": 1, "layer_runs": [2, 1, 2, 1], "layer_kinds": {"ssm": 4, "attention": 2},
    }
    assert state["state"] == {
        "bytes_per_row": TINY.state_bytes_per_row(4), "rows": 4, "dtype": "float32", "conv_dtype": "float32",
        "impl": "xla-bucket",  # a state 16 wide is no lane tile: tests/test_pallas_ssm.py serves an aligned one
    }
    assert sess.state_counts == {"state_rows": 4, "state_bytes": 4 * TINY.state_bytes_per_row(4)}
    while sess.active:
        step()
    assert bool(jnp.all(jnp.isfinite(sess.carry["ssm"]["s"])))  # done rows' states stay finite
    sess.close()
    assert [got[r.prompt] for r in reqs] == alone
    installs = [s for s in TRACER.spans(since=mark) if s.name == "session.join.install"]
    assert len(installs) == 2
    for span in installs:
        assert span.attrs["programs"] == 1 and span.attrs["state_bytes"] == TINY.state_bytes_per_row(4)


def test_a_joiner_in_a_retired_rows_slot_starts_from_its_own_state(engine):
    short = GenerationRequest(TINY.name, prompt(1, 140), max_new_tokens=4)
    long = GenerationRequest(TINY.name, prompt(2, 150), max_new_tokens=40)
    joiner = GenerationRequest(TINY.name, prompt(3, 133), max_new_tokens=9)
    alone = engine.generate(joiner).tokens
    sess = engine.decode_open([short, long], reserve_rows=2, slice_steps=4)
    got = {}
    while len(got) < 1:  # until the short row retires
        for res in sess.step():
            got[res.request.prompt] = res.tokens
    assert sess.free_slots == 1
    left = np.asarray(sess.carry["ssm"]["s"][:, 0])
    assert np.abs(left).max() > 0  # the leaver's state is still in the slot
    slot = sess.join(joiner)
    assert slot == 0
    assert not np.allclose(np.asarray(sess.carry["ssm"]["s"][:, 0]), left)
    while sess.active:
        for res in sess.step():
            got[res.request.prompt] = res.tokens
    sess.close()
    assert got[joiner.prompt] == alone


def test_install_state_row_writes_one_row_and_drops_a_slot_outside_the_bucket():
    state = init_state(TINY, 3, jnp.float32)
    row = jax.tree_util.tree_map(lambda a: jnp.ones_like(a[:, :1]), state)
    new = install_state_row(state, jnp.int32(1), row)
    assert float(new["s"][:, 1].min()) == 1.0 and float(jnp.abs(new["s"][:, [0, 2]]).max()) == 0.0
    same = install_state_row(state, jnp.int32(3), row)
    assert float(jnp.abs(same["s"]).max()) == 0.0 and float(jnp.abs(same["conv"]).max()) == 0.0


def test_admission_counts_bytes_a_row_beside_bytes_a_token():
    big = dataclasses.replace(
        TINY, name="hybrid-big", d_model=4096, n_heads=32, n_kv_heads=8, d_head=128, n_layers=40,
        layer_types=PUBLISHED_TYPES, ssm_n_heads=128, ssm_d_head=64, ssm_d_state=128, ssm_chunk_size=256,
        vocab_size=100352, d_ff=1536, d_ff_expert=768, n_experts=9, router_width=72, top_k_experts=10,
        max_seq_len=131072,
    )
    eng = JaxEngine(registry={big.name: big}, dtype=jnp.bfloat16, quantize="int8", paged_kv=True)
    assert eng._state_row_bytes(big) == 36 * (128 * 64 * 128 * 4 + 3 * 8448 * 2) == big.state_bytes_per_row(2)
    with_state = eng._paged_chunk_bytes(big, [3] * 16, 32, 256, True)
    without = eng._paged_chunk_bytes(dataclasses.replace(big, layer_types=(), n_layers=4), [3] * 16, 32, 256, True)
    assert with_state - without == 32 * eng._state_row_bytes(big)
    request = GenerationRequest(big.name, "x" * 200, max_new_tokens=256)
    assert eng.max_admission_rows(request) == 32  # 153 MB a row: the floor, where 16 KB a token would admit 256
    plain = dataclasses.replace(big, name="plain-big", layer_types=(), n_layers=4)
    eng2 = JaxEngine(registry={plain.name: plain}, dtype=jnp.bfloat16, quantize="int8", paged_kv=True)
    assert eng2.max_admission_rows(GenerationRequest(plain.name, "x" * 200, max_new_tokens=256)) > 32
    assert eng._contiguous_row_bytes(big, 256, 256) == 4 * 512 * 2 * 8 * 128 * 2 + eng._state_row_bytes(big)


# -- 8. refusals, counts, and a model without layer_types -------------------------------


def refusal(**kwargs):
    eng = JaxEngine(registry={TINY.name: TINY}, dtype=jnp.float32, **kwargs)
    with pytest.raises(UnsupportedMechanism) as err:
        eng.load_model(TINY.name)
    return err.value.mechanism


@pytest.mark.parametrize("kwargs,mechanism", [
    (dict(paged_kv=True, prefix_share=True), "prefix_share"),
    (dict(prefix_cache_size=2), "prefix_share"),
    (dict(kv_quantize="int8"), "kv_quantize"),
])
def test_the_engine_refuses_by_name_at_load(kwargs, mechanism):
    assert refusal(**kwargs) == mechanism


def test_speculative_decoding_is_refused_by_name():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.speculative import DraftSpec

    eng = JaxEngine(
        registry={TINY.name: TINY}, dtype=jnp.float32, speculative={TINY.name: DraftSpec("ngram", None, 2)},
    )
    with pytest.raises(UnsupportedMechanism) as err:
        eng.load_model(TINY.name)
    assert err.value.mechanism == "speculative"


def test_preemption_and_migration_bundles_are_refused_by_name(engine):
    req = GenerationRequest(TINY.name, prompt(1, 140), max_new_tokens=20)
    sess = engine.decode_open([req], reserve_rows=2, slice_steps=4)
    sess.step()
    with pytest.raises(UnsupportedMechanism) as err:
        sess.preempt(req)
    assert err.value.mechanism == "preemption"
    with pytest.raises(UnsupportedMechanism) as err:
        sess.resume_begin(None)
    assert err.value.mechanism == "migration"
    sess.close()


def test_a_contiguous_session_is_refused_by_name():
    eng = JaxEngine(registry={TINY.name: TINY}, dtype=jnp.float32)
    with pytest.raises(UnsupportedMechanism) as err:
        eng.decode_open([GenerationRequest(TINY.name, "abc", max_new_tokens=4)])
    assert err.value.mechanism == "contiguous_session"
    with pytest.raises(UnsupportedMechanism) as err:
        eng.generate_batch([GenerationRequest(TINY.name, "abc", max_new_tokens=4)] * 2)
    assert err.value.mechanism == "contiguous_session"
    streamed = [t for chunk in eng.generate_stream(GenerationRequest(TINY.name, "abc", max_new_tokens=6)) for t in chunk.tokens]
    assert streamed == eng.generate(GenerationRequest(TINY.name, "abc", max_new_tokens=6)).tokens
    assert eng.generate(GenerationRequest(TINY.name, "abc", max_new_tokens=4)).generated_tokens == 4


def test_a_mesh_and_pipeline_stages_are_refused_by_name():
    from jax.sharding import Mesh

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.pp import _check_stages
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.sharding import param_specs

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("tp",))
    with pytest.raises(UnsupportedMechanism) as err:
        param_specs(TINY, mesh)
    assert err.value.mechanism == "mesh"
    with pytest.raises(UnsupportedMechanism) as err:
        _check_stages(TINY, Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("pp",)), "pp")
    assert err.value.mechanism == "mesh"


def test_bad_layer_types_and_sizes_are_refused():
    with pytest.raises(ValueError):
        dataclasses.replace(TINY, layer_types=TYPES[:5])
    with pytest.raises(ValueError):
        dataclasses.replace(TINY, layer_types=("mamba",) * 5 + ("window",))
    with pytest.raises(ValueError):
        dataclasses.replace(TINY, ssm_n_heads=0)
    with pytest.raises(ValueError):
        dataclasses.replace(TINY, position_embedding="alibi")
    with pytest.raises(ValueError):
        dataclasses.replace(TINY, ssm_n_groups=3)


def test_counts_by_kind_of_layer(tiny):
    cfg, params = tiny
    d, mixer = 64, 64 * (2 * 128 + 32 + 8) + 128 * 64
    attn = 2 * 64 * 64 + 2 * 64 * 32
    ffn = 3 * 64 * 48 + 64 * 8
    assert cfg.layer_matmul_params(8, mixer=MIXER_SSM) == mixer + ffn + 8 * 3 * 64 * 32
    assert cfg.layer_matmul_params(8) == attn + ffn + 8 * 3 * 64 * 32
    assert cfg.stack_matmul_params(8) == 4 * mixer + 2 * attn + 6 * (ffn + 8 * 3 * 64 * 32)
    counted = sum(int(np.prod(v.shape)) for v in params.values())
    assert cfg.params_count == counted + d - d  # embed + stack + norms + the mixers' small leaves + final norm
    assert cfg.flops_per_token(100) - cfg.flops_per_token(0) == 100 * 2 * 4 * 4 * 16
    assert cfg.flops_per_token(0) == 2 * (
        4 * mixer + 2 * attn + 6 * (ffn + 3 * 3 * 64 * 32) + 512 * 64
    ) + 6 * 4 * 128 * 16


def dense_fingerprint():
    """sha256 of the StableHLO text of a tiny dense model's prefill and batched decode."""
    cfg = get_model_config("mistral:7b").tiny()
    params = jax.eval_shape(lambda k: init_params(cfg, k, jnp.float32), jax.random.PRNGKey(0))
    sds = jax.ShapeDtypeStruct
    kc = sds((2, 2, cfg.n_kv_heads, 32, 16), jnp.float32)
    prefill = jax.jit(lambda p, t, k, v: forward(p, cfg, t, jnp.int32(0), k, v)).lower(
        params, sds((2, 16), jnp.int32), kc, kc).as_text()
    decode = jax.jit(lambda p, t, o, k, v: logits_for(p, cfg, forward(p, cfg, t, o, k, v)[0][:, 0])).lower(
        params, sds((2, 1), jnp.int32), sds((2,), jnp.int32), kc, kc).as_text()
    return cfg, params, kc, prefill, hashlib.sha256((prefill + decode).encode()).hexdigest()


def test_a_model_without_layer_types_lowers_to_todays_program():
    """The StableHLO text of a tiny dense model's prefill and batched decode,
    pinned by its sha256 as the commit before the state-space layers lowered
    it (``scripts/forward_hlo_fingerprint.py`` does the same at the cells'
    full sizes); naming every layer "attention" changes nothing either."""
    cfg, params, kc, prefill, digest = dense_fingerprint()
    named = dataclasses.replace(cfg, layer_types=("attention",) * cfg.n_layers)
    again = jax.jit(lambda p, t, k, v: forward(p, named, t, jnp.int32(0), k, v)).lower(
        params, jax.ShapeDtypeStruct((2, 16), jnp.int32), kc, kc).as_text()
    assert again == prefill
    assert digest == PARENT_DIGEST, digest


PARENT_DIGEST = "3ad646a5cce934357b7d012ca0aecaae939803b7f48cae8317e4fa504441ce7d"
