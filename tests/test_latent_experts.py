"""The expert layer as one chip's share (held experts < router width,
identity experts, unnormalised scaled weights, grouped dispatch) and the
latent cache (one compressed row a token and attention block), at a small
size on the CPU in float32. The comparison with the plain reference lives
with the benchmark (tests/benchmark_suite/test_benchmark_longcat.py)."""

import dataclasses

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
    ModelConfig,
    get_model_config,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import (
    _activation,
    _moe_mlp,
    _moe_parts,
    _moe_route,
    expert_layer_leaves,
    init_params,
    moe_block_rows,
)

TINY = ModelConfig(
    name="latent-experts-tiny", vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=1,
    d_head=24, d_ff=128, rope_theta=1e7, norm_eps=1e-5, max_seq_len=1024,
    attention="latent", q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, mla_scale_q_lora=True, mla_scale_kv_lora=True, blocks_per_layer=2, d_ff_expert=32,
    n_experts=8, router_width=12, n_zero_experts=4, top_k_experts=3, routed_scaling_factor=6.0,
    renormalize_topk=False, router_bias=True,
)
# the published sizes of the benchmark's configuration, as the program sees its share
REAL = dataclasses.replace(
    TINY, name="longcat-flash:ep32", vocab_size=16384, d_model=6144, n_layers=6, n_heads=64, d_head=192,
    d_ff=12288, q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, d_ff_expert=2048, n_experts=16, router_width=768, n_zero_experts=256,
    top_k_experts=12, max_seq_len=131072,
)


@pytest.fixture(scope="module")
def layer():
    """The expert layer's stacked leaves and some tokens, float32."""
    params = init_params(TINY, jax.random.PRNGKey(3), jnp.float32)
    experts = {k: params[k] for k in expert_layer_leaves(TINY)}
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 9, TINY.d_model), dtype=jnp.float32)
    return experts, h


def dense_form(cfg, h, experts, li):
    """Every held expert computes every token (the form the layer had
    before it dispatched by pair), combined by the same weights."""
    hf = h.reshape(-1, h.shape[-1])
    top_i, top_w = _moe_route(cfg, hf, {k: v[li] for k, v in experts.items() if k.startswith("router")})
    combine = jnp.sum(jax.nn.one_hot(top_i, cfg.router_outputs) * top_w[..., None], axis=1)
    gate, up, down = (experts[k][li] for k in ("we_gate", "we_up", "we_down"))
    y = jnp.einsum("tef,efd->ted", _activation(cfg, jnp.einsum("td,edf->tef", hf, gate))
                   * jnp.einsum("td,edf->tef", hf, up), down)
    held = combine[:, cfg.first_expert : cfg.first_expert + cfg.n_experts]
    routed = jnp.einsum("te,ted->td", held, y)
    identity = hf * jnp.sum(combine[:, cfg.n_routed_experts :], axis=-1, keepdims=True)
    return routed.reshape(h.shape), identity.reshape(h.shape), top_i


@pytest.mark.parametrize("li", [0, 1])
def test_grouped_dispatch_equals_every_expert_for_every_token(layer, li):
    experts, h = layer
    routed, identity, counts = _moe_parts(TINY, h, experts, jnp.int32(li))
    want_routed, want_identity, top_i = dense_form(TINY, h, experts, li)
    np.testing.assert_allclose(np.asarray(routed), np.asarray(want_routed), atol=1e-5)
    np.testing.assert_allclose(np.asarray(identity), np.asarray(want_identity), atol=1e-6)
    held, zero, absent, touched, blocks = (int(c) for c in counts)
    # every pair is somewhere: tokens x top_k = held + identity + absent
    assert held + zero + absent == h.shape[0] * h.shape[1] * TINY.top_k_experts and absent == 0
    assert held == int(jnp.sum(top_i < 8)) and touched == len(set(np.asarray(top_i)[np.asarray(top_i) < 8]))
    per_expert = np.bincount(np.asarray(top_i)[np.asarray(top_i) < 8], minlength=8)
    assert blocks == int(np.sum(-(-per_expert // moe_block_rows(TINY, h.shape[0] * h.shape[1])))) >= touched


@pytest.mark.parametrize("cfg,tokens,rows", [
    (REAL, 16, 8),  # a decode step: 0.25 pairs an expert
    (REAL, 256, 8),  # the join chunk: 4 pairs an expert, ~64 on the 16 held: never 4,096 rows
    (REAL, 16 * 256, 64),  # a first fleet's grouped prefill
    (get_model_config("mixtral:8x7b"), 16, 8),
    (get_model_config("mixtral:8x7b"), 256, 64),  # 8 experts read once or twice, not once per 8 pairs
    (get_model_config("mixtral:8x7b"), 4096, 128),  # the most a block holds
])
def test_a_block_is_sized_from_the_pairs_an_expert_expects(cfg, tokens, rows):
    assert moe_block_rows(cfg, tokens) == rows


def test_grouped_dispatch_in_wide_blocks_equals_every_expert_for_every_token(layer):
    """128 tokens over 12 router outputs, top-3: 32 pairs an expert, blocks of 32 rows."""
    experts, _ = layer
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 64, TINY.d_model), dtype=jnp.float32)
    assert moe_block_rows(TINY, 128) == 32
    routed, identity, counts = _moe_parts(TINY, h, experts, jnp.int32(1))
    want_routed, want_identity, top_i = dense_form(TINY, h, experts, 1)
    np.testing.assert_allclose(np.asarray(routed), np.asarray(want_routed), atol=1e-5)
    np.testing.assert_allclose(np.asarray(identity), np.asarray(want_identity), atol=1e-6)
    assert int(counts[0]) == int(jnp.sum(top_i < 8)) and int(counts[3]) == 8


def test_the_shares_add_up(layer):
    """4 chips hold 2 experts each: their routed parts, with the identity
    part that every chip computes alike counted once, are the uncut layer."""
    experts, h = layer
    whole, _ = _moe_mlp(TINY, h, experts, jnp.int32(1))
    total = jnp.zeros_like(whole)
    absent = []
    for share in range(4):
        cfg = dataclasses.replace(TINY, n_experts=2, first_expert=2 * share)
        held = {k: (v[:, 2 * share : 2 * share + 2] if k.startswith("we_") else v) for k, v in experts.items()}
        routed, identity, counts = _moe_parts(cfg, h, held, jnp.int32(1))
        total = total + routed
        absent.append(int(counts[2]))
    np.testing.assert_allclose(np.asarray(total + identity), np.asarray(whole), atol=1e-5)
    assert all(a > 0 for a in absent)  # each share leaves the others' pairs out


def test_a_token_that_chooses_identity_experts_only_gets_h_times_its_weights(layer):
    experts, h = layer
    pushed = {**experts, "router": jnp.zeros_like(experts["router"]),
              "router_bias": jnp.zeros_like(experts["router_bias"]).at[:, TINY.n_routed_experts :].set(1.0)}
    out, counts = _moe_mlp(TINY, h, pushed, jnp.int32(0))
    # uniform softmax over 12 outputs, the bias picks three identity experts: w = 6 / 12 each
    np.testing.assert_array_equal(np.asarray(out), np.asarray(h * jnp.float32(3 * 6.0 / 12)))
    assert [int(c) for c in counts] == [0, h.shape[0] * h.shape[1] * 3, 0, 0, 0]


def test_the_bias_moves_the_choice_and_not_the_weights(layer):
    experts, h = layer
    hf = h.reshape(-1, h.shape[-1])
    view = {k: v[0] for k, v in experts.items() if k.startswith("router")}
    _, plain_w = _moe_route(TINY, hf, {**view, "router_bias": jnp.zeros_like(view["router_bias"])})
    biased = {**view, "router_bias": jnp.zeros_like(view["router_bias"]).at[5].set(10.0)}
    top_i, top_w = _moe_route(TINY, hf, biased)
    assert bool(jnp.all(jnp.any(top_i == 5, axis=-1)))  # every token now chooses output 5 ...
    probs = jax.nn.softmax(hf @ view["router"], axis=-1)
    np.testing.assert_allclose(  # ... at 6 x its own probability, bias or no bias
        np.asarray(jnp.sum(jnp.where(top_i == 5, top_w, 0.0), axis=-1)), np.asarray(6.0 * probs[:, 5]), rtol=1e-6)
    assert float(jnp.max(jnp.abs(jnp.sum(plain_w, axis=-1) - 1.0))) > 0.1  # not renormalised: no sum to one


def test_the_mixtral_parametrisation_still_renormalises():
    cfg = dataclasses.replace(get_model_config("mixtral:8x7b").tiny(), n_experts=4, top_k_experts=2)
    assert cfg.router_outputs == 4 and cfg.n_zero_experts == 0 and cfg.renormalize_topk
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    hf = jax.random.normal(jax.random.PRNGKey(1), (7, cfg.d_model), dtype=jnp.float32)
    _, top_w = _moe_route(cfg, hf, {"router": params["router"][0]})
    np.testing.assert_allclose(np.asarray(jnp.sum(top_w, axis=-1)), 1.0, rtol=1e-6)
    assert expert_layer_leaves(cfg) == ("w_gate", "w_up", "w_down", "router")


def test_masked_tokens_route_nowhere(layer):
    experts, h = layer
    mask = jnp.zeros(h.shape[:2], dtype=bool).at[0, :4].set(True)
    routed, identity, counts = _moe_parts(TINY, h, experts, jnp.int32(0), mask)
    assert int(jnp.sum(counts[:3])) == 4 * TINY.top_k_experts
    off = ~np.asarray(mask)
    assert not np.asarray(routed)[off].any() and not np.asarray(identity)[off].any()


def test_cache_properties_and_counts_of_the_published_sizes():
    assert REAL.kv_values_per_token == 576 and REAL.cache_layers == 12 and REAL.cache_heads == 1
    assert (REAL.cache_k_width, REAL.cache_v_width) == (576, 0)
    mistral = get_model_config("mistral:7b")
    assert mistral.kv_values_per_token == 2 * 8 * 128 and mistral.cache_layers == 32
    # a double layer outside its experts 639M, one routed expert 37.75M (ISSUE 28's reckoning)
    outside = REAL.layer_matmul_params(0)
    assert outside == pytest.approx(638.9e6, rel=1e-3)
    assert REAL.layer_matmul_params(1) - outside == 3 * 6144 * 2048
    assert REAL.params_count == pytest.approx(6 * (outside + 16 * 37.75e6) + 2 * 16384 * 6144, rel=1e-3)
    # a token uses 12 x 16 / 768 of a held expert, and reads 576 + 512 values of every cached row a head
    assert REAL.active_experts_per_token == pytest.approx(0.25)
    per_ctx = REAL.flops_per_token(1001) - REAL.flops_per_token(1000)
    assert per_ctx == 12 * 2 * 64 * (576 + 512)
    assert REAL.flops_per_token(0) == pytest.approx(2 * (6 * (outside + 0.25 * 37.75e6) + 16384 * 6144), rel=1e-3)


def test_modelled_bytes_read_the_cache_properties():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.memory import (
        decode_kv_stream_bytes,
        decode_weight_stream_bytes,
        estimate_weight_bytes,
    )

    assert decode_kv_stream_bytes(REAL, 1) == 12 * 576 * 2 == 13824
    assert decode_kv_stream_bytes(get_model_config("phi3:3.8b"), 1) == 393216
    stored = estimate_weight_bytes(REAL, "int8")
    assert stored == pytest.approx(7.66e9, rel=0.01)
    streamed = decode_weight_stream_bytes(REAL, "int8")
    # everything outside the experts, a quarter of an expert a layer, the head once
    assert streamed == pytest.approx(6 * (638.9e6 + 0.25 * 37.75e6) + 16384 * 6144, rel=0.01)


def _engine(**kw):
    return JaxEngine(registry={TINY.name: TINY}, dtype=jnp.float32, **kw)


@pytest.mark.parametrize("mechanism,kwargs", [
    ("kv_quantize", {"kv_quantize": "int8"}),
    ("prefix_share", {"paged_kv": True, "prefix_share": True}),
    ("speculative", {"speculative": {TINY.name: ("ngram", 4)}}),
    ("speculative", {"speculative": {"default": ("ngram", 2)}}),
])
def test_load_refuses_by_name(mechanism, kwargs):
    with pytest.raises(UnsupportedMechanism, match=mechanism) as err:
        _engine(**kwargs).load_model(TINY.name)
    assert err.value.mechanism == mechanism and err.value.model == TINY.name


def test_a_mesh_refuses_by_name():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.mesh import MeshSpec, build_mesh
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.sharding import param_specs
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.tp import TensorParallelEngine

    mesh = build_mesh(MeshSpec.tp_only(2), jax.devices()[:2])
    with pytest.raises(UnsupportedMechanism, match="mesh"):
        param_specs(TINY, mesh)
    with pytest.raises(UnsupportedMechanism, match="mesh"):
        TensorParallelEngine(mesh=mesh, registry={TINY.name: TINY}, dtype=jnp.float32).load_model(TINY.name)


@pytest.fixture(scope="module")
def session():
    eng = _engine(paged_kv=True, quantize="int8", seed=1)
    reqs = [GenerationRequest(TINY.name, "abc " * (33 + i), max_new_tokens=12) for i in range(2)]
    sess = eng.decode_open(reqs, reserve_rows=4, slice_steps=4)
    yield sess, reqs
    sess.close()


def test_the_pool_holds_one_row_a_token_and_block(session):
    sess, _ = session
    pool_k, pool_v = sess.carry["pool_k"], sess.carry["pool_v"]
    assert sess.debug_state()["attention"]["impl"] == "xla-pool" and sess.stacked
    # 24 values (16 + 8) a row, stored 128 lanes wide in the stacked pool; no second pool for values
    per_token = (pool_k.nbytes + pool_v.nbytes) / sess.pool.n_pages / sess.page_size
    assert per_token == TINY.cache_layers * 128 * 4 and pool_v.nbytes == 0
    assert pool_k.shape == (4, sess.pool.n_pages, 1, 128, 128)
    assert sess.carry["side_k"].shape[-1] == 24 and sess.carry["side_v"].shape[-1] == 0
    assert sess.pool.payload_nbytes() == pool_k.nbytes
    # what admission reckons is what the session holds
    reckoned = sess.engine._paged_chunk_bytes(TINY, [sess.pool.n_pages - 2], 4, sess.g_bucket, True)
    assert reckoned == pool_k.nbytes + sess.carry["side_k"].nbytes


def test_a_session_refuses_bundles_by_name(session):
    sess, reqs = session
    with pytest.raises(UnsupportedMechanism, match="preemption"):
        sess.preempt(reqs[0], policy="swap")
    with pytest.raises(UnsupportedMechanism, match="migration"):
        sess.resume_begin(None)
    assert sess.active == 2  # both rows still run
