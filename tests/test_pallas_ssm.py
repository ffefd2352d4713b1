"""The state-space decode step's kernel (ops/pallas_ssm.py, interpret mode
on the CPU) against ``models/ssm.py::_step``, the fallback it shares one
recurrence with: live rows among dead ones, every entry of the record, many
steps in a row; the rule that chooses between them (``ssm_step_impl``); and
a paged session of an aligned tiny hybrid served on both."""

import dataclasses
import types
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import GenerationRequest
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import JaxEngine
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import ModelConfig
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import unpartitioned_kernels_disabled
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.ssm import SSM_LEAVES, _step, init_state, ssm_mixer, ssm_step_impl
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import init_params
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.trace import TRACER
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops import pallas_ssm
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_attention import pallas_decode_attention
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_ssm import live_rows, ssm_step_live, ssm_step_supported

LS, B, H, P, N = 3, 8, 4, 8, 128

MASKS = {
    "interleaved": [1, 0, 1, 1, 0, 0, 1, 0],
    "first-and-last-dead": [0, 1, 1, 0, 1, 1, 1, 0],
    "one-live": [0, 0, 0, 0, 0, 1, 0, 0],
    "none-live": [0] * 8,
    "all-live": [1] * 8,
}


def shape_cfg(groups=1, heads=H):
    return types.SimpleNamespace(ssm_n_heads=heads, ssm_n_groups=groups)


def operands(seed, groups=1, heads=H, batch=B):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (
        jax.random.normal(ks[0], (batch, heads, P)),
        jax.random.normal(ks[1], (batch, groups, N)),
        jax.random.normal(ks[2], (batch, groups, N)),
        jax.nn.softplus(jax.random.normal(ks[3], (batch, heads))),
        -jnp.exp(jax.random.normal(ks[4], (heads,))),
        jax.random.normal(ks[5], (heads,)),
    )


def record(seed=0, heads=H):
    return jax.random.normal(jax.random.PRNGKey(seed), (LS, B, heads, P, N))


@jax.jit
def kernel(s, at, mask, ops):
    return ssm_step_live(s, at, *live_rows(mask, s.shape[1]), *ops)


def bucket(cfg, s, at, mask, ops):
    """Today's step: the entry sliced out, ``_step`` over every row with
    the dead rows' ``dt`` zeroed, the entry written back."""
    x, bm, cm, dt, a_neg, d_skip = ops
    y, s1 = _step(cfg, s[at], x, bm, cm, jnp.where(mask[:, None], dt, 0.0), a_neg, d_skip)
    return y, s.at[at].set(s1)


@pytest.mark.parametrize("at", range(LS))
@pytest.mark.parametrize("mask", sorted(MASKS))
def test_the_kernel_is_the_step_for_the_live_rows_and_nothing_for_the_rest(mask, at):
    live = jnp.asarray(MASKS[mask], bool)
    s, ops = record(), operands(1 + at)
    y, s1 = kernel(s, at, live, ops)
    want_y, want_s = bucket(shape_cfg(), s, at, live, ops)
    dead = ~np.asarray(live)
    np.testing.assert_allclose(np.asarray(y)[~dead], np.asarray(want_y)[~dead], rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1[at])[~dead], np.asarray(want_s[at])[~dead], rtol=1e-6, atol=1e-6)
    # a dead row's state is bit for bit what it was, its y is zero, and no other entry of the record moved
    assert np.array_equal(np.asarray(s1[at])[dead], np.asarray(s[at])[dead])
    assert not np.asarray(y)[dead].any()
    for other in range(LS):
        if other != at:
            assert np.array_equal(np.asarray(s1[other]), np.asarray(s[other]))


@pytest.mark.parametrize("mask", ["interleaved", "one-live"])
def test_sixteen_steps_in_a_row_decay_no_block_twice(mask):
    """The record through sixteen steps of each entry, a row retiring on
    the way, against sixteen ``_step``s."""
    start = np.asarray(MASKS[mask], bool)
    live = start.copy()
    s = want = record(2)
    for t in range(16):
        if t == 9 and live.sum() > 1:
            live[int(np.argmax(live))] = False  # the first live row retires
        for at in range(LS):
            ops = operands(100 * t + at)
            y, s = kernel(s, at, jnp.asarray(live), ops)
            want_y, want = bucket(shape_cfg(), want, at, jnp.asarray(live), ops)
            np.testing.assert_allclose(np.asarray(y)[live], np.asarray(want_y)[live], rtol=1e-5, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(s)[:, ~start], np.asarray(record(2))[:, ~start])  # never live: never touched


@pytest.mark.parametrize("groups,heads,tile_bytes", [
    (2, 4, 1 << 20),  # two groups of two heads: a head block reads its own group's B and C
    (1, 4, 2 * P * N * 4),  # two head blocks a row
    (4, 8, P * N * 4),  # one head a grid step, two heads a group
])
def test_head_blocks_and_groups(monkeypatch, groups, heads, tile_bytes):
    monkeypatch.setattr(pallas_ssm, "STEP_STATE_BYTES", tile_bytes)
    live = jnp.asarray(MASKS["interleaved"], bool)
    s, ops = record(3, heads), operands(4, groups, heads)
    y, s1 = jax.jit(lambda s, ops: ssm_step_live(s, 1, *live_rows(live, B), *ops))(s, ops)
    want_y, want_s = bucket(shape_cfg(groups, heads), s, 1, live, ops)
    np.testing.assert_allclose(np.asarray(y), np.where(np.asarray(live)[:, None, None], want_y, 0), rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(want_s), rtol=1e-6, atol=1e-6)


def test_without_a_mask_every_row_is_live():
    rows, n = live_rows(None, 5)
    assert rows.tolist() == [0, 1, 2, 3, 4] and int(n) == 5
    rows, n = live_rows(jnp.asarray(MASKS["interleaved"], bool), B)
    assert rows.tolist()[:4] == [0, 2, 3, 6] and int(n) == 4


# -- the rule ---------------------------------------------------------------------------

ALIGNED = ModelConfig(
    name="hybrid-aligned", vocab_size=512, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2, d_head=16,
    d_ff=48, d_ff_expert=32, n_experts=8, top_k_experts=3, tie_embeddings=True, norm_eps=1e-5,
    max_seq_len=1024, layer_types=("mamba", "mamba", "attention", "mamba"), ssm_n_heads=4, ssm_d_head=8,
    ssm_d_state=128, ssm_chunk_size=8, embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=1 / 32, logits_scaling=4.0, position_embedding="none",
)


@pytest.mark.parametrize("case,change,tokens,dtype", [
    ("a chunk of tokens", {}, 8, jnp.float32),
    ("a state that is not float32", {}, 1, jnp.bfloat16),
    ("a state width off the lane tile", {"ssm_d_state": 64}, 1, jnp.float32),
    ("a head size off the sublane tile", {"ssm_d_head": 12}, 1, jnp.float32),
    ("a head's state larger than a tile", {"ssm_d_head": 2048, "ssm_d_state": 256}, 1, jnp.float32),
])
def test_each_shape_the_rule_refuses_falls_back_to_the_bucket_step(case, change, tokens, dtype):
    cfg = dataclasses.replace(ALIGNED, **change)
    state = jax.eval_shape(lambda: init_state(cfg, 4, jnp.float32))
    state["s"] = jax.ShapeDtypeStruct(state["s"].shape, dtype)
    assert ssm_step_impl(cfg, state, tokens) == "xla-bucket", case
    assert ssm_step_impl(ALIGNED, jax.eval_shape(lambda: init_state(ALIGNED, 4, jnp.float32)), 1) == "pallas-live"
    if tokens == 1 and "larger" not in case:  # ... and the mixer runs there, on the fallback
        params = init_params(dataclasses.replace(cfg, n_layers=1, layer_types=("mamba",)), jax.random.PRNGKey(0), jnp.float32)
        layer = {k: params[k][0] for k in SSM_LEAVES}
        st = jax.tree_util.tree_map(lambda a: a[0], init_state(cfg, 2, jnp.float32))
        out, st = ssm_mixer(cfg, jnp.ones((2, 1, cfg.d_model)), layer, st)
        assert out.shape == (2, 1, cfg.d_model) and bool(jnp.all(jnp.isfinite(st["s"])))


def test_a_sharded_engines_trace_falls_back_and_the_kernel_refuses_what_the_rule_refuses():
    state = init_state(ALIGNED, 4, jnp.float32)
    with unpartitioned_kernels_disabled():
        assert ssm_step_impl(ALIGNED, state, 1) == "xla-bucket"
    assert ssm_step_impl(ALIGNED, state, 1) == "pallas-live"
    assert not ssm_step_supported(jnp.zeros((2, 2, 4, 8, 64)), 1)
    with pytest.raises(ValueError, match="ssm_step_supported"):
        ssm_step_live(jnp.zeros((2, 2, 4, 8, 64)), 0, *live_rows(None, 2), *operands(0, batch=2)[:1], *operands(0)[1:])


# -- the session ------------------------------------------------------------------------


def prompt(i, n):
    return "".join("abcdefgh "[(i * 7 + j * (1 + i % 3)) % 9] for j in range(n))


def serve(impl, monkeypatch):
    """A stacked paged session of the aligned hybrid: two rows open, one
    retires early, a third joins mid-flight. Returns the tokens by prompt,
    the session's /debug/state and its ``sched.slice``-bound counts."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine import stepped
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models import transformer

    if impl == "xla-bucket":  # the rule, turned in the test: no knob does this
        for module in (stepped, transformer):
            monkeypatch.setattr(module, "ssm_step_impl", lambda *a: "xla-bucket")
    engine = JaxEngine(
        registry={ALIGNED.name: ALIGNED}, dtype=jnp.float32, paged_kv=True, seed=3,
        decode_attention=pallas_decode_attention,
    )
    reqs = [
        GenerationRequest(ALIGNED.name, prompt(1, 140), max_new_tokens=5),  # retires in the first slice
        GenerationRequest(ALIGNED.name, prompt(2, 150), max_new_tokens=30),
        GenerationRequest(ALIGNED.name, prompt(3, 133), max_new_tokens=11),  # joins mid-flight
    ]
    alone = [engine.generate(r).tokens for r in reqs]
    sess = engine.decode_open(reqs[:2], reserve_rows=4, slice_steps=8)
    got, slices = {}, []

    def step():
        for res in sess.step():
            got[res.request.prompt] = res.tokens
        # an expert model's ``moe_tokens`` is the slice's summed n_row
        slices.append({**sess.state_counts, **sess.last_slice_state, "n_row": sess.last_slice_moe["moe_tokens"]})

    step()
    pending = sess.join_begin(reqs[2])
    while not sess.join_step(pending):
        step()
    sess.join_commit(pending)
    state = sess.debug_state()
    while sess.active:
        step()
    sess.close()
    return [got[r.prompt] for r in reqs], alone, state, slices


@pytest.mark.parametrize("impl", ["pallas-live", "xla-bucket"])
def test_a_session_with_a_join_and_a_retirement_serves_what_generate_serves(impl, monkeypatch):
    served, alone, state, slices = serve(impl, monkeypatch)
    assert served == alone
    assert state["state"]["impl"] == impl and state["state"]["rows"] == 4
    assert len(slices) >= 4
    for s in slices:
        steps = 8
        assert 0 < s["state_row_steps"] <= s["state_rows"] * steps
        if impl == "pallas-live":  # the live rows' own steps, not the bucket's
            assert s["state_row_steps"] == s["n_row"] < s["state_rows"] * steps


def test_the_scheduler_puts_state_row_steps_on_every_slice_span():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import ContinuousScheduler

    engine = JaxEngine(
        registry={ALIGNED.name: ALIGNED}, dtype=jnp.float32, paged_kv=True, seed=3,
        decode_attention=pallas_decode_attention,
    )
    mark = TRACER.seq()
    sched = ContinuousScheduler(engine, max_batch=4, slice_steps=8)
    sched.start()
    reqs = [GenerationRequest(ALIGNED.name, prompt(i, 131 + 4 * i), max_new_tokens=6 + 9 * i) for i in range(3)]
    try:
        with ThreadPoolExecutor(3) as pool:
            results = list(pool.map(sched.submit, reqs))
    finally:
        sched.stop()
    assert [r.generated_tokens for r in results] == [6, 15, 24]
    spans = [s for s in TRACER.spans(since=mark) if s.name == "sched.slice"]
    assert spans
    for span in spans:
        assert 0 < span.attrs["state_row_steps"] <= span.attrs["state_rows"] * 8
        assert span.attrs["state_row_steps"] == span.attrs["moe_tokens"]
