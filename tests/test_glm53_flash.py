"""GLM-5.3-Flash's layers on the served path, at a tiny size in float32:
Kimi Delta Attention (its chunked form against the recurrence, ragged ends,
padded positions, the live-row kernel against the step), sparse latent
attention with no rotary part behind a lightning indexer (its selection
against the plain reference's, below, at and past its reach, with the tail
block), four residual streams, a clamped SwiGLU, one chip's share of the
experts beside the shared expert, and the whole stack against the family's
plain reference (``benchmark/families/glm53_flash.py``) through prefill and
decode, with one test each that a fault the comparison must see is seen:
bfloat16 in place of float32, the decay, beta, the tail block, the clamp.
Then the stepped paged session (joins beside resident rows, the joiner's
state installed), the ``sched.slice`` index counters, real and fake, and
the refusals by name."""

import dataclasses
import json
import math
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import glm53_flash as fam
from benchmark.lib.system import model_config
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
    GenerationRequest,
    UnsupportedMechanism,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.fake import FakeBackend
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import JaxEngine
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models import kda, transformer
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import MIXER_ATTENTION, MIXER_KDA
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import quantize_leaf
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import (
    NON_LAYER_LEAVES,
    Transformer,
    forward,
    init_params,
    run_blocks,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.trace import TRACER
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_kda import kda_step_live
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_ssm import live_rows

ROOT = Path(__file__).resolve().parents[1]
CFG = json.loads((ROOT / "benchmark" / "configs" / "glm53-flash-ep8-pp5.json").read_text())
# the rehearsal's preset: 1 leading dense layer, then KDA, KDA, KDA, DSA, KDA, KDA, DSA... (7 layers), 4 of
# 16 experts held + 1 shared, 4 residual streams, an indexer of 2 heads x 8 keeping 4 blocks of 4
TINY = {**CFG, **CFG["dry"], "model": "glm53:tiny", "max_position_embeddings": 1024}
MC = model_config(TINY)
# float32 activations at this size: the program and the reference agree to ~3e-6 (summation order,
# the absorbed against the expanded latent form, the chunked against the token-by-token KDA scan)
TOL = 1e-4


def params_of(cfg_dict, dtype=jnp.float32, seed=11):
    mc = model_config(cfg_dict)
    return mc, jax.jit(lambda k: init_params(mc, k, dtype, post=lambda n, l: quantize_leaf(n, l, "int8")))(
        jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def tiny():
    mc, params = params_of(TINY)
    return mc, params, fam.make_weights(TINY, 11)


def head32(params, hidden):
    """The head in float32 (the program's own multiplies a quantized head in bfloat16)."""
    w = params["lm_head"]["q"].astype(jnp.float32) * params["lm_head"]["s"]
    return hidden.astype(jnp.float32) @ w


def program_logits(mc, params, tokens, split):
    """Float32 logits at every position: a prefill of ``split`` tokens (in a
    bucket padded past them, the pads masked) and one-token decode steps."""
    t = tokens.shape[1]
    k0, v0 = Transformer(cfg=mc, params=params).init_cache(1, t, params["final_norm"].dtype)
    pad = -(-split // 16) * 16
    first = jnp.zeros((1, pad), jnp.int32).at[:, :split].set(tokens[:, :split])
    hidden, kc, vc = jax.jit(
        lambda x, k, v: forward(params, mc, x, jnp.int32(0), k, v, token_mask=jnp.arange(pad)[None] < split)
    )(first, k0, v0)
    out = [head32(params, hidden[0, :split])]
    step = jax.jit(lambda x, p, k, v: forward(params, mc, x, p, k, v))
    for p in range(split, t):
        hidden, kc, vc = step(tokens[:, p : p + 1], jnp.int32(p), kc, vc)
        out.append(head32(params, hidden[0]))
    return jnp.concatenate(out)


TOKENS = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 3, 259)


def reference_logits(cfg_dict, weights):
    return fam.served_logits(cfg_dict, weights, [[int(t) for t in TOKENS[0]]], [(0, 64)])[0]


@pytest.fixture(scope="module")
def reference(tiny):
    return reference_logits(TINY, tiny[2])


# -- the whole stack and each mixer against the family's reference -----------------------

def test_the_program_makes_the_references_weights_leaf_for_leaf(tiny):
    _, params, weights = tiny
    assert set(weights) == {k for k in params if not k.endswith("norm")}
    for name, leaf in weights.items():
        for a, b in zip(jax.tree_util.tree_leaves(leaf), jax.tree_util.tree_leaves(params[name])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    assert weights["kda_in"]["q"].shape == (5, 64, 3 * 32 + 2 + 2 * 16) and weights["idx_wq"]["q"].shape == (2, 32, 16)
    assert weights["we_gate"]["q"].shape == (6, 4, 64, 32) and weights["router"].shape == (6, 64, 16)


def test_prefill_then_decode_match_the_reference_with_the_selection_binding(tiny, reference):
    mc, params, _ = tiny
    assert mc.layer_runs == ((True, 0, 1), (False, 1, 2), (False, 3, 1), (False, 4, 2), (False, 6, 1))
    assert 64 // mc.index_kpool > mc.index_topk  # 16 blocks at the end, 4 kept
    got = program_logits(mc, params, TOKENS, 40)
    assert float(jnp.max(jnp.abs(got - reference))) <= TOL


@pytest.mark.parametrize("kind", ["linear_attention", "deepseek_sparse_attention"])
def test_each_mixer_alone_matches_the_reference(kind):
    """A stack of one kind of mixer (a leading dense layer, then expert layers)."""
    types = [kind] * 3
    one = {**TINY, "num_hidden_layers": 3, "layer_types": types, "mlp_layer_types": ["dense", "sparse", "sparse"],
           "linear_attn_config": {**TINY["linear_attn_config"],
                                  "kda_layers": [0, 1, 2] if kind == "linear_attention" else []}}
    mc, params = params_of(one)
    assert (mc.state_layers, mc.attention_layers) == ((3, 0) if kind == "linear_attention" else (0, 3))
    got = program_logits(mc, params, TOKENS, 33)
    want = reference_logits(one, fam.make_weights(one, 11))
    assert float(jnp.max(jnp.abs(got - want))) <= TOL


# the faults on a shorter stack (a dense KDA layer, an expert KDA layer, an expert DSA layer), in one
# prefill of the 64 tokens (the chunked KDA form, the selection binding from position 20 on)
SHORT = {**TINY, "num_hidden_layers": 3, "layer_types": TINY["layer_types"][:2] + ["deepseek_sparse_attention"],
         "linear_attn_config": {**TINY["linear_attn_config"], "kda_layers": [0, 1]}}


@pytest.fixture(scope="module")
def short():
    mc, params = params_of(SHORT)
    return mc, params, reference_logits(SHORT, fam.make_weights(SHORT, 11))


def gap(mc, params, reference):
    """Widest difference of the program's logits from ``reference`` over one prefill of the 64 tokens."""
    k0, v0 = Transformer(cfg=mc, params=params).init_cache(1, 64, params["final_norm"].dtype)
    hidden = jax.jit(lambda x, k, v: forward(params, mc, x, jnp.int32(0), k, v)[0])(TOKENS, k0, v0)
    return float(jnp.max(jnp.abs(head32(params, hidden[0]) - reference)))


def test_the_short_stack_matches_and_a_program_in_bfloat16_fails_the_float32_tolerance(short):
    mc, params, reference = short
    assert gap(mc, params, reference) <= TOL
    mc, params = params_of(SHORT, jnp.bfloat16)
    assert not gap(mc, params, reference) <= TOL


@pytest.mark.parametrize("fault", ["decay", "beta"])
def test_a_kda_without_its_decay_or_its_beta_fails(short, monkeypatch, fault):
    mc, params, reference = short

    def faulty(form):
        def run(*args):
            *head, g, beta = args
            return form(*head, jnp.zeros_like(g) if fault == "decay" else g,
                        jnp.ones_like(beta) if fault == "beta" else beta)
        return run

    monkeypatch.setattr(kda, "_step", faulty(kda._step))
    monkeypatch.setattr(kda, "_chunked", faulty(kda._chunked))
    assert not gap(mc, params, reference) <= TOL


def test_a_selection_without_the_tail_block_fails(short, monkeypatch):
    mc, params, reference = short
    real = transformer._index_keep

    def no_tail(cfg, index, keys, qpos):
        keep = real(cfg, index, keys, qpos)
        pos = jnp.arange(keys.shape[1])
        return keep & ((pos // cfg.index_kpool)[None, None, :] != (qpos // cfg.index_kpool)[..., None])

    monkeypatch.setattr(transformer, "_index_keep", no_tail)
    assert not gap(mc, params, reference) <= TOL


def test_a_swiglu_without_its_clamp_fails():
    """At this size the FFNs' inputs stay well inside 10, so the clamp is
    set where it binds (0.5) in program and reference alike; the program
    then leaves it out."""
    tight = {**SHORT, "swiglu_limit": 0.5}
    mc, params = params_of(tight)
    want = reference_logits(tight, fam.make_weights(tight, 11))
    assert gap(mc, params, want) <= TOL
    assert not gap(dataclasses.replace(mc, swiglu_limit=0.0), params, want) <= TOL


# -- Kimi Delta Attention's two forms --------------------------------------------------

def kda_operands(length, batch=2, heads=2, dk=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = kda._l2(jax.random.normal(ks[0], (batch, length, heads, dk))) / math.sqrt(dk)
    k = kda._l2(jax.random.normal(ks[1], (batch, length, heads, dk)))
    v = jax.random.normal(ks[2], (batch, length, heads, dk))
    g = -5.0 * jax.nn.sigmoid(3 * jax.random.normal(ks[3], (batch, length, heads, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, length, heads)))
    s0 = jax.random.normal(ks[5], (batch, heads, dk, dk))
    return s0, q, k, v, g, beta


def recurrence(s0, q, k, v, g, beta):
    """``S = (I - beta k k^T) Diag(exp g) S + beta k v^T``, ``o = S^T q``, token by token."""
    eye = jnp.eye(q.shape[-1])
    outs, s = [], s0
    for t in range(q.shape[1]):
        kt, bt = k[:, t], beta[:, t]
        keep = eye - bt[..., None, None] * kt[..., :, None] * kt[..., None, :]
        s = jnp.einsum("bhij,bhjv->bhiv", keep, jnp.exp(g[:, t])[..., None] * s,
                       precision="highest") + bt[..., None, None] * kt[..., :, None] * v[:, t][..., None, :]
        outs.append(jnp.einsum("bhkv,bhk->bhv", s, q[:, t], precision="highest"))
    return jnp.stack(outs, axis=1), s


@pytest.mark.parametrize("length", [1, 5, 16, 17, 33, 40])
def test_the_chunked_form_equals_the_recurrence_with_ragged_ends(length):
    ops = kda_operands(length)
    want_o, want_s = recurrence(*ops)
    assert kda.kda_chunk(MC) == 16
    o, s = kda._chunked(MC, *ops)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=2e-5, rtol=1e-4)
    o1, s1 = kda._step(ops[0], *(t[:, 0] for t in ops[1:]))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(want_o[:, 0]), atol=2e-5)


def test_two_halves_with_the_state_handed_over_equal_the_chunk_whole():
    s0, *rest = kda_operands(40)
    o, s = kda._chunked(MC, s0, *rest)
    o1, s1 = kda._chunked(MC, s0, *(t[:, :23] for t in rest))
    o2, s2 = kda._chunked(MC, s1, *(t[:, 23:] for t in rest))
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 1)), np.asarray(o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s), atol=2e-5)


@pytest.mark.parametrize("real", [1, 3, 9, 16])
def test_padded_positions_leave_the_state_and_the_tail_where_the_last_real_token_left_them(tiny, real):
    mc, params, _ = tiny
    layer = {k: jax.tree_util.tree_map(lambda a: a[1], params[k]) for k in kda.KDA_LEAVES}
    u = jax.random.normal(jax.random.PRNGKey(4), (1, 16, mc.d_model))
    st0 = {"s": jnp.zeros((1, *mc.state_dims)), "conv": jnp.zeros((1, mc.kda_d_conv - 1, 3 * mc.kda_d_inner))}
    _, want = kda.kda_mixer(mc, u[:, :real], layer, st0)
    _, got = kda.kda_mixer(mc, u, layer, st0, token_mask=jnp.arange(16)[None] < real)
    for leaf in ("s", "conv"):
        np.testing.assert_allclose(np.asarray(got[leaf]), np.asarray(want[leaf]), atol=1e-5, err_msg=leaf)


def test_the_live_row_kernel_steps_the_live_rows_where_they_lie_and_no_other():
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    ls, b, h, d = 3, 5, 4, 128
    s = jax.random.normal(ks[0], (ls, b, h, d, d))
    q, k = (kda._l2(jax.random.normal(ks[i], (b, h, d))) for i in (1, 2))
    v = jax.random.normal(ks[3], (b, h, d))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (b, h)))
    mask = jnp.array([True, False, True, True, False])
    o, s_new = kda_step_live(s, 1, *live_rows(mask, b), q, k, v, g, beta)
    want_o, want_s = kda._step(s[1], q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(o), np.asarray(jnp.where(mask[:, None, None], want_o, 0.0)), atol=1e-5)
    want = s.at[1].set(jnp.where(mask[:, None, None, None], want_s, s[1]))
    np.testing.assert_allclose(np.asarray(s_new), np.asarray(want), atol=1e-5)
    assert kda.kda_step_impl(MC, {"s": s}, 1) == "pallas-live"
    assert kda.kda_step_impl(MC, {"s": s}, 2) == "xla-bucket"
    assert kda.kda_step_impl(MC, {"s": s[..., :16, :16]}, 1) == "xla-bucket"


# -- the lightning indexer's selection --------------------------------------------------

@pytest.mark.parametrize("length", [12, 16, 20, 48], ids=["below", "at", "past", "far-past"])
def test_the_selection_equals_the_references_below_at_and_past_its_reach(length):
    """4 blocks kept: 12 tokens hold 3 whole blocks at most, 16 hold 4 with
    the last query's own block its tail, 20 and 48 more than 4."""
    ks = jax.random.split(jax.random.PRNGKey(length), 3)
    iq = jax.random.normal(ks[0], (length, 2, 8))
    iw = jax.random.normal(ks[1], (length, 2))
    ik = jax.random.normal(ks[2], (length, 8))
    qpos = jnp.arange(length)[None]
    got = transformer._index_keep(MC, (iq[None], iw[None]), ik[None], qpos)[0]
    want = fam.index_keep(iq, iw, ik, 4, 4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    t = np.arange(length)
    causal = t[None, :] <= t[:, None]
    tail = (t[None, :] // 4) == (t[:, None] // 4)
    keep = np.asarray(got)
    assert (keep <= causal).all() and (keep[tail & causal]).all()  # causal; the tail block always
    kept_blocks = [len(set(t[keep[i]] // 4) - {i // 4}) for i in range(length)]
    # at most 4 whole blocks: all of them; past that 4, or more where scores tie at the 4th (relu's zeros)
    assert all(n == i // 4 if i // 4 <= 4 else n >= 4 for i, n in enumerate(kept_blocks))
    assert (keep == causal).all() == (length <= 20)  # every block kept while a query has at most 4 whole


# -- one chip's share of the expert layer ----------------------------------------------

def test_two_shares_and_the_shared_expert_once_equal_the_uncut_layer(tiny):
    """An expert layer's stream: the uncut layer (all 8 routed experts held)
    against what two chips' shares (experts 0-3, 4-7) add, the mixer and
    the shared expert counted once (four residual streams, so the stream
    maps are common too)."""
    mc, params, _ = tiny
    two = dataclasses.replace(mc, n_layers=2, layer_types=mc.layer_types[:2], router_width=8)
    def take(leaf, *at):
        return jax.tree_util.tree_map(lambda a: a[at], leaf)

    stacked = {}
    for k, v in params.items():
        if k in ("w_gate", "w_up", "w_down") or k.startswith(("ws_", "we_", "router")):
            stacked[k] = take(v, slice(0, 1))  # the dense layer's FFN, the first expert layer's
        elif k not in NON_LAYER_LEAVES and not k.startswith(("w_q", "w_kv", "wo", "idx_", "q_a", "kv_a")):
            stacked[k] = take(v, slice(0, 2))  # two KDA layers, their norms and stream maps
    stacked["router"] = stacked["router"][..., :8]
    stacked["router_bias"] = stacked["router_bias"][..., :8]
    x0 = jax.random.normal(jax.random.PRNGKey(9), (2, 24, 4, mc.d_model), jnp.float32)

    def stream(c, experts):
        k0, v0 = Transformer(c, params).init_cache(2, 24, jnp.float32)
        return run_blocks({**stacked, **experts}, c, x0, jnp.int32(0), k0, v0, None, None)[0]

    full = {  # 8 routed experts: the 4 held here, again
        k: jax.tree_util.tree_map(lambda a: jnp.concatenate([a, a], axis=1), stacked[k])
        for k in ("we_gate", "we_up", "we_down")
    }
    whole = stream(dataclasses.replace(two, n_experts=8), full)
    shares = [
        stream(dataclasses.replace(two, first_expert=f), {k: take(v, slice(None), slice(f, f + 4))
                                                          for k, v in full.items()})
        for f in (0, 4)
    ]
    without = stream(two, {k: jax.tree_util.tree_map(jnp.zeros_like, stacked[k]) for k in full})
    np.testing.assert_allclose(np.asarray(shares[0] + shares[1] - without), np.asarray(whole), atol=1e-4)
    assert float(jnp.max(jnp.abs(whole - without))) > 1e-3  # the experts add something


# -- the session ------------------------------------------------------------------------

def prompt(i, n):
    """Seeded random text: a periodic prompt makes blocks whose pooled index
    keys nearly repeat, and near ties among their scores, which bfloat16 and
    float32 may break apart differently."""
    rng = np.random.default_rng(i)
    return "".join(rng.choice(list("abcdefghijklmnopqrstuvwxyz "), n))


@pytest.fixture(scope="module")
def engine():
    return JaxEngine(registry={MC.name: MC}, dtype=jnp.float32, paged_kv=True, seed=11)


def test_joins_beside_resident_rows_serve_the_references_choice_and_count_the_index_blocks(engine, tiny, monkeypatch):
    """Two rows open; one retires; a two-chunk prompt (256 + a padded 64)
    joins into its slot beside the resident row, then a short one. Every
    served token is the float32 reference's own greedy choice (the joiners'
    states installed, not the slot's last owner's), and each slice's index
    counters are the live rows' blocks at the positions the device held,
    over the 2 DSA layers."""
    from benchmark.lib.traffic import token_ids
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine import stepped

    counted = []
    real = stepped.SteppedDecodeSession._index_block_counts

    def checked(self, live, ran):
        got = real(self, live, ran)
        counted.append((got, live, ran))
        return got

    monkeypatch.setattr(stepped.SteppedDecodeSession, "_index_block_counts", checked)
    reqs = [GenerationRequest(MC.name, prompt(0, 131), max_new_tokens=6, temperature=0.0),  # tables 4 pages wide
            GenerationRequest(MC.name, prompt(1, 140), max_new_tokens=40, temperature=0.0),
            GenerationRequest(MC.name, prompt(5, 290), max_new_tokens=12, temperature=0.0),
            GenerationRequest(MC.name, prompt(6, 105), max_new_tokens=9, temperature=0.0)]
    mark = TRACER.seq()
    sess = engine.decode_open(reqs[:2], reserve_rows=2, slice_steps=8)
    assert sess.stacked and sess.carry["ssm"]["s"].shape == (5, 2, 2, 16, 16)
    got = {}

    def step():
        pos = np.asarray(sess.carry["offsets"])
        for res in sess.step():
            got[res.request.prompt] = res.tokens
        counts, live, ran = counted[-1]
        steps = [pos[r] + i for r, n in zip(live, ran) for i in range(n)]
        assert counts == {"index_blocks_seen": 2 * sum(t // 4 + 1 for t in steps),
                          "index_blocks_kept": 2 * sum(min(t // 4, 4) + 1 for t in steps)}

    step()
    left = None
    for req in reqs[2:]:
        while sess.free_slots == 0:
            step()
        left = np.asarray(sess.carry["ssm"]["s"]) if left is None else left
        pending = sess.join_begin(req)
        while not sess.join_step(pending):
            step()
        sess.join_commit(pending)
    assert np.abs(left).max() > 0  # a leaver's state was in the slot the joiner took
    state = sess.debug_state()
    assert state["stack"] == {
        "residual_streams": 4, "layer_runs": [1, 2, 1, 2, 1], "layer_kinds": {"kda": 5, "sparse_latent": 2},
    }
    assert state["state"]["impl"] == "xla-bucket" and state["state"]["rows"] == 2  # 16 wide: no lane tile
    assert state["state"]["bytes_per_row"] == MC.state_bytes_per_row(4)
    assert state["attention"]["index"] == {"topk": 4, "kpool": 4} and state["attention"]["impl"] == "xla-pool"
    while sess.active:
        step()
    sess.close()
    assert any(c["index_blocks_kept"] < c["index_blocks_seen"] for c, _, _ in counted)  # the selection binds
    installs = [s for s in TRACER.spans(since=mark) if s.name == "session.join.install"]
    assert len(installs) == 2 and all(s.attrs["state_bytes"] == MC.state_bytes_per_row(4) for s in installs)
    rows = [token_ids(r.prompt) + list(got[r.prompt]) for r in reqs]
    spans = [(len(token_ids(r.prompt)) - 1, len(got[r.prompt])) for r in reqs]
    assert [len(got[r.prompt]) for r in reqs] == [r.max_new_tokens for r in reqs]
    for logits, r in zip(fam.served_logits(TINY, tiny[2], rows, spans), reqs):
        served = jnp.asarray(got[r.prompt])
        gap_ = jnp.max(logits, -1) - jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
        # the served head multiplies in bfloat16 (int8 codes dequantized): logits ~N(0, 1) here move
        # by up to ~0.01, so a near tie among the best two may go the other way (the rehearsal's limit)
        assert float(jnp.max(gap_)) <= TINY["check"]["max_logit_gap"], r.prompt[:12]


def test_the_scheduler_puts_the_index_counters_on_every_slice_span_real_and_fake(engine):
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import ContinuousScheduler

    for backend, model in ((engine, MC.name), (FakeBackend(), "m")):
        mark = TRACER.seq()
        sched = ContinuousScheduler(backend, max_batch=2, slice_steps=8)
        sched.start()
        reqs = [GenerationRequest(model, prompt(i, 100), max_new_tokens=12 + i, stop_at_eos=False) for i in range(2)]
        try:
            with ThreadPoolExecutor(2) as pool:
                list(pool.map(sched.submit, reqs))
        finally:
            sched.stop()
        spans = [s for s in TRACER.spans(since=mark) if s.name == "sched.slice"]
        assert spans
        for span in spans:
            kept, blocks = span.attrs["index_blocks_kept"], span.attrs["index_blocks_seen"]
            assert (kept == blocks == 0) if model == "m" else (0 < kept < blocks)


# -- refusals by name, and the config's own checks --------------------------------------

def refusal(**kwargs):
    eng = JaxEngine(registry={MC.name: MC}, dtype=jnp.float32, **kwargs)
    with pytest.raises(UnsupportedMechanism) as err:
        eng.load_model(MC.name)
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

    eng = JaxEngine(registry={MC.name: MC}, dtype=jnp.float32, speculative={MC.name: DraftSpec("ngram", None, 2)})
    with pytest.raises(UnsupportedMechanism) as err:
        eng.load_model(MC.name)
    assert err.value.mechanism == "speculative"


def test_preemption_migration_and_a_contiguous_session_are_refused_by_name(engine):
    req = GenerationRequest(MC.name, prompt(1, 100), max_new_tokens=20)
    sess = engine.decode_open([req], reserve_rows=2, slice_steps=8)
    sess.step()
    with pytest.raises(UnsupportedMechanism) as err:
        sess.preempt(req)
    assert err.value.mechanism == "preemption"
    with pytest.raises(UnsupportedMechanism) as err:
        sess.resume_begin(None)
    assert err.value.mechanism == "migration"
    sess.close()
    with pytest.raises(UnsupportedMechanism) as err:
        JaxEngine(registry={MC.name: MC}, dtype=jnp.float32).decode_open([req])
    assert err.value.mechanism == "contiguous_session"


def test_a_mesh_and_pipeline_stages_are_refused_by_name():
    from jax.sharding import Mesh

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.pp import _check_stages
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.sharding import param_specs

    with pytest.raises(UnsupportedMechanism) as err:
        param_specs(MC, Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("tp",)))
    assert err.value.mechanism == "mesh"
    with pytest.raises(UnsupportedMechanism) as err:
        _check_stages(MC, Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("pp",)), "pp")
    assert err.value.mechanism == "mesh"


def test_bad_indexer_and_kda_settings_are_refused():
    with pytest.raises(ValueError):  # an indexer needs latent attention
        dataclasses.replace(MC, attention="mha", index_topk=4)
    with pytest.raises(ValueError):  # sparse attention layers are an indexer's
        dataclasses.replace(MC, index_topk=0)
    with pytest.raises(ValueError):
        dataclasses.replace(MC, kda_lower_bound=0.0)
    with pytest.raises(ValueError):  # NoPE latent attention needs no rotary position embedding
        dataclasses.replace(MC, position_embedding="rope")
    assert MC.mixer_kind(0) == MIXER_KDA and MC.mixer_kind(3) == MIXER_ATTENTION and MC.state_mixer == MIXER_KDA


def test_counts_by_kind_of_layer():
    """``stack_matmul_params(e)`` counts ``e`` experts, the shared one among them."""
    d, di, h = 64, 32, 2
    kda_mixer = d * (3 * di + h + 2 * 16) + 2 * 16 * di + di * d
    assert MC.state_matmul_params == kda_mixer and MC.kda_in_width == 3 * di + h + 32
    latent = d * 32 + 32 * 4 * 16 + d * 16 + 16 * 4 * 32 + 4 * 16 * d + (32 * 2 * 8 + d * (8 + 2))
    assert MC.layer_matmul_params(0, mixer=MIXER_ATTENTION) == latent + d * 16
    assert MC.cache_k_width == 16 + 8 and MC.latent_row_width == 16
    assert MC.state_bytes_per_row(4) == 5 * (2 * 16 * 16 * 4 + 3 * 3 * di * 4)
    experts = 3 * d * 32 * 2 + d * 16  # two experts counted (the shared one among them), the router's 16 outputs
    assert MC.stack_matmul_params(2) == (
        (kda_mixer + 3 * d * 96) + 4 * (kda_mixer + experts) + 2 * (latent + experts)
    )
