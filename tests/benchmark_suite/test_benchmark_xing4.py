"""The Xing4.0 family (``benchmark/families/xing4.py``): the contract, the file
against the catalog row, the counts against ISSUE 32's reckoning and against
the program's own, the program against the plain reference at a small size in
float32 (``forward`` through the contiguous cache; the stepped paged session
with a mid-flight chunked join), YaRN against the reference's, the cell's
``--dry`` run with another family's reference in this one's place, and the two
new readers on a hand-made trace and span list."""

import json
import re
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import xing4 as fam
from benchmark.lib import family
from benchmark.lib import spans as SP
from benchmark.lib import trace
from benchmark.readers import (
    Context,
    scope_path_ms_per_step,
    scope_prefix_ms_per_step,
    slice_moe_rate_expert_layers,
)
from test_benchmark_dry import last_line, run_cli
from test_benchmark_scopes import event, field, place, plane

ROOT = Path(__file__).resolve().parents[2]
CELL = "xing4-29b-a4b-pp4.topics-closed"
CFG = json.loads((ROOT / "benchmark" / "configs" / "xing4-29b-a4b-pp4.json").read_text())
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
# the catalog row's ``config`` (model-configs guide, architectures.jsonl: Xing4.0-29B-A4B)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2, "hidden_act": "silu", "hidden_size": 3584,
    "intermediate_size": 9216, "kv_lora_rank": 512, "max_position_embeddings": 262144, "model_type": "xing4_0",
    "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072,
}
TINY = {**CFG, **CFG["dry"], "model": "xing4:tiny", "max_position_embeddings": 1024}


def test_the_family_keeps_the_contract_and_imports_nothing_of_the_program():
    module = family.load(CFG)
    assert module is fam and module.REQUIRED_KEYS == (
        "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers", "first_k_dense_replace",
        "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "n_routed_experts", "n_shared_experts", "num_experts_per_tok", "routed_scaling_factor", "scoring_func",
        "norm_topk_prob", "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min", "mhc_h_res_clamp_max",
        "rope_scaling", "rope_theta", "rms_norm_eps", "vocab_size")
    source = Path(fam.__file__).read_text()
    assert not re.search(r"^\s*(from|import) .*cain_2025", source, re.M)
    assert all(hasattr(fam, n) for n in ("expert_bytes", "latent_bytes", "expert_layers", "experts_touched"))


def test_the_file_holds_every_published_key_and_states_the_cut():
    entry = next(c for c in BENCH["configs"] if c["name"] == "xing4-29b-a4b-pp4")
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == CFG["source"]
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert CFG[key] == 12 < value and CFG["published"][key] == value
        else:
            assert CFG[key] == value and type(CFG[key]) is type(value), key
    # the guide's floors: a whole period and >= 4 layers after the leading dense ones, >= 8 experts, >= 1/8 vocabulary
    assert fam.expert_layers(CFG) == 10 >= 4 and CFG["n_routed_experts"] >= 8 and CFG["vocab_size"] == 131072
    assert "four pipeline stages" in CFG["deployment"] and "NO layer is divided" in CFG["deployment"]
    assert {"mhc_clamp", "mhc_eps", "mhc_ends", "mhc_values", "num_nextn_predict_layers"} <= set(CFG["assumed"])
    assert CFG["engine"] == json.loads((ROOT / "benchmark" / "configs" / "longcat-flash-ep32.json").read_text())["engine"]
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "topics-closed" and "every expert held" in cell["why"]
    listed = [m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])]
    assert len(listed) == 16 + 4 + 3 and listed[-3:] == [
        "moe.pairs_per_expert_layer", "moe.experts_touched_per_expert_layer", "step.hc_ms_per_step"]
    # these two divide by every layer of the file (``lib/slice_counts.py::layers``): not this model's
    assert not {"moe.held_pairs_per_step", "moe.experts_touched_mean"} & set(listed)
    assert {"ttft_p95_ms.topics-closed", "step.moe_ms_per_step", "moe.expert_roofline", "attn.latent_roofline",
            "step.hbm_roofline", "step.mfu"} <= set(listed)
    assert all(m["workloads"] == [CELL] for m in BENCH["per_layer"][-3:])


@pytest.mark.parametrize("what,got,want", [
    ("one latent block", fam.params(CFG)["attention"], 28.41e6),
    ("a leading layer's dense FFN", fam.params(CFG)["ffn"], 99.09e6),
    ("one expert", fam.params(CFG)["expert"], 11.01e6),
    ("a leading dense layer", fam.dense_layer_params(CFG), 127.5e6),
    ("an expert layer", fam.expert_layer_params(CFG, 64), 744.1e6),
    ("bytes stored here", fam.weight_bytes(CFG), 8.64e9),
    ("cache bytes a token", fam.kv_bytes_per_token(CFG), 13824),
    ("the residual-stream maps", fam.map_bytes(CFG), 33e6),
])
def test_counts_equal_the_issues_reckoning(what, got, want):
    assert got == pytest.approx(want, rel=5e-3), what


def test_bytes_and_flops_of_a_step():
    p = fam.params(CFG)
    touched = fam.experts_touched(CFG, 12)
    assert touched == pytest.approx(64 * (1 - (1 - 4 / 64) ** 12)) and 34 < touched < 35
    assert fam.expert_bytes(CFG, 2.5) == 2.5 * p["expert"] and fam.latent_bytes(CFG, 1000) == 13824000
    step = fam.decode_step_bytes(CFG, 12, 12 * 350)
    outside = 2 * fam.dense_layer_params(CFG) + 10 * (fam.expert_layer_params(CFG, 0) + p["router"]) + p["head"]
    assert 1.14e9 < outside + fam.map_bytes(CFG) < 1.20e9  # ISSUE 32: 1.15 GB of everything else
    assert step == pytest.approx(outside + fam.map_bytes(CFG) + 10 * touched * p["expert"] + 12 * 3584
                                 + 12 * 350 * 13824 + 12 * 13824 + 12 * 131072 * 4)
    assert 4.9e9 < step < 5.1e9  # 3.8 GB of experts beside 1.15 of everything else
    flops = fam.decode_token_flops(CFG, 0)
    assert flops == pytest.approx(2 * (2 * fam.dense_layer_params(CFG) + 10 * fam.expert_layer_params(CFG, 4)
                                       + 24 * p["map"] + p["head"]))
    assert fam.decode_token_flops(CFG, 100) - flops == 100 * 12 * 2 * 32 * (576 + 512)
    assert fam.prefill_flops(CFG, 256) == pytest.approx(256 * fam.decode_token_flops(CFG, 128.5) - 255 * 2 * p["head"])


def test_the_programs_own_counts_agree_with_the_familys():
    """``ModelConfig``, ``utils/memory.py`` and ``obs/energy.py`` (the energy model's inputs, admission's bytes)."""
    from benchmark.lib.system import model_config
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.energy import slice_window_stats
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.memory import (
        decode_kv_stream_bytes,
        decode_weight_stream_bytes,
        estimate_weight_bytes,
    )

    mc = model_config(CFG)
    p = fam.params(CFG)
    assert mc.layer_runs == ((True, 0, 2), (False, 2, 10)) and mc.cache_layers == 12 and mc.residual_streams == 4
    assert mc.active_experts_per_token == 4 + 1
    assert decode_kv_stream_bytes(mc, 1) == fam.kv_bytes_per_token(CFG)
    assert estimate_weight_bytes(mc, "int8") == pytest.approx(fam.weight_bytes(CFG), rel=0.01)
    assert mc.flops_per_token(300) == pytest.approx(fam.decode_token_flops(CFG, 300), rel=1e-3)
    codes = 2 * fam.dense_layer_params(CFG) + 10 * fam.expert_layer_params(CFG, 64) + 2 * p["head"]
    assert mc.params_count == pytest.approx(codes + fam.map_bytes(CFG) / 4, rel=1e-3)
    # a token's weight stream: its 4 routed experts and the shared one a layer, the head once
    stream = (2 * fam.dense_layer_params(CFG) + 10 * (fam.expert_layer_params(CFG, 4) + p["router"]) + p["head"]
              + fam.map_bytes(CFG))
    assert decode_weight_stream_bytes(mc, "int8") == pytest.approx(stream, rel=0.01)
    est = slice_window_stats(mc, [(300, 16)] * 12, duration_s=0.16, steps=16, quantize="int8")
    assert est["bytes"] == pytest.approx(16 * stream + 12 * 16 * 308 * fam.kv_bytes_per_token(CFG), rel=0.01)
    assert est["flops"] == pytest.approx(12 * 16 * fam.decode_token_flops(CFG, 316), rel=1e-3)


def test_yarn_against_the_references_and_the_issues_numbers():
    from benchmark.lib.system import model_config
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.rope import rope_angles, rope_score_scale, yarn_ramp_bounds

    mc = model_config(CFG)
    inv_freq, low, high, magnitude, factor = fam.yarn(CFG)
    assert (low, high) == (10, 23) == yarn_ramp_bounds(mc.rope_scaling, 64, 10000.0) and magnitude == 1.0
    assert factor == pytest.approx(2.0048, rel=1e-4) == rope_score_scale(mc.rope_scaling)
    assert fam.score_scale(CFG) == pytest.approx(2.0048 / 13.8564, rel=1e-4)
    # the program's frequencies, read off the angles at position 1
    cos, sin = rope_angles(jnp.ones((1,), jnp.int32), 64, 10000.0, mc.rope_scaling)
    np.testing.assert_allclose(np.arctan2(np.asarray(sin[0]), np.asarray(cos[0])), inv_freq, atol=1e-7)
    assert inv_freq[0] == 1.0 and inv_freq[31] == pytest.approx(10000.0 ** (-62 / 64) / 64)


# -- the program against the reference, small and in float32 -----------------------

@pytest.fixture(scope="module")
def tiny():
    from benchmark.lib.system import model_config
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import quantize_leaf
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import init_params

    mc = model_config(TINY)
    params = jax.jit(lambda k: init_params(mc, k, jnp.float32, post=lambda n, l: quantize_leaf(n, l, "int8")))(
        jax.random.PRNGKey(11))
    return mc, params, fam.make_weights(TINY, 11)


def test_the_program_makes_the_references_weights_leaf_for_leaf(tiny):
    _, params, weights = tiny
    assert set(weights) == {k for k in params if not k.endswith("norm")}
    for name, leaf in weights.items():
        for a, b in zip(jax.tree_util.tree_leaves(leaf), jax.tree_util.tree_leaves(params[name])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    assert weights["we_gate"]["q"].shape == (2, 8, 64, 32) and weights["hc_attn_phi"].dtype == jnp.float32
    control = fam.make_weights(TINY, 11, bits=4)
    assert int(jnp.max(jnp.abs(control["we_up"]["q"]))) == 7 and int(jnp.max(jnp.abs(control["embed"]["q"]))) == 127
    np.testing.assert_array_equal(np.asarray(control["hc_mlp_phi"]), np.asarray(weights["hc_mlp_phi"]))


def test_the_stand_in_group_reaches_program_and_reference_alike():
    """The file's ``stand_in`` numbers go to the program's ``ModelConfig``
    and to ``make_weights``; a file without the group is the recipe every
    other configuration uses."""
    assert fam.stand_in(CFG) == (CFG["stand_in"]["embed_std"], CFG["stand_in"]["routed_down_gain"])
    plain = {k: v for k, v in TINY.items() if k != "stand_in"}
    assert fam.stand_in(plain) == (0.02, 1.0)
    got = fam.program_config(TINY)
    assert (got["init_embed_std"], got["init_routed_gain"]) == fam.stand_in(CFG)
    moved, base = fam.make_weights(TINY, 3), fam.make_weights(plain, 3)
    ratio = CFG["stand_in"]["routed_down_gain"]
    np.testing.assert_allclose(np.asarray(moved["we_down"]["s"]), np.asarray(base["we_down"]["s"]) * ratio, rtol=1e-2)
    np.testing.assert_array_equal(np.asarray(moved["we_up"]["q"]), np.asarray(base["we_up"]["q"]))
    np.testing.assert_array_equal(np.asarray(moved["wo"]["q"]), np.asarray(base["wo"]["q"]))


def reference_head(params, hidden):
    """The head in float32 (the program's own multiplies a quantized head in bfloat16)."""
    return hidden.astype(jnp.float32) @ (params["lm_head"]["q"].astype(jnp.float32) * params["lm_head"]["s"])


def test_prefill_then_decode_through_the_cache_match_the_reference(tiny):
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import Transformer, forward

    mc, params, weights = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 128), 3, 259)
    want = fam.served_logits(TINY, weights, [[int(t) for t in tokens[0]]], [(0, 128)])[0]
    k0, v0 = Transformer(cfg=mc, params=params).init_cache(1, 128, jnp.float32)
    assert k0.shape == (4, 1, 1, 128, 24) and v0.shape == (4, 1, 1, 128, 0)
    stats = {}
    hidden, kc, vc = forward(params, mc, tokens[:, :100], jnp.int32(0), k0, v0, stats=stats)
    assert float(jnp.max(jnp.abs(reference_head(params, hidden[0]) - want[:100]))) <= 1e-4
    assert stats["moe"].tolist()[:3] == [100 * 2 * 3, 0, 0]  # tokens x expert layers x top-k, none zero or absent
    step = jax.jit(lambda tok, t, kc, vc: forward(params, mc, tok, t, kc, vc))
    worst = 0.0
    for t in range(100, 128):
        hidden, kc, vc = step(tokens[:, t : t + 1], jnp.int32(t), kc, vc)
        worst = max(worst, float(jnp.max(jnp.abs(reference_head(params, hidden[0, 0]) - want[t]))))
    assert worst <= 1e-4
    # the comparison sees the data-dependent half of the map: with the maps' gains zeroed the logits move
    still = {k: (jnp.zeros_like(v) if k.endswith("_alpha") else v) for k, v in params.items()}
    hidden, _, _ = forward(still, mc, tokens[:, :100], jnp.int32(0), k0, v0)
    assert float(jnp.max(jnp.abs(reference_head(params, hidden[0]) - want[:100]))) > 1e-2


def test_the_stepped_paged_session_with_a_chunked_join_serves_what_forward_serves(tiny):
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import GenerationRequest
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import JaxEngine

    mc, _, _ = tiny
    eng = JaxEngine(registry={mc.name: mc}, dtype=jnp.float32, quantize="int8", paged_kv=True, seed=11)
    reqs = [GenerationRequest(mc.name, "".join("abcdefgh "[(i * 7 + j) % 9] for j in range(131 + 9 * i)),
                              max_new_tokens=14 + 5 * i) for i in range(3)]
    alone = [eng.generate(r).tokens for r in reqs]  # forward over the contiguous cache
    sess = eng.decode_open(reqs[:2], reserve_rows=4, slice_steps=8)
    got, slices = {}, []

    def step():
        for res in sess.step():
            got[res.request.prompt] = res.tokens
        slices.append(dict(sess.last_slice_moe))

    step()
    pending = sess.join_begin(reqs[2])  # joins mid-flight, one chunk a turn
    while not sess.join_step(pending):
        step()
    sess.join_commit(pending)
    assert sess.debug_state()["stack"] == {"residual_streams": 4, "layer_runs": [2, 2]}
    while sess.active:
        step()
    sess.close()
    assert [got[r.prompt] for r in reqs] == alone
    for s in slices:  # every pair of every live row's token lands on a held expert of an EXPERT layer
        assert s["moe_held"] == s["moe_tokens"] * 2 * 3 and s["moe_zero"] == s["moe_absent"] == 0
        assert 0 < s["moe_steps"] <= 8 and s["moe_touched"] <= s["moe_steps"] * 2 * 8
    assert slices[0]["moe_tokens"] == 16
    # the served tokens are the reference's own greedy choice
    from benchmark.lib.traffic import token_ids
    weights = fam.make_weights(TINY, 11)
    ids = token_ids(reqs[2].prompt) + list(alone[2])
    logits = fam.served_logits(TINY, weights, [ids], [(len(ids) - len(alone[2]) - 1, len(alone[2]))])[0]
    gap = jnp.max(logits, -1) - jnp.take_along_axis(logits, jnp.asarray(alone[2])[:, None], -1)[:, 0]
    assert float(jnp.max(gap)) <= 1e-3


# -- the cell's rehearsal, and another family's reference in this one's place ---------

LONGCAT_IN_ITS_PLACE = '''\
from . import longcat_flash
from .xing4 import *  # noqa: F401,F403


def _as_longcat(cfg):
    return {**cfg, "ffn_hidden_size": cfg["intermediate_size"], "expert_ffn_hidden_size": cfg["moe_intermediate_size"],
            "num_layers": cfg["num_hidden_layers"], "zero_expert_num": 0, "moe_topk": cfg["num_experts_per_tok"],
            "published": {"n_routed_experts": cfg["n_routed_experts"]}}


def make_weights(cfg, seed, bits=8):
    return longcat_flash.make_weights(_as_longcat(cfg), seed, bits)


def served_logits(cfg, weights, token_rows, spans):
    return longcat_flash.served_logits(_as_longcat(cfg), weights, token_rows, spans)
'''


def test_the_cells_dry_run_is_correct_and_the_longcat_reference_in_its_place_is_not(tmp_path):
    line = last_line(run_cli(ROOT, "--workload", CELL, "--seed", str(2**31 + 32), "--seconds", "2",
                             "--trace", "1", "--dry"))
    assert line["correct"] is True and line["failed"] == 0
    assert line["check"]["logit_gap_max"]["value"] < 0.05 < line["check"]["logit_gap_max"]["limit"]
    assert {"dry.moe.pairs_per_expert_layer", "dry.moe.experts_touched_per_expert_layer",
            "dry.ttft_p95_ms.topics-closed", "dry.sched.live_rows_mean"} <= set(line["metrics"])
    # 3 of 8 experts a token and EXPERT layer, every one held: three pairs a live row
    rows = line["metrics"]["dry.sched.live_rows_mean"]["value"]
    pairs = line["metrics"]["dry.moe.pairs_per_expert_layer"]["value"]
    assert 0 < line["metrics"]["dry.moe.experts_touched_per_expert_layer"]["value"] <= min(8, pairs) <= 3 * 4
    assert pairs == pytest.approx(3 * rows, rel=0.5)
    # device readers find no TPU plane on the CPU and stay silent
    assert not {"dry.step.hc_ms_per_step", "dry.moe.expert_roofline", "dry.step.moe_ms_per_step"} & set(line["metrics"])
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".jax_cache", ".out", "__pycache__"))
    (tmp_path / "benchmark" / "families" / "xing4_longcatref.py").write_text(LONGCAT_IN_ITS_PLACE)
    path = tmp_path / "benchmark" / "configs" / "xing4-29b-a4b-pp4.json"
    path.write_text(json.dumps({**CFG, "family": "xing4_longcatref"}))
    wrong = last_line(run_cli(tmp_path, "--workload", CELL, "--seed", str(2**31 + 32), "--seconds", "2",
                              "--trace", "0", "--dry"))
    assert wrong["correct"] is False and wrong["failed"] == 0
    assert wrong["check"]["logit_gap_max"]["value"] > wrong["check"]["logit_gap_max"]["limit"]


# -- the two new readers on a hand-made trace and span list ---------------------------

def hc_xspace():
    """Two runs of the decode slice, 10 ms each: 1 ms in a map, 0.5 reading the
    streams, 0.5 writing them, 2 ms of experts, 3 ms attention, 2 ms mlp, 1 ms unscoped."""
    tf_op = 7
    path = "jit(decode)/while/body/while/body/closed_call/"
    names = {2: "hc.map/dot_general:", 3: "hc.pre/mul:", 4: "hc.post/add:", 5: "moe.experts/while/body/moe.experts/dot_general:",
             6: "attn.core/dot_general:", 7: "mlp/dot_general:"}
    event_meta = {1: ("jit_decode(5)", []), 8: ("%copy.1 = bf16[8] copy(%c)", []),
                  9: ("%while.2 = (s32[]) while(%t)", [field(1, tf_op) + field(5, path + "hc.map/while:")]),
                  10: ("jit_prefill(6)", []),
                  11: ("%fusion.11 = f32[8] fusion(%a)", [field(1, tf_op) + field(5, "jit(prefill)/while/body/hc.map/exp:")])}
    for mid, tail in names.items():
        event_meta[mid] = (f"%fusion.{mid} = f32[8] fusion(%a)", [field(1, tf_op) + field(5, path + tail)])
    ms = 10**9
    ops, modules = [], []
    for start in (0, 20 * ms):
        modules.append(event(1, start, 10 * ms))
        ops.append(event(9, start, 3 * ms))  # a loop wraps its body's operations: counts for nothing
        at = start
        for mid, dur in ((2, 2), (3, 1), (4, 1), (5, 4), (6, 6), (7, 4), (8, 2)):  # half milliseconds
            ops.append(event(mid, at, dur * ms // 2))
            at += dur * ms // 2
    modules.append(event(10, 12 * ms, 2 * ms))  # a join's chunk between the slices: another program
    ops.append(event(11, 12 * ms, 2 * ms))
    tpu = plane("/device:TPU:0", [("XLA Ops", 5_000_000_000, ops), ("XLA Modules", 5_000_000_000, modules)],
                event_meta, {tf_op: "tf_op"})
    return field(1, tpu)


def S(name, t0, t1, span_id, **attrs):
    return SP.S(name, t0, t1, 1, span_id, None, None, attrs)


SLICES = [
    S("sched.slice", 100.002, 100.012, 1, rows=12, ctx_tokens=4000, moe_held=7680, moe_zero=0, moe_absent=0,
      moe_touched=5500, moe_steps=16, moe_tokens=192),
    S("sched.slice", 105.0, 105.01, 2, rows=11, ctx_tokens=4100, moe_held=7040, moe_zero=0, moe_absent=0,
      moe_touched=5300, moe_steps=16, moe_tokens=176),  # after the traced part, inside the window
    S("sched.slice", 106.0, 106.01, 3, rows=3, ctx_tokens=900),  # a model without an expert layer: no counts
]


@pytest.fixture()
def traced(tmp_path, monkeypatch):
    place(tmp_path, monkeypatch, hc_xspace())
    scope_prefix_ms_per_step._DEVICE.clear()
    monkeypatch.setattr(SP, "finished", lambda t0, t1: [s for s in SLICES if s.t0 >= t0 and s.t1 <= t1])
    dev = trace.DeviceTrace(ops=[(5.0, 5.03, "fusion.1")], modules=[(5.0, 5.03, "jit_decode(5)")])
    tr = trace.Trace(devices={0: dev}, host=[(5.0, 5.04, "bench:window")])
    return Context.build(cfg=CFG, mix={}, cell={}, chip={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
                         trace=tr, records=[], slices=[], slice_steps=16, compiles=0,
                         t0=100.0, t1=100.04, window_t1=140.0)


def params_of(name):
    return json.loads((ROOT / "benchmark" / "layer_metrics" / f"{name}.json").read_text())["params"]


def test_the_two_new_readers_on_a_hand_made_trace(traced):
    assert params_of("step.hc_ms_per_step") == {"module": "^jit_decode", "prefixes": ["hc."]}
    # 2 runs x 16 steps; hc.* holds 2 of a run's 10 ms; the loop's own event and the prefill's map count for nothing
    assert scope_prefix_ms_per_step.read(traced, params_of("step.hc_ms_per_step")) == pytest.approx(4.0 / 32)
    assert scope_prefix_ms_per_step.under("jit(decode)/while/body/attn.out/hc.post/add:add", ("hc.",))
    assert not scope_prefix_ms_per_step.under("jit(decode)/while/body/mlp/dot_general:hc.", ("hc.",))
    # the accepted sibling still reads moe.* alone: 2 ms a run
    assert scope_path_ms_per_step.read(traced, params_of("step.moe_ms_per_step")) == pytest.approx(4.0 / 32)
    # the whole window's two counted slices, per EXPERT layer (10 of the file's 12) and step (32)
    assert slice_moe_rate_expert_layers.read(traced, params_of("moe.pairs_per_expert_layer")) == pytest.approx(
        14720 / (32 * 10))
    assert slice_moe_rate_expert_layers.read(traced, params_of("moe.experts_touched_per_expert_layer")) == pytest.approx(
        10800 / (32 * 10))


def test_the_two_new_readers_read_nothing_where_there_is_nothing(traced, monkeypatch):
    """No run of the program in the trace, no trace, no counts on the spans, a family without expert layers."""
    assert scope_prefix_ms_per_step.read(traced, {"module": "^jit_verify", "prefixes": ["hc."]}) is None
    # a run of a program without such scopes reads 0, not nothing: the program ran, and spent no time there
    assert scope_prefix_ms_per_step.read(traced, {"module": "^jit_decode", "prefixes": ["zz."]}) == 0.0
    monkeypatch.setattr(SP, "finished", lambda t0, t1: [s for s in SLICES[2:] if s.t0 >= t0 and s.t1 <= t1])
    assert slice_moe_rate_expert_layers.read(traced, params_of("moe.pairs_per_expert_layer")) is None
    monkeypatch.setattr(SP, "finished", lambda t0, t1: SLICES[:2])
    dense = Context.build(cfg={}, mix={}, cell={}, chip=None, trace=None, records=[], slices=[], slice_steps=16,
                          compiles=0, t0=100.0, t1=100.04, window_t1=140.0)
    assert slice_moe_rate_expert_layers.read(dense, params_of("moe.pairs_per_expert_layer")) is None
    assert scope_prefix_ms_per_step.read(dense, params_of("step.hc_ms_per_step")) is None
