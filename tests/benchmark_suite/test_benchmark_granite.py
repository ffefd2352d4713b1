"""The Granite 4.0-H family (``benchmark/families/granite_hybrid.py``): the
contract, the file against the catalog row, the counts against ISSUE 34's
reckoning and against the program's own, the program against the plain
reference at a small size in float32 (``forward`` through cache and state;
the stepped paged session with a mid-flight join of two chunks whose last is
padded; the state dropped at an install or moved by pads is seen), the cell's
``--dry`` run with another family's reference in this one's place, and the new
reader on a hand-made trace and span list."""

import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import granite_hybrid as fam
from benchmark.lib import family
from benchmark.lib import spans as SP
from benchmark.lib import trace
from benchmark.readers import Context, scope_prefix_ms_per_step, ssm_roofline
from test_benchmark_dry import last_line, run_cli
from test_benchmark_scopes import event, field, place, plane

ROOT = Path(__file__).resolve().parents[2]
CELL = "granite-4h-small-ep8.topics-closed"
CFG = json.loads((ROOT / "benchmark" / "configs" / "granite-4h-small-ep8.json").read_text())
# the catalog row's ``config`` (model-configs guide, architectures.jsonl: granite-4.0-h-small)
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125, "embedding_multiplier": 12, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 768,
    "layer_types": ["attention" if i in (5, 15, 25, 35) else "mamba" for i in range(40)],
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352,
}
TINY = {**CFG, **CFG["dry"], "model": "granite:tiny", "max_position_embeddings": 1024}


def test_the_family_keeps_the_contract_and_imports_nothing_of_the_program():
    module = family.load(CFG)
    assert module is fam and set(fam.REQUIRED_KEYS) <= set(CFG)
    assert {"layer_types", "mamba_n_heads", "mamba_d_state", "shared_intermediate_size", "first_expert",
            "attention_multiplier", "logits_scaling", "position_embedding_type"} <= set(fam.REQUIRED_KEYS)
    source = (ROOT / "benchmark" / "families" / "granite_hybrid.py").read_text()
    assert "cain_2025" not in source and "import benchmark" not in source and "from .." not in source
    for name in ("weight_bytes", "kv_bytes_per_token", "state_bytes_per_row", "expert_layers", "experts_touched",
                 "expert_bytes", "ssm_state_bytes", "ssm_proj_bytes", "decode_step_bytes", "decode_token_flops",
                 "prefill_flops"):
        assert callable(getattr(fam, name)), name


def test_the_file_holds_every_published_key_but_the_experts_held(bench):
    entry = next(c for c in bench["configs"] if c["name"] == "granite-4h-small-ep8")
    assert entry["reduced"] == ["num_local_experts"] and entry["file"] == "benchmark/configs/granite-4h-small-ep8.json"
    assert entry["source"] == CFG["source"] == "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json"
    differ = {k for k, v in PUBLISHED.items() if CFG.get(k, "absent") != v}
    assert differ == {"num_local_experts"}
    assert CFG["num_local_experts"] == 9 and CFG["published"] == {"num_local_experts": 72} and CFG["first_expert"] == 0
    assert CFG["layer_types"].count("mamba") == 36 and len(CFG["layer_types"]) == 40
    assert CFG["family"] == "granite_hybrid" and CFG["model"] == "granite-4.0-h-small:ep8"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "granite-4h-small-ep8", "traffic": "topics-closed", "chips": 1}
    listed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    # 16 of every cell, 3 of the expert cells, the two rates per expert layer (the family's ``expert_layers``: 40),
    # 3 of its own state-space layers, the serving loop's own account 4
    assert len(listed) == 16 + 3 + 2 + 3 + 4
    assert {"moe.expert_roofline", "step.hbm_roofline", "step.mfu", "step.moe_ms_per_step",
            "ttft_p95_ms.topics-closed", "moe.pairs_per_expert_layer", "moe.experts_touched_per_expert_layer",
            "step.ssm_ms_per_step", "ssm.state_roofline", "ssm.proj_roofline", "sched.stall_ms_per_s",
            "sched.stall_named_pct", "host.gc_ms_per_s", "host.process_stall_ms_per_s"} <= set(listed)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ("step.ssm_ms_per_step", "ssm.state_roofline", "ssm.proj_roofline"):
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["layer"] == "model step", name
    assert not {"moe.held_pairs_per_step", "moe.experts_touched_mean", "attn.latent_roofline",
                "step.hc_ms_per_step"} & set(listed)


P = fam.params(CFG)


@pytest.mark.parametrize("what,got,want", [
    ("a Mamba-2 mixer (in_proj, out_proj, conv)", P["ssm"], 102.3e6),
    ("in_proj", 4096 * (2 * 8192 + 2 * 128 + 128), 68.68e6),
    ("an attention mixer", P["attention"], 41.9e6),
    ("the shared MLP", P["shared"], 18.87e6),
    ("one expert", P["expert"], 9.437e6),
    ("the router", P["router"], 0.295e6),
    ("the embedding", P["head"], 411.0e6),
    ("this chip's int8 bytes", fam.weight_bytes(CFG), 8.42e9),
    ("KV bytes a token", fam.kv_bytes_per_token(CFG), 16384),
    ("state bytes a row (the tail in bfloat16)", fam.state_bytes_per_row(CFG), 152.8e6),
    ("state bytes a row with the tail at 4 bytes: ISSUE 34's 154.6 MB", fam.state_bytes_per_row(CFG, 4), 154.6e6),
    ("the mixers' projections", fam.ssm_proj_bytes(CFG), 3.68e9),
    ("12 live rows' state read and written", fam.ssm_state_bytes(CFG, 12), 3.67e9),
    ("experts touched a layer by 12 rows", fam.experts_touched(CFG, 12), 7.5),
    ("touched experts' bytes a step", 40 * fam.expert_bytes(CFG, fam.experts_touched(CFG, 12)), 2.83e9),
    ("least bytes a step at 12 rows", fam.decode_step_bytes(CFG, 12, 12 * 300), 11.55e9),
    ("expert layers", fam.expert_layers(CFG), 40),
])
def test_counts_equal_the_issues_reckoning(what, got, want):
    assert got == pytest.approx(want, rel=6e-3), what


def test_bytes_and_flops_of_a_step():
    assert fam.experts_touched(CFG, 12) == pytest.approx(9 * (1 - (62 / 72) ** 12))
    outside = fam.outside_experts_params(CFG)
    assert outside == 36 * P["ssm"] + 4 * P["attention"] + 40 * (P["shared"] + P["router"])
    step = fam.decode_step_bytes(CFG, 12, 4000)
    assert step == pytest.approx(outside + P["head"] + 40 * fam.expert_bytes(CFG, fam.experts_touched(CFG, 12))
                                 + 2 * 12 * fam.state_bytes_per_row(CFG) + 4000 * 16384 + 12 * 16384 + 12 * 4096
                                 + 12 * 100352 * 4)
    flops = fam.decode_token_flops(CFG, 0)
    assert flops == pytest.approx(2 * (outside + 40 * 1.25 * P["expert"] + P["head"]) + 6 * 36 * 8192 * 128)
    assert fam.decode_token_flops(CFG, 100) - flops == 100 * 4 * 4 * 32 * 128
    assert fam.prefill_flops(CFG, 256) == pytest.approx(256 * fam.decode_token_flops(CFG, 128.5) - 255 * 2 * P["head"])
    assert 2.3e12 < fam.prefill_flops(CFG, 256) < 2.9e12  # ISSUE 34: a 256-token chunk is ~2.6 TFLOP


def test_the_programs_own_counts_agree_with_the_familys():
    """``ModelConfig``, ``utils/memory.py`` and ``obs/energy.py`` (the energy model's inputs, admission's bytes)."""
    from benchmark.lib.system import model_config
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.energy import slice_window_stats
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.memory import (
        decode_kv_stream_bytes,
        decode_state_stream_bytes,
        decode_weight_stream_bytes,
        estimate_decode_read_bytes_per_step,
        estimate_weight_bytes,
    )

    mc = model_config(CFG)
    assert [count for _, _, count in mc.layer_runs] == [5, 1, 9, 1, 9, 1, 9, 1, 4]
    assert mc.cache_layers == 4 and mc.state_layers == 36 and mc.router_outputs == 72 and mc.n_experts == 9
    assert mc.active_experts_per_token == 10 * 9 / 72
    assert decode_kv_stream_bytes(mc, 1) == fam.kv_bytes_per_token(CFG)
    assert mc.state_bytes_per_row(2) == fam.state_bytes_per_row(CFG)
    assert decode_state_stream_bytes(mc, 12) == fam.ssm_state_bytes(CFG, 12)
    assert estimate_weight_bytes(mc, "int8") == pytest.approx(fam.weight_bytes(CFG), rel=0.01)
    assert mc.flops_per_token(300) == pytest.approx(fam.decode_token_flops(CFG, 300), rel=1e-3)
    assert mc.params_count == pytest.approx(fam.weight_bytes(CFG), rel=1e-3)
    # a token's weight stream: everything outside the experts, its 1.25 held experts a layer, the head once
    stream = fam.outside_experts_params(CFG) + 40 * 1.25 * P["expert"] + P["head"]
    assert decode_weight_stream_bytes(mc, "int8") == pytest.approx(stream, rel=0.01)
    assert estimate_decode_read_bytes_per_step(mc, "int8", 300) == pytest.approx(
        decode_weight_stream_bytes(mc, "int8") + 300 * 16384 + 2 * fam.state_bytes_per_row(CFG))
    est = slice_window_stats(mc, [(300, 16)] * 12, duration_s=0.4, steps=16, quantize="int8")
    assert est["bytes"] == pytest.approx(
        16 * decode_weight_stream_bytes(mc, "int8") + 12 * 16 * (308 * 16384 + 2 * fam.state_bytes_per_row(CFG)))
    assert est["flops"] == pytest.approx(12 * 16 * fam.decode_token_flops(CFG, 316), rel=1e-3)


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
    assert weights["we_gate"]["q"].shape == (6, 4, 64, 32) and weights["router"].shape == (6, 64, 8)
    assert weights["ssm_in"]["q"].shape == (4, 64, 2 * 128 + 2 * 16 + 8) and weights["wq"]["q"].shape == (2, 64, 64)
    control = fam.make_weights(TINY, 11, bits=4)
    assert int(jnp.max(jnp.abs(control["ssm_in"]["q"]))) == 7 and int(jnp.max(jnp.abs(control["embed"]["q"]))) == 127
    np.testing.assert_array_equal(np.asarray(control["ssm_a_log"]), np.asarray(weights["ssm_a_log"]))
    assert float(jnp.min(weights["ssm_a_log"])) >= 0.0 and float(jnp.max(weights["ssm_a_log"])) <= float(np.log(16)) + 1e-6
    dt0 = np.logaddexp(np.asarray(weights["ssm_dt_bias"]), 0.0)
    assert 1e-3 * 0.999 <= dt0.min() and dt0.max() <= 1e-1 * 1.001


def test_the_stand_in_group_reaches_program_and_reference_alike():
    assert (fam.embed_std(CFG), fam.final_norm_gain(CFG)) == (0.003, 8.0)
    assert fam.program_config(CFG)["init_final_norm_gain"] == 8.0 and fam.final_norm_gain({}) == 1.0
    moved = {**TINY, "stand_in": {"embed_std": 0.5}}
    assert fam.program_config(moved)["init_embed_std"] == 0.5 == fam.embed_std(moved)
    a, b = fam.make_weights(moved, 3), fam.make_weights(TINY, 3)
    ratio = 0.5 / fam.embed_std(TINY)
    np.testing.assert_allclose(np.asarray(a["embed"]["s"]), np.asarray(b["embed"]["s"]) * ratio, rtol=1e-2)
    np.testing.assert_array_equal(np.asarray(a["ssm_in"]["q"]), np.asarray(b["ssm_in"]["q"]))


def reference_head(params, hidden):
    """The tied head in float32 (the program's own multiplies a quantized head in bfloat16)."""
    embed = params["embed"]["q"].astype(jnp.float32) * params["embed"]["s"]
    return (hidden.astype(jnp.float32) @ embed.T) / float(TINY["logits_scaling"])  # hidden carries the final gain


def test_prefill_then_decode_through_cache_and_state_match_the_reference(tiny):
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import Transformer, forward

    mc, params, weights = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 128), 3, 259)
    want = fam.served_logits(TINY, weights, [[int(t) for t in tokens[0]]], [(0, 128)])[0]
    k0, v0 = Transformer(cfg=mc, params=params).init_cache(1, 128, jnp.float32)
    assert k0["kv"].shape == (2, 1, 2, 128, 16) and k0["ssm"]["s"].shape == (4, 1, 8, 16, 16)
    padded = jnp.zeros((1, 112), jnp.int32).at[:, :100].set(tokens[:, :100])
    stats = {}
    hidden, kc, vc = forward(params, mc, padded, jnp.int32(0), k0, v0, stats=stats,
                             token_mask=jnp.arange(112)[None, :] < 100)
    assert float(jnp.max(jnp.abs(reference_head(params, hidden[0, :100]) - want[:100]))) <= 1e-4
    held, zero, absent = stats["moe"].tolist()[:3]
    assert held + absent == 100 * 6 * 3 and zero == 0 and 0 < held < 100 * 6 * 3  # pads route nowhere
    step = jax.jit(lambda tok, t, kc, vc: forward(params, mc, tok, t, kc, vc))

    def decode(kc, vc):
        worst = 0.0
        for t in range(100, 128):
            hidden, kc, vc = step(tokens[:, t : t + 1], jnp.int32(t), kc, vc)
            worst = max(worst, float(jnp.max(jnp.abs(reference_head(params, hidden[0, 0]) - want[t]))))
        return worst

    assert decode(kc, vc) <= 1e-4
    # the check sees the state: S or the convolution's tail zeroed where the decode starts, pads that moved the state
    scale = float(jnp.max(jnp.abs(want)))
    for leaf in ("s", "conv"):
        dropped = {"kv": kc["kv"], "ssm": {**kc["ssm"], leaf: jnp.zeros_like(kc["ssm"][leaf])}}
        assert decode(dropped, vc) > max(1e-3, 1e-2 * scale), leaf
    _, kc_pads, vc_pads = forward(params, mc, padded, jnp.int32(0), k0, v0)
    assert decode(kc_pads, vc_pads) > max(1e-3, 1e-2 * scale)


def test_the_stepped_paged_session_with_a_padded_two_chunk_join_serves_what_the_reference_chooses(tiny):
    from benchmark.lib.traffic import token_ids
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import GenerationRequest
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import JaxEngine

    mc, _, weights = tiny
    eng = JaxEngine(registry={mc.name: mc}, dtype=jnp.float32, quantize="int8", paged_kv=True, seed=11)
    reqs = [GenerationRequest(mc.name, "".join("abcdefgh "[(i * 7 + j) % 9] for j in range(131 + 9 * i)),
                              max_new_tokens=14 + 5 * i) for i in range(2)]
    reqs.append(GenerationRequest(mc.name, "".join("abcdefgh "[(3 + j * 5) % 9] for j in range(290)), max_new_tokens=20))
    alone = [eng.generate(r).tokens for r in reqs]  # forward over the contiguous cache and state
    sess = eng.decode_open(reqs[:2], reserve_rows=4, slice_steps=8)
    got, slices = {}, []

    def step():
        for res in sess.step():
            got[res.request.prompt] = res.tokens
        slices.append({**sess.last_slice_moe, **sess.state_counts})

    step()
    pending = sess.join_begin(reqs[2])  # joins mid-flight: two chunks, 256 and a padded 64 holding 35
    assert [b for _, b in pending.chunks] == [256, 64]
    while not sess.join_step(pending):
        step()
    sess.join_commit(pending)
    state = sess.debug_state()
    assert state["stack"]["layer_kinds"] == {"ssm": 4, "attention": 2} and state["stack"]["layer_runs"] == [2, 1, 2, 1]
    assert state["state"]["rows"] == 4 and state["state"]["bytes_per_row"] == mc.state_bytes_per_row(4)
    while sess.active:
        step()
    sess.close()
    assert [got[r.prompt] for r in reqs] == alone
    for s in slices:  # every pair of every live row's token is held or absent; the state of the whole bucket streams
        assert s["moe_held"] + s["moe_absent"] == s["moe_tokens"] * 6 * 3 and s["moe_zero"] == 0
        assert s["state_rows"] == 4 and s["state_bytes"] == 4 * mc.state_bytes_per_row(4)
    # the served tokens are the reference's own greedy choice, the two-chunk joiner's among them
    ids = token_ids(reqs[2].prompt) + list(alone[2])
    logits = fam.served_logits(TINY, weights, [ids], [(len(ids) - len(alone[2]) - 1, len(alone[2]))])[0]
    gap = jnp.max(logits, -1) - jnp.take_along_axis(logits, jnp.asarray(alone[2])[:, None], -1)[:, 0]
    assert float(jnp.max(gap)) <= 1e-3


# -- the cell's rehearsal, and another family's reference in this one's place ---------

XING4_IN_ITS_PLACE = '''\
from . import xing4
from .granite_hybrid import *  # noqa: F401,F403


def _as_xing4(cfg):
    return {**cfg, "moe_intermediate_size": cfg["intermediate_size"], "intermediate_size": cfg["shared_intermediate_size"],
            "first_k_dense_replace": 1, "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": cfg["num_local_experts"], "n_shared_experts": 1,
            "routed_scaling_factor": 1.0, "scoring_func": "sigmoid", "norm_topk_prob": True, "hc_mult": 2,
            "hc_sinkhorn_iters": 4, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
            "rope_theta": 10000.0,
            "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4, "mscale": 1, "mscale_all_dim": 1,
                             "original_max_position_embeddings": 32, "type": "yarn"}}


def make_weights(cfg, seed, bits=8):
    return xing4.make_weights(_as_xing4(cfg), seed, bits)


def served_logits(cfg, weights, token_rows, spans):
    return xing4.served_logits(_as_xing4(cfg), weights, token_rows, spans)
'''


def test_the_cells_dry_run_is_correct_and_the_xing4_reference_in_its_place_is_not(tmp_path):
    line = last_line(run_cli(ROOT, "--workload", CELL, "--seed", str(2**31 + 34), "--seconds", "2",
                             "--trace", "1", "--dry"))
    assert line["correct"] is True and line["failed"] == 0
    assert line["check"]["logit_gap_max"]["value"] < line["check"]["logit_gap_max"]["limit"] == 1e-4
    assert {"dry.ttft_p95_ms.topics-closed", "dry.sched.live_rows_mean", "dry.session.window_compiles"} <= set(
        line["metrics"])
    assert 0 < line["metrics"]["dry.sched.live_rows_mean"]["value"] <= 16
    # every layer is an expert layer: 3 of 8 experts a token, 4 of them held here
    pairs = line["metrics"]["dry.moe.pairs_per_expert_layer"]["value"]
    assert 0 < line["metrics"]["dry.moe.experts_touched_per_expert_layer"]["value"] <= min(4, pairs)
    # device readers find no TPU plane on the CPU and stay silent
    assert not {"dry.step.ssm_ms_per_step", "dry.ssm.state_roofline", "dry.ssm.proj_roofline",
                "dry.moe.expert_roofline"} & set(line["metrics"])
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".jax_cache", ".out", "__pycache__"))
    (tmp_path / "benchmark" / "families" / "granite_xing4ref.py").write_text(XING4_IN_ITS_PLACE)
    path = tmp_path / "benchmark" / "configs" / "granite-4h-small-ep8.json"
    path.write_text(json.dumps({**CFG, "family": "granite_xing4ref"}))
    wrong = last_line(run_cli(tmp_path, "--workload", CELL, "--seed", str(2**31 + 34), "--seconds", "2",
                              "--trace", "0", "--dry"))
    assert wrong["correct"] is False and wrong["failed"] == 0
    assert wrong["check"]["logit_gap_max"]["value"] > wrong["check"]["logit_gap_max"]["limit"]


# -- the new reader on a hand-made trace and span list ---------------------------------

def ssm_xspace():
    """Two runs of the decode slice, 16 ms each: 2 ms of in_proj, 1 of conv, 6 of
    update, 0.5 of gate_norm, 1.5 of out_proj, 2 of experts, 1 attention, 2 unscoped."""
    tf_op = 7
    path = "jit(decode)/while/body/while/body/closed_call/"
    names = {2: "ssm.in_proj/dot_general:", 3: "ssm.conv/mul:", 4: "ssm.update/add:", 5: "ssm.gate_norm/mul:",
             6: "ssm.out_proj/dot_general:", 7: "moe.experts/pallas_moe_gate_up:", 8: "attn.core/dot_general:"}
    event_meta = {1: ("jit_decode(5)", []), 9: ("%copy.1 = bf16[8] copy(%c)", []),
                  10: ("%while.2 = (s32[]) while(%t)", [field(1, tf_op) + field(5, path + "ssm.update/while:")]),
                  11: ("jit_prefill(6)", []),
                  12: ("%fusion.12 = f32[8] fusion(%a)", [field(1, tf_op) + field(5, "jit(prefill)/while/body/ssm.update/dot_general:")])}
    for mid, tail in names.items():
        event_meta[mid] = (f"%fusion.{mid} = f32[8] fusion(%a)", [field(1, tf_op) + field(5, path + tail)])
    ms = 10**9
    ops, modules = [], []
    for start in (0, 30 * ms):
        modules.append(event(1, start, 16 * ms))
        ops.append(event(10, start, 3 * ms))  # a loop wraps its body's operations: counts for nothing
        at = start
        for mid, dur in ((2, 4), (3, 2), (4, 12), (5, 1), (6, 3), (7, 4), (8, 2), (9, 4)):  # half milliseconds
            ops.append(event(mid, at, dur * ms // 2))
            at += dur * ms // 2
    modules.append(event(11, 18 * ms, 4 * ms))  # a join's chunk between the slices: another program
    ops.append(event(12, 18 * ms, 4 * ms))
    tpu = plane("/device:TPU:0", [("XLA Ops", 5_000_000_000, ops), ("XLA Modules", 5_000_000_000, modules)],
                event_meta, {tf_op: "tf_op"})
    return field(1, tpu)


def S(name, t0, t1, span_id, **attrs):
    return SP.S(name, t0, t1, 1, span_id, None, None, attrs)


SLICES = [
    S("sched.slice", 100.002, 100.018, 1, rows=12, ctx_tokens=4000, state_rows=32, state_bytes=32 * 152819712),
    S("sched.slice", 100.020, 100.036, 2, rows=10, ctx_tokens=4100, state_rows=32, state_bytes=32 * 152819712),
    S("sched.slice", 105.0, 105.01, 3, rows=3, ctx_tokens=900),  # after the traced part
]


@pytest.fixture()
def traced(tmp_path, monkeypatch):
    place(tmp_path, monkeypatch, ssm_xspace())
    scope_prefix_ms_per_step._DEVICE.clear()
    monkeypatch.setattr(SP, "finished", lambda t0, t1: [s for s in SLICES if s.t0 >= t0 and s.t1 <= t1])
    dev = trace.DeviceTrace(ops=[(5.0, 5.05, "fusion.1")], modules=[(5.0, 5.05, "jit_decode(5)")])
    tr = trace.Trace(devices={0: dev}, host=[(5.0, 5.06, "bench:window")])
    return Context.build(cfg=CFG, mix={}, cell={}, chip={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
                         trace=tr, records=[], slices=[], slice_steps=16, compiles=0,
                         t0=100.0, t1=100.06, window_t1=140.0)


def params_of(name):
    return json.loads((ROOT / "benchmark" / "layer_metrics" / f"{name}.json").read_text())["params"]


def test_the_new_metrics_files_and_the_reader_on_a_hand_made_trace(traced):
    assert params_of("step.ssm_ms_per_step") == {"module": "^jit_decode", "prefixes": ["ssm."]}
    assert params_of("ssm.state_roofline") == {"part": "state", "module": "^jit_decode",
                                               "prefixes": ["ssm.conv", "ssm.update"]}
    assert params_of("ssm.proj_roofline") == {"part": "proj", "module": "^jit_decode",
                                              "prefixes": ["ssm.in_proj", "ssm.out_proj"]}
    # 2 runs x 16 steps; ssm.* holds 11 of a run's 16 ms; the loop's own event and the prefill's count for nothing
    assert scope_prefix_ms_per_step.read(traced, params_of("step.ssm_ms_per_step")) == pytest.approx(22.0 / 32)
    assert ssm_roofline.live_rows(100.0, 100.06) == 11.0
    # conv + update: 7 ms a run = 0.4375 ms a step; 11 live rows' state read and written at 819 GB/s
    need = 2 * 11 * fam.state_bytes_per_row(CFG) / 819e9
    assert ssm_roofline.read(traced, params_of("ssm.state_roofline")) == pytest.approx(100 * need / 0.4375e-3)
    # in_proj + out_proj: 3.5 ms a run = 0.21875 ms a step
    need = fam.ssm_proj_bytes(CFG) / 819e9
    assert ssm_roofline.read(traced, params_of("ssm.proj_roofline")) == pytest.approx(100 * need / 0.21875e-3)


def test_the_new_reader_reads_nothing_where_there_is_nothing(traced, monkeypatch):
    """No run of the program, no operation under the scopes, no traced slice, a family that
    does not count these bytes, no trace at all: nothing, and no error."""
    state = params_of("ssm.state_roofline")
    assert ssm_roofline.read(traced, {**state, "module": "^jit_verify"}) is None
    assert ssm_roofline.read(traced, {**state, "prefixes": ["zz."]}) is None
    monkeypatch.setattr(SP, "finished", lambda t0, t1: [])
    assert ssm_roofline.read(traced, state) is None
    assert ssm_roofline.read(traced, params_of("ssm.proj_roofline")) is not None  # needs no slice
    other = json.loads((ROOT / "benchmark" / "configs" / "mistral-7b.json").read_text())
    dense = Context.build(cfg=other, mix={}, cell={}, chip={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
                          trace=traced.trace, records=[], slices=[], slice_steps=16, compiles=0,
                          t0=100.0, t1=100.06, window_t1=140.0)
    assert ssm_roofline.read(dense, state) is None
    bare = Context.build(cfg=CFG, mix={}, cell={}, chip=None, trace=None, records=[], slices=[], slice_steps=16,
                         compiles=0, t0=100.0, t1=100.06, window_t1=140.0)
    assert ssm_roofline.read(bare, state) is None and ssm_roofline.read(bare, params_of("ssm.proj_roofline")) is None
