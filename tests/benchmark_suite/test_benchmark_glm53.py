"""The GLM-5.3-Flash family (``benchmark/families/glm53_flash.py``): the
contract, the file against the published config, the byte and FLOP counts
against the deployment's reckoning by hand and against the program's own, the check's
control (the reference's int4 weights fail the rehearsal's limit), the cell's
``--dry`` rehearsal, and the four new metrics read by name on a hand-made
trace and span list."""

import json
from pathlib import Path

import jax.numpy as jnp
import pytest

from benchmark.families import glm53_flash as fam
from benchmark.lib import family
from benchmark.lib import spans as SP
from benchmark.lib import trace
from benchmark.readers import Context, scope_prefix_ms_per_step, ssm_roofline
from test_benchmark_dry import last_line, run_cli
from test_benchmark_scopes import event, field, place, plane

ROOT = Path(__file__).resolve().parents[2]
CELL = "glm53-flash-ep8-pp5.topics-closed"
CFG = json.loads((ROOT / "benchmark" / "configs" / "glm53-flash-ep8-pp5.json").read_text())
TINY = {**CFG, **CFG["dry"], "model": "glm53:tiny", "max_position_embeddings": 1024}
NEW = ("step.kda_ms_per_step", "kda.state_roofline", "kda.proj_roofline", "step.index_ms_per_step")


# the published config.json (zai-org/GLM-5.3-Flash), every key
_KDA_LAYERS = [i for i in range(45) if i % 4 != 3]
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 3, "hc_eps": 1e-06, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "head_dim": 0, "hidden_act": "silu", "hidden_size": 4096, "index_head_dim": 128, "index_kpool": 4,
    "index_kpool_always_select_tail": True, "index_kpool_compress": True, "index_n_heads": 32, "index_topk": 2048,
    "index_share_for_mtp_iteration": True, "indexer_rope_interleave": True, "indexer_types": ["full"] * 45,
    "intermediate_size": 12288, "kv_lora_rank": 512,
    "layer_types": ["linear_attention" if i in _KDA_LAYERS else "deepseek_sparse_attention" for i in range(45)],
    "linear_attn_config": {"num_heads": 64, "gate_lower_bound": -5, "head_dim": 128, "short_conv_kernel_size": 4,
                           "kda_layers": _KDA_LAYERS, "full_attn_layers": list(range(3, 45, 4))},
    "max_position_embeddings": 1048576, "mhc": True, "mla_use_nope": True,
    "mlp_layer_types": ["dense"] * 3 + ["sparse"] * 42, "model_type": "glm5_next_text", "moe_intermediate_size": 2048,
    "n_group": 1, "n_routed_experts": 288, "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 45, "num_key_value_heads": 64, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_head_dim": 256, "qk_nope_head_dim": 256, "qk_rope_head_dim": 0, "rms_norm_eps": 1e-05,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "swiglu_limit": 10, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 256, "vocab_size": 154880,
}


def test_the_family_keeps_the_contract_and_imports_nothing_of_the_program():
    assert family.load(CFG) is fam and set(fam.REQUIRED_KEYS) <= set(CFG)
    source = (ROOT / "benchmark" / "families" / "glm53_flash.py").read_text()
    assert "cain_2025" not in source and "import benchmark" not in source and "from .." not in source
    for name in ("weight_bytes", "kv_bytes_per_token", "latent_bytes", "state_bytes_per_row", "expert_layers",
                 "experts_touched", "expert_bytes", "ssm_state_bytes", "ssm_proj_bytes", "decode_step_bytes",
                 "decode_token_flops", "prefill_flops"):
        assert callable(getattr(fam, name)), name


def test_the_file_holds_every_published_key_but_the_three_cut(bench):
    entry = next(c for c in bench["configs"] if c["name"] == "glm53-flash-ep8-pp5")
    assert entry["source"] == CFG["source"] == "https://huggingface.co/zai-org/GLM-5.3-Flash/blob/main/config.json"
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    differ = {k for k, v in PUBLISHED.items() if CFG.get(k, "absent") != v}
    assert differ == set(entry["reduced"])
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"], CFG["vocab_size"]) == (9, 36, 154880 // 8)
    assert CFG["published"] == {"num_hidden_layers": 45, "n_routed_experts": 288, "vocab_size": 154880}
    assert CFG["engine"] == json.loads((ROOT / "benchmark" / "configs" / "granite-4h-small-ep8.json").read_text())[
        "engine"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "glm53-flash-ep8-pp5", "traffic": "topics-closed", "chips": 1}
    listed = {m["name"]: m for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert len(listed) == 26 + 4
    for name in NEW:
        assert listed[name]["workloads"] == [CELL] and listed[name]["layer"] == "model step", name
    assert {"attn.latent_roofline", "moe.expert_roofline", "step.mfu", "sched.stall_ms_per_s"} <= set(listed)
    # step.hc_ms_per_step stays xing4's alone: test_benchmark_xing4.py pins its list to that one cell
    assert not {"ssm.state_roofline", "step.ssm_ms_per_step", "moe.held_pairs_per_step",
                "step.hc_ms_per_step"} & set(listed)


P = fam.params(CFG)


@pytest.mark.parametrize("what,got,want", [
    # the deployment's table (PERF.md section 4), each by hand from the published widths
    ("a KDA mixer", P["kda"], 4096 * (3 * 8192 + 64 + 2 * 128) + 2 * 128 * 8192 + 8192 * 4096 + 4 * 3 * 8192),
    ("a KDA mixer, 137.7M", P["kda"], 137.7e6),
    ("the indexer, 7.0M", P["index"], 1536 * 32 * 128 + 4096 * (128 + 32)),
    ("a DSA mixer, 124.4M", P["dsa"], 124.4e6),
    ("a dense FFN, 151.0M", P["ffn"], 3 * 4096 * 12288),
    ("one expert, 25.17M", P["expert"], 3 * 4096 * 2048),
    ("the router", P["router"], 4096 * 288),
    ("this chip's bytes: 7.42 GB int8 + 28 MB of maps", fam.weight_bytes(CFG), 7.42e9 + 28.3e6),
    ("state a row, 30.4 MB", fam.state_bytes_per_row(CFG), 7 * (64 * 128 * 128 * 4 + 3 * 3 * 8192 * 2)),
    ("cached bytes a token: 2 layers x (512 + 128) x 2 B", fam.kv_bytes_per_token(CFG), 2560),
    ("the KDA projections", fam.ssm_proj_bytes(CFG), 7 * (4096 * 24896 + 2 * 128 * 8192 + 8192 * 4096)),
    ("12 live rows' state read and written, ~0.70 GB", fam.ssm_state_bytes(CFG, 12), 24 * 30392320),
    ("experts touched a layer by 12 rows, ~10.2", fam.experts_touched(CFG, 12), 36 * (1 - (280 / 288) ** 12)),
    ("expert layers", fam.expert_layers(CFG), 6),
])
def test_counts_equal_the_reckoning_by_hand(what, got, want):
    assert got == pytest.approx(want, rel=3e-3), what


def test_bytes_and_flops_of_a_step():
    outside = fam.outside_experts_params(CFG)
    assert outside == 7 * P["kda"] + 2 * P["dsa"] + 3 * P["ffn"] + 6 * (P["expert"] + P["router"])
    assert outside + P["head"] == pytest.approx(1.90e9, rel=0.01)  # ~1.90 GB outside the experts
    touched = fam.experts_touched(CFG, 12)
    assert 6 * fam.expert_bytes(CFG, touched) == pytest.approx(1.54e9, rel=0.02)
    step = fam.decode_step_bytes(CFG, 12, 3600)
    assert step == pytest.approx(outside + P["head"] + fam.map_bytes(CFG) + 6 * touched * P["expert"]
                                 + 24 * 30392320 + 3600 * 2560 + 12 * 2560 + 12 * 4096 + 12 * 19360 * 4)
    assert fam.latent_bytes(CFG, 1000) == 1000 * 2 * 512 * 2
    flops = fam.decode_token_flops(CFG, 0)
    tokens = outside + 6 * 1.0 * P["expert"] + 18 * P["map"]  # 8 x 36 / 288 = 1 routed expert a token here
    assert flops == pytest.approx(2 * (tokens + P["head"]) + 7 * 7 * 64 * 128 * 128)
    # past the indexer's reach attention reads 2,048 blocks of 4; the indexer scores every block
    at = fam.decode_token_flops(CFG, 20000) - flops
    assert at == pytest.approx(2 * (2 * 64 * 8192 * 1024 + 2 * 32 * 128 * 5000))
    assert fam.prefill_flops(CFG, 256) == pytest.approx(256 * fam.decode_token_flops(CFG, 128.5) - 255 * 2 * P["head"])


def test_the_programs_own_counts_agree_with_the_familys():
    """``ModelConfig``, ``utils/memory.py`` and ``obs/energy.py`` (the energy model's inputs, admission's bytes)."""
    from benchmark.lib.system import model_config
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.energy import slice_window_stats
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.memory import (
        decode_kv_stream_bytes,
        decode_state_stream_bytes,
        decode_weight_stream_bytes,
        estimate_weight_bytes,
    )

    mc = model_config(CFG)
    assert mc.layer_runs == ((True, 0, 3), (False, 3, 1), (False, 4, 3), (False, 7, 1), (False, 8, 1))
    assert (mc.state_layers, mc.cache_layers, mc.router_outputs, mc.n_experts) == (7, 2, 288, 36)
    assert mc.cache_k_width == 640 and mc.active_experts_per_token == 2.0
    assert decode_kv_stream_bytes(mc, 1) == fam.kv_bytes_per_token(CFG)
    assert mc.state_bytes_per_row(2) == fam.state_bytes_per_row(CFG)
    assert decode_state_stream_bytes(mc, 12) == fam.ssm_state_bytes(CFG, 12)
    assert estimate_weight_bytes(mc, "int8") == pytest.approx(fam.weight_bytes(CFG), rel=0.01)
    for context in (300, 20000):
        assert mc.flops_per_token(context) == pytest.approx(fam.decode_token_flops(CFG, context), rel=1e-3)
    stream = fam.outside_experts_params(CFG) + 6 * P["expert"] + P["head"]
    assert decode_weight_stream_bytes(mc, "int8") == pytest.approx(stream, rel=0.02)
    est = slice_window_stats(mc, [(300, 16)] * 12, duration_s=0.4, steps=16, quantize="int8")
    assert est["flops"] == pytest.approx(12 * 16 * fam.decode_token_flops(CFG, 316), rel=1e-3)


def test_the_int4_controls_choice_fails_the_rehearsals_limit():
    """At the rehearsal's size: what the int4 reference puts first, judged by
    the int8 reference, lies further below its best than the limit allows."""
    import jax

    tokens = [int(t) for t in jax.random.randint(jax.random.PRNGKey(3), (200,), 3, 259)]
    spans = [(50, 150)]
    ref = fam.served_logits(TINY, fam.make_weights(TINY, 5), [tokens], spans)[0]
    control = jnp.argmax(fam.served_logits(TINY, fam.make_weights(TINY, 5, bits=4), [tokens], spans)[0], axis=-1)
    gaps = jnp.max(ref, -1) - jnp.take_along_axis(ref, control[:, None], -1)[:, 0]
    assert float(jnp.max(gaps)) > 2 * TINY["check"]["max_logit_gap"]


def test_the_cells_dry_run_is_correct_and_its_device_metrics_stay_silent():
    line = last_line(run_cli(ROOT, "--workload", CELL, "--seed", str(2**31 + 41), "--seconds", "2",
                             "--trace", "1", "--dry"))
    assert line["correct"] is True and line["failed"] == 0
    assert line["check"]["logit_gap_max"]["value"] <= line["check"]["logit_gap_max"]["limit"] == 0.05
    assert {"dry.ttft_p95_ms.topics-closed", "dry.sched.live_rows_mean", "dry.moe.pairs_per_expert_layer"} <= set(
        line["metrics"])
    # device readers find no TPU plane on the CPU and stay silent
    assert not {f"dry.{name}" for name in NEW} & set(line["metrics"])


# -- the four new metrics on a hand-made trace and span list -----------------------------

def kda_xspace():
    """Two runs of the decode slice, 16 ms each: 2 ms of kda.in_proj, 1 of kda.conv, 3 of
    kda.update, 0.5 of kda.gate_norm, 1.5 of kda.out_proj, 1 of attn.index under attn.core,
    1 more of attn.core, 2 of experts, 4 unscoped."""
    tf_op = 7
    path = "jit(decode)/while/body/while/body/closed_call/"
    names = {2: "kda.in_proj/dot_general:", 3: "kda.conv/mul:", 4: "kda.update/add:", 5: "kda.gate_norm/mul:",
             6: "kda.out_proj/dot_general:", 7: "attn.core/attn.index/top_k:", 8: "attn.core/dot_general:",
             9: "moe.experts/pallas_moe_gate_up:"}
    event_meta = {1: ("jit_decode(5)", []), 10: ("%copy.1 = bf16[8] copy(%c)", [])}
    for mid, tail in names.items():
        event_meta[mid] = (f"%fusion.{mid} = f32[8] fusion(%a)", [field(1, tf_op) + field(5, path + tail)])
    ms = 10**9
    ops, modules = [], []
    for start in (0, 30 * ms):
        modules.append(event(1, start, 16 * ms))
        at = start
        for mid, dur in ((2, 4), (3, 2), (4, 6), (5, 1), (6, 3), (7, 2), (8, 2), (9, 4), (10, 8)):  # half ms
            ops.append(event(mid, at, dur * ms // 2))
            at += dur * ms // 2
    tpu = plane("/device:TPU:0", [("XLA Ops", 5_000_000_000, ops), ("XLA Modules", 5_000_000_000, modules)],
                event_meta, {tf_op: "tf_op"})
    return field(1, tpu)


def S(name, t0, t1, span_id, **attrs):
    return SP.S(name, t0, t1, 1, span_id, None, None, attrs)


SLICES = [
    S("sched.slice", 100.002, 100.018, 1, rows=12, ctx_tokens=4000, index_blocks_seen=9000, index_blocks_kept=9000),
    S("sched.slice", 100.020, 100.036, 2, rows=10, ctx_tokens=4100, index_blocks_seen=8000, index_blocks_kept=8000),
]


@pytest.fixture()
def traced(tmp_path, monkeypatch):
    place(tmp_path, monkeypatch, kda_xspace())
    scope_prefix_ms_per_step._DEVICE.clear()
    monkeypatch.setattr(SP, "finished", lambda t0, t1: [s for s in SLICES if s.t0 >= t0 and s.t1 <= t1])
    dev = trace.DeviceTrace(ops=[(5.0, 5.05, "fusion.1")], modules=[(5.0, 5.05, "jit_decode(5)")])
    tr = trace.Trace(devices={0: dev}, host=[(5.0, 5.06, "bench:window")])
    return Context.build(cfg=CFG, mix={}, cell={}, chip={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
                         trace=tr, records=[], slices=[], slice_steps=16, compiles=0,
                         t0=100.0, t1=100.06, window_t1=140.0)


def params_of(name):
    return json.loads((ROOT / "benchmark" / "layer_metrics" / f"{name}.json").read_text())


def test_the_four_metrics_read_by_name_on_a_hand_made_trace(traced):
    assert params_of("step.kda_ms_per_step") == {
        "reader": "scope_prefix_ms_per_step", "params": {"module": "^jit_decode", "prefixes": ["kda."]}}
    assert params_of("step.index_ms_per_step") == {
        "reader": "scope_prefix_ms_per_step", "params": {"module": "^jit_decode", "prefixes": ["attn.index"]}}
    assert params_of("kda.state_roofline") == {"reader": "ssm_roofline", "params": {
        "part": "state", "module": "^jit_decode", "prefixes": ["kda.conv", "kda.update"]}}
    assert params_of("kda.proj_roofline") == {"reader": "ssm_roofline", "params": {
        "part": "proj", "module": "^jit_decode", "prefixes": ["kda.in_proj", "kda.out_proj"]}}
    read = {name: params_of(name)["params"] for name in NEW}
    # 2 runs x 16 steps; kda.* holds 8 of a run's 16 ms, attn.index 1 (inside attn.core)
    assert scope_prefix_ms_per_step.read(traced, read["step.kda_ms_per_step"]) == pytest.approx(16.0 / 32)
    assert scope_prefix_ms_per_step.read(traced, read["step.index_ms_per_step"]) == pytest.approx(2.0 / 32)
    # conv + update: 4 ms a run = 0.25 ms a step; 11 live rows' KDA state read and written at 819 GB/s
    need = 2 * 11 * fam.state_bytes_per_row(CFG) / 819e9
    assert ssm_roofline.read(traced, read["kda.state_roofline"]) == pytest.approx(100 * need / 0.25e-3)
    # in_proj + out_proj: 3.5 ms a run = 0.21875 ms a step
    need = fam.ssm_proj_bytes(CFG) / 819e9
    assert ssm_roofline.read(traced, read["kda.proj_roofline"]) == pytest.approx(100 * need / 0.21875e-3)


def test_the_four_metrics_read_nothing_where_there_is_nothing(traced, monkeypatch):
    """Another program, no operation under the scopes (granite's trace has none), no trace: nothing, no error."""
    for name in NEW:
        p = params_of(name)["params"]
        reader = ssm_roofline if "roofline" in name else scope_prefix_ms_per_step
        assert reader.read(traced, {**p, "module": "^jit_verify"}) is None
        assert reader.read(traced, {**p, "prefixes": ["zz."]}) in (None, 0.0)
        bare = Context.build(cfg=CFG, mix={}, cell={}, chip=None, trace=None, records=[], slices=[], slice_steps=16,
                             compiles=0, t0=100.0, t1=100.06, window_t1=140.0)
        assert reader.read(bare, p) is None
