"""The LongCat-Flash family (``benchmark/families/longcat_flash.py``): the
contract, the counts against ISSUE 28's reckoning, the program against the
plain reference at a small size in float32 (``forward`` through the
contiguous cache; the stepped paged session with a mid-flight chunked join),
the cell's ``--dry`` run with the dense reference in the family's place, and
the new readers on a hand-made trace and span list."""

import gzip
import json
import re
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import longcat_flash as fam
from benchmark.lib import family, scope_paths, slice_counts
from benchmark.lib import spans as SP
from benchmark.lib import trace
from benchmark.readers import (
    Context,
    latent_attention_roofline,
    moe_expert_roofline,
    scope_path_ms_per_step,
    slice_moe_rate,
)
from test_benchmark_dry import last_line, run_cli
from test_benchmark_scopes import event, field, place, plane

ROOT = Path(__file__).resolve().parents[2]
CELL = "longcat-flash-ep32.topics-closed"
CFG = json.loads((ROOT / "benchmark" / "configs" / "longcat-flash-ep32.json").read_text())
# the catalog row's ``config`` (model-configs guide, architectures.jsonl: LongCat-Flash-Chat)
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144, "ffn_hidden_size": 12288,
    "expert_ffn_hidden_size": 2048, "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "routed_scaling_factor": 6, "n_routed_experts": 512,
    "max_position_embeddings": 131072, "rms_norm_eps": 1e-05, "rope_theta": 10000000, "attention_method": "MLA",
    "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12,
}
TINY = {**CFG, **CFG["dry"], "model": "longcat-flash:tiny", "max_position_embeddings": 1024}


def test_the_family_keeps_the_contract_and_imports_nothing_of_the_program():
    module = family.load(CFG)
    assert module is fam and module.REQUIRED_KEYS == (
        "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size", "num_layers", "num_attention_heads",
        "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
        "zero_expert_num", "moe_topk", "routed_scaling_factor", "vocab_size", "rms_norm_eps", "rope_theta")
    source = Path(fam.__file__).read_text()
    assert not re.search(r"^\s*(from|import) .*cain_2025", source, re.M)
    assert all(hasattr(fam, n) for n in ("expert_bytes", "latent_bytes"))


def test_the_file_holds_every_published_width_and_states_the_cut(bench):
    entry = next(c for c in bench["configs"] if c["name"] == "longcat-flash-ep32")
    assert entry["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert CFG[key] < value and CFG["published"][key] == value
        else:
            assert CFG[key] == value and type(CFG[key]) is type(value), key
    # the guide's floors: >= 4 layers, >= 8 routed experts, >= 1/8 of the vocabulary
    assert CFG["num_layers"] >= 4 and CFG["n_routed_experts"] >= 8 and CFG["vocab_size"] * 8 >= 131072
    assert "32 chips share each layer" in CFG["deployment"] and CFG["first_expert"] == 0
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and "1/32" in cell["why"]
    listed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    # 16 of every cell, 6 of this one, the serving loop's own account 4
    assert len(listed) == 16 + 6 + 4
    assert {"ttft_p95_ms.topics-closed", "step.moe_ms_per_step", "moe.held_pairs_per_step",
            "moe.experts_touched_mean", "moe.expert_roofline", "attn.latent_roofline",
            "sched.stall_ms_per_s", "sched.stall_named_pct", "host.gc_ms_per_s",
            "host.process_stall_ms_per_s"} <= set(listed)


@pytest.mark.parametrize("what,got,want", [
    ("a double layer outside its experts", fam.layer_params_outside_experts(CFG), 639e6),
    ("one routed expert", fam.params(CFG)["expert"], 37.75e6),
    ("int8 bytes stored here", fam.weight_bytes(CFG), 7.66e9),
    ("cache bytes a token", fam.kv_bytes_per_token(CFG), 13824),
])
def test_counts_equal_the_issues_reckoning(what, got, want):
    assert got == pytest.approx(want, rel=5e-3), what


def test_bytes_and_flops_of_a_step():
    p = fam.params(CFG)
    touched = fam.experts_touched(CFG, 16)
    assert touched == pytest.approx(16 * (1 - (1 - 12 / 768) ** 16)) and 3.5 < touched < 3.7
    assert fam.expert_bytes(CFG, 2.5) == 2.5 * p["expert"] and fam.latent_bytes(CFG, 1000) == 13824000
    step = fam.decode_step_bytes(CFG, 16, 16 * 350)
    weights = 6 * (fam.layer_params_outside_experts(CFG) + p["router"] + touched * p["expert"]) + p["head"]
    assert step == pytest.approx(weights + 16 * 6144 + 16 * 350 * 13824 + 16 * 13824 + 16 * 16384 * 4)
    assert 4.7e9 < step < 5.0e9  # ISSUE 28: about 4.8 GB, 5.9 ms at 819 GB/s
    # a token: the blocks, a quarter of an expert a layer, the head; attention over 576 + 512 columns a head
    flops = fam.decode_token_flops(CFG, 0)
    assert flops == pytest.approx(2 * (6 * (fam.layer_params_outside_experts(CFG) + 0.25 * p["expert"]) + p["head"]))
    assert fam.decode_token_flops(CFG, 100) - flops == 100 * 12 * 2 * 64 * (576 + 512)
    assert 1.9e12 < fam.prefill_flops(CFG, 256) < 2.2e12  # ISSUE 28: a chunk's 2 TFLOP


def test_the_programs_own_counts_agree_with_the_familys():
    """``ModelConfig`` and ``utils/memory.py`` (the energy model's inputs, admission's bytes)."""
    from benchmark.lib.system import model_config
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.memory import (
        decode_kv_stream_bytes,
        estimate_weight_bytes,
    )

    mc = model_config(CFG)
    assert decode_kv_stream_bytes(mc, 1) == fam.kv_bytes_per_token(CFG)
    assert estimate_weight_bytes(mc, "int8") == pytest.approx(fam.weight_bytes(CFG), rel=0.01)
    assert mc.flops_per_token(300) == pytest.approx(fam.decode_token_flops(CFG, 300), rel=1e-3)


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
    assert set(weights) == {k for k in params if not k.endswith("norm") and "norm_" not in k}
    for name, leaf in weights.items():
        for a, b in zip(jax.tree_util.tree_leaves(leaf), jax.tree_util.tree_leaves(params[name])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    control = fam.make_weights(TINY, 11, bits=4)
    assert int(jnp.max(jnp.abs(control["w_qa_0"]["q"]))) == 7 and int(jnp.max(jnp.abs(control["embed"]["q"]))) == 127


def reference_head(params, hidden):
    """The head in float32 (the program's own multiplies a quantized head in bfloat16)."""
    return hidden.astype(jnp.float32) @ (params["lm_head"]["q"].astype(jnp.float32) * params["lm_head"]["s"])


def test_prefill_then_decode_through_the_cache_match_the_expanded_reference(tiny):
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import Transformer, forward

    mc, params, weights = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 128), 3, 259)
    want = fam.served_logits(TINY, weights, [[int(t) for t in tokens[0]]], [(0, 128)])[0]
    k0, v0 = Transformer(cfg=mc, params=params).init_cache(1, 128, jnp.float32)
    assert k0.shape == (4, 1, 1, 128, 24) and v0.shape == (4, 1, 1, 128, 0)
    stats = {}
    hidden, kc, vc = forward(params, mc, tokens[:, :100], jnp.int32(0), k0, v0, stats=stats)
    assert float(jnp.max(jnp.abs(reference_head(params, hidden[0]) - want[:100]))) <= 1e-4
    assert int(jnp.sum(stats["moe"][:3])) == 100 * 2 * 3  # tokens x layers x moe_topk
    step = jax.jit(lambda tok, t, kc, vc: forward(params, mc, tok, t, kc, vc))
    worst = 0.0
    for t in range(100, 128):
        hidden, kc, vc = step(tokens[:, t : t + 1], jnp.int32(t), kc, vc)
        worst = max(worst, float(jnp.max(jnp.abs(reference_head(params, hidden[0, 0]) - want[t]))))
    assert worst <= 1e-4


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
    while sess.active:
        step()
    sess.close()
    assert [got[r.prompt] for r in reqs] == alone
    for s in slices:  # every pair of every live row's token is counted once
        assert s["moe_held"] + s["moe_zero"] + s["moe_absent"] == s["moe_tokens"] * 2 * 3
        assert 0 < s["moe_steps"] <= 8 and s["moe_touched"] <= s["moe_steps"] * 2 * 4
    assert slices[0]["moe_tokens"] == 16 and sum(s["moe_zero"] for s in slices) > 0


# -- the cell's rehearsal, and the dense reference in the family's place ---------

DENSE_IN_ITS_PLACE = '''\
from . import dense
from .longcat_flash import *  # noqa: F401,F403


def _as_dense(cfg):
    return {**cfg, "intermediate_size": cfg["ffn_hidden_size"], "num_hidden_layers": cfg["num_layers"],
            "num_key_value_heads": cfg["num_attention_heads"],
            "head_dim": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]}


def make_weights(cfg, seed, bits=8):
    return dense.make_weights(_as_dense(cfg), seed, bits)


def served_logits(cfg, weights, token_rows, spans):
    return dense.served_logits(_as_dense(cfg), weights, token_rows, spans)
'''


def test_the_cells_dry_run_is_correct_and_the_dense_reference_in_its_place_is_not(tmp_path):
    line = last_line(run_cli(ROOT, "--workload", CELL, "--seed", str(2**31 + 28), "--seconds", "2",
                             "--trace", "1", "--dry"))
    assert line["correct"] is True and line["failed"] == 0
    assert line["check"]["logit_gap_max"]["value"] < 0.05 < line["check"]["logit_gap_max"]["limit"]
    assert {"dry.moe.held_pairs_per_step", "dry.moe.experts_touched_mean", "dry.ttft_p95_ms.topics-closed",
            "dry.sched.live_rows_mean"} <= set(line["metrics"])
    # 3 of 12 outputs a token, 4 of them held: about a pair a row, a layer and a step
    assert 0 < line["metrics"]["dry.moe.experts_touched_mean"]["value"] <= line["metrics"][
        "dry.moe.held_pairs_per_step"]["value"] < 4 * 3
    # device readers find no TPU plane on the CPU and stay silent
    assert not {"dry.moe.expert_roofline", "dry.attn.latent_roofline", "dry.step.moe_ms_per_step"} & set(line["metrics"])
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".jax_cache", ".out", "__pycache__"))
    (tmp_path / "benchmark" / "families" / "longcat_denseref.py").write_text(DENSE_IN_ITS_PLACE)
    path = tmp_path / "benchmark" / "configs" / "longcat-flash-ep32.json"
    path.write_text(json.dumps({**CFG, "family": "longcat_denseref"}))
    wrong = last_line(run_cli(tmp_path, "--workload", CELL, "--seed", str(2**31 + 28), "--seconds", "2",
                              "--trace", "0", "--dry"))
    assert wrong["correct"] is False and wrong["failed"] == 0
    assert wrong["check"]["logit_gap_max"]["value"] > wrong["check"]["logit_gap_max"]["limit"]


# -- the new readers on a hand-made trace and span list ---------------------------

def moe_xspace():
    """Two runs of the decode slice, 10 ms each: 2 ms in the experts' matmuls,
    1 ms dispatch, 1 ms router, 2 ms attention core + gather, 3 ms mlp, 1 ms unscoped."""
    tf_op = 7
    path = "jit(decode)/while/body/while/body/closed_call/"
    names = {2: "moe.experts/while/body/moe.experts/dot_general:", 3: "moe.experts/while/body/moe.dispatch/gather:",
             4: "moe.router/dot_general:", 5: "attn.core/attn.kv_gather/gather:", 6: "attn.core/dot_general:",
             7: "mlp/dot_general:"}
    event_meta = {1: ("jit_decode(5)", []), 8: ("%copy.1 = bf16[8] copy(%c)", []),
                  9: ("%while.2 = (s32[]) while(%t)", [field(1, tf_op) + field(5, path + "moe.experts/while:")])}
    for mid, tail in names.items():
        event_meta[mid] = (f"%fusion.{mid} = f32[8] fusion(%a)", [field(1, tf_op) + field(5, path + tail)])
    ms = 10**9
    ops, modules = [], []
    for start in (0, 20 * ms):
        modules.append(event(1, start, 10 * ms))
        ops.append(event(9, start, 3 * ms))  # the loop wraps its body's operations: counts for nothing
        at = start
        for mid, dur in ((2, 2), (3, 1), (4, 1), (5, 1), (6, 1), (7, 3), (8, 1)):
            ops.append(event(mid, at, dur * ms))
            at += dur * ms
    tpu = plane("/device:TPU:0", [("XLA Ops", 5_000_000_000, ops), ("XLA Modules", 5_000_000_000, modules)],
                event_meta, {tf_op: "tf_op"})
    return field(1, tpu)


def S(name, t0, t1, span_id, **attrs):
    return SP.S(name, t0, t1, 1, span_id, None, None, attrs)


SLICES = [
    S("sched.slice", 100.002, 100.012, 1, rows=15, ctx_tokens=5000, moe_held=400, moe_zero=5700, moe_absent=11180,
      moe_touched=336, moe_steps=16, moe_tokens=240),
    S("sched.slice", 100.020, 100.030, 2, rows=16, ctx_tokens=5240, moe_held=360, moe_zero=6000, moe_absent=12072,
      moe_touched=288, moe_steps=16, moe_tokens=256),
    S("sched.slice", 105.0, 105.01, 3, rows=16, ctx_tokens=5400, moe_held=410, moe_zero=6000, moe_absent=12022,
      moe_touched=300, moe_steps=16, moe_tokens=256),  # after the traced part, inside the window
    S("sched.slice", 106.0, 106.01, 4, rows=3, ctx_tokens=900),  # a model without an expert layer: no counts
]


@pytest.fixture()
def traced(tmp_path, monkeypatch):
    place(tmp_path, monkeypatch, moe_xspace())
    scope_paths._DEVICE.clear()
    monkeypatch.setattr(SP, "finished", lambda t0, t1: [s for s in SLICES if s.t0 >= t0 and s.t1 <= t1])
    dev = trace.DeviceTrace(ops=[(5.0, 5.03, "fusion.1")], modules=[(5.0, 5.03, "jit_decode(5)")])
    tr = trace.Trace(devices={0: dev}, host=[(5.0, 5.04, "bench:window")])
    return Context.build(cfg=CFG, mix={}, cell={}, chip={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
                         trace=tr, records=[], slices=[], slice_steps=16, compiles=0,
                         t0=100.0, t1=100.04, window_t1=140.0)


def params_of(name):
    return json.loads((ROOT / "benchmark" / "layer_metrics" / f"{name}.json").read_text())["params"]


def test_innermost_scope_knows_the_expert_layers_names():
    assert scope_paths.innermost("jit(decode)/while/body/moe.experts/while/body/moe.combine/scatter-add:") == "moe.combine"
    assert scope_paths.innermost("jit(decode)/while/body/mlp/moe.router/dot_general:") == "moe.router"
    assert scope_paths.innermost("jit(decode)/while/body/attn.core/attn.kv_gather/gather:") == "attn.kv_gather"
    assert scope_paths.innermost("jit(decode)/while:") is None
    assert scope_paths.wanted("moe.zero", ["moe."]) and not scope_paths.wanted("mlp", ["moe."])


def test_the_new_readers_on_a_hand_made_trace(traced):
    # 2 runs x 16 steps; moe.* holds 4 of a run's 10 ms; the loop's own event counts for nothing
    assert scope_path_ms_per_step.read(traced, params_of("step.moe_ms_per_step")) == pytest.approx(8.0 / 32)
    # the whole window's three counted slices, per layer (6) and step (48)
    assert slice_moe_rate.read(traced, params_of("moe.held_pairs_per_step")) == pytest.approx(1170 / (48 * 6))
    assert slice_moe_rate.read(traced, params_of("moe.experts_touched_mean")) == pytest.approx(924 / (48 * 6))
    # the traced part's two slices: 624 expert reads in 32 steps against 2 ms of matmuls a run
    need = fam.expert_bytes(CFG, 624 / 32) / 819e9
    got = moe_expert_roofline.read(traced, params_of("moe.expert_roofline"))
    assert got == pytest.approx(100 * need / (0.004 / 32)) and got > 100  # the hand-made times are not a chip's
    context = (16 * (5000 + 120) + 16 * (5240 + 128)) / 32
    need = fam.latent_bytes(CFG, context) / 819e9
    assert latent_attention_roofline.read(traced, params_of("attn.latent_roofline")) == pytest.approx(
        100 * need / (0.004 / 32))


def test_the_new_readers_read_nothing_from_an_older_program(traced, monkeypatch):
    """The parent's trace has no ``moe.*`` scope and its spans no counts: every reader stays silent."""
    monkeypatch.setattr(SP, "finished", lambda t0, t1: [s for s in SLICES[3:] if s.t0 >= t0 and s.t1 <= t1])
    for name in ("moe.held_pairs_per_step", "moe.experts_touched_mean"):
        assert slice_moe_rate.read(traced, params_of(name)) is None
    assert moe_expert_roofline.read(traced, params_of("moe.expert_roofline")) is None
    assert latent_attention_roofline.read(traced, params_of("attn.latent_roofline")) is None
    assert slice_counts.slices(100.0, 140.0) == []
    monkeypatch.setattr(SP, "finished", lambda t0, t1: SLICES[:3])
    place_raw = moe_xspace().replace(b"moe.", b"xxx.").replace(b"attn.", b"xxxx.").replace(b"/mlp/", b"/xxx/")
    import benchmark.lib.scopes as scopes
    d = next(scopes.TRACE_DIR.glob("plugins/profile/*/"))
    (d / "host.xplane.pb").write_bytes(place_raw)
    scope_paths._DEVICE.clear()
    assert scope_path_ms_per_step.read(traced, params_of("step.moe_ms_per_step")) is None
    assert moe_expert_roofline.read(traced, params_of("moe.expert_roofline")) is None
