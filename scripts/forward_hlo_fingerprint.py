#!/usr/bin/env python3
"""sha256 of the StableHLO text that ``forward`` lowers to at a benchmark
configuration's FULL size (abstract weights: nothing is allocated, nothing
runs), in the cache variants the served path uses: a prefill chunk over
the contiguous cache, batched decode over the carry, and paged decode over
pool + side caches (the XLA parts path over the pool in place, which every
cell's session compiles). Run it in two checkouts to
show that a change to ``models/transformer.py`` left another model's
program as it was (PERF.md, PR 32):

    python3 scripts/forward_hlo_fingerprint.py phi3-mini mistral-7b longcat-flash-ep32

``--slice`` fingerprints instead the program a served session runs between
two fetches (``jit_decode``: ``JaxEngine._paged_batch_decode_step_fn``, 16
steps over a carry of 32 rows, lowered for a DESCRIBED v5e so that the chip's
attention and expert kernels are the ones chosen): what ``forward`` alone
does not show of ``engine/jax_engine.py`` (PERF.md, PR 34).
"""

import hashlib
import json
import os
import re
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.lib.system import model_config  # noqa: E402
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import JaxEngine  # noqa: E402
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.paged_kv import pool_widths  # noqa: E402
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import quantize_leaf  # noqa: E402
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import forward, init_params, logits_for  # noqa: E402
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_attention import (  # noqa: E402
    pool_page_owners,
    xla_paged_decode_attention_parts,
)

ROWS, CACHE, PAGES, PAGE, TABLE, SIDE = 16, 512, 64, 128, 4, 256


def texts(cfg):
    params = jax.eval_shape(
        lambda k: init_params(cfg, k, jnp.bfloat16, post=lambda n, leaf: quantize_leaf(n, leaf, "int8")),
        jax.random.PRNGKey(0))
    sds = jax.ShapeDtypeStruct
    lead = (cfg.cache_layers, ROWS, cfg.cache_heads)
    kc = sds(lead + (CACHE, cfg.cache_k_width), jnp.bfloat16)
    vc = sds(lead + (CACHE, cfg.cache_v_width), jnp.bfloat16)
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    yield "prefill", jax.jit(lambda p, t, k, v: forward(p, cfg, t, jnp.int32(0), k, v)).lower(
        params, i32(ROWS, 256), kc, vc).as_text()
    yield "carry", jax.jit(lambda p, t, o, k, v: logits_for(p, cfg, forward(p, cfg, t, o, k, v)[0][:, 0])).lower(
        params, i32(ROWS, 1), i32(ROWS), kc, vc).as_text()
    if cfg.latent:
        attend = JaxEngine._paged_decode_attention(None, cfg)  # the latent closure reads nothing of the engine
    else:
        # what the dense cells' sessions compile (``impl: xla-pool``): the XLA parts path over the pool in place
        def attend(q, kc, vc, lengths):
            return xla_paged_decode_attention_parts(
                q, kc["pool"], vc["pool"], kc["table"], lengths, owners=kc.get("owners"))

    def paged(p, t, o, pk, pv, table, sk, sv, plens):
        shared = {"table": table, "write_pos": o - plens, "prompt_lens": plens}
        k = {**shared, "pool": pk, "side": sk, "owners": pool_page_owners(table, plens, PAGES, PAGE)}
        v = {**shared, "pool": pv, "side": sv}
        stats = {}
        extra = {"token_mask": jnp.ones((ROWS, 1), bool), "stats": stats} if cfg.n_experts else {}
        h, k, v = forward(p, cfg, t, o, k, v, attend, **extra)
        return logits_for(p, cfg, h[:, 0]), k["side"], v["side"], stats.get("moe")

    kw, vw = pool_widths(cfg, True)
    pool = lambda w: sds((cfg.cache_layers, PAGES, cfg.cache_heads, PAGE, w), jnp.bfloat16)  # noqa: E731
    side = lambda w: sds(lead + (SIDE, w), jnp.bfloat16)  # noqa: E731
    yield "paged", jax.jit(paged).lower(
        params, i32(ROWS, 1), i32(ROWS), pool(kw), pool(vw), i32(ROWS, TABLE), side(cfg.cache_k_width),
        side(cfg.cache_v_width), i32(ROWS)).as_text()


def session_texts(cfg, rows=32):
    """StableHLO of the decode slice at the cell's session shape (bucket 32,
    128 pool pages, a 4-page table, 256 side slots) and of a joiner's
    prefill chunk, for a described v5e."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine as je
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import Transformer
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops import (
        pallas_attention,
        pallas_moe,
        pallas_paged_attention,
        pallas_quant,
        pallas_ssm,
    )

    one = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    je._stepped_donation = lambda: {"donate_argnums": (1,)}
    for module in (je, pallas_attention, pallas_paged_attention, pallas_moe, pallas_quant, pallas_ssm):
        module.on_tpu = lambda: True

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def place(tree):
        return jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), tree)

    params = place(jax.eval_shape(
        lambda k: init_params(cfg, k, jnp.bfloat16, post=lambda n, leaf: quantize_leaf(n, leaf, "int8")),
        jax.random.PRNGKey(0)))
    eng = JaxEngine(registry={cfg.name: cfg}, dtype=jnp.bfloat16, quantize="int8", paged_kv=True,
                    decode_attention=pallas_attention.pallas_decode_attention,
                    prefill_attention=pallas_attention.pallas_prefill_attention)
    eng._models[cfg.name] = Transformer(cfg, params)
    kw, vw = pool_widths(cfg, True)
    lead = (cfg.cache_layers, rows, cfg.cache_heads)
    carry = {
        "tokens": sds((rows,), jnp.int32), "offsets": sds((rows,), jnp.int32),
        "prompt_lens": sds((rows,), jnp.int32), "remaining": sds((rows,), jnp.int32),
        "temps": sds((rows,), jnp.float32), "top_ps": sds((rows,), jnp.float32), "rps": sds((rows,), jnp.float32),
        "presence": sds((rows, cfg.vocab_size), jnp.bool_), "done": sds((rows,), jnp.bool_),
        "rngs": sds((rows, 2), jnp.uint32), "table": sds((rows, TABLE), jnp.int32),
        "side_k": sds(lead + (SIDE, cfg.cache_k_width), jnp.bfloat16),
        "side_v": sds(lead + (SIDE, cfg.cache_v_width), jnp.bfloat16),
        "pool_k": sds((cfg.cache_layers, 2 * PAGES, cfg.cache_heads, PAGE, kw), jnp.bfloat16),
        "pool_v": sds((cfg.cache_layers, 2 * PAGES, cfg.cache_heads, PAGE, vw), jnp.bfloat16),
    }
    if cfg.n_experts:
        carry["moe_counts"] = sds((5,), jnp.int32)
    if getattr(cfg, "state_layers", 0):
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.ssm import init_state

        carry["ssm"] = place(jax.eval_shape(lambda: init_state(cfg, rows, jnp.bfloat16)))
    def text(lowered):
        # a Mosaic kernel's serialized body names the files and lines it was traced from (the checkout's path
        # among them): it is left out, the call with its operands, shapes and grid stays; ops/*.py compare as files
        return re.sub(r'(\\22body\\22: \\22)[^\\]*', r"\1", lowered.as_text())

    step = eng._paged_batch_decode_step_fn(cfg.name, 16, 0, False, False, True, False, False, carry=carry)
    yield "slice", text(step.lower(params, carry, sds((), jnp.int32)))
    # a joiner's 256-token chunk (``jit_prefill``) over its private contiguous cache
    kc = sds((cfg.cache_layers, 1, cfg.cache_heads, 256, cfg.cache_k_width), jnp.bfloat16)
    vc = sds((cfg.cache_layers, 1, cfg.cache_heads, 256, cfg.cache_v_width), jnp.bfloat16)
    if "ssm" in carry:
        kc = {"kv": kc, "ssm": place(jax.eval_shape(lambda: init_state(cfg, 1, jnp.bfloat16)))}
    yield "chunk", text(eng._prefill_fn(cfg.name, 256, 256).lower(
        params, sds((1, 256), jnp.int32), sds((), jnp.int32), sds((1,), jnp.int32), kc, vc))


def main(names):
    sliced = "--slice" in names
    names = [n for n in names if n != "--slice"] or ["phi3-mini", "mistral-7b", "longcat-flash-ep32", "xing4-29b-a4b-pp4"]
    for name in names:
        cfg = model_config(json.loads((ROOT / "benchmark" / "configs" / f"{name}.json").read_text()))
        for variant, text in (session_texts(cfg) if sliced else texts(cfg)):
            print(name, variant, len(text.splitlines()), hashlib.sha256(text.encode()).hexdigest()[:16])


if __name__ == "__main__":
    main(sys.argv[1:])
