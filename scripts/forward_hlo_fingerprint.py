#!/usr/bin/env python3
"""sha256 of the StableHLO text that ``forward`` lowers to at a benchmark
configuration's FULL size (abstract weights: nothing is allocated, nothing
runs), in the cache variants the served path uses: a prefill chunk over
the contiguous cache, batched decode over the carry and, for a latent
model, paged decode over pool + side caches. Run it in two checkouts to
show that a change to ``models/transformer.py`` left another model's
program as it was (PERF.md, PR 32):

    python3 scripts/forward_hlo_fingerprint.py phi3-mini mistral-7b longcat-flash-ep32
"""

import hashlib
import json
import os
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
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_attention import pool_page_owners  # noqa: E402

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
    if not cfg.latent:
        return  # a dense model's paged decode is a Pallas kernel chosen on the chip
    attend = JaxEngine._paged_decode_attention(None, cfg)  # the latent closure reads nothing of the engine

    def paged(p, t, o, pk, pv, table, sk, sv, plens):
        shared = {"table": table, "write_pos": o - plens, "prompt_lens": plens}
        k = {**shared, "pool": pk, "side": sk, "owners": pool_page_owners(table, plens, PAGES, PAGE)}
        v = {**shared, "pool": pv, "side": sv}
        stats = {}
        h, k, v = forward(p, cfg, t, o, k, v, attend, token_mask=jnp.ones((ROWS, 1), bool), stats=stats)
        return logits_for(p, cfg, h[:, 0]), k["side"], v["side"], stats.get("moe")

    kw, vw = pool_widths(cfg, True)
    pool = lambda w: sds((cfg.cache_layers, PAGES, cfg.cache_heads, PAGE, w), jnp.bfloat16)  # noqa: E731
    side = lambda w: sds(lead + (SIDE, w), jnp.bfloat16)  # noqa: E731
    yield "paged", jax.jit(paged).lower(
        params, i32(ROWS, 1), i32(ROWS), pool(kw), pool(vw), i32(ROWS, TABLE), side(cfg.cache_k_width),
        side(cfg.cache_v_width), i32(ROWS)).as_text()


def main(names):
    for name in names:
        cfg = model_config(json.loads((ROOT / "benchmark" / "configs" / f"{name}.json").read_text()))
        for variant, text in texts(cfg):
            print(name, variant, len(text.splitlines()), hashlib.sha256(text.encode()).hexdigest()[:16])


if __name__ == "__main__":
    main(sys.argv[1:] or ["phi3-mini", "mistral-7b", "longcat-flash-ep32"])
