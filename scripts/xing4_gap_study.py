"""How far the served xing4 model's first choice lies below the plain
reference's, by stand-in variant: the study behind ``check.max_logit_gap``
and the ``stand_in`` group of ``benchmark/configs/xing4-29b-a4b-pp4.json``
(PERF.md section 6, PR 32).

Teacher-forced, no serving loop: ``--rows`` rows of ``--len`` tokens drawn
from the seed over the whole vocabulary go through the program's
``forward`` (int8 weights, the engine's dtype, contiguous cache) and through
``benchmark/families/xing4.py``'s reference (float32, the same int8
weights) and its int4 control. For every position the gap of the
reference's best logit over its logit of the token another side puts first
is read, as ``benchmark/lib/check.py`` reads it for served tokens.

A variant is ``name:embed_std:routed_down_gain[:fault]``; a fault changes
the PROGRAM's weights alone and leaves the reference as it is (one-sided):
``alpha0`` zeroes the residual maps' three gains (their data-dependent
half), ``noshared`` zeroes the shared expert's output, ``unscaled`` halves
the routed experts' output (the routed scaling factor left out).

    python3 scripts/xing4_gap_study.py --size medium --seeds 1,2 \
        --variants base:0.02:1,tok:4:0.25,tok-alpha0:4:0.25:alpha0      # CPU, minutes
    python3 scripts/xing4_gap_study.py --size file --seeds 4200000201 ...   # the chip only
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

MEDIUM = {
    "hidden_size": 512, "intermediate_size": 1024, "moe_intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 8, "q_lora_rank": 128, "kv_lora_rank": 64, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 16, "v_head_dim": 32, "vocab_size": 8192, "max_position_embeddings": 4096,
}


def _faulty(params, fault):
    import jax.numpy as jnp

    if fault in ("", "none"):  # "none": the variant as it is, its control left out
        return params
    if fault == "alpha0":
        return {k: (jnp.zeros_like(v) if k.endswith("_alpha") else v) for k, v in params.items()}
    if fault in ("noshared", "unscaled"):
        name, by = ("ws_down", 0.0) if fault == "noshared" else ("we_down", 0.5)
        return {**params, name: {**params[name], "s": params[name]["s"] * by}}
    raise SystemExit(f"unknown fault {fault!r}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--config", default="benchmark/configs/xing4-29b-a4b-pp4.json")
    ap.add_argument("--size", choices=("file", "dry", "medium"), default="medium")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--variants", default="file")
    ap.add_argument("--rows", type=int, default=3)
    ap.add_argument("--len", type=int, default=384, dest="length")
    ap.add_argument("--no-control", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmark.families import xing4 as fam
    from benchmark.lib.system import model_config
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models import transformer as T
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import quantize_leaf

    base = json.load(open(args.config))
    if args.size == "dry":
        base = {**base, **base["dry"]}
    elif args.size == "medium":
        base = {**base, **MEDIUM}
    base["engine"] = {**base["engine"], "dtype": "bfloat16"}
    lines = []
    references = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        tokens = np.random.default_rng(seed).integers(
            3, int(base["vocab_size"]), size=(args.rows, args.length)).astype(np.int32)
        rows, spans = [list(map(int, r)) for r in tokens], [(0, args.length)] * args.rows
        for variant in args.variants.split(","):
            name, *rest = variant.split(":")
            cfg = dict(base)
            if rest:
                cfg["stand_in"] = {"embed_std": float(rest[0]), "routed_down_gain": float(rest[1])}
            fault = rest[2] if len(rest) > 2 else ""
            mc = model_config(cfg)
            t0 = time.time()
            params = jax.jit(lambda k: T.init_params(
                mc, k, jnp.bfloat16, post=lambda n, leaf: quantize_leaf(n, leaf, "int8")))(jax.random.PRNGKey(seed))
            params = _faulty(params, fault)

            @jax.jit
            def first_choice(p, toks):
                k0 = jnp.zeros((mc.cache_layers, 1, 1, args.length, mc.cache_k_width), jnp.bfloat16)
                v0 = jnp.zeros((mc.cache_layers, 1, 1, args.length, 0), jnp.bfloat16)
                h, _, _ = T.forward(p, mc, toks, jnp.int32(0), k0, v0)
                return jnp.argmax(T.logits_for(p, mc, h[0]), -1)

            chosen = {"program": np.stack([np.asarray(first_choice(params, tokens[i:i + 1]))
                                           for i in range(args.rows)])}
            del params
            gc.collect()
            t1 = time.time()
            key = (seed, json.dumps(cfg.get("stand_in"), sort_keys=True))
            if key not in references:  # a fault leaves the reference as it is
                w = fam.make_weights(cfg, seed)
                references[key] = [np.asarray(x) for x in fam.served_logits(cfg, w, rows, spans)]
                del w
                gc.collect()
            ref = references[key]
            t2 = time.time()
            if not args.no_control and not fault:
                w4 = fam.make_weights(cfg, seed, bits=4)
                chosen["control"] = np.stack(
                    [np.asarray(jnp.argmax(x, -1)) for x in fam.served_logits(cfg, w4, rows, spans)])
                del w4
                gc.collect()
            best = np.stack([r.max(-1) for r in ref])
            top = np.stack([r.argmax(-1) for r in ref])
            line = {"variant": name, "seed": seed, "size": args.size, "stand_in": cfg.get("stand_in"),
                    "fault": fault, "positions": int(best.size), "logit_std": float(np.std(ref[0])),
                    "seconds": {"program": round(t1 - t0, 1), "reference": round(t2 - t1, 1),
                                "control": round(time.time() - t2, 1)}}
            for side, c in chosen.items():
                gap = best - np.stack([np.take_along_axis(r, k[:, None], -1)[:, 0] for r, k in zip(ref, c)])
                line[side] = {"max": float(gap.max()), "mean": float(gap.mean()),
                              "q99": float(np.quantile(gap, 0.99)), "agree": float((c == top).mean())}
            print(json.dumps(line), flush=True)
            lines.append(line)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)


if __name__ == "__main__":
    main()
