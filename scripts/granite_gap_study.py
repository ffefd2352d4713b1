"""How far the served granite-4.0-h-small's first choice lies below the plain
reference's, with and without one-sided faults: the study behind
``check.max_logit_gap`` of ``benchmark/configs/granite-4h-small-ep8.json``
(PERF.md section 6, PR 34).

Teacher-forced, no serving loop: ``--rows`` rows of ``--prompt`` + ``--decode``
tokens drawn from the seed over the whole vocabulary. The program side runs
what a join and a decode slice run: the prompt through ``forward`` in chunks
of 256 with the recurrent state handed from chunk to chunk (the last chunk
padded to its bucket, its pads masked), then the rest one token at a time
against cache and state (int8 weights, the engine's dtype, the contiguous
cache). The reference (``benchmark/families/granite_hybrid.py``: float32, the
same int8 weights, a scan over tokens from position 0) and its int4 control
see the whole row at once. For every DECODED position the gap of the
reference's best logit over its logit of the token another side puts first is
read, as ``benchmark/lib/check.py`` reads it for served tokens.

A variant is ``name[:fault[:embed_std]]``; a fault changes the PROGRAM alone
and leaves the reference as it is (one-sided): ``state0`` zeroes the
recurrent state where the decode starts (the state dropped at an install),
``conv0`` its convolution tails alone, ``pads`` lets the last chunk's pads
move the state, ``noresid`` leaves the residual multiplier out.

    python3 scripts/granite_gap_study.py --size medium --seeds 1,2 --variants base,state0:state0   # CPU, minutes
    python3 scripts/granite_gap_study.py --size file --seeds 3400000101 ...                          # the chip only
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

CHUNK = 256
# a size between the rehearsal's and the file's, for the CPU: minutes a variant
MEDIUM = {
    "hidden_size": 512, "intermediate_size": 128, "shared_intermediate_size": 256, "num_hidden_layers": 10,
    "layer_types": ["attention" if i in (2, 7) else "mamba" for i in range(10)], "num_attention_heads": 8,
    "num_key_value_heads": 2, "mamba_n_heads": 16, "mamba_d_head": 64, "mamba_d_state": 64, "mamba_chunk_size": 64,
    "vocab_size": 8192, "max_position_embeddings": 4096,
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--config", default="benchmark/configs/granite-4h-small-ep8.json")
    ap.add_argument("--size", choices=("file", "dry", "medium"), default="dry")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--variants", default="base,state0:state0,conv0:conv0,pads:pads,noresid:noresid")
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--prompt", type=int, default=300)
    ap.add_argument("--decode", type=int, default=96)
    ap.add_argument("--no-control", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmark.families import granite_hybrid as fam
    from benchmark.lib.system import model_config
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models import transformer as T
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import quantize_leaf

    base = json.load(open(args.config))
    if args.size == "dry":
        base = {**base, **base["dry"]}
    elif args.size == "medium":
        base = {**base, **MEDIUM}
    dtype = jnp.dtype(base["engine"]["dtype"])
    length = args.prompt + args.decode
    lines, references = [], {}
    for seed in (int(s) for s in args.seeds.split(",")):
        tokens = np.random.default_rng(seed).integers(
            3, int(base["vocab_size"]), size=(args.rows, length)).astype(np.int32)
        rows, spans = [list(map(int, r)) for r in tokens], [(args.prompt - 1, args.decode)] * args.rows
        for variant in args.variants.split(","):
            name, *rest = variant.split(":")
            fault = rest[0] if rest else ""
            cfg = dict(base)
            if len(rest) > 1:
                cfg["stand_in"] = {"embed_std": float(rest[1])}
            mc = model_config(cfg)
            run_cfg = dataclasses.replace(mc, residual_multiplier=1.0) if fault == "noresid" else mc
            t0 = time.time()
            params = jax.jit(lambda k: T.init_params(
                mc, k, dtype, post=lambda n, leaf: quantize_leaf(n, leaf, "int8")))(jax.random.PRNGKey(seed))

            @jax.jit
            def chunk(p, toks, offset, real, kc, vc):
                mask = None if fault == "pads" else jnp.arange(toks.shape[1])[None, :] < real
                h, kc, vc = T.forward(p, run_cfg, toks, offset, kc, vc, token_mask=mask)
                return jnp.argmax(T.logits_for(p, run_cfg, h[0, real - 1]), -1), kc, vc

            @jax.jit
            def step(p, tok, offset, kc, vc):
                h, kc, vc = T.forward(p, run_cfg, tok, offset, kc, vc)
                return jnp.argmax(T.logits_for(p, run_cfg, h[0, 0]), -1), kc, vc

            chosen = []
            for row in tokens:
                kc, vc = T.Transformer(mc, params).init_cache(1, -(-length // CHUNK) * CHUNK + CHUNK, dtype)
                for start in range(0, args.prompt, CHUNK):
                    real = min(CHUNK, args.prompt - start)
                    bucket = CHUNK if real == CHUNK else max(16, 1 << (real - 1).bit_length())
                    toks = np.zeros((1, bucket), np.int32)
                    toks[0, :real] = row[start : start + real]
                    first, kc, vc = chunk(params, jnp.asarray(toks), jnp.int32(start), jnp.int32(real), kc, vc)
                if fault in ("state0", "conv0"):
                    ssm = dict(kc["ssm"])
                    for leaf in ("s", "conv") if fault == "state0" else ("conv",):
                        ssm[leaf] = jnp.zeros_like(ssm[leaf])
                    kc = {"kv": kc["kv"], "ssm": ssm}
                picks = [int(first)]  # what the program puts first after the prompt, then after each forced token
                for t in range(args.prompt, length - 1):
                    nxt, kc, vc = step(params, jnp.asarray(row[None, t : t + 1]), jnp.int32(t), kc, vc)
                    picks.append(int(nxt))
                chosen.append(picks)
                del kc, vc
            chosen = {"program": np.asarray(chosen)}
            del params
            gc.collect()
            t1 = time.time()
            key = (seed, json.dumps(cfg.get("stand_in"), sort_keys=True))
            if key not in references:  # a fault leaves the reference as it is
                w = fam.make_weights(cfg, seed)
                references[key] = [np.asarray(x) for x in fam.served_logits(cfg, w, rows, spans)]
                del w
                gc.collect()
                if not args.no_control:
                    w4 = fam.make_weights(cfg, seed, bits=4)
                    references[key + ("control",)] = np.stack(
                        [np.asarray(jnp.argmax(x, -1)) for x in fam.served_logits(cfg, w4, rows, spans)])
                    del w4
                    gc.collect()
            ref = references[key]
            if not fault and key + ("control",) in references:
                chosen["control"] = references[key + ("control",)]
            best = np.stack([r.max(-1) for r in ref])
            top = np.stack([r.argmax(-1) for r in ref])
            line = {"variant": name, "seed": seed, "size": args.size, "fault": fault, "stand_in": cfg.get("stand_in"),
                    "positions": int(best.size), "logit_std": float(np.std(ref[0])),
                    "seconds": {"program": round(t1 - t0, 1), "reference": round(time.time() - t1, 1)}}
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
