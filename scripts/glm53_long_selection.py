#!/usr/bin/env python3
"""Serve ONE long request of the GLM-5.3-Flash configuration through the
benchmark's system (``JaxEngine`` -> ``ContinuousScheduler`` ->
``SteppedDecodeSession``, greedy) and compare its decoded tokens with the
family's plain float32 reference, as the cell's check does.

The prompt (``--prompt-tokens``, 8,960 by default: 2,240 blocks of 4, past
the lightning indexer's 2,048) is seeded random lowercase text, so the
indexer drops blocks in every DSA layer of every decode step and every
prefill chunk past its reach; the ``sched.slice`` spans' ``index_blocks_seen``
and ``index_blocks_kept`` show how many. Prints one JSON line: the counters,
the widest gap of a served token's reference logit below the reference's
best beside the configuration's ``check.max_logit_gap``, and the seconds
the serving and the check took. Chip only at the published widths:

    python3 scripts/glm53_long_selection.py --seed 41
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--config", default="glm53-flash-ep8-pp5")
    ap.add_argument("--prompt-tokens", type=int, default=8960)
    ap.add_argument("--output-tokens", type=int, default=32)
    ap.add_argument("--dry", action="store_true", help="the configuration's dry group, on the CPU")
    args = ap.parse_args(argv)

    import numpy as np

    cfg = json.loads((ROOT / "benchmark" / "configs" / f"{args.config}.json").read_text())
    if args.dry:
        cfg = {**cfg, **cfg.get("dry", {})}
    import jax.numpy as jnp

    from benchmark.lib import check, family, system
    from benchmark.lib.traffic import token_ids
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.trace import TRACER

    rng = np.random.default_rng(args.seed)
    prompt = "".join(rng.choice(list("abcdefghijklmnopqrstuvwxyz"), args.prompt_tokens - 1))  # + BOS
    t0 = time.monotonic()
    sut = system.System(cfg, args.seed)
    t_up = time.monotonic()
    mark = TRACER.seq()
    tokens, error = [], None
    for ev in sut.submit(prompt, args.output_tokens).events():
        if ev.kind == "delta":
            tokens.extend(int(t) for t in ev.tokens)
        elif ev.kind == "error":
            error = f"{type(ev.error).__name__}: {ev.error}"
    t_served = time.monotonic()
    slices = [s.attrs for s in TRACER.spans(since=mark) if s.name == "sched.slice"]
    sut.close()
    del sut
    out = {
        "prompt_tokens": len(token_ids(prompt)), "output_tokens": len(tokens), "error": error,
        "index_blocks_seen": sum(a.get("index_blocks_seen", 0) for a in slices),
        "index_blocks_kept": sum(a.get("index_blocks_kept", 0) for a in slices),
        "slices": len(slices), "setup_s": round(t_up - t0, 3), "serve_s": round(t_served - t_up, 3),
    }
    if tokens:
        fam = family.load(cfg)
        ids = token_ids(prompt) + tokens
        ref = fam.served_logits(cfg, fam.make_weights(cfg, args.seed), [ids], [(len(ids) - len(tokens) - 1, len(tokens))])
        gaps = check.gaps_below_best(ref, [tokens])
        out["logit_gap_max"] = float(gaps.max())
        out["limit"] = float(cfg["check"]["max_logit_gap"])
        out["correct"] = bool(out["logit_gap_max"] <= out["limit"] and len(tokens) == args.output_tokens)
        out["first_choice_share"] = float(jnp.mean(jnp.asarray(gaps) == 0.0))
    out["check_s"] = round(time.monotonic() - t_served, 3)
    print(json.dumps(out), flush=True)
    return 0 if out.get("correct") else 1


if __name__ == "__main__":
    sys.exit(main())
