"""Decode ablation bench: where does a decode step's time go, end-to-end.

Runs the real engine decode (the same path bench.py measures) across a
small grid:

  quantize ∈ {int8, int4}  ×  vocab ∈ {full 151936, ablated 8192}

The vocab ablation isolates the logits-head + embedding share of a step
(the full-vocab logits matmul streams the whole int8 embed table every
step); int8 vs int4 isolates the weight-stream + dequant-kernel share.
Prints one JSON line per configuration as it completes (partial output
stays useful if a later configuration dies) and a summary at the end.
"""

from __future__ import annotations

import dataclasses
import faulthandler
import json
import os
import sys
import time

faulthandler.enable()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax.numpy as jnp

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationRequest,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    base = get_model_config("qwen2:1.5b")
    prompt = "In 1000 words, please give me information about the solar system"
    results = {}
    for quantize in ("int8", "int4"):
        for vocab in (base.vocab_size, 8192):
            cfg = dataclasses.replace(base, vocab_size=vocab)
            name = f"{quantize}-v{vocab}"
            t0 = time.monotonic()
            engine = JaxEngine(
                registry={cfg.name: cfg},
                dtype=jnp.bfloat16,
                decode_attention="auto",
                quantize=quantize,
            )
            warm = GenerationRequest(cfg.name, prompt, max_new_tokens=16)
            engine.generate(warm)
            req = GenerationRequest(cfg.name, prompt, max_new_tokens=256)
            engine.generate(req)  # compile the 256 bucket
            best = None
            for seed in (1, 2, 3):
                r = engine.generate(dataclasses.replace(req, seed=seed))
                tps = r.generated_tokens / r.decode_s
                best = max(best or 0.0, tps)
            line = {
                "config": name,
                "tokens_per_s": round(best, 2),
                "ms_per_step": round(1000.0 / best, 3),
                "warm_total_s": round(time.monotonic() - t0, 1),
            }
            results[name] = line
            print(json.dumps(line), flush=True)
            del engine

    full8 = results.get(f"int8-v{base.vocab_size}")
    slim8 = results.get("int8-v8192")
    full4 = results.get(f"int4-v{base.vocab_size}")
    slim4 = results.get("int4-v8192")
    if all((full8, slim8, full4, slim4)):
        print(
            json.dumps(
                {
                    "summary": {
                        "logits_embed_ms_int8": round(
                            full8["ms_per_step"] - slim8["ms_per_step"], 3
                        ),
                        "logits_embed_ms_int4": round(
                            full4["ms_per_step"] - slim4["ms_per_step"], 3
                        ),
                        "body_ms_int8": slim8["ms_per_step"],
                        "body_ms_int4": slim4["ms_per_step"],
                    }
                }
            ),
            flush=True,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
