"""Op-level device-trace attribution of the paged-decode residual.

docs/PERF.md's round-4 anatomy ruled out byte volume (AOT cost analysis:
+0.6 GB/step ~= 1 ms at sustained bandwidth vs a measured +6-10 ms/step),
kernel overhead, page size and the scan schedule for the ~2.3x gap between
contiguous and stacked-paged batched decode, leaving "execution efficiency
(serialized scatter/gather lanes or fusion stalls)" as the verdict an
op-level XLA profile would have to apportion. `jax.profiler.trace`
records full per-op device spans (hlo_category, device duration,
bytes_accessed, source attribution) — dispatch jitter moves *step*
timing, but intra-step op spans are device-clocked.

This script runs the same 32-row x 256-token A/B as docs/PERF.md, traces
one decode window per engine, and aggregates the XLA Ops spans inside the
decode while-loop's module spans into a per-category / per-op table:

  python scripts/paged_trace.py            # full A/B + docs/paged_trace.json

The artifact is the committed evidence for VERDICT round-4 directive #2
(per-op trace table naming where the +ms/step goes).
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS = int(os.environ.get("PAGED_TRACE_ROWS", "32"))
TOKENS = int(os.environ.get("PAGED_TRACE_TOKENS", "256"))


def _load_trace(logdir: str) -> dict:
    paths = glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.trace.json.gz")
    )
    if not paths:
        raise FileNotFoundError(f"no trace.json.gz under {logdir}")
    # one trace per start/stop; take the newest
    with gzip.open(sorted(paths)[-1]) as f:
        return json.load(f)


def _device_events(trace: dict):
    """(module_spans, op_events) from the TPU device process.

    Module spans are (start_ps, dur_ps, name); op events are the raw
    Chrome-trace dicts from the "XLA Ops" line with device_offset_ps /
    device_duration_ps args.
    """
    pnames, tnames = {}, {}
    for e in trace["traceEvents"]:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            pnames[e["pid"]] = e["args"].get("name", "")
        elif e.get("name") == "thread_name":
            tnames[(e["pid"], e["tid"])] = e["args"].get("name", "")
    tpu_pids = {p for p, n in pnames.items() if "TPU" in (n or "")}
    modules, ops = [], []
    for e in trace["traceEvents"]:
        if e.get("ph") != "X" or e["pid"] not in tpu_pids:
            continue
        line = tnames.get((e["pid"], e["tid"]), "")
        args = e.get("args", {})
        if "device_offset_ps" not in args:
            continue
        if line == "XLA Modules":
            modules.append(
                (
                    int(args["device_offset_ps"]),
                    int(args["device_duration_ps"]),
                    e.get("name", ""),
                )
            )
        elif line == "XLA Ops":
            ops.append(e)
    return modules, ops


def attribute(logdir: str, module_prefix: str = "jit_decode") -> dict:
    """Aggregate op spans inside `module_prefix` module executions."""
    modules, ops = _device_events(_load_trace(logdir))
    windows = [
        (s, s + d) for s, d, name in modules if name.startswith(module_prefix)
    ]
    if not windows:
        names = sorted({name for _, _, name in modules})
        raise RuntimeError(
            f"no '{module_prefix}*' module span in trace; saw: {names}"
        )
    windows.sort()
    by_cat = collections.Counter()
    by_op = collections.defaultdict(lambda: [0, 0, "", 0])  # ps, n, long, bytes
    total_ps = 0
    for e in ops:
        args = e["args"]
        t0 = int(args["device_offset_ps"])
        if not any(a <= t0 < b for a, b in windows):
            continue
        dur = int(args["device_duration_ps"])
        cat = args.get("hlo_category", "?")
        by_cat[cat] += dur
        total_ps += dur
        # strip the SSA id suffix so repeated loop iterations aggregate
        name = e.get("name", "?").rstrip("0123456789").rstrip(".")
        rec = by_op[(cat, name)]
        rec[0] += dur
        rec[1] += 1
        if not rec[2]:
            rec[2] = args.get("long_name", "")[:220]
        rec[3] += int(args.get("bytes_accessed", 0))
    module_ps = sum(b - a for a, b in windows)
    return {
        "n_module_spans": len(windows),
        "module_total_ms": module_ps / 1e9,
        "ops_total_ms": total_ps / 1e9,
        "by_category_ms": {
            k: round(v / 1e9, 3) for k, v in by_cat.most_common()
        },
        "top_ops": [
            {
                "category": cat,
                "op": name,
                "total_ms": round(ps / 1e9, 3),
                "count": n,
                "mean_us": round(ps / n / 1e6, 2),
                "GB_accessed": round(nbytes / 1e9, 3),
                "long_name": long,
            }
            for (cat, name), (ps, n, long, nbytes) in sorted(
                by_op.items(), key=lambda kv: -kv[1][0]
            )[:24]
        ],
    }


def main() -> int:
    import jax
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
    cfg = get_model_config("qwen2:1.5b")
    prompt = "In 1000 words, please give me information about the solar system"
    reqs = [
        GenerationRequest(cfg.name, prompt, max_new_tokens=TOKENS, seed=10 + i)
        for i in range(ROWS)
    ]
    out = {"rows": ROWS, "tokens": TOKENS, "engines": {}}
    for label, paged in (("contiguous", False), ("paged", True)):
        engine = JaxEngine(
            registry={cfg.name: cfg},
            dtype=jnp.bfloat16,
            decode_attention="auto",
            quantize="int8",
            paged_kv=paged,
        )
        engine.generate_batch(reqs)  # compile
        t0 = time.monotonic()
        rs = engine.generate_batch(reqs)  # warm, untraced
        wall = time.monotonic() - t0
        toks = sum(r.generated_tokens for r in rs)
        decode_s = rs[0].decode_s
        logdir = f"/tmp/paged_trace/{label}"
        with jax.profiler.trace(logdir):
            rs = engine.generate_batch(reqs)
        att = attribute(logdir)
        steps = max(r.generated_tokens for r in rs)
        att["untraced_agg_tok_per_s"] = round(toks / decode_s, 1)
        att["untraced_decode_s"] = round(decode_s, 3)
        att["untraced_wall_s"] = round(wall, 3)
        att["decode_steps"] = steps
        att["device_ms_per_step"] = round(att["module_total_ms"] / steps, 3)
        out["engines"][label] = att
        print(
            json.dumps(
                {
                    "engine": label,
                    "agg_tok_per_s": att["untraced_agg_tok_per_s"],
                    "device_ms_per_step": att["device_ms_per_step"],
                    "by_category_ms": att["by_category_ms"],
                }
            ),
            flush=True,
        )
        del engine

    c = out["engines"]["contiguous"]
    p = out["engines"]["paged"]
    cats = sorted(
        set(c["by_category_ms"]) | set(p["by_category_ms"]),
        key=lambda k: -(
            p["by_category_ms"].get(k, 0) - c["by_category_ms"].get(k, 0)
        ),
    )
    delta = {
        k: round(
            (
                p["by_category_ms"].get(k, 0.0) / p["decode_steps"]
                - c["by_category_ms"].get(k, 0.0) / c["decode_steps"]
            ),
            4,
        )
        for k in cats
    }
    out["delta_ms_per_step_by_category"] = delta
    print(json.dumps({"delta_ms_per_step": delta}), flush=True)
    # the canonical 32-row artifact keeps the bare name; other widths
    # get their own file so re-runs never clobber the committed evidence
    suffix = "" if ROWS == 32 else f"_{ROWS}rows"
    dst = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs",
        f"paged_trace{suffix}.json",
    )
    with open(dst, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {dst}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
