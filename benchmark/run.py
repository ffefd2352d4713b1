#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration in
``benchmark/configs/<config>.json``, its traffic mix in
``benchmark/traffic/<traffic>.json`` and each per-layer metric in
``benchmark/layer_metrics/<name>.json`` (which names its reader under
``benchmark/readers/``). Prints one JSON object as the last line of
standard output. ``--dry`` rehearses the whole command at tiny sizes on
the CPU and prints every metric under a ``dry.`` name.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

T_PROCESS = time.monotonic()
HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_SECONDS = 3.0  # the traced part of a --trace 1 window
QUIET_SECONDS = 2.0  # warm-up ends only after this long without a compile
SETUP_LIMIT_SECONDS = 1100  # a cell's first run in a checkout may take 1200 s in all
STALL_TICK_SECONDS = 0.05  # the stall watch's sleep
STALL_SECONDS = 0.25  # an overshoot of the sleep worth a line on standard error


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv: Optional[List[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry", action="store_true",
                    help="CPU rehearsal at tiny sizes; metrics print under dry.* names")
    ap.add_argument("--control", action="store_true",
                    help="put the int4 reference's tokens in the served tokens' place: correct has to read false")
    return ap.parse_args(argv)


def load_cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(bench: Dict[str, Any], group: str, cell: str, reports: set) -> List[Dict[str, Any]]:
    """The metrics of ``group`` this cell reports: those that list it, or
    list nothing (per-layer: then wherever the metric they move is reported)."""
    out = []
    for m in bench[group]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in reports:
            out.append(m)
    return out


def configure_jax(dry: bool) -> None:
    if dry:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if dry:
        return  # a rehearsal keeps no compile cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cache = HERE / ".jax_cache"
        cache.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(cache))


def device_info(cell: Dict[str, Any], dry: bool) -> Dict[str, Any]:
    import jax

    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if not dry:
        if info["platform"] != "tpu":
            raise SystemExit(
                f"no accelerator: JAX reports platform {info['platform']!r}; "
                "device metrics come from the chip only (use --dry to rehearse)"
            )
        if info["count"] < int(cell["chips"]):
            raise SystemExit(f"cell needs {cell['chips']} chips, JAX reports {info['count']}")
    return info


def memory_stat(key: str) -> int:
    """``key`` of ``memory_stats()`` on the fullest chip."""
    import jax

    return max(int((d.memory_stats() or {}).get(key, 0)) for d in jax.devices())


def watch_stalls(stop: threading.Event, stalls: List[Tuple[float, float]]) -> None:
    """Sleeps a tick at a time and keeps ``(when, how long)`` of every
    overshoot: a process that was not running (a host that stalled, a thread
    that kept the interpreter lock) shows here whatever the scheduler did,
    so a window that reads low can be told from a program that ran slow."""
    prev = time.monotonic()
    while not stop.wait(STALL_TICK_SECONDS):
        now = time.monotonic()
        if now - prev - STALL_TICK_SECONDS >= STALL_SECONDS:
            stalls.append((prev, now - prev - STALL_TICK_SECONDS))
        prev = now


def run_cell(args: argparse.Namespace) -> Dict[str, Any]:
    sys.path.insert(0, str(ROOT))
    from benchmark.lib import check, loadgen, peaks, stats, system, trace, traffic
    from benchmark.readers import WINDOW_SPAN, Context

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = load_cell(bench, args.workload)
    cfg = json.loads((HERE / "configs" / f"{cell['config']}.json").read_text())
    mix = traffic.load_mix(HERE / "traffic" / f"{cell['traffic']}.json", dry=args.dry)
    if args.dry:
        cfg = {**cfg, **cfg.get("dry", {})}
    configure_jax(args.dry)
    device = device_info(cell, args.dry)
    chip = None if args.dry else peaks.peaks_for(device["kind"])
    log(f"device {device}; cell {cell['name']}; seed {args.seed}")
    t_device = time.monotonic()

    # -- set-up: weights, scheduler, first fleet, warm-up traffic -------------
    sut = system.System(cfg, args.seed)
    t_loaded = time.monotonic()
    load = loadgen.Load(sut, mix, args.seed)
    load.start()
    warm = float(mix.get("warmup_seconds", 5.0))
    need = traffic.warmup_requests(mix)
    settle = max(2, traffic.first_fleet(mix))  # requests to finish after the last compile
    t_first = None
    last_compiles, t_quiet, done_quiet = sut.compile_count(), time.monotonic(), 0
    t_beat = time.monotonic()
    while True:
        time.sleep(0.05)
        now = time.monotonic()
        if t_first is None and load.first_token_seen():
            t_first = now
            log(f"first token {now - T_PROCESS:.1f}s after start; session {sut.session_shape()}")
        c, done = sut.compile_count(), load.finished()
        if c != last_compiles:
            # a compile (or a load from the cache) just ended: the stream
            # has to flow for a while without one before the window opens
            last_compiles, t_quiet, done_quiet = c, now, done
        if (
            t_first is not None
            and now - t_first >= warm
            and done >= need
            and now - t_quiet >= QUIET_SECONDS
            and done - done_quiet >= settle
        ):
            break
        if now - t_beat >= 30:
            t_beat = now
            log(f"set-up {now - T_PROCESS:.0f}s: compiles {c}, requests finished {done}, "
                f"in use {memory_stat('bytes_in_use') / 1e9:.2f} GB")
        failed = load.first_error()
        if failed is not None:
            # traffic is chosen so that no request fails: one that does
            # during set-up (a fleet that does not fit, say) ends the run
            raise SystemExit(f"a request failed during set-up: {failed}")
        if t_first is None and done >= need:
            # results without token events: the stepped session did not open
            # and the scheduler fell back to one-shot batches
            raise SystemExit(f"{done} requests finished and none streamed a token: "
                             "the session did not open (see PERF.md, phi3 fleet size)")
        if now - T_PROCESS > SETUP_LIMIT_SECONDS:
            raise SystemExit(f"set-up did not settle within {SETUP_LIMIT_SECONDS} s")

    # -- the measured window ----------------------------------------------------
    trace_dir = HERE / ".out" / "trace"
    traced_s = min(float(args.seconds), TRACE_SECONDS)
    if args.trace:
        import jax

        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    stalls: List[Tuple[float, float]] = []
    watch_stop = threading.Event()
    watch = threading.Thread(target=watch_stalls, args=(watch_stop, stalls), name="stall-watch", daemon=True)
    watch.start()
    compiles0 = sut.compile_count()
    t0 = time.monotonic()
    setup_s = t0 - T_PROCESS
    if args.trace:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            time.sleep(traced_s)
        jax.profiler.stop_trace()
    time.sleep(max(0.0, t0 + args.seconds - time.monotonic()))
    t1 = time.monotonic()
    watch_stop.set()
    watch.join()
    compiles = sut.compile_count() - compiles0
    stuck = load.stop()
    peak_bytes = memory_stat("peak_bytes_in_use")
    slices = list(sut.slices)
    slice_steps = sut.slice_steps
    sut.close()
    del sut
    records = load.records
    log(f"bytes in use after the program's state is dropped: {memory_stat('bytes_in_use') / 1e9:.2f} GB")
    log(f"set-up {setup_s:.1f}s (device up at {t_device - T_PROCESS:.1f}s, weights+scheduler at "
        f"{t_loaded - T_PROCESS:.1f}s, first token at {t_first - T_PROCESS:.1f}s); "
        f"window {t1 - t0:.2f}s; compiles in window {compiles}; peak {peak_bytes / 1e9:.2f} GB")
    in_window = stats.slices_within(slices, t0, t1)
    if in_window:
        t_end, gap, _ = max(in_window, key=lambda s: s[1])
        log(f"slice periods in the window: {len(in_window)}, median "
            f"{stats.percentile([s[1] for s in in_window], 50) * 1e3:.1f} ms, longest {gap * 1e3:.1f} ms "
            f"ending {t_end - t0:.1f}s in")
    log("process stalls in the window (when, seconds): "
        + (", ".join(f"+{t - t0:.1f}s {d:.2f}" for t, d in stalls) or "none") + f" of {STALL_SECONDS}s or more")

    # -- metrics ------------------------------------------------------------------
    w = stats.window_metrics(records, t0, t1)
    log(f"samples: tokens {w['tokens_per_s']['samples']}, ttft {len(w['ttft_ms']['values'])}, "
        f"gaps {len(w['stream_gap_ms']['values'])}, requests attempted {w['attempted']} failed {w['failed']}")
    if load.late_s:
        log(f"generator lateness p50 {stats.percentile(load.late_s, 50) * 1e3:.2f} ms "
            f"max {max(load.late_s) * 1e3:.2f} ms over {len(load.late_s)} sends")
    e2e_values = {"setup_s": setup_s, "tokens_per_s": w["tokens_per_s"]["value"]}
    if w["ttft_ms"]["values"]:
        e2e_values["ttft_p95_ms"] = stats.percentile(w["ttft_ms"]["values"], 95)
    if w["stream_gap_ms"]["values"]:
        e2e_values["stream_gap_p95_ms"] = stats.percentile(w["stream_gap_ms"]["values"], 95)

    log("whole window, whichever --trace: " + ", ".join(f"{k}={v:.4f}" for k, v in e2e_values.items()))

    e2e = metrics_of(bench, "end_to_end", cell["name"], set())
    prefix = "dry." if args.dry else ""
    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if not args.trace:
        for m in e2e:
            if m["name"] in e2e_values:
                metrics[prefix + m["name"]] = {"value": e2e_values[m["name"]], "unit": m["unit"]}
    else:
        tr = None
        xplane = trace.find_xplane(trace_dir)
        if xplane is not None:
            tr = trace.load(xplane)
        ctx = Context.build(cfg=cfg, mix=mix, cell=cell, chip=chip, trace=tr, records=records,
                            slices=slices, slice_steps=slice_steps, compiles=compiles,
                            t0=t0, t1=t0 + traced_s, window_t1=t1)
        reports = {m["name"] for m in e2e}
        for m in metrics_of(bench, "per_layer", cell["name"], reports):
            spec = json.loads((HERE / "layer_metrics" / f"{m['name']}.json").read_text())
            reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
            value = reader.read(ctx, spec.get("params", {}))
            if value is not None:
                metrics[prefix + m["name"]] = {"value": float(value), "unit": m["unit"]}
        if ctx.device is not None:
            device["busy_s"] = trace.busy_seconds(ctx.device, ctx.trace_t0, ctx.trace_t1)
            device["window_s"] = ctx.trace_t1 - ctx.trace_t0
            breakdown = {
                "device_ops": trace.top_device_ops(ctx.device, ctx.trace_t0, ctx.trace_t1),
                "idle_gaps": trace.top_idle_gaps(tr, ctx.device, ctx.trace_t0, ctx.trace_t1),
            }
        shutil.rmtree(trace_dir, ignore_errors=True)
    device["memory_peak_bytes"] = peak_bytes

    # -- correct: the reference, after the program's state is gone ---------------
    t_check = time.monotonic()
    verdict = check.run(cfg, args.seed, records, t0, t1, control=args.control)
    log(f"check took {time.monotonic() - t_check:.1f}s over requests {verdict['sampled']}"
        + ("; CONTROL: the int4 reference's tokens stand in the served tokens' place" if args.control else ""))
    numbers = verdict["numbers"]
    correct = verdict["correct"] and not stuck and w["failed"] == 0
    numbers["requests_failed"] = {"value": w["failed"], "limit": 0}
    numbers["clients_stuck"] = {"value": len(stuck), "limit": 0}
    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": w["attempted"],
        "failed": w["failed"],
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = numbers
    for name, n in numbers.items():
        log(f"check {name}={n['value']} limit={n['limit']}")
    return result


def main(argv: Optional[List[str]] = None) -> int:
    args = parse(argv)
    try:
        result = run_cell(args)
    except SystemExit as exc:
        log(str(exc))
        return 1
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    # daemon consumer threads and the profiler hold nothing worth a slow exit
    os._exit(code)
