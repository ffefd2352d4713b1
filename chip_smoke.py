#!/usr/bin/env python3
"""Chip smoke: does the serving path still start and answer on the TPU?

    python3 chip_smoke.py                               # one chip
    python3 chip_smoke.py --backend jax-tp --tp 2 --dp 2   # passed to `serve`

Starts ``python -m cain_2025_device_remote_llm_energy_rep_pkg_tpu serve
--models qwen2:1.5b --quantize int8 --paged-kv --scheduler continuous`` as
its ONE child (the parent never imports jax: a chip belongs to one
process), refuses the server unless it reports a TPU the peaks table
knows, then drives a handful of requests through ``serve/client.py`` that
touch each mechanism of the default path — flash prefill + stepped decode
slices, a chunked mid-flight join, a page table wide enough for the
Pallas parts kernel, SSE streaming, concurrent rows that retire and
recycle pages — and checks every answer plus the server's own counters.
Weights are random from the engine's seed: no network, no checkpoint.

Exit 0 only if every check held; the last stdout line is then
``{"ok": true, "device": {"platform", "kind", "count"}}`` as JAX reports
the device. Any failure exits non-zero with the reason on stderr and
prints no result line. Everything else printed is a smoke OBSERVATION
(seconds include compilation), not a metric.
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional

REPO = os.path.dirname(os.path.abspath(__file__))
PACKAGE = "cain_2025_device_remote_llm_energy_rep_pkg_tpu"
MODEL = "qwen2:1.5b"
SERVE_FLAGS = [
    "--models", MODEL, "--quantize", "int8", "--paged-kv",
    "--scheduler", "continuous",
]
# Hard stop for the whole script (the contract allows 1200 s).
DEADLINE_S = 1100.0
FAILURE_EVENTS = ("batch_fallback", "anomaly", "crash_dump")
# engine/jax_engine.PAGED_XLA_PARTS_MAX_JMAX's default: page tables up to
# this wide take the XLA parts (importing it would import jax here)
XLA_PARTS_MAX_WIDTH = 8


class SmokeFailure(Exception):
    """One violated check; the message is the reason printed on exit."""


def check(ok: bool, reason: str) -> None:
    if not ok:
        raise SmokeFailure(reason)


@dataclasses.dataclass(frozen=True)
class Plan:
    """Request sizes in BYTE-TOKENIZER tokens (one per prompt byte + BOS).

    The defaults are the chip plan. ``short`` needs two pages (>128
    tokens) so its session's page table can seat the three-page joiner;
    ``join`` exceeds one 256-token prefill chunk; ``long`` needs more
    than eight 128-token pages so the table is wider than
    PAGED_XLA_PARTS_MAX_JMAX and the Pallas parts kernel runs."""

    short_prompt: int = 160
    short_new: int = 64
    join_prompt: int = 320
    join_new: int = 32
    long_prompt: int = 1100
    long_new: int = 64
    burst_prompt: int = 150
    burst_new: tuple = (16, 24, 32, 40)


def prompt_of(n_tokens: int, salt: str) -> str:
    """A deterministic ASCII prompt that encodes to ``n_tokens`` tokens."""
    words = (salt + " energy of a generated token on device and remote ") * (
        n_tokens // 8 + 1
    )
    return words[: n_tokens - 1]  # + BOS


def get_json(base_url: str, path: str, timeout_s: float = 10.0) -> Any:
    with urllib.request.urlopen(base_url + path, timeout=timeout_s) as resp:
        check(resp.status == 200, f"GET {path} returned {resp.status}")
        return json.loads(resp.read().decode("utf-8"))


def run_requests(
    base_url: str,
    model: str = MODEL,
    plan: Plan = Plan(),
    observe: Callable[[str], None] = print,
) -> Dict[str, Any]:
    """The request script: drive a running server through the smoke's
    phases and check every answer and the server's own counters. Raises
    :class:`SmokeFailure` with the reason on the first violated check;
    returns the observations otherwise."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationRequest,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.metrics import (
        parse_exposition,
        sample_value,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.client import (
        RemoteHTTPBackend,
        RemoteServerError,
    )

    client = RemoteHTTPBackend(base_url, timeout_s=900.0)
    obs: Dict[str, Any] = {"requests": []}
    observe_lock = threading.Lock()  # concurrent phases report whole lines
    # concurrent requests run here; a future re-raises its SmokeFailure
    workers = ThreadPoolExecutor(max_workers=1 + len(plan.burst_new))
    flight0 = get_json(base_url, "/debug/state")["flight"]["by_type"]
    t_start = time.monotonic()

    def request(n_prompt: int, n_new: int, salt: str) -> GenerationRequest:
        return GenerationRequest(
            model=model,
            prompt=prompt_of(n_prompt, salt),
            max_new_tokens=n_new,
        )

    def checked(label: str, req: GenerationRequest, result, wall_s: float):
        """HTTP 200 is implied (the client raises otherwise)."""
        extras = result.extras or {}
        check(
            result.generated_tokens >= 1 and len(result.tokens) >= 1,
            f"{label}: empty response",
        )
        check(
            result.generated_tokens <= req.max_new_tokens,
            f"{label}: {result.generated_tokens} tokens over the budget "
            f"{req.max_new_tokens}",
        )
        check(
            extras.get("retire_reason") in ("eos", "budget"),
            f"{label}: no finish reason (extras {sorted(extras)})",
        )
        check(
            extras.get("stepped") is True,
            f"{label}: not served by a stepped session",
        )
        row = {
            "phase": label,
            "prompt_tokens": result.prompt_tokens,
            "generated_tokens": result.generated_tokens,
            "finish": extras["retire_reason"],
            "wall_s": round(wall_s, 3),
        }
        with observe_lock:
            obs["requests"].append(row)
            observe(f"smoke observation: request {json.dumps(row)}")
        return result

    def generate(label: str, req: GenerationRequest):
        t0 = time.monotonic()
        try:
            result = client.generate(req)
        except (RemoteServerError, urllib.error.URLError, OSError) as exc:
            raise SmokeFailure(f"{label}: {exc}") from exc
        return checked(label, req, result, time.monotonic() - t0)

    def stream(label: str, req: GenerationRequest, on_first: Callable[[], None]):
        """Read one ``stream: true`` request to its final record;
        ``on_first`` runs once, after the first delta arrived (the
        request's session is live from then until the final record)."""
        t0 = time.monotonic()
        deltas: List[int] = []
        final = None
        fired = False
        try:
            for chunk in client.generate_stream(req):
                if chunk.done:
                    final = chunk.result
                    break
                deltas.extend(chunk.tokens)
                if not fired:
                    fired = True
                    on_first()
        except (RemoteServerError, urllib.error.URLError, OSError) as exc:
            raise SmokeFailure(f"{label}: {exc}") from exc
        check(final is not None, f"{label}: stream ended with no final record")
        check(fired, f"{label}: stream carried no delta before its final record")
        check(
            deltas == final.tokens,
            f"{label}: streamed deltas differ from the final token list",
        )
        return checked(label, req, final, time.monotonic() - t0)

    # (1) short prompt alone: flash prefill + stepped decode slices
    short = request(plan.short_prompt, plan.short_new, "solo")
    solo = generate("solo", short)
    obs["first_response_s"] = round(time.monotonic() - t_start, 3)

    # (2)+(4) the same request again, STREAMED; once its first delta is
    # out (its session is live) a longer prompt arrives and must join
    # mid-flight in prefill chunks. The twin's tokens must equal (1)'s.
    joiner_req = request(plan.join_prompt, plan.join_new, "join")
    live: Dict[str, Any] = {}

    def send_joiner() -> None:
        live["joiner"] = workers.submit(generate, "joined", joiner_req)
        live["state"] = get_json(base_url, "/debug/state")

    twin = stream("twin-streamed", short, send_joiner)
    joined = live["joiner"].result(timeout=900.0)
    check(
        twin.tokens == solo.tokens,
        "greedy request returned different tokens solo vs beside a joiner",
    )
    sched_extras = (joined.extras or {}).get("sched") or {}
    check(
        sched_extras.get("joined") is True
        and sched_extras.get("join_chunks", 0) >= 2,
        f"joined: not admitted as a chunked mid-flight join ({sched_extras})",
    )
    session = (live["state"].get("scheduler") or {}).get("session") or {}
    check(
        live["state"].get("scheduler_mode") == "continuous",
        f"scheduler_mode is {live['state'].get('scheduler_mode')!r}",
    )
    check(session.get("paged") is True, "live session is not on the paged layout")

    # (3) a prompt of more than eight pages, streamed so the decode
    # window is known: poll the device's live bytes while it runs
    in_use: List[List[int]] = []
    long_state: Dict[str, Any] = {}
    polling = threading.Event()

    def poll_memory() -> None:
        def run() -> None:
            while not polling.is_set():
                try:
                    state = get_json(base_url, "/debug/state")
                except (SmokeFailure, urllib.error.URLError, OSError):
                    return  # the phase's own checks report a dead server
                memory = (state.get("device") or {}).get("memory")
                if memory:
                    in_use.append([m["bytes_in_use"] for m in memory])
                if (state.get("scheduler") or {}).get("session"):
                    long_state.update(state)
                time.sleep(0.01)

        threading.Thread(target=run, daemon=True).start()

    try:
        long_result = stream(
            "long-streamed",
            request(plan.long_prompt, plan.long_new, "long"),
            poll_memory,
        )
    finally:
        polling.set()
    check(
        long_result.prompt_tokens == plan.long_prompt,
        f"long: served {long_result.prompt_tokens} prompt tokens, "
        f"sent {plan.long_prompt}",
    )

    # (5) concurrent short requests: rows retire at different steps and
    # their pages go back to the pool
    burst = [
        workers.submit(
            generate, f"burst-{i}", request(plan.burst_prompt, n_new, f"burst{i}")
        )
        for i, n_new in enumerate(plan.burst_new)
    ]
    for future in burst:
        future.result(timeout=900.0)
    workers.shutdown()

    # -- the server's own account, at idle ------------------------------------
    deadline = time.monotonic() + 30.0
    while True:
        state = get_json(base_url, "/debug/state")
        sched = state.get("scheduler") or {}
        if sched.get("session") is None and not sched.get("queue_depth"):
            break
        check(time.monotonic() < deadline, "server never went idle")
        time.sleep(0.1)
    by_type = state["flight"]["by_type"]
    delta = {k: by_type.get(k, 0) - flight0.get(k, 0) for k in by_type}
    n_requests = len(obs["requests"])
    for bad in FAILURE_EVENTS:
        if delta.get(bad, 0):
            events = get_json(base_url, f"/debug/flight?type={bad}&n=3")["events"]
            slices = [
                (e["seq"], e["dur_s"], e["rows"], e.get("compiled", False))
                for e in get_json(base_url, "/debug/flight?type=slice&n=64")["events"]
            ]
            raise SmokeFailure(
                f"{delta[bad]} {bad} flight event(s): {json.dumps(events)[:1500]}; "
                f"recent slices (seq, seconds, rows, compiled): {slices}"
            )
    # sessions compile their step at open: no slice may have compiled
    # with rows resident (one would also have fired an anomaly above)
    slices = get_json(
        base_url, f"/debug/flight?type=slice&n={max(1, delta.get('slice', 0))}"
    )["events"]
    obs["slices"] = {
        "count": len(slices),
        "compiled": sum(1 for e in slices if e.get("compiled")),
        "max_s": max((e["dur_s"] for e in slices), default=None),
    }
    check(
        obs["slices"]["compiled"] == 0,
        f"{obs['slices']['compiled']} decode slice(s) compiled with rows resident",
    )
    check(delta.get("join_chunk", 0) >= 2, f"no chunked join counted ({delta})")
    check(
        delta.get("row_retired", 0) == n_requests,
        f"{delta.get('row_retired')} retirements for {n_requests} requests",
    )
    with urllib.request.urlopen(base_url + "/metrics", timeout=10.0) as resp:
        families = parse_exposition(resp.read().decode("utf-8"))
    pages = sample_value(families, "llm_paged_pool_pages")
    free = sample_value(families, "llm_paged_pool_free_pages")
    dp = int(((state.get("mesh") or {}).get("axes") or {}).get("dp", 1))
    check(
        pages is not None and free is not None and 1 <= pages - free <= max(1, dp),
        f"pool not back at its idle free count: {free} free of {pages} pages "
        f"(idle keeps one parking page per dp shard, dp={dp})",
    )
    obs["flight_delta"] = delta
    obs["pool_idle"] = {"pages": pages, "free_pages": free}

    # one pool per slice: with the carry donated, the most a device held
    # during the long request's decode is idle + ONE copy of the KV
    # payload (+ small per-step temporaries); a second pool would double it
    long_session = (long_state.get("scheduler") or {}).get("session") or {}
    pool = long_session.get("pool") or {}
    check(
        long_session.get("paged") is True and pool.get("pages", 0) > 0,
        "long: no live paged session observed while it decoded",
    )
    mesh = long_session.get("mesh") or {}
    kv_bytes = mesh.get("per_device_kv_bytes") or pool.get("payload_bytes")
    # which attention the long request's decode step compiled to, as the
    # session reports it. One device: the table must be wide enough to
    # leave the XLA parts, and the Pallas parts kernel must be what ran.
    # On a mesh the rule is by head/dp divisibility (parallel/tp.py), so
    # the implementation is reported, not required.
    attention = long_session.get("attention") or {}
    if not mesh:
        check(
            attention.get("table_width", 0) > XLA_PARTS_MAX_WIDTH
            and attention.get("impl") == "pallas",
            f"long: decode attention was {attention}, not the Pallas parts "
            f"kernel over a table wider than {XLA_PARTS_MAX_WIDTH}",
        )
    obs["long_session"] = {
        "attention": attention,
        "pool_pages": pool.get("pages"),
        "pool_payload_bytes": pool.get("payload_bytes"),
        "per_device_kv_bytes": mesh.get("per_device_kv_bytes"),
        "mesh": {k: mesh.get(k) for k in ("devices", "axes")} if mesh else None,
    }
    idle_memory = (state.get("device") or {}).get("memory")
    if in_use and idle_memory:
        idle = [m["bytes_in_use"] for m in idle_memory]
        held = [max(s[d] for s in in_use) - idle[d] for d in range(len(idle))]
        obs["decode_bytes_over_idle"] = held
        obs["memory_idle"] = idle_memory
        check(
            max(held) < 1.5 * kv_bytes,
            f"a device held {max(held)} bytes over idle during decode, more "
            f"than 1.5x the {kv_bytes}-byte KV payload: the slice holds two pools",
        )
    else:
        obs["decode_bytes_over_idle"] = "not measured (no memory_stats)"
    return obs


# -- the one child: `serve` on a free port ------------------------------------
def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def tail(path: str, n: int = 40) -> str:
    with open(path, errors="replace") as f:
        return "".join(f.readlines()[-n:])


def stop_server(proc: subprocess.Popen) -> int:
    """SIGINT, then (only if it ignores that) kill; returns the exit code."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=60.0)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            return -9
    return proc.returncode


def versions() -> Dict[str, str]:
    from importlib import metadata

    out = {}
    for dist in ("jax", "jaxlib", "libtpu"):
        try:
            out[dist] = metadata.version(dist)
        except metadata.PackageNotFoundError:
            out[dist] = "not installed"
    return out


def main(argv: Optional[List[str]] = None) -> int:
    serve_extra = list(sys.argv[1:] if argv is None else argv)
    try:
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.profilers.tpu import (
            CHIP_PEAKS,
        )
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.compile_cache import (
            DEFAULT_CACHE_DIR,
        )
    except ImportError as exc:
        print(f"chip_smoke: FAIL: {PACKAGE} is not importable: {exc}", file=sys.stderr)
        return 1

    port = free_port()
    base_url = f"http://127.0.0.1:{port}"
    log_fd, log_path = tempfile.mkstemp(prefix="chip_smoke_serve_", suffix=".log")
    env = dict(os.environ, PYTHONPATH=REPO)
    cmd = [
        sys.executable, "-m", PACKAGE, "serve", "--host", "127.0.0.1",
        "--port", str(port), *SERVE_FLAGS, *serve_extra,
    ]
    print(f"smoke observation: versions {json.dumps(versions())}")
    print(f"smoke observation: serve command {' '.join(cmd[2:])}")
    print(
        "smoke observation: compile cache directory "
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR') or DEFAULT_CACHE_DIR}"
    )
    t0 = time.monotonic()
    proc = subprocess.Popen(
        cmd, cwd=REPO, env=env, stdout=log_fd, stderr=subprocess.STDOUT
    )
    os.close(log_fd)

    def out_of_time() -> None:
        print(f"chip_smoke: FAIL: not done after {DEADLINE_S:.0f} s", file=sys.stderr)
        proc.kill()
        os._exit(1)

    watchdog = threading.Timer(DEADLINE_S, out_of_time)
    watchdog.daemon = True
    watchdog.start()
    failure: Optional[str] = None
    device = None
    try:
        # the server answers /debug/state once the engine exists — no
        # model is loaded yet, so a wrong device costs seconds
        while True:
            check(
                proc.poll() is None,
                f"serve exited with code {proc.returncode} before listening",
            )
            try:
                state = get_json(base_url, "/debug/state", timeout_s=2.0)
                break
            except (urllib.error.URLError, OSError):
                check(time.monotonic() - t0 < 300.0, "serve never listened")
                time.sleep(0.25)
        device = state.get("device") or {}
        print(f"smoke observation: device {json.dumps(device)}")
        check(
            "device_error" not in state,
            f"the serving process could not name its device: "
            f"{state.get('device_error')} — nothing was loaded",
        )
        check(
            device.get("platform") == "tpu",
            f"the serving process is on platform {device.get('platform')!r}, "
            f"not a TPU — nothing was loaded",
        )
        check(
            device.get("kind") in CHIP_PEAKS,
            f"device_kind {device.get('kind')!r} is not in the peaks table "
            f"{sorted(CHIP_PEAKS)}",
        )
        t_listening = time.monotonic() - t0
        obs = run_requests(base_url)
        print(
            "smoke observation: seconds from starting serve to the first "
            "response, model load and compilation included: "
            f"{t_listening + obs['first_response_s']:.1f}"
        )
        print(f"smoke observation: {json.dumps(obs)}")
    except SmokeFailure as exc:
        failure = str(exc)
    finally:
        watchdog.cancel()
        code = stop_server(proc)
    if failure is None and code != 0:
        failure = f"serve exited with code {code} on SIGINT"
    if failure is not None:
        print(f"chip_smoke: FAIL: {failure}", file=sys.stderr)
        print(f"--- serve log tail ({log_path}) ---\n{tail(log_path)}", file=sys.stderr)
        return 1
    os.unlink(log_path)
    print(f"smoke observation: total seconds {time.monotonic() - t0:.1f}")
    report = {k: device[k] for k in ("platform", "kind", "count")}
    print(json.dumps({"ok": True, "device": report}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
