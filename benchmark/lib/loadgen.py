"""Drives the system with a mix's traffic from one process: a consumer
thread per closed-loop client (or per open-loop request), all timing on
``time.monotonic`` at the consumer of the stream."""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

from .stats import Record
from .traffic import Planned, planned

DRAIN_TIMEOUT_S = 90.0


class Load:
    """Traffic of (mix, seed) against ``system``. ``start()`` begins it,
    ``stop()`` ends new submissions and waits for what is in flight."""

    def __init__(self, system, mix: Dict[str, Any], seed: int) -> None:
        self.system = system
        self.mix = mix
        self.records: List[Record] = []
        self._plan = planned(mix, seed)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self.t_start: Optional[float] = None
        self.late_s: List[float] = []  # open loop: how late each send ran

    # -- shared ---------------------------------------------------------------
    def _record(self, p: Planned, t_due: Optional[float]) -> Record:
        now = time.monotonic()
        rec = Record(
            index=p.index, prompt_tokens=p.prompt_tokens,
            output_tokens=p.output_tokens, t_submit=now,
            t_due=now if t_due is None else t_due, prompt=p.prompt,
        )
        with self._lock:
            self.records.append(rec)
        return rec

    def _consume(self, rec: Record, stream) -> None:
        for ev in stream.events():
            now = time.monotonic()
            if ev.kind == "delta":
                if ev.tokens:
                    rec.events.append((now, len(ev.tokens)))
                    rec.tokens.extend(int(t) for t in ev.tokens)
            elif ev.kind == "done":
                rec.t_done = now
            elif ev.kind == "error":
                rec.error = f"{type(ev.error).__name__}: {ev.error}"
                rec.t_done = now

    def _send(self, p: Planned, t_due: Optional[float] = None):
        """Submit one request; closed loop: it is due when it is sent."""
        rec = self._record(p, t_due)
        try:
            return rec, self.system.submit(p.prompt, p.output_tokens)
        except Exception as exc:  # noqa: BLE001 - a refused request is a failed one
            rec.error = f"{type(exc).__name__}: {exc}"
            rec.t_done = time.monotonic()
            return rec, None

    # -- closed loop -----------------------------------------------------------
    def _client(self, first) -> None:
        """One client: consume a request's stream to its end, then send the
        plan's next request, whichever client that makes it: requests go out
        in the plan's order, so warm-up knows what has been admitted."""
        rec, stream = first
        while True:
            if stream is not None:
                self._consume(rec, stream)
            if self._stop.is_set():
                return
            with self._lock:
                p = next(self._plan)
            rec, stream = self._send(p)

    def _start_closed(self) -> None:
        clients = int(self.mix["clients"])
        fleet = [next(self._plan) for _ in range(clients)]
        # the whole first fleet enters the queue before the scheduler's
        # thread can open a session: its static shapes come from all of it
        firsts = [self._send(p) for p in fleet]
        for c in range(clients):
            th = threading.Thread(
                target=self._client, args=(firsts[c],), name=f"client-{c}", daemon=True,
            )
            self._threads.append(th)
            th.start()

    # -- open loop -------------------------------------------------------------
    def _one(self, p: Planned, t_due: float) -> None:
        rec, stream = self._send(p, t_due)
        if stream is not None:
            self._consume(rec, stream)

    def _dispatch(self) -> None:
        burst0 = int(self.mix.get("first_burst", 0))
        for p in self._plan:
            t_due = self.t_start + (0.0 if p.index < burst0 else p.due_s)
            delay = t_due - time.monotonic()
            if delay > 0 and self._stop.wait(delay):
                return
            if self._stop.is_set():
                return
            self.late_s.append(max(0.0, time.monotonic() - t_due))
            th = threading.Thread(
                target=self._one, args=(p, t_due),
                name=f"request-{p.index}", daemon=True,
            )
            with self._lock:
                self._threads.append(th)
            th.start()

    # -- lifecycle -------------------------------------------------------------
    def start(self) -> None:
        self.t_start = time.monotonic()
        if self.mix["arrival"] == "closed":
            self._start_closed()
        else:
            th = threading.Thread(target=self._dispatch, name="dispatch", daemon=True)
            self._threads.append(th)
            th.start()

    def first_token_seen(self) -> bool:
        with self._lock:
            return any(r.events for r in self.records)

    def first_error(self) -> Optional[str]:
        with self._lock:
            return next((r.error for r in self.records if r.error is not None), None)

    def finished(self) -> int:
        with self._lock:
            return sum(1 for r in self.records if r.t_done is not None)

    def stop(self) -> List[str]:
        """End new submissions, wait for every request in flight, and
        return the names of threads that did not end in time."""
        self._stop.set()
        deadline = time.monotonic() + DRAIN_TIMEOUT_S
        left = []
        while True:
            with self._lock:
                threads = list(self._threads)
            alive = [t for t in threads if t.is_alive()]
            if not alive:
                break
            if time.monotonic() > deadline:
                left = [t.name for t in alive]
                break
            alive[0].join(timeout=max(0.0, min(1.0, deadline - time.monotonic())))
        return left
