"""Closed-loop load through SearchClient.

Each of `clients` callers holds one request at a time: it submits, reads
the request's moves as they commit through its handle's `moves()`, and
submits its next request the moment the last move arrives.  Reading a
handle's next move polls the client until that move commits, so the
loop reads only requests that hold a slot or have finished: a queued
request never keeps the callers of finished ones from resubmitting.

Handles are read in submission order, skipping queued ones unless all
are queued; a read of a finished request never polls.

A move's latency runs from its request's submission (first move) or its
previous move to the moment the caller reads it.  Moves count in the
window when read by its close.  After the close no request is
submitted; those in flight run to their end (`drain`), so every
attempted request has its whole result for the correctness check.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Iterator


@dataclasses.dataclass
class Live:
    caller: int
    spec: dict               # SearchRequest fields
    handle: object
    moves: Iterator
    last_t: float


@dataclasses.dataclass
class Window:
    seconds: float = 0.0
    moves: int = 0                     # moves read inside the window
    latencies_s: list = dataclasses.field(default_factory=list)
    attempted: list = dataclasses.field(default_factory=list)  # specs
    results: dict = dataclasses.field(default_factory=dict)    # uid -> res


def no_span(name: str):
    return contextlib.nullcontext()


def run(client, next_request: Callable[[], dict], clients: int,
        seconds: float, span=no_span, on_tick=None) -> Window:
    """Drive `client` closed loop for `seconds`; `next_request()` gives
    the fields of the next SearchRequest.  `span(name)` wraps each call
    into the client (a profiler annotation in traced runs); `on_tick(t)`
    is called before each round of reads, for a caller that marks a
    stretch."""
    from repro.service import SearchRequest

    win = Window(seconds=float(seconds))
    live: list[Live] = []

    def submit(caller: int, now: float):
        spec = next_request()
        with span("submit"):
            h = client.submit(SearchRequest(**spec))
        live.append(Live(caller, spec, h, h.moves(), now))
        win.attempted.append(spec)

    def read(lv: Live, t_end: float, in_window: bool) -> bool:
        """Advance one handle to its next move; False once it ended."""
        with span("poll"):
            ev = next(lv.moves, None)
        now = time.perf_counter()
        if ev is not None:
            if in_window and now <= t_end:
                win.moves += 1
                win.latencies_s.append(now - lv.last_t)
            lv.last_t = now
            if not ev.last:
                return True
        with span("result"):
            win.results[lv.spec["uid"]] = lv.handle.result(wait=False)
        return False

    t0 = time.perf_counter()
    t_end = t0 + seconds
    for c in range(clients):
        submit(c, t0)
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if on_tick is not None:
            on_tick(now)
        ready = [lv for lv in live if lv.handle.status() != "queued"]
        for lv in ready or live[:1]:
            if not read(lv, t_end, True):
                live.remove(lv)
                if time.perf_counter() < t_end:
                    submit(lv.caller, time.perf_counter())
    drain(live, lambda lv: read(lv, t_end, False))
    return win


def drain(live: list, read) -> None:
    """Run every request still in flight to its end."""
    while live:
        for lv in list(live):
            if not read(lv):
                live.remove(lv)


def warm(client, requests: list[dict]) -> list:
    """Serve `requests` to their end (set-up: compiles and fills every
    program cache the window will use)."""
    from repro.service import SearchRequest

    handles = [client.submit(SearchRequest(**r)) for r in requests]
    return [h.result() for h in handles]
