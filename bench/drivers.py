"""Drive the system under test through one measured window.

One asyncio event loop in one thread: the load generator, the PICE
pipeline and every engine front-end's step loop share it, as they do in
`python -m repro.launch.serve`. Each request runs in a task of its own with
`probe.REQUEST` set, so the front-ends can tell whose tokens they served.

With `span` = (offset, length, start, stop), `start()` runs `offset`
seconds into the window and `stop()` `length` seconds later (the traced
span of a `--trace 1` run).

Open loop: each request is submitted at its due time, and its latency runs
from that due time to its complete answer, so a stalled generator or a
queue shows up in the latency; the lateness of each submission is kept.
Requests due in the window are drained afterwards, up to `drain_s`; one
that is not done by then has failed. Closed loop: the window opens when
`window_opens` says (after a lead-in, or once the slots are busy) and
counts what completes inside it; nothing is drained.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Callable, Dict, List, Optional

from bench import probe as probe_lib


@dataclasses.dataclass
class Done:
    index: int
    due: float                  # perf_counter stamp the latency runs from
    end: float = 0.0
    ok: bool = False
    answer_tokens: int = 0
    mode: str = ""
    degraded: str = ""
    error: str = ""


@dataclasses.dataclass
class Window:
    open: float = 0.0
    close: float = 0.0
    records: List[Done] = dataclasses.field(default_factory=list)
    lateness: List[float] = dataclasses.field(default_factory=list)
    in_flight_at_close: int = 0
    drain_s: float = 0.0
    open_loop: bool = True
    span_task: Optional[asyncio.Task] = None   # the traced span's timer

    @property
    def seconds(self) -> float:
        return self.close - self.open


async def _serve_one(system, item, due: float, done: Done,
                     probe: probe_lib.Probe) -> None:
    """One request through the entry the cell drives."""
    from repro.serving.frontend import CompletionRequest
    from repro.serving.requests import Request
    probe_lib.REQUEST.set(done.index)
    p = item.payload
    try:
        if system.kind == "pice_fleet":
            resp = await system.pipeline.handle_async(Request(
                query=p["query"], category=p["category"],
                max_new_tokens=p["max_new_tokens"], arrival_time_s=due))
            done.mode, done.degraded = resp.mode, resp.degraded
            done.answer_tokens = (resp.edge_tokens if resp.mode ==
                                  "progressive" else resp.cloud_tokens)
        elif "suffixes" in p:
            outs = await system.frontend.generate_fanout_async(
                p["prefix"], p["suffixes"], max_new=p["max_new"])
            done.answer_tokens = sum(len(t) for t, _ in outs)
        else:
            h = system.frontend.submit(CompletionRequest(
                prompt=p["prompt"], max_tokens=p["max_tokens"],
                arrival_time_s=due))
            await h.wait()
            if h.state != "done":
                raise RuntimeError(f"request ended {h.state}: "
                                   f"{h.finish_reason} {h.error or ''}")
            probe.record(system.frontend.engine.name, "chat", [p["prompt"]],
                         [(h.tokens, h.logprobs)])
            done.answer_tokens = len(h.tokens)
        done.ok = True
    except Exception as exc:    # a failed request is a data point
        done.error = f"{type(exc).__name__}: {exc}"
    done.end = time.perf_counter()


def _busy_share(system) -> float:
    engines = list(system.engines.values())
    return min(sum(1 for s in e.slots if s.active) / e.max_batch
               for e in engines)


async def _span(t0: float, span) -> None:
    offset, length, start, stop = span
    await asyncio.sleep(max(0.0, t0 + offset - time.perf_counter()))
    start()
    await asyncio.sleep(length)
    stop()


def _begin(span, on_open, w: "Window") -> float:
    on_open()
    t0 = time.perf_counter()
    if span is not None:
        w.span_task = asyncio.get_running_loop().create_task(_span(t0, span))
    return t0


async def _open_loop(system, items, seconds, drain_s, probe, on_open,
                     on_close, span) -> Window:
    w = Window(open_loop=True)
    tasks = []
    w.open = t0 = _begin(span, on_open, w)
    for i, item in enumerate(items):
        due = t0 + item.due_s
        wait = due - time.perf_counter()
        if wait > 0:
            await asyncio.sleep(wait)
        w.lateness.append(time.perf_counter() - due)
        d = Done(i, due)
        w.records.append(d)
        tasks.append(asyncio.get_running_loop().create_task(
            _serve_one(system, item, due, d, probe)))
    wait = t0 + seconds - time.perf_counter()
    if wait > 0:
        await asyncio.sleep(wait)
    w.in_flight_at_close = sum(1 for t in tasks if not t.done())
    w.close = time.perf_counter()
    on_close()
    pending = [t for t in tasks if not t.done()]
    if pending:
        await asyncio.wait(pending, timeout=drain_s)
    w.drain_s = time.perf_counter() - w.close  # tracing's stop included
    for d, t in zip(w.records, tasks):
        if not t.done():
            d.error = f"not done {drain_s:.0f}s after the window closed"
            t.cancel()
    return w


async def _closed_loop(system, items, seconds, opens: dict, clients: int,
                       probe, on_open, on_close, span) -> Window:
    w = Window(open_loop=False)
    started = time.perf_counter()
    state = {"next": 0, "stop": False}
    all_done: List[Done] = []

    async def client():
        while not state["stop"]:
            i = state["next"]
            state["next"] += 1
            d = Done(i, time.perf_counter())
            all_done.append(d)
            await _serve_one(system, items[i % len(items)], d.due, d, probe)

    loop = asyncio.get_running_loop()
    tasks = [loop.create_task(client()) for _ in range(clients)]
    lead = opens.get("after_s", 0.0)
    busy = opens.get("slots_busy")
    while (time.perf_counter() - started < lead
           or (busy is not None and _busy_share(system) < busy)):
        await asyncio.sleep(0.005)
    w.open = _begin(span, on_open, w)
    await asyncio.sleep(max(0.0, w.open + seconds - time.perf_counter()))
    state["stop"] = True
    w.close = time.perf_counter()
    on_close()
    w.records = [d for d in all_done if w.open <= d.end <= w.close]
    w.in_flight_at_close = sum(1 for d in all_done if d.end == 0.0)
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return w


def measure(system, items, traffic: dict, seconds: float,
            probe: probe_lib.Probe, on_open: Callable[[], None],
            on_close: Callable[[], None], span=None) -> Window:
    arr = traffic["arrival"]

    async def go():
        if arr["process"] == "closed":
            return await _closed_loop(system, items, seconds,
                                      traffic["window_opens"], arr["clients"],
                                      probe, on_open, on_close, span)
        return await _open_loop(system, items, seconds, traffic["drain_s"],
                                probe, on_open, on_close, span)
    return asyncio.run(go())


def latency_quantile(records: List[Done], q: float) -> float:
    """Nearest-rank quantile of latencies from due time; a failed request
    counts as never answered (infinite)."""
    lat = sorted((d.end - d.due) if d.ok else float("inf") for d in records)
    if not lat:
        return float("nan")
    k = max(0, min(len(lat) - 1, int(-(-q * len(lat) // 1)) - 1))
    return lat[k]


def modes(records: List[Done]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for d in records:
        key = (d.mode or "-") + ("/" + d.degraded if d.degraded else "")
        if not d.ok:
            key = "failed"
        out[key] = out.get(key, 0) + 1
    return out
