"""The benchmark's spans and counters around the program's layers.

Nothing here is inside `src/`: the harness wraps the calls into each layer.

- Front-ends: `ProbedFrontend` subclasses the program's `EngineFrontend`;
  its `generate_async` / `generate_fanout_async` / `submit` record the
  tokens each call served (for the correctness check), which request of
  the window asked (a context variable the driver sets), when a sketch came
  back, and how many expansion groups ran.
- Engines: `Probe.instrument` wraps one engine's `step`, `prefill_prefix`,
  decode dispatch, ragged ingest and single-slot chunk feed, and sets its
  `step_hook`. Inside the window (`counting`) it counts steps and active
  slots, and records every attention-kernel call's shapes: the context
  length of each decode row, the (offset, length) of each ingest row.
- With `tracing` on, each of those calls runs under a
  `jax.profiler.TraceAnnotation` named `bench.<what>.<engine>`, so the
  profiler's trace can say what the host was doing in each device gap.
  Spans that stay open across awaits are named `bench.await.*`.
"""
from __future__ import annotations

import contextlib
import contextvars
import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from repro.serving.frontend import EngineFrontend

# index of the window request a coroutine serves (set by the driver)
REQUEST = contextvars.ContextVar("bench_request", default=None)


class Probe:
    def __init__(self, tracing: bool = False):
        self.tracing = tracing
        self.counting = False
        self.steps: Dict[str, int] = defaultdict(int)
        self.active_share_sum: Dict[str, float] = defaultdict(float)
        # (engine, [context length of each decode row]) per decode call
        self.decode_calls: List[Tuple[str, List[int]]] = []
        # (engine, [(offset, length) of each row]) per prefill-kernel call
        self.prefill_calls: List[Tuple[str, List[Tuple[int, int]]]] = []
        # per window request: [(engine, role, prompt, tokens)]
        self.served: Dict[int, list] = defaultdict(list)
        self.sketch_done: Dict[int, float] = {}
        self.groups: Dict[int, int] = defaultdict(int)

    def span(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    # -- engines --------------------------------------------------------
    def instrument(self, engine) -> None:
        name = engine.name
        step, prefix = engine.step, engine.prefill_prefix
        dispatch, ingest = engine._dispatch_decode, engine._run_ingest
        feed = engine._feed_chunk

        def hook(eng):
            if self.counting:
                self.steps[name] += 1
                self.active_share_sum[name] += (
                    sum(1 for s in eng.slots if s.active) / eng.max_batch)

        def probed_step():
            with self.span(f"bench.step.{name}"):
                return step()

        def probed_prefix(toks):
            with self.span(f"bench.prefill_prefix.{name}"):
                return prefix(toks)

        def probed_dispatch(plan):
            if self.counting:
                self.decode_calls.append(
                    (name, [engine.slots[i].ctx_len for i in plan.active_ids]))
            return dispatch(plan)

        def probed_ingest():
            if self.counting:
                C = engine.prefill_chunk
                rows = [(s.ctx_len, min(C, len(s.prefill_toks)))
                        for s in engine.slots if s.active and s.prefill_toks]
                if rows:
                    self.prefill_calls.append((name, rows))
            return ingest()

        def probed_feed(slot, chunk, offset):
            if self.counting:
                self.prefill_calls.append((name, [(offset, len(chunk))]))
            return feed(slot, chunk, offset)

        engine.step_hook = hook
        engine.step = probed_step
        engine.prefill_prefix = probed_prefix
        engine._dispatch_decode = probed_dispatch
        engine._run_ingest = probed_ingest
        engine._feed_chunk = probed_feed

    # -- requests -------------------------------------------------------
    def record(self, engine: str, role: str, prompts, outs) -> None:
        i = REQUEST.get()
        if i is None:
            return
        for p, (toks, _) in zip(prompts, outs):
            self.served[i].append((engine, role, list(p), list(toks)))


def frontend_class(probe: Probe):
    """An `EngineFrontend` whose pipeline facades record through `probe`."""

    class ProbedFrontend(EngineFrontend):
        async def generate_async(self, prompts, max_new: int = 128,
                                 priorities=None,
                                 deadline_s: Optional[float] = None,
                                 role: str = "generic"):
            with probe.span(f"bench.await.{role}.{self.engine.name}"):
                outs = await super().generate_async(
                    prompts, max_new=max_new, priorities=priorities,
                    deadline_s=deadline_s, role=role)
            i = REQUEST.get()
            if role == "sketch" and i is not None:
                probe.sketch_done[i] = time.perf_counter()
            probe.record(self.engine.name, role, prompts, outs)
            return outs

        async def generate_fanout_async(self, prefix, suffixes,
                                        max_new: int = 128,
                                        priority: int = 0,
                                        deadline_s: Optional[float] = None,
                                        role: str = "expansion_primary"):
            with probe.span(f"bench.await.{role}.{self.engine.name}"):
                outs = await super().generate_fanout_async(
                    prefix, suffixes, max_new=max_new, priority=priority,
                    deadline_s=deadline_s, role=role)
            i = REQUEST.get()
            if i is not None:
                probe.groups[i] += len(suffixes)
            probe.record(self.engine.name, role,
                         [list(prefix) + list(s) for s in suffixes], outs)
            return outs

    return ProbedFrontend
