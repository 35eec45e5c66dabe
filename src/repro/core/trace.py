"""In-program spans and intervals on the profiler's host clock.

One process-wide tracer, off by default. Whoever measures the program
turns it on with `enable()`, off with `disable()`, and reads what it kept
with `records()`; there is no flag or environment variable.

- `span(name, **attrs)` times synchronous work. When on it keeps a
  `Record` and enters a `jax.profiler.TraceAnnotation` (named
  `<name>.<engine>` where the span has an `engine` attribute), so a
  profiler trace shows the same span. Spans nest per thread: a span's
  `parent` is the span open around it. A span must not hold an `await`
  (the annotations of one thread must nest).
- `interval(name, start_ns, end_ns, **attrs)` records a wait that crosses
  `await`s (a request's queueing, a pipeline stage). It goes to memory
  only, never to an annotation; its parent is given, not inferred.
- `REQUEST` carries the current user request's id. Every record made while
  it is set gets a `request` attribute, so the spans and sub-requests of
  one request can be found together.
- Counts are attributes of the span where the work happens (the rows an
  ingest call ran, the context length of each decode row).
- While on, each backend compile and each read from the persistent
  compile cache becomes a `jax.compile` / `jax.cache_read` record under
  the innermost open span, so a run can say which phase built a program.

Stamps are `time.time_ns()`: the JAX profiler stamps host events with the
same wall clock, and an xplane's event times are offsets from its
`profile_start_time`. Records live in a bounded buffer (the oldest drop).

Off costs one attribute check per call site and returns a shared null
context: no stamp, no annotation, no record. A call site that would build
an attribute (a list, a sum) guards it with the span it opened:
`with trace.span(...) as sp: if sp: sp.attrs[...] = ...`.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import threading
import time
from typing import Deque, List, NamedTuple, Optional

import jax

now = time.time_ns

REQUEST: contextvars.ContextVar = contextvars.ContextVar(
    "repro_trace_request", default=None)

# records kept; past it the oldest drop
CAPACITY = 1 << 18

# jax.monitoring duration events -> the record they become
COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "jax.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_read",
}


class Record(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    id: int
    parent: Optional[int]
    attrs: dict


class _State:
    def __init__(self):
        self.on = False
        self.buffer: Deque[Record] = collections.deque(maxlen=CAPACITY)
        self.ids = itertools.count(1)
        self.local = threading.local()
        self.listening = False


_S = _State()
_NULL = contextlib.nullcontext()


def _stack() -> list:
    st = getattr(_S.local, "stack", None)
    if st is None:
        st = _S.local.stack = []
    return st


def _tag(attrs: dict) -> dict:
    rid = REQUEST.get()
    if rid is not None:
        attrs.setdefault("request", rid)
    return attrs


class _Span:
    __slots__ = ("name", "attrs", "id", "parent", "start", "ann")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_Span":
        stack = _stack()
        self.parent = stack[-1].id if stack else None
        self.id = next(_S.ids)
        stack.append(self)
        _tag(self.attrs)
        eng = self.attrs.get("engine")
        self.ann = jax.profiler.TraceAnnotation(
            f"{self.name}.{eng}" if eng else self.name)
        self.start = now()
        self.ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self.ann.__exit__(*exc)
        end = now()
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        _S.buffer.append(Record(self.name, self.start, end, self.id,
                                self.parent, self.attrs))
        return False


def span(name: str, **attrs):
    """Context manager timing synchronous work (see the module doc). The
    value bound by `with` is the open span, or None when the tracer is
    off; attributes known only inside go to `sp.attrs`."""
    if not _S.on:
        return _NULL
    return _Span(name, attrs)


def interval(name: str, start_ns: int, end_ns: int,
             parent: Optional[int] = None, span_id: Optional[int] = None,
             **attrs) -> Optional[int]:
    """Record a wait from `start_ns` to `end_ns` (profiler clock) that
    crossed awaits. `span_id` is an id taken earlier with `new_id()` (so
    children recorded first can name it as their `parent`). Returns the
    record's id, or None when off."""
    if not _S.on:
        return None
    rid = next(_S.ids) if span_id is None else span_id
    _S.buffer.append(Record(name, start_ns, end_ns, rid, parent,
                            _tag(attrs)))
    return rid


def new_id() -> Optional[int]:
    """An id for an interval to be recorded later, or None when off."""
    return next(_S.ids) if _S.on else None


def from_perf(t_s: float) -> int:
    """A `time.perf_counter()` stamp on the profiler clock, in ns."""
    return now() - int((time.perf_counter() - t_s) * 1e9)


def enabled() -> bool:
    return _S.on


def enable() -> None:
    """Turn the tracer on with an empty buffer."""
    if not _S.listening:
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _S.listening = True
    _S.buffer = collections.deque(maxlen=CAPACITY)
    _S.on = True


def disable() -> None:
    """Turn the tracer off; what it recorded stays readable."""
    _S.on = False


def records() -> List[Record]:
    return list(_S.buffer)


def _on_event(event: str, duration_s: float, **_) -> None:
    kind = COMPILE_EVENTS.get(event)
    if not _S.on or kind is None:
        return
    stack = _stack()
    inner = stack[-1] if stack else None
    attrs = {"seconds": duration_s}
    if inner is not None:
        attrs["span"] = inner.name
        if "engine" in inner.attrs:
            attrs["engine"] = inner.attrs["engine"]
    end = now()
    _S.buffer.append(Record(kind, end - int(duration_s * 1e9), end,
                            next(_S.ids),
                            inner.id if inner is not None else None,
                            _tag(attrs)))
