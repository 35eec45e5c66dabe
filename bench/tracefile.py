"""Reduce a JAX profiler trace to what the per-layer metrics read.

`load` turns the `.xplane.pb` the profiler wrote into plain lists: the
operations each device ran (name, start, end in ns) and the host spans the
benchmark recorded (`bench.*` TraceAnnotations). Both sit on the
profiler's one clock. An operation's name is its HLO instruction's
(`%paged_decode_attention.10 = ...` gives `paged_decode_attention.10`);
a Pallas kernel's custom call is named after the function that calls
`pallas_call`. The device's "XLA Ops" line nests the operations of a loop
body inside the loop's own event. The rest is arithmetic on those lists,
checked on hand-built traces by bench/tests:

- busy: the union of a device's operation intervals inside the window;
- kernel time: the summed durations of the operations whose name, less
  its `.N` suffix, starts with the kernel's name;
- op totals: device time per operation name, control-flow containers
  (`while`, `conditional`, `call`) left out so that nothing counts twice;
- idle gaps: the stretches of the window with no operation on the device,
  each named by the innermost harness span that covers the gap's
  midpoint, skipping spans held open across awaits
  (`bench.await.*`), which say what was pending, not what the host did.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Interval = Tuple[str, int, int]      # (name, start_ns, end_ns)
HOST_PREFIX = "bench."
NO_SPAN = "host outside any harness span"


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str) -> Dict[str, object]:
    """{"devices": {plane name: [ops]}, "spans": [bench.* host spans]}."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "NON_CORE" not in \
                plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get("XLA Ops")
            if line is None:
                continue
            devices[plane.name] = [
                (op_name(e.name), int(e.start_ns),
                 int(e.start_ns + e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(HOST_PREFIX):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns)))
    return {"devices": devices, "spans": spans}


def op_name(text: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion.12`."""
    return text.split(" = ", 1)[0].lstrip("%")


def window_of(spans: List[Interval], name: str) -> Optional[Tuple[int, int]]:
    hits = [(s, e) for n, s, e in spans if n == name]
    return hits[-1] if hits else None


def merged(ops: List[Interval], lo: int, hi: int) -> List[Tuple[int, int]]:
    """The union of the op intervals, clipped to [lo, hi], as sorted
    disjoint intervals."""
    ivs = sorted((max(s, lo), min(e, hi)) for _, s, e in ops
                 if e > lo and s < hi)
    out: List[List[int]] = []
    for s, e in ivs:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: List[Interval], lo: int, hi: int) -> int:
    return sum(e - s for s, e in merged(ops, lo, hi))


def gaps(ops: List[Interval], lo: int, hi: int) -> List[Tuple[int, int]]:
    out, t = [], lo
    for s, e in merged(ops, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gap_list: List[Tuple[int, int]], spans: List[Interval],
              skip: Tuple[str, ...] = ("bench.await.", "bench.span")
              ) -> Dict[str, int]:
    """Idle ns per harness span name: the innermost span covering each
    gap's midpoint (the latest-starting one, as spans of one thread nest),
    else NO_SPAN."""
    usable = sorted(((s, e, n) for n, s, e in spans
                     if not n.startswith(skip)), key=lambda x: x[0])
    starts = [s for s, _, _ in usable]
    out: Dict[str, int] = defaultdict(int)
    for gs, ge in gap_list:
        mid = (gs + ge) // 2
        name = NO_SPAN
        for i in range(bisect.bisect_right(starts, mid) - 1,
                       max(-1, bisect.bisect_right(starts, mid) - 65), -1):
            s, e, n = usable[i]
            if e >= mid:
                name = n
                break
        out[name] += ge - gs
    return dict(out)


_SUFFIX = re.compile(r"\.\d+$")
CONTAINERS = ("while", "conditional", "call")


def base_name(name: str) -> str:
    return _SUFFIX.sub("", name)


def op_totals(ops: List[Interval], lo: int, hi: int) -> Dict[str, int]:
    """Device ns per operation name, numbered copies (`fusion.12`) merged,
    loop and call containers left out (their bodies are listed)."""
    out: Dict[str, int] = defaultdict(int)
    for n, s, e in ops:
        b = base_name(n)
        if e > lo and s < hi and b not in CONTAINERS:
            out[b] += min(e, hi) - max(s, lo)
    return dict(out)


def kernel_ns(ops: List[Interval], kernel: str, lo: int, hi: int
              ) -> Tuple[int, int]:
    """(summed ns, event count) of the ops named after `kernel`."""
    hits = [(s, e) for n, s, e in ops
            if base_name(n).startswith(kernel) and e > lo and s < hi]
    return sum(e - s for s, e in hits), len(hits)


def top(d: Dict[str, int], k: int = 10) -> List[List[object]]:
    return [[n, v / 1e9] for n, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:k]]
