"""What the per-layer readers (bench/metrics/*.py) read: one traced
window's probe counts, its trace reduced by bench/tracefile.py, the
engines' sizes and the chip's peaks. A reader returns None where it finds
nothing to read, and the harness then leaves its metric out of the line;
on the chip, a metric that BENCHMARK.json lists for the cell and that reads
nothing fails the traced run (bench/run.py), so a reader that loses its
source cannot go unnoticed."""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from bench import flops, tracefile

# custom-call names of the Pallas kernels in a TPU trace (named after the
# kernels/*/kernel.py functions that call pallas_call); the prefill name
# covers the ragged call and its one-row form
DECODE_KERNEL = "paged_decode_attention"
PREFILL_KERNEL = "paged_prefill_attention"


@dataclasses.dataclass
class Context:
    probe: object                       # bench.probe.Probe
    window: object                      # bench.drivers.Window
    dims: Dict[str, dict]               # engine -> sizes (fleet.engine_dims)
    peaks: Optional[dict]               # bench/peaks.py entry
    ops: List[tracefile.Interval]       # device ops of the chip used
    lo: int = 0                         # traced span, profiler clock (ns)
    hi: int = 0
    spans: List[tracefile.Interval] = dataclasses.field(default_factory=list)

    def finished(self):
        """The window's requests that completed, drained ones included:
        per-request readings do not depend on where the traced span fell
        or how far the host lagged."""
        return [d for d in self.window.records if d.ok]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        return tracefile.busy_ns(self.ops, self.lo, self.hi) / 1e9

    def kernel_s(self, kernel: str) -> float:
        return tracefile.kernel_ns(self.ops, kernel, self.lo, self.hi)[0] / 1e9

    def roofline_share(self, kernel: str, calls, cost) -> Optional[float]:
        """Least time of the recorded calls (every layer of each) over the
        kernel's traced time, in %; None without calls or kernel time."""
        t = self.kernel_s(kernel)
        if not calls or t <= 0 or self.peaks is None:
            return None
        least = sum(self.dims[eng]["n_layers"]
                    * flops.least_seconds(*cost(self.dims[eng], rows),
                                          self.peaks)[0]
                    for eng, rows in calls)
        return 100.0 * least / t
