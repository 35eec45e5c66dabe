"""Shared kernel-runtime policy helpers.

Every public Pallas wrapper in repro.kernels (`ops.py`) takes
`interpret: Optional[bool]`; `None` resolves through `default_interpret()`,
so the served call sites compile real Mosaic kernels on a TPU. Interpret
mode exists for the CPU test suite only: it runs the same kernel bodies in
Python to check them against their `ref.py` oracles, and says nothing about
whether the TPU compiler accepts them (tests/test_tpu_compile.py does). The
`*_pallas` functions in each `kernel.py` take `interpret` as a required
keyword, so no direct caller lands in interpret mode by default.
"""
from __future__ import annotations

from typing import Optional

import jax


def default_interpret() -> bool:
    """True when Pallas must run in interpret mode (no TPU backend)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Caller override if given, else the backend default."""
    return default_interpret() if interpret is None else bool(interpret)
