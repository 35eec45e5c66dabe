"""Jitted public wrapper for the experts' grouped matmul."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.moe_grouped_matmul import kernel as _kernel
from repro.kernels.runtime import resolve_interpret


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_grouped_matmul(lhs, rhs, group_sizes, layer=0,
                       interpret: Optional[bool] = None):
    """Rows of lhs (m, k), sorted by expert, times their expert's weight in
    rhs[layer]: rhs is a layer loop's stack of held-expert weights (L, G,
    k, n), read in place at the traced int32 `layer`, or one layer's (G, k,
    n); group_sizes (G,) int32, summing to at most m. Rows past the last
    group are unspecified. -> (m, n) in lhs's dtype."""
    return _kernel.moe_grouped_matmul_pallas(
        lhs, rhs, group_sizes, layer,
        interpret=resolve_interpret(interpret))
