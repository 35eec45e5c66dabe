"""Pure-jnp oracle for the grouped matmul: `jax.lax.ragged_dot` (rows past
the last group come out zero there; the kernel leaves them unspecified)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def moe_grouped_matmul_ref(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)
