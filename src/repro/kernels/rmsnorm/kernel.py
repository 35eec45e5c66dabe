"""Pallas TPU fused RMSNorm.

One VMEM tile of (block_rows, D) rows per grid step; the f32 mean-of-squares
reduction, rsqrt, and scale multiply fuse into a single HBM round trip (the
unfused jnp version reads x twice and writes an f32 temporary). D stays whole
in the lane dimension — RMSNorm needs the full row; block_rows tiles the
sublane dimension in multiples of 8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rms_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_pallas(x, scale, eps: float = 1e-6, block_rows: int = 256, *,
                   interpret: bool):
    orig_shape = x.shape
    D = x.shape[-1]
    xf = x.reshape(-1, D)
    R = xf.shape[0]
    br = min(block_rows, R)
    while R % br:
        br -= 1
    kernel = functools.partial(_rms_kernel, eps=eps)
    out = pl.pallas_call(
        kernel,
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((br, D), lambda r: (r, 0)),
            pl.BlockSpec((1, D), lambda r: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        interpret=interpret,
    )(xf, scale.reshape(1, D))
    return out.reshape(orig_shape)
