"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

TPU adaptation: the GPU Mamba2 kernel parallelizes over (batch, head) blocks
with warp-level intra-chunk matmuls. On TPU we map the chunk loop onto the
*sequential* minor grid dimension (TPU grids execute in order), carrying the
(P, N) recurrent state in a VMEM scratch accumulator — the same pattern flash
attention uses for its running softmax. Intra-chunk work is MXU matmuls on
(Q, N) x (N, Q) and (Q, Q) x (Q, P) tiles; Q and N are chosen as multiples of
128 for MXU alignment (P=64 packs two heads per lane tile in practice; we keep
P free and let Mosaic pick the layout).

Grid: (B*H, S // Q) — state scratch persists across the minor (chunk) axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,   # inputs
                y_ref, state_out_ref,                 # outputs
                h_scratch,                            # scratch (P, N) f32
                *, nc: int):
    """One (batch*head, chunk) step.

    x_ref: (Q, P); dt_ref: (Q, 1); a_ref: (1, 1); b_ref/c_ref: (Q, N);
    y_ref: (Q, P); state_out_ref: (P, N); h_scratch: (P, N).
    """
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scratch[...] = jnp.zeros_like(h_scratch)

    x = x_ref[0].astype(jnp.float32)                   # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)                 # (Q, 1)
    A = a_ref[0, 0, 0].astype(jnp.float32)             # scalar
    B = b_ref[0].astype(jnp.float32)                   # (Q, N)
    C = c_ref[0].astype(jnp.float32)                   # (Q, N)
    Q = x.shape[0]

    dA = dt[:, 0] * A                                  # (Q,)
    ca = jnp.cumsum(dA)                                # inclusive
    ca_end = ca[-1]

    # intra-chunk
    decay = ca[:, None] - ca[None, :]                  # (Q, Q) t,s
    tri = jnp.tril(jnp.ones((Q, Q), jnp.bool_))
    L = jnp.where(tri, jnp.exp(decay), 0.0)
    cb = jnp.dot(C, B.T, preferred_element_type=jnp.float32)   # (Q, Q)
    w = cb * L * dt[None, :, 0]                        # weight[t, s]
    y_intra = jnp.dot(w, x, preferred_element_type=jnp.float32)  # (Q, P)

    # state contribution from previous chunks
    h_prev = h_scratch[...]                            # (P, N)
    y_state = jnp.dot(C, h_prev.T, preferred_element_type=jnp.float32)  # (Q, P)
    y_state = y_state * jnp.exp(ca)[:, None]
    y_ref[0] = (y_intra + y_state).astype(y_ref.dtype)

    # update carried state: h = exp(ca_end) h_prev + sum_s exp(ca_end-ca_s) dt_s x_s B_s^T
    kdecay = jnp.exp(ca_end - ca) * dt[:, 0]           # (Q,)
    G = jnp.dot((x * kdecay[:, None]).T, B,
                preferred_element_type=jnp.float32)    # (P, N)
    h_new = h_prev * jnp.exp(ca_end) + G
    h_scratch[...] = h_new

    @pl.when(ci == nc - 1)
    def _emit():
        state_out_ref[0] = h_new.astype(state_out_ref.dtype)


def ssd_pallas(x, dt, A, B, C, chunk: int = 128, *, interpret: bool):
    """x: (Bb,S,H,P), dt: (Bb,S,H), A: (H,), B/C: (Bb,S,N).

    Returns (y (Bb,S,H,P) f32, final_state (Bb,H,P,N) f32).
    Zero initial state (models pass prefill-from-scratch here; decode uses the
    recurrent jnp step).
    """
    Bb, S, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, S)
    while S % Q:
        Q //= 2
    nc = S // Q

    # flatten (batch, head) onto the parallel grid axis
    xf = jnp.moveaxis(x, 2, 1).reshape(Bb * H, S, P)
    dtf = jnp.moveaxis(dt, 2, 1).reshape(Bb * H, S, 1)
    af = jnp.tile(A.reshape(1, H, 1, 1), (Bb, 1, 1, 1)).reshape(Bb * H, 1, 1)
    bf = jnp.repeat(B[:, None], H, axis=1).reshape(Bb * H, S, N)
    cf = jnp.repeat(C[:, None], H, axis=1).reshape(Bb * H, S, N)

    kernel = functools.partial(_ssd_kernel, nc=nc)
    y, state = pl.pallas_call(
        kernel,
        grid=(Bb * H, nc),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, Q, 1), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, 1, 1), lambda g, c: (g, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, Q, N), lambda g, c: (g, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, P, N), lambda g, c: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb * H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((Bb * H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xf, dtf, af, bf, cf)
    y = jnp.moveaxis(y.reshape(Bb, H, S, P), 1, 2)
    state = state.reshape(Bb, H, P, N)
    return y, state
