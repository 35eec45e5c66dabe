"""Pallas TPU grouped matmul for the serving expert layer (models/moe.py).

Rows of `lhs` (m, k) are sorted by expert: the first group_sizes[0] rows
belong to group 0, the next group_sizes[1] to group 1, and so on; rows past
the last group belong to none. Each group's rows are multiplied by that
group's weight `rhs[layer, g]` (k, n). `rhs` is a layer loop's whole stack
of held-expert weights (L, G, k, n) and `layer` a traced index into it,
prefetched to SMEM with the group metadata: the weight tiles stream
straight out of the stack, so no caller slices one layer's experts out of
it (XLA would materialise that slice, every expert of it, as the custom
call's operand). A single layer's (G, k, n) is the L = 1, layer 0 case.
Adapted from the megablox `gmm` kernel that JAX ships
(jax/experimental/pallas/ops/tpu/megablox/gmm.py), cut to what the expert
layer needs: every group of a layer is held here (no group offset), no
output is carried in, and the tiles divide k and n. Its own call is named
`moe_grouped_matmul`, so a profiler trace tells it apart.

  * the grid is (n tiles, m tiles visited, k tiles). An m tile is visited
    once per group that has rows in it, with that group's weight tiles, and
    only tiles that hold a group's rows are visited: the visit count is a
    traced number, so an empty expert streams no weight and rows past the
    last group cost nothing. A tile shared by two groups is computed twice
    and each visit stores only its own group's rows.
  * accumulation is in f32 scratch across the k tiles; the output is
    stored in `lhs`'s dtype.

Rows past the last group are left unwritten: the caller masks them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

LANE = 128


def group_metadata(group_sizes, m: int, tm: int):
    """(group_offsets (G+1,), group_ids, m_tile_ids (tiles_m + G - 1,)),
    and the number of grid visits: which group and which m tile each visit
    of the grid's middle axis works on (megablox's make_group_metadata with
    every group held and empty groups skipped)."""
    G = group_sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    rounded = (ends + tm - 1) // tm * tm - starts // tm * tm
    group_tiles = jnp.where(group_sizes == 0, 0, rounded // tm)
    group_ids = jnp.repeat(jnp.arange(G, dtype=jnp.int32), group_tiles,
                           total_repeat_length=tiles_m + G - 1)
    # a tile is visited once by the group that owns its first row, and once
    # more by each group that starts inside it
    partial = jnp.where((starts % tm == 0) | (group_sizes == 0), tiles_m,
                        starts // tm)
    visits = jnp.histogram(partial, bins=tiles_m,
                           range=(0, tiles_m - 1))[0] + 1
    m_tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32),
                            visits.astype(jnp.int32),
                            total_repeat_length=tiles_m + G - 1)
    return (offsets.astype(jnp.int32), group_ids, m_tile_ids), \
        group_tiles.sum()


def tile(dim: int, most: int) -> int:
    """The largest multiple of 128 up to `most` that divides `dim` (the
    whole dim when it is no multiple of 128, or none fits)."""
    best = 0
    for t in range(LANE, min(dim, most) + 1, LANE):
        if dim % t == 0:
            best = t
    return best or dim


def tiling(m: int, k: int, n: int):
    """(tm, tk, tn): row tiles of 128 for decode-sized calls and 256 for
    larger ones; weight tiles of at most 1536 x 1536 (a (1408, 1024) bf16
    weight tile is 2.9 MB, double-buffered in VMEM)."""
    tm = 128 if m <= 1024 else 256
    return tm, tile(k, 1536), tile(n, 1536)


def _gmm_kernel(meta_offsets, meta_groups, meta_tiles, layer_ref, lhs_ref,
                rhs_ref, out_ref, acc_ref, *, tm: int, tn: int, tiles_k: int):
    grid_id = pl.program_id(1)
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(lhs_ref[...], rhs_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _store():
        g = meta_groups[grid_id]
        lo, hi = meta_offsets[g], meta_offsets[g + 1]
        row = meta_tiles[grid_id] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, tn), 0)
        mine = (row >= lo) & (row < hi)
        out_ref[...] = jnp.where(mine, acc_ref[...],
                                 out_ref[...].astype(jnp.float32)
                                 ).astype(out_ref.dtype)


def moe_grouped_matmul_pallas(lhs, rhs, group_sizes, layer=0, *,
                              interpret: bool):
    """lhs: (m, k) rows sorted by group; rhs: (L, G, k, n), or one layer's
    (G, k, n); group_sizes: (G,) int32 with sum <= m; layer: int32 scalar
    (traced) below L. -> (m, n) in lhs's dtype: row i of group g is
    lhs[i] @ rhs[layer, g]; rows past the last group are unwritten."""
    if rhs.ndim == 3:
        rhs = rhs[None]
    m, k = lhs.shape
    _, G, _, n = rhs.shape
    tm, tk, tn = tiling(m, k, n)
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    mp = m + pad
    meta, visits = group_metadata(group_sizes.astype(jnp.int32), mp, tm)
    tiles_k, tiles_n = k // tk, n // tn

    def lhs_map(n_i, grid_id, k_i, offsets, groups, tiles, layer):
        return tiles[grid_id], k_i

    def rhs_map(n_i, grid_id, k_i, offsets, groups, tiles, layer):
        return layer[0], groups[grid_id], k_i, n_i

    def out_map(n_i, grid_id, k_i, offsets, groups, tiles, layer):
        return tiles[grid_id], n_i

    kernel = functools.partial(_gmm_kernel, tm=tm, tn=tn, tiles_k=tiles_k)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((mp, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((None, 1, tk, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            grid=(tiles_n, visits, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(*meta, jnp.asarray(layer, jnp.int32).reshape(1), lhs, rhs)
    return out[:m]
