"""Pallas TPU grouped matmuls for MoE expert FFNs: the capacity layout
(``gmm_pallas``) and the ragged row list (``gmm_ragged_pallas``).

Capacity layout: grid (E, C/block_c, F/block_f); ``group_sizes`` arrives
via scalar prefetch (SMEM) and row-blocks entirely past an expert's token
count skip their MXU work via ``pl.when`` — this recovers the padding FLOPs
the plain batched-matmul XLA path wastes at low expert load.  Their inputs
are not fetched either: a dead block's index maps point at the blocks the
expert's last live step read (its last live row block, the last F block of
the weight), which the pipeline already holds, so an expert's weight is
read once per live row block, and an expert with no rows not at all.

Ragged row list: the experts' rows lie one group after another, each
group padded to a multiple of ``BLOCK_ROWS``, so that every row block
belongs to one expert, named by a scalar-prefetched block -> expert map.
Grid (R/BLOCK_ROWS,), one whole (D, F) weight a row block: consecutive
blocks of one expert keep the weight the pipeline holds, so each
expert's weight is read once a call.  Blocks past the live count skip their
MXU work and, pointed at the last live block's inputs, their fetches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.moe_gmm.ref import BLOCK_ROWS


def _precision(dtype):
    # pinned, not inherited from jax_default_matmul_precision: Mosaic has
    # no fp32-contract matmul of bf16 operands (whose products are exact
    # in the f32 accumulator anyway)
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _dot(x, w, precision):
    return jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)


def _gmm_kernel(sizes_ref, index_ref, x_ref, w_ref, o_ref, *, block_c,
                precision):
    e = pl.program_id(0)
    ic = pl.program_id(1)
    size = sizes_ref[e]
    live = ic * block_c < size

    @pl.when(live)
    def _compute():
        acc = _dot(x_ref[0], w_ref[...], precision)   # (block_c, block_f)
        # zero padded rows inside a partially-filled block
        rows = ic * block_c + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        acc = jnp.where(rows < size, acc, 0.0)
        o_ref[0] = acc.astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _skip():
        o_ref[0] = jnp.zeros_like(o_ref[0])


def gmm_pallas(x, w, group_sizes, w_index, *, block_c: int = 128,
               block_f: int = 128, interpret: bool = False):
    """x: (E, C, D); w: a stack (L, E_all, D, F) with ``w_index`` int32
    [l, e0] naming the experts w[l, e0:e0+E] (a stack the caller keeps
    whole, e.g. a scan's loop-invariant operand, which slicing would
    copy); group_sizes: (E,).  Returns (E, C, F)."""
    E, C, D = x.shape
    F = w.shape[-1]
    block_c = min(block_c, C)
    block_f = min(block_f, F)
    assert C % block_c == 0 and F % block_f == 0

    kernel = functools.partial(_gmm_kernel, block_c=block_c,
                               precision=_precision(x.dtype))
    nf = F // block_f

    def x_block(e, i, j, sz, wi):
        last = jnp.maximum(sz[e] - 1, 0) // block_c
        return e, jnp.minimum(i, last), 0

    def w_block(e, i, j, sz, wi):
        return (wi[0], wi[1] + e, 0,
                jnp.where(i * block_c < sz[e], j, nf - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(E, C // block_c, nf),
        in_specs=[pl.BlockSpec((1, block_c, D), x_block),
                  pl.BlockSpec((None, None, D, block_f), w_block)],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda e, i, j, *_: (e, i, j)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(group_sizes.astype(jnp.int32), w_index.astype(jnp.int32), x, w)


def _ragged_kernel(experts_ref, live_ref, index_ref, x_ref, w_ref, o_ref, *,
                   precision):
    live = pl.program_id(0) < live_ref[0]

    @pl.when(live)
    def _compute():
        o_ref[...] = _dot(x_ref[...], w_ref[...],
                          precision).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _skip():
        o_ref[...] = jnp.zeros_like(o_ref)


def gmm_ragged_pallas(x, w, block_expert, live, w_index, *,
                      interpret: bool = False):
    """x: (R, D), rows in blocks of ``BLOCK_ROWS``, block b all of expert
    ``block_expert[b]``; w: a stack (L, E_all, D, F) read in place at
    [w_index[0], w_index[1] + block_expert[b]], one (D, F) weight a block;
    ``live`` int32 (1,): the blocks computed, the rest zero.  Returns
    (R, F)."""
    R, D = x.shape
    F = w.shape[-1]
    assert R % BLOCK_ROWS == 0

    def row(i, lv):
        return jnp.minimum(i, jnp.maximum(lv[0] - 1, 0))

    def x_block(i, be, lv, wi):
        return row(i, lv), 0

    def w_block(i, be, lv, wi):
        return wi[0], wi[1] + be[row(i, lv)], 0, 0

    # double-buffered blocks of x, w and the output, and the f32 product
    itemsize = x.dtype.itemsize
    vmem = (2 * itemsize * (D * F + BLOCK_ROWS * (D + F))
            + 4 * BLOCK_ROWS * F)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R // BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((BLOCK_ROWS, D), x_block),
                  pl.BlockSpec((None, None, D, F), w_block)],
        out_specs=pl.BlockSpec((BLOCK_ROWS, F), lambda i, *_: (i, 0)),
    )
    return pl.pallas_call(
        functools.partial(_ragged_kernel, precision=_precision(x.dtype)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, F), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(vmem + vmem // 2, 16 << 20)),
        interpret=interpret,
    )(block_expert.astype(jnp.int32), live.astype(jnp.int32).reshape(1),
      w_index.astype(jnp.int32), x, w)
