"""Pallas TPU grouped matmul for MoE expert FFNs (capacity layout).

Design: grid (E, C/block_c, F/block_f); ``group_sizes`` arrives via scalar
prefetch (SMEM) and row-blocks entirely past an expert's token count skip
their MXU work via ``pl.when`` — this recovers the padding FLOPs the plain
batched-matmul XLA path wastes at low expert load (the kernel-level win this
module exists for).  Their inputs are not fetched either: a dead block's
index maps point at the blocks the expert's last live step read (its last
live row block, the last F block of the weight), which the pipeline
already holds, so a layout whose capacity is far above the rows routed
(a dropless expert layer holds every token's row) reads each expert's
weight once per live row block, and an expert with no rows not at all.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(sizes_ref, index_ref, x_ref, w_ref, o_ref, *, block_c,
                precision):
    e = pl.program_id(0)
    ic = pl.program_id(1)
    size = sizes_ref[e]
    live = ic * block_c < size

    @pl.when(live)
    def _compute():
        x = x_ref[0]                     # (block_c, D)
        w = w_ref[...]                   # (D, block_f)
        acc = jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)
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

    # pinned, not inherited from jax_default_matmul_precision: Mosaic has
    # no fp32-contract matmul of bf16 operands (whose products are exact
    # in the f32 accumulator anyway)
    precision = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    kernel = functools.partial(_gmm_kernel, block_c=block_c,
                               precision=precision)
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
