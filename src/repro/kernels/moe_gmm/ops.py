"""Public wrappers for the MoE grouped matmuls with impl dispatch.

``gmm``, the capacity layout: the XLA path is a plain batched matmul
(computes padding rows — wasted FLOPs at low expert load); the Pallas
kernel skips row-blocks past each group's size, recovering the padding
waste on TPU.  ``gmm_ragged``, the ragged row list: the XLA path is
``lax.ragged_dot`` over the live blocks' groups; the Pallas kernel maps
each row block to its expert by scalar prefetch.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro import flags
from repro.kernels import forward_only
from repro.kernels.moe_gmm.ref import BLOCK_ROWS, gmm_ragged_ref, gmm_ref


@partial(jax.jit, static_argnames=("impl",))
def gmm(x, w, group_sizes, w_index=None, *, impl: Optional[str] = None):
    """x (E, C, D) @ w (E, D, F) per group, rows past ``group_sizes``
    zero (padding).  With ``w_index`` int32 [l, e0], ``w`` is a stack
    (L, E_all, D, F) and the groups' weights are w[l, e0:e0+E]: the Pallas
    kernel reads them in place."""
    impl = flags.moe_impl(impl)
    if w_index is None:
        w, w_index = w[None], jnp.zeros((2,), jnp.int32)
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels.moe_gmm.pallas_kernel import gmm_pallas
        return forward_only("moe_gmm", partial(
            gmm_pallas, interpret=(impl == "pallas_interpret")),
            x, w, group_sizes, w_index)
    E, D, F = x.shape[0], w.shape[-2], w.shape[-1]
    w = jax.lax.dynamic_slice(w, (w_index[0], w_index[1], 0, 0),
                              (1, E, D, F))[0]
    if impl == "ref":
        return gmm_ref(x, w, group_sizes)
    if impl == "xla":
        return jnp.einsum("ecd,edf->ecf", x, w)
    raise ValueError(f"unknown moe impl {impl!r}")


@partial(jax.jit, static_argnames=("impl",))
def gmm_ragged(x, w, block_expert, live, w_index, *,
               impl: Optional[str] = None):
    """x (R, D), rows in blocks of ``BLOCK_ROWS``: block b's rows @ the
    weights of expert ``block_expert[b]``, for the first ``live`` blocks
    (whose experts do not decrease); the other rows zero.  ``w`` a stack
    (L, E_all, D, F) whose experts are w[l, e0 + block_expert], with
    ``w_index`` int32 [l, e0]: the Pallas kernel reads them in place.
    Returns (R, F)."""
    impl = flags.moe_impl(impl)
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels.moe_gmm.pallas_kernel import gmm_ragged_pallas
        return forward_only("moe_gmm_ragged", partial(
            gmm_ragged_pallas, interpret=(impl == "pallas_interpret")),
            x, w, block_expert, live, w_index)
    w = jax.lax.dynamic_index_in_dim(w, w_index[0], keepdims=False)
    experts = block_expert + w_index[1]
    if impl == "ref":
        return gmm_ragged_ref(x, w, experts, live)
    if impl == "xla":
        nb = x.shape[0] // BLOCK_ROWS
        blocks = jnp.where(jnp.arange(nb) < live, BLOCK_ROWS, 0)
        sizes = jnp.zeros((w.shape[0],), jnp.int32).at[experts].add(blocks)
        return jax.lax.ragged_dot(x, w, sizes)
    raise ValueError(f"unknown moe impl {impl!r}")
