"""Public wrapper for the MoE grouped matmul with impl dispatch.

The XLA path is a plain batched matmul over the capacity layout (computes
padding rows — wasted FLOPs at low expert load).  The Pallas kernel skips
row-blocks past each group's size, recovering the padding waste on TPU.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro import flags
from repro.kernels import forward_only
from repro.kernels.moe_gmm.ref import gmm_ref


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
