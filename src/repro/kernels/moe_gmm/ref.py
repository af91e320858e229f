"""Oracles for the MoE expert FFN's grouped matmuls.

``gmm_ref``, the capacity layout: x (E, C, D) expert-batched tokens (rows
beyond group_sizes[e] are padding), w (E, D, F); returns (E, C, F) with
padded rows zeroed.  ``gmm_ragged_ref``, the ragged row list: x (R, D) in
blocks of ``BLOCK_ROWS`` rows, block b of expert experts[b], the first
``live`` blocks computed; returns (R, F), the other rows zero.
"""
from __future__ import annotations

import jax.numpy as jnp

BLOCK_ROWS = 128            # rows of a ragged row block: one expert each


def gmm_ref(x, w, group_sizes):
    E, C, D = x.shape
    y = jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                   w.astype(jnp.float32))
    valid = jnp.arange(C)[None, :] < group_sizes[:, None]
    return (y * valid[..., None]).astype(x.dtype)


def gmm_ragged_ref(x, w, experts, live):
    R, D = x.shape
    nb = R // BLOCK_ROWS
    y = jnp.einsum("bcd,bdf->bcf",
                   x.reshape(nb, BLOCK_ROWS, D).astype(jnp.float32),
                   w[experts].astype(jnp.float32))
    valid = jnp.arange(nb) < live
    return (y * valid[:, None, None]).reshape(R, -1).astype(x.dtype)
