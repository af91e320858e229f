"""Pure-jnp oracle for flash attention (naive materialized softmax).

Shapes (GQA layout):
  q: (B, Sq, H, D)    with H = KH * G
  k: (B, Sk, KH, D)
  v: (B, Sk, KH, Dv)  (Dv may be narrower than D: latent attention)
Returns (B, Sq, H, Dv).

Masking: causal (q position i attends to kv position j <= i), optional
sliding window (i - j < window), optional segment ids (block-diagonal
packing), optional tanh logit softcap.  ``q_offset`` places the q block at
absolute positions offset..offset+Sq-1 against kv positions 0..Sk-1.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp


def attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                  softcap: float = 0.0, scale: Optional[float] = None,
                  q_offset: int = 0, seg_q=None, seg_kv=None):
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    G = H // KH
    scale = scale if scale is not None else D ** -0.5

    qf = q.astype(jnp.float32).reshape(B, Sq, KH, G, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    # scores: (B, KH, G, Sq, Sk)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    qpos = q_offset + jnp.arange(Sq)[:, None]      # (Sq, 1)
    kpos = jnp.arange(Sk)[None, :]                 # (1, Sk)
    mask = jnp.ones((Sq, Sk), dtype=bool)
    if causal:
        mask = mask & (kpos <= qpos)
    if window:
        mask = mask & (qpos - kpos < window)
    mask = mask[None, None, None]
    if seg_q is not None:
        segm = seg_q[:, :, None] == seg_kv[:, None, :]   # (B, Sq, Sk)
        mask = mask & segm[:, None, None]
    s = jnp.where(mask, s, -jnp.inf)

    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)         # fully-masked rows
    e = jnp.exp(s - m)
    denom = jnp.sum(e, axis=-1, keepdims=True)
    p = e / jnp.maximum(denom, 1e-30)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
    return o.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)
