"""XLA-native chunked flash attention (production path on the CPU stand-in
backend and the implementation the dry-run lowers).

Online-softmax over kv chunks with a *statically pruned* chunk range per q
chunk: causal and sliding-window layers only visit the kv chunks that can
contain unmasked entries, so HLO FLOPs match the algorithmic FLOPs (this is
what keeps the roofline compute term honest).  The q-chunk loop is a Python
loop (static), the kv-chunk loop is a ``lax.scan``.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _chunk_attn(qf, ks, vs, koffs, *, causal, window, softcap, q_offset,
                qi, q_chunk, seg_q=None, seg_kvs=None, qf_dtype=None):
    """Online softmax over the stacked kv chunks ``ks``/``vs``.

    qf: (B, Cq, KH, G, D) fp32, pre-scaled.
    ks: (nk, B, Ck, KH, D); vs: (nk, B, Ck, KH, Dv); koffs: (nk,) chunk
    start positions.
    Returns (B, Cq, KH, G, Dv) fp32 (unnormalized handled internally).
    """
    B, Cq, KH, G, _ = qf.shape
    Dv = vs.shape[-1]
    qf_dtype = qf_dtype or ks.dtype
    Ck = ks.shape[2]
    qpos = q_offset + qi * q_chunk + jnp.arange(Cq)

    def step(carry, inp):
        m, l, acc = carry
        if seg_kvs is not None:
            kc, vc, koff, seg_kv = inp
        else:
            kc, vc, koff = inp
            seg_kv = None
        # scores: (B, KH, G, Cq, Ck)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf.astype(kc.dtype), kc,
                       preferred_element_type=jnp.float32)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        kpos = koff + jnp.arange(Ck)
        mask = jnp.ones((Cq, Ck), dtype=bool)
        if causal:
            mask = mask & (kpos[None, :] <= qpos[:, None])
        if window:
            mask = mask & (qpos[:, None] - kpos[None, :] < window)
        mask = mask[None, None, None]
        if seg_q is not None:
            segm = seg_q[:, :, None] == seg_kv[:, None, :]
            mask = mask & segm[:, None, None]
        s = jnp.where(mask, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask, p, 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        # probabilities participate in the pv matmul at the input dtype
        # (bf16 for bf16 models): halves the p-tensor traffic at fusion
        # boundaries; accumulation stays f32
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(qf_dtype), vc,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KH, G, Cq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KH, G, Cq), jnp.float32)
    a0 = jnp.zeros((B, KH, G, Cq, Dv), jnp.float32)
    xs = (ks, vs, koffs) if seg_kvs is None else (ks, vs, koffs, seg_kvs)
    (m, l, acc), _ = lax.scan(step, (m0, l0, a0), xs)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 3, 1, 2, 4))  # (B, Cq, KH, G, D)


def attention_xla(q, k, v, *, causal: bool = True, window: int = 0,
                  softcap: float = 0.0, scale: Optional[float] = None,
                  q_offset: int = 0, seg_q=None, seg_kv=None,
                  q_chunk: int = 512, kv_chunk: int = 512):
    """Chunked attention.  Layout: q (B,Sq,H,D), k (B,Sk,KH,D), v
    (B,Sk,KH,Dv); returns (B,Sq,H,Dv)."""
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    Dv = v.shape[-1]
    G = H // KH
    scale = scale if scale is not None else D ** -0.5

    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    if Sq % q_chunk or Sk % kv_chunk:
        from repro.kernels.flash_attention.ref import attention_ref
        return attention_ref(q, k, v, causal=causal, window=window,
                             softcap=softcap, scale=scale, q_offset=q_offset,
                             seg_q=seg_q, seg_kv=seg_kv)

    nq, nk = Sq // q_chunk, Sk // kv_chunk
    k_ch = k.reshape(B, nk, kv_chunk, KH, D).transpose(1, 0, 2, 3, 4)
    v_ch = v.reshape(B, nk, kv_chunk, KH, Dv).transpose(1, 0, 2, 3, 4)
    seg_kv_ch = (seg_kv.reshape(B, nk, kv_chunk).transpose(1, 0, 2)
                 if seg_kv is not None else None)
    koffs = jnp.arange(nk) * kv_chunk

    outs = []
    for qi in range(nq):
        qc = q[:, qi * q_chunk:(qi + 1) * q_chunk]
        qf = (qc.astype(jnp.float32) * scale).reshape(B, q_chunk, KH, G, D)
        sq = (seg_q[:, qi * q_chunk:(qi + 1) * q_chunk]
              if seg_q is not None else None)
        # static kv-chunk range pruning
        q_lo = q_offset + qi * q_chunk
        q_hi = q_lo + q_chunk - 1
        hi = min(nk, math.floor(q_hi / kv_chunk) + 1) if causal else nk
        lo = max(0, math.floor((q_lo - window + 1) / kv_chunk)) if window else 0
        hi = max(hi, lo + 1)
        # checkpoint: recompute the online-softmax in the backward pass
        # instead of saving per-(q,kv)-chunk probability residuals
        # (flash-attention-style backward on the XLA path)
        attn_fn = jax.checkpoint(
            lambda qf_, ks_, vs_, ko_, sq_, skv_: _chunk_attn(
                qf_, ks_, vs_, ko_, causal=causal, window=window,
                softcap=softcap, q_offset=q_offset, qi=qi,
                q_chunk=q_chunk, seg_q=sq_, seg_kvs=skv_),
            static_argnums=())
        o = attn_fn(qf, k_ch[lo:hi], v_ch[lo:hi], koffs[lo:hi], sq,
                    seg_kv_ch[lo:hi] if seg_kv_ch is not None else None)
        outs.append(o.reshape(B, q_chunk, H, Dv))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)
