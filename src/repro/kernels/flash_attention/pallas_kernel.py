"""Pallas TPU flash attention (forward).

TPU-native design (not a CUDA port):
  * grid = (B*KH, num_q_blocks, num_kv_blocks) with the kv dimension
    innermost and "arbitrary" so the online-softmax scratch accumulators
    (VMEM-resident) persist across kv steps — the TPU idiom replacing the
    CUDA shared-memory loop;
  * GQA folded into the q block: the (G, block_q) rows of one kv-head group
    form a single (G*block_q, head_dim) MXU operand, so q-heads sharing a
    kv head share the k/v VMEM tiles;
  * block sizes default to 128 (MXU-aligned); causal / sliding-window masks
    are applied with 2-D iotas, and fully-masked kv blocks are skipped with
    ``pl.when`` (grid-level pruning is done by the XLA path at trace time;
    here predication skips the MXU work).

Validated in interpret mode on CPU against ref.py; used as the hot path on
real TPU (``--attn_impl=pallas``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale, causal, window, softcap, q_offset, block_q, block_kv,
                 nkv, G):
    ik = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = G * block_q
    # positions: row r of the folded block is q position (r % block_q)
    rpos = q_offset + iq * block_q + \
        jax.lax.broadcasted_iota(jnp.int32, (rows, block_kv), 0) % block_q
    cpos = ik * block_kv + \
        jax.lax.broadcasted_iota(jnp.int32, (rows, block_kv), 1)
    mask = jnp.ones((rows, block_kv), dtype=jnp.bool_)
    if causal:
        mask = mask & (cpos <= rpos)
    if window:
        mask = mask & (rpos - cpos < window)

    # skip fully-masked kv blocks (block-level predication)
    q_lo = q_offset + iq * block_q
    q_hi = q_lo + block_q - 1
    kv_lo = ik * block_kv
    kv_hi = kv_lo + block_kv - 1
    live = jnp.bool_(True)
    if causal:
        live = live & (kv_lo <= q_hi)
    if window:
        live = live & (kv_hi > q_lo - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].reshape(rows, q_ref.shape[-1])      # (G*Bq, D)
        k = k_ref[0]                                     # (Bkv, D)
        s = jax.lax.dot_general(
            q.astype(jnp.float32), k.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                 # (Bkv, Dv)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(ik == nkv - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = out.reshape(G, block_q, o_ref.shape[-1]) \
            .astype(o_ref.dtype)


def _block(want: int, n: int) -> int:
    """The largest block of at most ``want`` rows, halving, that divides
    ``n``: few grid steps (each has a fixed cost) on any length."""
    b = min(want, n)
    while n % b:
        b //= 2
    return b


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           softcap: float = 0.0,
                           scale: Optional[float] = None, q_offset: int = 0,
                           seg_q=None, seg_kv=None,
                           block_q: int = 128, block_kv: int = 128,
                           interpret: bool = False):
    """q: (B, Sq, H, D); k: (B, Sk, KH, D); v: (B, Sk, KH, Dv), Dv <= D
    (latent attention's values are narrower than its keys).  Returns
    (B, Sq, H, Dv)."""
    if seg_q is not None:
        raise NotImplementedError("segment ids: use the xla path")
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    Dv = v.shape[-1]
    G = H // KH
    scale = scale if scale is not None else D ** -0.5
    block_q, block_kv = _block(block_q, Sq), _block(block_kv, Sk)
    nq, nkv = Sq // block_q, Sk // block_kv

    # (B, Sq, KH, G, D) -> (B, KH, G, Sq, D); k/v -> (B, KH, Sk, D)
    qr = q.reshape(B, Sq, KH, G, D).transpose(0, 2, 3, 1, 4)
    kr = k.transpose(0, 2, 1, 3)
    vr = v.transpose(0, 2, 1, 3)
    qf = qr.reshape(B * KH, G, Sq, D)
    kf = kr.reshape(B * KH, Sk, D)
    vf = vr.reshape(B * KH, Sk, Dv)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, q_offset=q_offset, block_q=block_q,
        block_kv=block_kv, nkv=nkv, G=G)

    rows = G * block_q
    out = pl.pallas_call(
        kernel,
        grid=(B * KH, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, G, block_q, D), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, Dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, block_q, Dv),
                               lambda b, i, j: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KH, G, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),   # m
            pltpu.VMEM((rows, 1), jnp.float32),   # l
            pltpu.VMEM((rows, Dv), jnp.float32),  # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)

    # (B*KH, G, Sq, Dv) -> (B, Sq, H, Dv)
    return out.reshape(B, KH, G, Sq, Dv).transpose(0, 3, 1, 2, 4) \
        .reshape(B, Sq, H, Dv)
