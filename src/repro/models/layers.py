"""Model substrate layers: norms, rope, MLP variants, GQA attention, MoE,
Mamba-1 mixer, RG-LRU mixer — pure-functional (params are pytrees of arrays).

Conventions:
  * params stored in ``cfg.param_dtype``; compute in ``cfg.dtype``
    (norm/softmax/scan accumulation in float32).
  * activations layout (B, S, D); attention heads (B, S, H, head_dim).
  * ``mesh`` is threaded explicitly; ``None`` means single-device (tests).
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import flags
from repro.configs.base import ModelConfig
from repro.dist.sharding import cache_shardings
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.mamba.ops import selective_scan, selective_step
from repro.kernels.moe_gmm.ops import gmm, gmm_ragged
from repro.kernels.moe_gmm.ref import BLOCK_ROWS
from repro.kernels.rglru.ops import linear_scan

Params = Dict[str, Any]

RGLRU_C = 8.0  # Griffin's recurrent-gate temperature


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _pd(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def _impl(cfg: ModelConfig) -> Optional[str]:
    """Kernel impl the config pins (``None``: the repro.flags default)."""
    return cfg.kernel_impl or None


def cast(cfg: ModelConfig, w):
    return w.astype(_dt(cfg))


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


# ---------------------------------------------------------------- norms

def init_norm(cfg: ModelConfig, d: Optional[int] = None) -> Params:
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), _pd(cfg))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), _pd(cfg))
    return p


def apply_norm(cfg: ModelConfig, p: Params, x, eps: Optional[float] = None):
    eps = cfg.norm_eps if eps is None else eps
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        xf = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        out = xf * p["scale"].astype(jnp.float32)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        out = (xf - mu) * lax.rsqrt(var + eps)
        out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def rms_head_norm(x, scale, eps: float = 1e-6):
    """qk-norm: rmsnorm over head_dim with a learned (head_dim,) scale."""
    xf = x.astype(jnp.float32)
    xf = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------- positions

def apply_rope(x, positions, theta: float):
    """x: (B, S, H, D); positions: (B, S)."""
    D = x.shape[-1]
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv    # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def sinusoidal_pos(positions, d: int):
    """Absolute sinusoidal embeddings: positions (...,) -> (..., d)."""
    half = d // 2
    inv = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                  * (math.log(10_000.0) / max(half - 1, 1)))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------- MLP

def init_mlp(cfg: ModelConfig, key, d_ff: Optional[int] = None) -> Params:
    d_ff = d_ff or cfg.d_ff
    D = cfg.d_model
    std_in = 0.02
    std_out = 0.02 / math.sqrt(2 * cfg.num_layers)
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"wi": _normal(k1, (D, d_ff), std_in, _pd(cfg)),
         "wo": _normal(k2, (d_ff, D), std_out, _pd(cfg))}
    if cfg.mlp in ("swiglu", "geglu"):
        p["wg"] = _normal(k3, (D, d_ff), std_in, _pd(cfg))
    return p


def _mlp_act(cfg: ModelConfig, hi, hg):
    if cfg.mlp == "swiglu":
        return jax.nn.silu(hg) * hi
    if cfg.mlp == "geglu":
        return jax.nn.gelu(hg, approximate=True) * hi
    if cfg.mlp == "relu2":
        return jnp.square(jax.nn.relu(hi))
    if cfg.mlp == "gelu":
        return jax.nn.gelu(hi, approximate=True)
    raise ValueError(cfg.mlp)


def apply_mlp(cfg: ModelConfig, p: Params, x):
    hi = x @ cast(cfg, p["wi"])
    hg = x @ cast(cfg, p["wg"]) if "wg" in p else None
    return _mlp_act(cfg, hi, hg) @ cast(cfg, p["wo"])


# ---------------------------------------------------------------- attention

def init_attn(cfg: ModelConfig, key, cross: bool = False) -> Params:
    D, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    std = 0.02
    std_out = 0.02 / math.sqrt(2 * cfg.num_layers)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {"wq": _normal(k1, (D, qd), std, _pd(cfg)),
         "wk": _normal(k2, (D, kvd), std, _pd(cfg)),
         "wv": _normal(k3, (D, kvd), std, _pd(cfg)),
         "wo": _normal(k4, (qd, D), std_out, _pd(cfg))}
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((cfg.head_dim,), _pd(cfg))
        p["k_norm"] = jnp.ones((cfg.head_dim,), _pd(cfg))
    return p


def _theta_for(cfg: ModelConfig, kind: str) -> float:
    if kind == "global" and cfg.rope_theta_global:
        return cfg.rope_theta_global
    return cfg.rope_theta


def _qkv(cfg: ModelConfig, p: Params, x, positions, kind: str):
    B, S, _ = x.shape
    q = (x @ cast(cfg, p["wq"])).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = (x @ cast(cfg, p["wk"])).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = (x @ cast(cfg, p["wv"])).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_head_norm(q, p["q_norm"])
        k = rms_head_norm(k, p["k_norm"])
    theta = _theta_for(cfg, kind)
    if theta:  # theta == 0 -> absolute sinusoidal positions (added upstream)
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    return q, k, v


def apply_attn(cfg: ModelConfig, p: Params, x, *, kind: str, positions,
               seg_ids=None, mem=None, mesh=None):
    """Self- or cross-attention.  kind: global | local | enc | cross."""
    B, S, _ = x.shape
    if kind == "cross":
        q = (x @ cast(cfg, p["wq"])).reshape(B, S, cfg.num_heads, cfg.head_dim)
        Sm = mem.shape[1]
        k = (mem @ cast(cfg, p["wk"])).reshape(B, Sm, cfg.num_kv_heads, cfg.head_dim)
        v = (mem @ cast(cfg, p["wv"])).reshape(B, Sm, cfg.num_kv_heads, cfg.head_dim)
        o = flash_attention(q, k, v, causal=False, window=0,
                            softcap=cfg.attn_softcap,
                            scale=cfg.attn_scale or None,
                            impl=_impl(cfg))
    else:
        q, k, v = _qkv(cfg, p, x, positions, kind)
        causal = kind != "enc"
        window = cfg.sliding_window if kind == "local" else 0
        o = flash_attention(q, k, v, causal=causal, window=window,
                            softcap=cfg.attn_softcap,
                            scale=cfg.attn_scale or None,
                            seg_q=seg_ids, seg_kv=seg_ids, impl=_impl(cfg))
    return o.reshape(B, S, cfg.q_dim) @ cast(cfg, p["wo"])


# -- decode (single new token against a cache) ------------------------------

def _decode_attention(cfg: ModelConfig, q, kc, vc, mask, k_new=None,
                      v_new=None, scale: Optional[float] = None):
    """q: (B,1,H,Dh); kc/vc: (B,Sc,KH*Dh), each position's kv heads side
    by side (the decode cache's format); mask: broadcastable to (B,Sc).
    ``k_new``/``v_new`` (B,1,KH*Dh), when given, are the new token's own
    k/v, attended as one more key column beside the cache (one softmax
    over both), so the cache need not hold them yet.

    The cache is read in its own layout, never split into heads: each
    head's scaled query sits in its kv head's lanes of a block-diagonal
    (KH*Dh, H) query, so both contractions run over the whole lane axis
    (KH times the operations of a per-head product, against a cache read
    that costs the same either way).  Splitting the lane axis into heads
    would relay out every layer's cache on the device.  ``scale``
    defaults to the config's, else Dh**-0.5."""
    B, _, H, Dh = q.shape
    Sc, F = kc.shape[1], kc.shape[2]
    KH = F // Dh
    G = H // KH
    f32 = jnp.float32
    scale = scale or cfg.attn_scale or Dh ** -0.5
    sel = jnp.repeat(jnp.eye(KH, dtype=f32), Dh, axis=0)         # (F, KH)
    qf = q.astype(f32).reshape(B, KH, G, Dh) * scale
    qb = (jnp.swapaxes(qf, 2, 3).reshape(B, 1, F, G)
          * sel.T[None, :, :, None])                              # (B,KH,F,G)
    s = jnp.einsum("bsf,bkfg->bkgs", kc.astype(f32), qb)
    mask = jnp.broadcast_to(mask, (B, Sc))
    if k_new is not None:
        s = jnp.concatenate([s, jnp.einsum(
            "bsf,bkfg->bkgs", k_new.astype(f32), qb)], axis=-1)
        mask = jnp.concatenate([mask, jnp.ones((B, 1), bool)], axis=-1)
    if cfg.attn_softcap:
        s = jnp.tanh(s / cfg.attn_softcap) * cfg.attn_softcap
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    pden = jnp.sum(e, axis=-1, keepdims=True)
    pr = e / jnp.maximum(pden, 1e-30)
    o = jnp.einsum("bkgs,bsf->bkgf", pr[..., :Sc], vc.astype(f32))
    if v_new is not None:
        o = o + jnp.einsum("bkgs,bsf->bkgf", pr[..., Sc:],
                           v_new.astype(f32))
    # each head keeps the lanes of its own kv head
    o = jnp.einsum("bkgjd,jk->bkgd", o.reshape(B, KH, G, KH, Dh),
                   jnp.eye(KH, dtype=f32))
    return o.reshape(B, 1, H * Dh).astype(q.dtype)


def flat_heads(x):
    """(..., KH, Dh) -> (..., KH*Dh): k/v in the decode cache's format."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


def ring_cache(cfg: ModelConfig, kind: str) -> bool:
    """Whether a layer of ``kind`` decodes against a sliding-window ring
    cache (batch-synchronized, rewritten whole each step) rather than a
    full-length cache that takes one new k/v row per batch row."""
    return kind == "local" and bool(cfg.sliding_window)


def attn_decode(cfg: ModelConfig, p: Params, x, cache: Params, positions,
                *, kind: str, mesh=None) -> Tuple[jax.Array, Params]:
    """x: (B,1,D); positions: (B,) — PER-ROW cache positions: each batch
    row attends under its own causal mask, so a continuous-batching
    server can admit requests into a live decode wave at unequal
    sequence offsets.  Caches hold each position's kv heads side by side,
    (B, S, KH*Dh) (``flat_heads``).

    Full-length caches (global layers, local layers without a window) are
    only read here: the row attends to its cache below ``positions`` plus
    its own new k/v, and the new rows ``{"k", "v"}`` (B,1,KH*Dh) are
    returned for the caller to write at ``positions`` — ``decode_step``
    writes every layer's rows at once into the (donated) stacked cache,
    in place.  Sliding-window ring caches (``ring_cache``) stay
    batch-synchronized (positions[0]): they carry one shared ``pos``
    vector with no batch dimension, and the updated ring is returned
    whole.  Returns (out (B,1,D), new rows or updated ring cache)."""
    q, k, v = _qkv(cfg, p, x, positions[:, None], kind)
    k, v = flat_heads(k), flat_heads(v)

    if kind == "cross":
        raise ValueError("use attn_decode_cross")
    if ring_cache(cfg, kind):
        pos = positions[0]               # ring cache: batch-synchronized
        W = cache["k"].shape[1]
        slot = pos % W
        kc = lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
        vc = lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
        pc = lax.dynamic_update_slice_in_dim(
            cache["pos"], pos[None].astype(jnp.int32), slot, axis=0)
        mask = (pc <= pos) & (pc > pos - W) & (pc >= 0)
        out = _decode_attention(cfg, q, kc, vc, mask[None, :])
        return out @ cast(cfg, p["wo"]), {"k": kc, "v": vc, "pos": pc}
    S = cache["k"].shape[1]
    mask = jnp.arange(S)[None, :] < positions[:, None]
    out = _decode_attention(cfg, q, cache["k"], cache["v"], mask, k, v)
    return out @ cast(cfg, p["wo"]), {"k": k, "v": v}


def write_rows(cfg: ModelConfig, c, rows, positions, *, axis: int,
               mesh=None):
    """Write each batch row's new k/v ``rows`` (.., B, 1, KH*Dh) into the
    cache ``c`` (.., B, S, KH*Dh) at that row's ``positions`` (B,);
    ``axis`` is the batch axis (1 for the scanned ``blocks`` stack, 0 for
    a ``tail`` layer).  One ``dynamic_update_slice`` per row, which XLA
    does in place on a donated cache, touching only the row: the row is
    contiguous on the lane axis.  (A batched scatter is lowered through a
    relayout of the whole cache; a cache with heads and head_dim as its
    two minor axes is laid out with positions on the lanes, where one
    row's update costs about a pass over the cache.)
    On a ``mesh`` each device writes its own rows into its own shard
    (``dist.sharding.cache_shardings``; the sequence axis is never
    sharded).  A position past the end is clamped (only an empty slot
    sits there)."""
    def local(cl, rl, pl):
        for b in range(cl.shape[axis]):
            start = [0] * cl.ndim
            start[axis], start[axis + 1] = b, pl[b]
            cl = lax.dynamic_update_slice(
                cl, lax.slice_in_dim(rl, b, b + 1, axis=axis), start)
        return cl

    if mesh is None:
        return local(c, rows, positions)
    spec = cache_shardings(cfg, mesh, {"k": jax.ShapeDtypeStruct(
        c.shape, c.dtype)})["k"].spec
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(spec, spec, P(spec[axis])),
                         out_specs=spec, check_vma=False)(c, rows, positions)


def attn_decode_cross(cfg: ModelConfig, p: Params, x, cache: Params):
    """Cross-attention decode: kv precomputed at prefill (static), in the
    cache's (B, Sm, KH*Dh) format."""
    B = x.shape[0]
    q = (x @ cast(cfg, p["wq"])).reshape(B, 1, cfg.num_heads, cfg.head_dim)
    Sm = cache["xk"].shape[1]
    mask = jnp.ones((B, Sm), dtype=bool)
    out = _decode_attention(cfg, q, cache["xk"], cache["xv"], mask)
    return out @ cast(cfg, p["wo"])


# ---------------------------------------------------------------- MLA

def init_mla(cfg: ModelConfig, key) -> Params:
    """Latent attention without a q latent: ``wq`` (D, H*(nope+rope)),
    ``wkv_a`` (D, rank+rope) giving the latent and the shared roped key,
    ``kv_norm`` on the latent, ``wkv_b`` (rank, H*(nope+v)) expanding it to
    each head's unroped key and value, ``wo`` (H*v, D)."""
    D, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    std = 0.02
    std_out = 0.02 / math.sqrt(2 * cfg.num_layers)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {"wq": _normal(k1, (D, cfg.q_dim), std, _pd(cfg)),
            "wkv_a": _normal(k2, (D, r + cfg.qk_rope_dim), std, _pd(cfg)),
            "kv_norm": init_norm(cfg, r),
            "wkv_b": _normal(k3, (r, H * (cfg.qk_nope_dim + cfg.v_head_dim)),
                             std, _pd(cfg)),
            "wo": _normal(k4, (H * cfg.v_head_dim, D), std_out, _pd(cfg))}


def _mla_scale(cfg: ModelConfig) -> float:
    return cfg.attn_scale or cfg.head_dim ** -0.5


def _mla_q(cfg: ModelConfig, p: Params, x, positions):
    """(q_nope (B,S,H,nope), q_rope (B,S,H,rope) roped)."""
    B, S, _ = x.shape
    q = (x @ cast(cfg, p["wq"])).reshape(B, S, cfg.num_heads, cfg.head_dim)
    dn = cfg.qk_nope_dim
    return q[..., :dn], apply_rope(q[..., dn:], positions, cfg.rope_theta)


def mla_latent(cfg: ModelConfig, p: Params, x, positions):
    """One cache row a position: the RMS-normed latent beside the roped
    key that every head shares, (B, S, rank + rope)."""
    r = cfg.kv_lora_rank
    kva = x @ cast(cfg, p["wkv_a"])
    c = apply_norm(cfg, p["kv_norm"], kva[..., :r])
    k_r = apply_rope(kva[..., None, r:], positions, cfg.rope_theta)[:, :, 0]
    return jnp.concatenate([c, k_r.astype(c.dtype)], axis=-1)


def apply_mla(cfg: ModelConfig, p: Params, x, *, positions, seg_ids=None):
    """Prefill / training: the latent expanded to per-head keys and values
    (k = [W_UK c ; k_rope], v = W_UV c), causal attention over q/k width
    nope+rope and value width v.  Returns (out (B,S,D), latent rows
    (B,S,rank+rope), the decode cache's format)."""
    B, S, _ = x.shape
    H, r, dn, dv = (cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_dim,
                    cfg.v_head_dim)
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    row = mla_latent(cfg, p, x, positions)
    kv = (row[..., :r] @ cast(cfg, p["wkv_b"])).reshape(B, S, H, dn + dv)
    k_rope = jnp.broadcast_to(row[:, :, None, r:],
                              (B, S, H, cfg.qk_rope_dim))
    q = jnp.concatenate([q_nope, q_rope.astype(q_nope.dtype)], axis=-1)
    k = jnp.concatenate([kv[..., :dn], k_rope], axis=-1)
    o = flash_attention(q, k, kv[..., dn:], causal=True,
                        scale=_mla_scale(cfg), seg_q=seg_ids, seg_kv=seg_ids,
                        impl=_impl(cfg))
    return o.reshape(B, S, H * dv) @ cast(cfg, p["wo"]), row


def mla_decode(cfg: ModelConfig, p: Params, x, cache: Params, positions):
    """Absorbed decode: each head's query is moved into the latent space
    (q_lat_h = W_UK_h^T q_nope_h, beside q_rope_h), scored against the
    cache's rows [c ; k_rope] (rank+rope lanes, one "kv head" that all
    heads share) and the new row, and its output is W_UV_h applied to the
    weighted sum of latents.  The cache ``ckv`` (B, S, rank+rope) is only
    read; the new row is returned for ``decode_step`` to write at
    ``positions``, as for any full-length cache."""
    B = x.shape[0]
    H, r, dn = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_dim
    pos = positions[:, None]
    q_nope, q_rope = _mla_q(cfg, p, x, pos)
    row = mla_latent(cfg, p, x, pos)
    wkv_b = cast(cfg, p["wkv_b"]).reshape(r, H, dn + cfg.v_head_dim)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, wkv_b[..., :dn])
    qc = jnp.concatenate([q_lat, q_rope.astype(q_lat.dtype)], axis=-1)
    S = cache["ckv"].shape[1]
    mask = jnp.arange(S)[None, :] < positions[:, None]
    o = _decode_attention(cfg, qc, cache["ckv"], cache["ckv"], mask, row,
                          row, scale=_mla_scale(cfg))
    o_lat = o.reshape(B, 1, H, r + cfg.qk_rope_dim)[..., :r]
    o = jnp.einsum("bshr,rhd->bshd", o_lat, wkv_b[..., dn:])
    return o.reshape(B, 1, -1) @ cast(cfg, p["wo"]), {"ckv": row}


# ---------------------------------------------------------------- MoE

def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


ROUTER_BIAS_STD = 0.1   # e_score_correction_bias drawn from the seed

# rows of one chunk of the ragged expert layout: a dropless layer whose
# capacity layout would hold more rows than this lays its routed rows out
# ragged and runs them a chunk at a time
EXPERT_CHUNK_ROWS = 4096


def init_moe(cfg: ModelConfig, key) -> Params:
    """Router over all ``num_experts`` (and, for ``sigmoid_bias``, the
    selection bias), the held experts' weights and the shared experts.
    Expert ``e`` draws its weights from ``fold_in(k, e)`` of its global
    id, so a rank that holds experts [base, base+held) holds exactly the
    uncut model's weights of those experts."""
    D, F, E = cfg.d_model, cfg.expert_ff, cfg.num_experts
    std = 0.02
    std_out = 0.02 / math.sqrt(2 * cfg.num_layers)
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    base = cfg.expert_rank * cfg.held_experts
    ids = jnp.arange(base, base + cfg.held_experts)

    def experts(k, shape, sd):
        return jax.vmap(lambda e: _normal(jax.random.fold_in(k, e), shape,
                                          sd, _pd(cfg)))(ids)

    p = {"router": _normal(k1, (D, E), std, jnp.float32),
         "wi": experts(k2, (D, F), std),
         "wo": experts(k3, (F, D), std_out)}
    if cfg.mlp in ("swiglu", "geglu"):
        p["wg"] = experts(k4, (D, F), std)
    if cfg.router == "sigmoid_bias":
        p["bias"] = _normal(k5, (E,), ROUTER_BIAS_STD, jnp.float32)
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(cfg, k6, cfg.num_shared_experts * F)
    return p


def route(cfg: ModelConfig, p: Params, xt):
    """Routing of tokens ``xt`` (T, D) over all ``num_experts``, in f32.
    Returns (weights (T, k), expert ids (T, k), aux load-balance loss).

    ``softmax``: top-k of the softmax, renormalised.  ``sigmoid_bias``
    (DeepSeek-V3 noaux_tc, one group): s = sigmoid(x W_r); the top-k of
    s + bias choose the experts; their unbiased s, divided by its sum and
    times ``routed_scale``, weigh them."""
    E, k = cfg.num_experts, cfg.experts_per_tok
    logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    if cfg.router == "sigmoid_bias":
        s = jax.nn.sigmoid(logits)
        _, idx = lax.top_k(s + p["bias"].astype(jnp.float32), k)
        wts = jnp.take_along_axis(s, idx, axis=-1)
        wts = wts / (wts.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scale
        probs = s / s.sum(-1, keepdims=True)
    elif cfg.router == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        wts, idx = lax.top_k(probs, k)
        wts = wts / jnp.maximum(wts.sum(-1, keepdims=True), 1e-9)
    else:
        raise ValueError(cfg.router)
    # aux loss (switch-style), computed over full expert population
    T = xt.shape[0]
    f = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (T * k)
    aux = E * jnp.sum(f * jnp.mean(probs, axis=0))
    return wts, idx, aux


class _Capacity(NamedTuple):
    """Each held expert's rows in a buffer of C: ``tok`` (E_local, C) the
    rows' token ids (``T`` for an empty row, which reads a zero row),
    ``sizes`` (E_local,) each expert's row count (the grouped matmul skips
    the row blocks past it), ``pos`` (E_local, T) each token's row in each
    expert's list (``C`` where it has none) and ``wt`` (E_local, T) its
    routing weight there (0 where it has none)."""
    tok: Any
    sizes: Any
    pos: Any
    wt: Any


class _Ragged(NamedTuple):
    """The held experts' rows one group after another, each group padded
    to a multiple of ``BLOCK_ROWS``: ``tok`` (N,) each row's token id (``T``
    for a padding row), ``wt`` (N,) its routing weight (0 for padding),
    ``experts`` (N / BLOCK_ROWS,) each row block's held expert, ``live``
    the blocks that hold rows and ``chunks`` the ``EXPERT_CHUNK_ROWS``
    chunks they fill."""
    tok: Any
    wt: Any
    experts: Any
    live: Any
    chunks: Any


def _ragged(cfg: ModelConfig, E_local: int, C: int,
            capacity_factor: float) -> bool:
    """Whether the layer lays its held experts' rows out ragged: dropless,
    where the capacity layout would hold more than a chunk of rows, under
    the forward-only (Pallas) expert kernel.  The chunk loop runs as many
    times as the routing fills chunks, which reverse mode cannot
    differentiate: the differentiable XLA path keeps the capacity
    layout."""
    return (not capacity_factor and E_local * C > EXPERT_CHUNK_ROWS
            and flags.moe_impl(_impl(cfg)) in ("pallas", "pallas_interpret"))


def _expert_ffn(cfg: ModelConfig, p: Params, xt, rows):
    """The held experts' weighted sum for each token, over ``rows``, a
    ``_Capacity`` or ``_Ragged`` layout.  Capacity: each token gathers its
    rows back, so no scatter of the mostly empty rows.  Ragged: a loop
    over the chunks the rows fill gathers each chunk's token rows and adds
    its weighted outputs into the tokens' sums.  The expert weights are
    this layer's (E_local, D, F), or the whole stack (layers, E_local, D,
    F) with ``p["layer"]`` its index (``transformer.scan_stack``); either
    way the kernel is handed the array and an index, never a slice.
    Returns (T, D) f32."""
    T, D = xt.shape
    xpad = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)])
    idx = jnp.stack([p.get("layer", 0), 0]).astype(jnp.int32)
    ws = {k: cast(cfg, p[k] if "layer" in p else p[k][None])
          for k in ("wi", "wg", "wo") if k in p}

    def ffn(x, matmul):
        hi = matmul(x, ws["wi"])
        hg = matmul(x, ws["wg"]) if "wg" in ws else None
        return matmul(_mlp_act(cfg, hi, hg), ws["wo"])

    if isinstance(rows, _Capacity):
        E_local, C = rows.tok.shape
        ye = ffn(xpad[rows.tok], lambda x, w: gmm(
            x, w, rows.sizes, idx, impl=_impl(cfg)))       # (E_local, C, D)
        # a token without a row in an expert reads its last row, at weight 0
        back = ye[jnp.arange(E_local)[:, None], jnp.minimum(rows.pos, C - 1)]
        return jnp.sum(rows.wt[..., None] * back.astype(jnp.float32), axis=0)

    R, nb = EXPERT_CHUNK_ROWS, EXPERT_CHUNK_ROWS // BLOCK_ROWS

    def chunk(c, y):
        tok = lax.dynamic_slice_in_dim(rows.tok, c * R, R)
        wt = lax.dynamic_slice_in_dim(rows.wt, c * R, R)
        experts = lax.dynamic_slice_in_dim(rows.experts, c * nb, nb)
        live = jnp.clip(rows.live - c * nb, 0, nb)
        ye = ffn(xpad[tok], lambda x, w: gmm_ragged(
            x, w, experts, live, idx, impl=_impl(cfg)))    # (R, D)
        # padding rows add nothing, into the dummy row T
        return y.at[tok].add(wt[:, None] * ye.astype(jnp.float32))

    y = lax.fori_loop(0, rows.chunks, chunk,
                      jnp.zeros((T + 1, D), jnp.float32))
    return y[:T]


def _moe_local(cfg: ModelConfig, p: Params, xt, e_base, E_local: int,
               capacity_factor: float):
    """Sorted dispatch for the local expert slice [e_base, e_base+E_local).

    xt: (T, D) local tokens.  ``p["wi"/"wg"/"wo"]`` hold the E_local-sized
    slice already (shard_map in_specs deliver the local shard; a rank
    that holds ``experts_held`` gets its own from ``init_moe``); ``e_base``
    may be traced (lax.axis_index).  Each expert takes at most
    ``capacity`` rows: ``capacity_factor`` times its even share, or, at
    ``capacity_factor`` 0 (dropless), all T tokens, which no routing can
    exceed (a token picks an expert once).  A dropless layer whose
    capacity layout would waste rows lays them out ragged (``_ragged``).
    Returns (y (T, D) partial sum over local experts, aux load-balance
    loss over the full expert population, int32 [rows the local experts
    computed, local experts with a row, rows routed here but dropped past
    capacity, rows of the layout the grouped matmuls ran: E_local * C, or
    the ragged chunks' rows]).
    """
    T, D = xt.shape
    E, k = cfg.num_experts, cfg.experts_per_tok
    wts, idx, aux = route(cfg, p, xt)

    eids = idx.reshape(-1)                                       # (T*k,)
    tids = jnp.repeat(jnp.arange(T), k)
    wv = wts.reshape(-1)

    el = eids - e_base
    inrange = (el >= 0) & (el < E_local)
    sort_key = jnp.where(inrange, el, E_local)
    order = jnp.argsort(sort_key, stable=True)
    el_s = sort_key[order]
    tid_s = tids[order]
    w_s = wv[order]

    counts = jnp.zeros((E_local + 1,), jnp.int32).at[sort_key].add(1)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(T * k) - offs[el_s]

    if capacity_factor:
        cap_block = 128 if T * k // max(E_local, 1) >= 128 else 8
        C = max(cap_block, _round_up(
            int(math.ceil(T * k / E * capacity_factor)), cap_block))
    else:
        C = _round_up(T, 128 if T >= 128 else 8)

    if _ragged(cfg, E_local, C, capacity_factor):
        # groups padded to whole row blocks; every routed row fits in the
        # bound, however skewed the routing (a token picks an expert once)
        padded = (counts[:E_local] + BLOCK_ROWS - 1) // BLOCK_ROWS \
            * BLOCK_ROWS
        ends = jnp.cumsum(padded)
        N = _round_up(T * min(k, E_local) + E_local * (BLOCK_ROWS - 1),
                      EXPERT_CHUNK_ROWS)
        row = jnp.where(el_s < E_local,
                        (ends - padded)[jnp.minimum(el_s, E_local - 1)]
                        + pos_in_e, N)
        live = ends[-1] // BLOCK_ROWS
        blocks = jnp.arange(N // BLOCK_ROWS) * BLOCK_ROWS
        rows = _Ragged(
            tok=jnp.full((N + 1,), T, jnp.int32).at[row].set(tid_s)[:N],
            wt=jnp.zeros((N + 1,), jnp.float32).at[row].set(w_s)[:N],
            experts=jnp.minimum(jnp.searchsorted(ends, blocks, side="right"),
                                E_local - 1).astype(jnp.int32),
            live=live,
            chunks=-(-live // (EXPERT_CHUNK_ROWS // BLOCK_ROWS)))
        sizes = counts[:E_local]
        slots = rows.chunks * EXPERT_CHUNK_ROWS
    else:
        keep = (pos_in_e < C) & (el_s < E_local)
        slot = jnp.where(keep, el_s * C + pos_in_e, E_local * C)
        tok = jnp.full((E_local * C + 1,), T, jnp.int32).at[slot].set(tid_s)
        # each token's row and weight in each held expert, for the gather
        # back
        e_k = jnp.where(keep, el_s, E_local)
        t_k = jnp.where(keep, tid_s, T)
        pos = jnp.full((E_local + 1, T + 1), C, jnp.int32).at[e_k, t_k].set(
            pos_in_e)
        wt = jnp.zeros((E_local + 1, T + 1), jnp.float32).at[e_k, t_k].set(
            w_s)
        sizes = jnp.minimum(counts[:E_local], C)
        rows = _Capacity(tok[:-1].reshape(E_local, C), sizes,
                         pos[:E_local, :T], wt[:E_local, :T])
        slots = E_local * C
    y = _expert_ffn(cfg, p, xt, rows)
    computed = jnp.sum(sizes)
    stats = jnp.stack([computed, jnp.sum(sizes > 0),
                       jnp.sum(counts[:E_local]) - computed,
                       slots]).astype(jnp.int32)
    return y.astype(xt.dtype), aux, stats


def moe_layer(cfg: ModelConfig, p: Params, x, *, mesh=None):
    """The expert MLP: the held (or local) experts' part of the routed sum,
    plus the shared experts whole.  Returns (y, aux_loss, stats): ``stats``
    int32 [expert rows computed, held experts with a row, rows dropped,
    rows of the expert layout run] (``_moe_local``).
    EP via shard_map when mesh has a 'model' axis and the profile is
    tp_ep; otherwise dispatch is local per data shard (expert weights
    TP-sharded by GSPMD for the grok-style profile).  A rank holding a
    share of the experts (``experts_held``) runs without a mesh: its
    exchange with the other ranks is not part of this layer."""
    B, S, D = x.shape
    cf = cfg.moe_capacity
    routed = {k: v for k, v in p.items() if k != "shared"}

    if mesh is None:
        E_local = cfg.held_experts
        y, aux, stats = _moe_local(cfg, routed, x.reshape(-1, D),
                                   cfg.expert_rank * E_local, E_local, cf)
        y = y.reshape(B, S, D)
    elif cfg.experts_held:
        raise ValueError("a rank holding experts_held experts runs "
                         "without a mesh")
    else:
        y, aux, stats = _moe_mesh(cfg, routed, x, mesh, cf)
    if "shared" in p:
        y = y + apply_mlp(cfg, p["shared"], x)
    return y, aux, stats


def _moe_mesh(cfg: ModelConfig, p: Params, x, mesh, capacity_factor):
    B, S, D = x.shape
    E = cfg.num_experts
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if cfg.sharding_profile == "tp_ep":
        mdl = mesh.shape["model"]
        E_local = E // mdl

        def f(xb, pl):
            T = xb.shape[0] * xb.shape[1]
            j = lax.axis_index("model")
            y, aux, st = _moe_local(cfg, pl, xb.reshape(T, D),
                                    j * E_local, E_local, capacity_factor)
            y = lax.psum(y, "model")
            aux = lax.pmean(aux, data_axes)
            st = lax.psum(st, ("model",) + data_axes)
            return y.reshape(xb.shape), aux, st

        pspecs = {k: (P("model", None, None) if k in ("wi", "wg", "wo")
                      else P()) for k in p}
        return jax.shard_map(
            f, mesh=mesh,
            in_specs=(P(data_axes, None, None), pspecs),
            out_specs=(P(data_axes, None, None), P(), P()),
            check_vma=False)(x, p)

    # tp profile (few big experts): dispatch local per data shard; expert
    # matmuls sharded over "model" by GSPMD (auto axes inside shard_map).
    # NOTE: three attempts to make the boundary gather move bf16 instead of
    # f32 (tree-level cast, optimization_barrier'd cast, manual
    # all_gather-inside) all trip an XLA SPMD-partitioner CHECK failure
    # ("invalid binary instruction opcode copy") at 256 partitions — the
    # f32 gather stands on this backend; EXPERIMENTS.md §Perf grok.
    def f(xb, pl):
        T = xb.shape[0] * xb.shape[1]
        y, aux, st = _moe_local(cfg, pl, xb.reshape(T, D), 0, E,
                                capacity_factor)
        aux = lax.pmean(aux, data_axes)
        st = lax.psum(st, data_axes)
        return y.reshape(xb.shape), aux, st

    pspecs = jax.tree.map(lambda _: P(), p)
    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(data_axes, None, None), pspecs),
        out_specs=(P(data_axes, None, None), P(), P()),
        axis_names=set(data_axes),
        check_vma=False)(x, p)


# ---------------------------------------------------------------- conv1d

def causal_conv1d(x, w, b):
    """Depthwise causal conv.  x: (B,S,C); w: (cw, C); b: (C,).

    Implemented as cw shifted elementwise multiply-accumulates instead of
    ``lax.conv_general_dilated``: XLA lowers the depthwise conv *backward*
    into a full CxC cross-channel correlation (measured 9e15 FLOPs for
    falcon-mamba's 8192 channels — see EXPERIMENTS.md §Perf falcon/step 1);
    the shift-mul form is pure VPU work with an equally cheap transpose.
    """
    cw, C = w.shape
    xf = x.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    acc = xf * wf[cw - 1]
    for j in range(1, cw):
        shifted = jnp.pad(xf[:, :-j, :], ((0, 0), (j, 0), (0, 0)))
        acc = acc + shifted * wf[cw - 1 - j]
    return (acc + b.astype(jnp.float32)).astype(x.dtype)


def conv1d_step(x1, buf, w, b):
    """Single-token conv step.  x1: (B,C); buf: (B,cw-1,C) past inputs.
    Returns (y (B,C), new buf)."""
    cw, C = w.shape
    wf = w.astype(jnp.float32)
    full = jnp.concatenate([buf, x1[:, None, :]], axis=1)  # (B, cw, C)
    y = jnp.einsum("bwc,wc->bc", full.astype(jnp.float32), wf)
    y = (y + b.astype(jnp.float32)).astype(x1.dtype)
    return y, full[:, 1:]


# ---------------------------------------------------------------- RG-LRU

def init_rglru(cfg: ModelConfig, key) -> Params:
    D, W = cfg.d_model, cfg.lru_width_
    std = 0.02
    std_out = 0.02 / math.sqrt(2 * cfg.num_layers)
    ks = jax.random.split(key, 6)
    # Lambda init so that a^c is in [0.9, 0.999]
    u = jax.random.uniform(ks[0], (W,), jnp.float32, 0.9, 0.999)
    root = u ** (1.0 / RGLRU_C)
    a_param = jnp.log(root / (1.0 - root))          # logit
    return {
        "wx": _normal(ks[1], (D, W), std, _pd(cfg)),
        "wy": _normal(ks[2], (D, W), std, _pd(cfg)),
        "conv_w": _normal(ks[3], (cfg.ssm_conv, W), std, _pd(cfg)),
        "conv_b": jnp.zeros((W,), _pd(cfg)),
        "wa": _normal(ks[4], (W, W), std, _pd(cfg)),
        "wi_g": _normal(ks[5], (W, W), std, _pd(cfg)),
        "a_param": a_param.astype(jnp.float32),
        "wo": _normal(jax.random.fold_in(key, 7), (W, D), std_out, _pd(cfg)),
    }


def _rglru_gates(p: Params, xb):
    """Returns (a, x_eff) for h_t = a_t h_{t-1} + x_eff_t (float32)."""
    xf = xb.astype(jnp.float32)
    r = jax.nn.sigmoid(xf @ p["wa"].astype(jnp.float32))
    i = jax.nn.sigmoid(xf @ p["wi_g"].astype(jnp.float32))
    log_a = RGLRU_C * r * jax.nn.log_sigmoid(p["a_param"])[None]
    a = jnp.exp(log_a)
    mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    return a, mult * i * xf


def apply_rglru(cfg: ModelConfig, p: Params, x, *, mesh=None,
                h0=None, conv_buf=None, return_state: bool = False):
    """Griffin recurrent mixer.  x: (B,S,D)."""
    B, S, _ = x.shape
    W = cfg.lru_width_
    xb = x @ cast(cfg, p["wx"])
    yb = jax.nn.gelu(x @ cast(cfg, p["wy"]), approximate=True)
    if conv_buf is None:
        xb = causal_conv1d(xb, p["conv_w"], p["conv_b"])
        new_buf = None
    else:  # stateful prefill continuation (unused in training)
        raise NotImplementedError
    a, x_eff = _rglru_gates(p, xb)
    h0 = h0 if h0 is not None else jnp.zeros((B, W), jnp.float32)
    h, h_last = linear_scan(x_eff, a, h0, impl=_impl(cfg))
    out = (h.astype(_dt(cfg)) * yb) @ cast(cfg, p["wo"])
    if return_state:
        # conv state: last (cw-1) pre-conv inputs
        pre = x @ cast(cfg, p["wx"])
        buf = pre[:, -(cfg.ssm_conv - 1):, :]
        return out, {"h": h_last, "conv": buf}
    return out


def rglru_decode(cfg: ModelConfig, p: Params, x, cache: Params):
    """x: (B,1,D).  cache: {"h": (B,W) f32, "conv": (B,cw-1,W)}."""
    x1 = x[:, 0, :]
    xb1 = x1 @ cast(cfg, p["wx"])
    yb1 = jax.nn.gelu(x1 @ cast(cfg, p["wy"]), approximate=True)
    xc, new_buf = conv1d_step(xb1, cache["conv"], p["conv_w"], p["conv_b"])
    a, x_eff = _rglru_gates(p, xc[:, None, :])
    h = a[:, 0] * cache["h"] + x_eff[:, 0]
    out = (h.astype(_dt(cfg)) * yb1) @ cast(cfg, p["wo"])
    return out[:, None, :], {"h": h, "conv": new_buf}


# ---------------------------------------------------------------- Mamba

def init_mamba(cfg: ModelConfig, key) -> Params:
    D, di, n, dr = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank_
    std = 0.02
    std_out = 0.02 / math.sqrt(2 * cfg.num_layers)
    ks = jax.random.split(key, 6)
    A = jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32)[None], (di, 1))
    dt = jnp.exp(jax.random.uniform(ks[0], (di,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))         # inverse softplus
    return {
        "in_proj": _normal(ks[1], (D, 2 * di), std, _pd(cfg)),
        "conv_w": _normal(ks[2], (cfg.ssm_conv, di), std, _pd(cfg)),
        "conv_b": jnp.zeros((di,), _pd(cfg)),
        "x_proj": _normal(ks[3], (di, dr + 2 * n), std, _pd(cfg)),
        "dt_proj": _normal(ks[4], (dr, di), dr ** -0.5, _pd(cfg)),
        "dt_bias": dt_bias.astype(jnp.float32),
        "A_log": jnp.log(A),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": _normal(ks[5], (di, D), std_out, _pd(cfg)),
    }


def _mamba_bcdt(cfg: ModelConfig, p: Params, xin):
    n, dr = cfg.ssm_state, cfg.dt_rank_
    xdbc = xin @ cast(cfg, p["x_proj"])
    dt_r, Bm, Cc = jnp.split(xdbc, [dr, dr + n], axis=-1)
    dt = jax.nn.softplus(
        dt_r.astype(jnp.float32) @ p["dt_proj"].astype(jnp.float32)
        + p["dt_bias"][None])
    return dt, Bm, Cc


def apply_mamba(cfg: ModelConfig, p: Params, x, *, mesh=None,
                return_state: bool = False):
    B, S, _ = x.shape
    di, n = cfg.d_inner, cfg.ssm_state
    xz = x @ cast(cfg, p["in_proj"])
    xin, z = jnp.split(xz, 2, axis=-1)
    xin = causal_conv1d(xin, p["conv_w"], p["conv_b"])
    pre_conv = jnp.split(x @ cast(cfg, p["in_proj"]), 2, axis=-1)[0] \
        if return_state else None
    xin = jax.nn.silu(xin)
    dt, Bm, Cc = _mamba_bcdt(cfg, p, xin)
    A = -jnp.exp(p["A_log"])
    h0 = jnp.zeros((B, di, n), jnp.float32)
    y, h_last = selective_scan(xin, dt, A, Bm, Cc, p["D"], h0,
                               impl=_impl(cfg))
    y = y * jax.nn.silu(z)
    out = y @ cast(cfg, p["out_proj"])
    if return_state:
        buf = pre_conv[:, -(cfg.ssm_conv - 1):, :]
        return out, {"h": h_last, "conv": buf}
    return out


def mamba_decode(cfg: ModelConfig, p: Params, x, cache: Params):
    """x: (B,1,D).  cache: {"h": (B,di,n) f32, "conv": (B,cw-1,di)}."""
    x1 = x[:, 0, :]
    xz = x1 @ cast(cfg, p["in_proj"])
    xin, z = jnp.split(xz, 2, axis=-1)
    xc, new_buf = conv1d_step(xin, cache["conv"], p["conv_w"], p["conv_b"])
    xc = jax.nn.silu(xc)
    dt, Bm, Cc = _mamba_bcdt(cfg, p, xc)
    A = -jnp.exp(p["A_log"])
    y, h = selective_step(xc, dt, A, Bm, Cc, p["D"], cache["h"])
    y = y * jax.nn.silu(z)
    out = (y @ cast(cfg, p["out_proj"]))[:, None, :]
    return out, {"h": h, "conv": new_buf}
