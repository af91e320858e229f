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
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.dist.sharding import cache_shardings
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.mamba.ops import selective_scan, selective_step
from repro.kernels.moe_gmm.ops import gmm
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


def apply_norm(cfg: ModelConfig, p: Params, x, eps: float = 1e-6):
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
                      v_new=None):
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
    would relay out every layer's cache on the device."""
    B, _, H, Dh = q.shape
    Sc, F = kc.shape[1], kc.shape[2]
    KH = F // Dh
    G = H // KH
    f32 = jnp.float32
    scale = cfg.attn_scale or Dh ** -0.5
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


# ---------------------------------------------------------------- MoE

def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def init_moe(cfg: ModelConfig, key) -> Params:
    D, F, E = cfg.d_model, cfg.expert_ff, cfg.num_experts
    std = 0.02
    std_out = 0.02 / math.sqrt(2 * cfg.num_layers)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {"router": _normal(k1, (D, E), std, jnp.float32),
         "wi": _normal(k2, (E, D, F), std, _pd(cfg)),
         "wo": _normal(k3, (E, F, D), std_out, _pd(cfg))}
    if cfg.mlp in ("swiglu", "geglu"):
        p["wg"] = _normal(k4, (E, D, F), std, _pd(cfg))
    return p


def _moe_local(cfg: ModelConfig, p: Params, xt, e_base, E_local: int,
               capacity_factor: float):
    """Sort+scatter dispatch for the local expert slice [e_base, e_base+E_local).

    xt: (T, D) local tokens.  ``p["wi"/"wg"/"wo"]`` hold the E_local-sized
    slice already (shard_map in_specs deliver the local shard); ``e_base``
    may be traced (lax.axis_index).  Returns (y (T, D) partial sum over
    local experts, aux load-balance loss over the full expert population).
    """
    T, D = xt.shape
    E, k = cfg.num_experts, cfg.experts_per_tok
    logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    wts, idx = lax.top_k(probs, k)                               # (T, k)
    wts = wts / jnp.maximum(wts.sum(-1, keepdims=True), 1e-9)

    eids = idx.reshape(-1)                                       # (T*k,)
    tids = jnp.repeat(jnp.arange(T), k)
    wv = wts.reshape(-1)

    # aux loss (switch-style), computed over full expert population
    f = jnp.zeros((E,), jnp.float32).at[eids].add(1.0) / (T * k)
    pbar = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * pbar)

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

    cap_block = 128 if T * k // max(E_local, 1) >= 128 else 8
    C = max(cap_block,
            _round_up(int(math.ceil(T * k / E * capacity_factor)), cap_block))
    keep = (pos_in_e < C) & (el_s < E_local)
    slot = jnp.where(keep, el_s * C + pos_in_e, E_local * C)

    xe = jnp.zeros((E_local * C + 1, D), xt.dtype)
    xe = xe.at[slot].set(xt[tid_s] * keep[:, None].astype(xt.dtype))
    xe = xe[:-1].reshape(E_local, C, D)
    group_sizes = jnp.minimum(counts[:E_local], C)

    hi = gmm(xe, cast(cfg, p["wi"]), group_sizes, impl=_impl(cfg))
    hg = gmm(xe, cast(cfg, p["wg"]), group_sizes, impl=_impl(cfg)) \
        if "wg" in p else None
    h = _mlp_act(cfg, hi, hg)
    ye = gmm(h, cast(cfg, p["wo"]), group_sizes, impl=_impl(cfg))

    flat = jnp.concatenate([ye.reshape(E_local * C, D),
                            jnp.zeros((1, D), ye.dtype)])
    back = flat[slot] * (keep & inrange[order])[:, None].astype(ye.dtype)
    y = jnp.zeros((T, D), xt.dtype).at[tid_s].add(
        back * w_s[:, None].astype(ye.dtype))
    return y, aux


def apply_moe(cfg: ModelConfig, p: Params, x, *, mesh=None,
              capacity_factor: float = 1.25):
    """Returns (y, aux_loss).  EP via shard_map when mesh has a 'model' axis
    and the profile is tp_ep; otherwise dispatch is local per data shard
    (expert weights TP-sharded by GSPMD for the grok-style profile)."""
    B, S, D = x.shape
    E = cfg.num_experts

    if mesh is None:
        y, aux = _moe_local(cfg, p, x.reshape(-1, D), 0, E, capacity_factor)
        return y.reshape(B, S, D), aux

    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if cfg.sharding_profile == "tp_ep":
        mdl = mesh.shape["model"]
        E_local = E // mdl

        def f(xb, pl):
            T = xb.shape[0] * xb.shape[1]
            j = lax.axis_index("model")
            y, aux = _moe_local(cfg, pl, xb.reshape(T, D),
                                j * E_local, E_local, capacity_factor)
            y = lax.psum(y, "model")
            aux = lax.pmean(aux, data_axes)
            return y.reshape(xb.shape), aux

        pspecs = {"router": P(None, None), "wi": P("model", None, None),
                  "wo": P("model", None, None)}
        if "wg" in p:
            pspecs["wg"] = P("model", None, None)
        return jax.shard_map(
            f, mesh=mesh,
            in_specs=(P(data_axes, None, None), pspecs),
            out_specs=(P(data_axes, None, None), P()),
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
        y, aux = _moe_local(cfg, pl, xb.reshape(T, D), 0, E, capacity_factor)
        aux = lax.pmean(aux, data_axes)
        return y.reshape(xb.shape), aux

    pspecs = jax.tree.map(lambda _: P(), p)
    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(data_axes, None, None), pspecs),
        out_specs=(P(data_axes, None, None), P()),
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
