"""Composable model definition: parameter init, forward (train/prefill) and
decode step for every assigned architecture family.

Layer stacks are scanned over *pattern periods*: the scan unit is one full
cycle of ``cfg.layer_pattern`` (so per-layer attention kinds stay static and
the chunked attention can prune kv ranges); remainder layers are unrolled in
``tail``.  Heterogeneous stacks (recurrentgemma) set ``scan_layers=False`` and
unroll entirely.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.dist.sharding import constrain_batch, constrain_logits
from repro.models import layers as L

Params = Dict[str, Any]


# ---------------------------------------------------------------- init

def _init_block(cfg: ModelConfig, key, kind: str, *, cross: bool = False,
                enc: bool = False, dense: bool = False) -> Params:
    """One layer's parameters; ``dense``: a leading layer that keeps the
    dense MLP in a model with experts."""
    ks = jax.random.split(key, 8)
    p: Params = {"ln1": L.init_norm(cfg)}
    if kind in ("global", "local", "enc"):
        p["attn"] = (L.init_mla(cfg, ks[0]) if cfg.mla and not enc
                     else L.init_attn(cfg, ks[0]))
        if cfg.post_norms:
            p["ln1_post"] = L.init_norm(cfg)
        if cross:
            p["lnx"] = L.init_norm(cfg)
            p["xattn"] = L.init_attn(cfg, ks[1], cross=True)
        p["ln2"] = L.init_norm(cfg)
        if cfg.num_experts and not enc and not dense:
            p["moe"] = L.init_moe(cfg, ks[2])
        else:
            p["mlp"] = L.init_mlp(cfg, ks[2])
        if cfg.post_norms:
            p["ln2_post"] = L.init_norm(cfg)
    elif kind == "rec":
        p["rec"] = L.init_rglru(cfg, ks[0])
        p["ln2"] = L.init_norm(cfg)
        p["mlp"] = L.init_mlp(cfg, ks[1])
    elif kind == "mamba":
        p["mamba"] = L.init_mamba(cfg, ks[0])
    else:
        raise ValueError(kind)
    return p


def _layout(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(period, num_scanned_groups, num_tail_layers) of the layers after
    the ``first_dense_layers``, which are unrolled ahead of the stack
    (params and cache under ``dense``/``block_<j>``).  The scanned stack's
    sub-layer ``s`` has kind ``layer_pattern[s]``."""
    period = len(cfg.layer_pattern)
    n = cfg.num_layers - cfg.first_dense_layers
    if not cfg.scan_layers:
        return period, 0, n
    G = n // period
    return period, G, n - G * period


def _kind(cfg: ModelConfig, G: int, period: int, j: int) -> str:
    """Kind of tail layer ``j``."""
    return cfg.layer_kind(cfg.first_dense_layers + G * period + j)


def scan_stack(cfg: ModelConfig, blocks: Params):
    """(xs, layer) for a scan over the stacked layers ``blocks``: the body
    gets its layer's parameters as ``layer(x)`` of its slice ``x``.

    With the Pallas expert kernel (forward only: prefill and decode) the
    expert weights stay out of ``xs``: the body gets the whole stacks and
    the layer index, which the kernel reads in place.  Sliced per
    iteration, a custom call's operand is copied out of the stack: three
    copies of (held experts, D, F) a layer in every step.  Otherwise
    ``xs`` is ``blocks`` itself: the differentiable paths keep the slices,
    whose gradient is one layer's, where a stack read by index would
    carry a whole stack's gradient through every iteration."""
    from repro import flags
    hoist = {s: {k: sub["moe"][k] for k in ("wi", "wg", "wo")
                 if k in sub["moe"]}
             for s, sub in blocks.items() if "moe" in sub}
    if not hoist or flags.moe_impl(L._impl(cfg)) not in (
            "pallas", "pallas_interpret"):
        return blocks, lambda bp: bp
    G = jax.tree.leaves(blocks)[0].shape[0]
    xs = {s: {**sub, "moe": {k: v for k, v in sub["moe"].items()
                              if k not in hoist[s]}} if s in hoist else sub
          for s, sub in blocks.items()}

    def layer(x):
        bp, i = x
        return {s: {**sub, "moe": {**sub["moe"], **hoist[s], "layer": i}}
                if s in hoist else sub for s, sub in bp.items()}
    return (xs, jnp.arange(G)), layer


def init_params(cfg: ModelConfig, key) -> Params:
    kE, kH, kB, kT, kEnc = jax.random.split(key, 5)
    D, V = cfg.d_model, cfg.vocab_size
    params: Params = {
        "embed": {"tok": L._normal(kE, (V, D), 0.02, L._pd(cfg))},
        "final_norm": L.init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        params["head"] = L._normal(kH, (D, V), 0.02, L._pd(cfg))

    period, G, n_tail = _layout(cfg)
    cross = cfg.encoder_layers > 0
    if cfg.first_dense_layers:
        # keys folded from the head's, leaving every other draw as it was
        params["dense"] = {
            f"block_{j}": _init_block(
                cfg, jax.random.fold_in(kH, j + 1), cfg.layer_kind(j),
                cross=cross, dense=True)
            for j in range(cfg.first_dense_layers)}

    def init_group(k):
        sub = {}
        for s in range(period):
            sub[f"sub_{s}"] = _init_block(
                cfg, jax.random.fold_in(k, s), cfg.layer_pattern[s],
                cross=cross)
        return sub

    if G:
        params["blocks"] = jax.vmap(init_group)(jax.random.split(kB, G))
    tail = {}
    for j in range(n_tail):
        tail[f"block_{j}"] = _init_block(
            cfg, jax.random.fold_in(kT, j), _kind(cfg, G, period, j),
            cross=cross)
    if tail:
        params["tail"] = tail

    if cfg.encoder_layers:
        def init_enc(k):
            return {"sub_0": _init_block(cfg, k, "enc", enc=True)}
        params["enc"] = {
            "blocks": jax.vmap(init_enc)(
                jax.random.split(kEnc, cfg.encoder_layers)),
            "final_norm": L.init_norm(cfg),
        }
    return params


# ---------------------------------------------------------------- blocks

def _no_stats():
    return jnp.zeros((4,), jnp.int32)


def forward_block(cfg: ModelConfig, bp: Params, h, kind: str, *, positions,
                  seg_ids, mem, mesh, cache_len: Optional[int]):
    """Returns (h, aux, cache_or_None, stats): ``stats`` the expert layer's
    int32 counts (``layers.moe_layer``), zeros without one."""
    aux = jnp.zeros((), jnp.float32)
    stats = _no_stats()
    cache = None
    if kind in ("global", "local", "enc"):
        xin = L.apply_norm(cfg, bp["ln1"], h)
        if cfg.mla and kind != "enc":
            a, row = L.apply_mla(cfg, bp["attn"], xin, positions=positions,
                                 seg_ids=seg_ids)
            if cache_len:
                S = row.shape[1]
                cache = {"ckv": jnp.pad(
                    row, ((0, 0), (0, cache_len - S), (0, 0)))}
        elif cache_len:
            a, kv = _attn_with_cache(cfg, bp["attn"], xin, kind=kind,
                                     positions=positions, seg_ids=seg_ids,
                                     mesh=mesh, cache_len=cache_len)
            cache = kv
        else:
            a = L.apply_attn(cfg, bp["attn"], xin, kind=kind,
                             positions=positions, seg_ids=seg_ids, mesh=mesh)
        if cfg.post_norms:
            a = L.apply_norm(cfg, bp["ln1_post"], a)
        h = h + a
        if "xattn" in bp and mem is not None:
            xin = L.apply_norm(cfg, bp["lnx"], h)
            if cache_len:
                xa, xkv = _cross_with_cache(cfg, bp["xattn"], xin, mem)
                cache.update(xkv)
            else:
                xa = L.apply_attn(cfg, bp["xattn"], xin, kind="cross",
                                  positions=positions, mem=mem, mesh=mesh)
            h = h + xa
        xin = L.apply_norm(cfg, bp["ln2"], h)
        if "moe" in bp:
            y, aux, stats = L.moe_layer(cfg, bp["moe"], xin, mesh=mesh)
        else:
            y = L.apply_mlp(cfg, bp["mlp"], xin)
        if cfg.post_norms:
            y = L.apply_norm(cfg, bp["ln2_post"], y)
        h = h + y
    elif kind == "rec":
        xin = L.apply_norm(cfg, bp["ln1"], h)
        if cache_len:
            m, cache = L.apply_rglru(cfg, bp["rec"], xin, mesh=mesh,
                                     return_state=True)
        else:
            m = L.apply_rglru(cfg, bp["rec"], xin, mesh=mesh)
        h = h + m
        y = L.apply_mlp(cfg, bp["mlp"], L.apply_norm(cfg, bp["ln2"], h))
        h = h + y
    elif kind == "mamba":
        xin = L.apply_norm(cfg, bp["ln1"], h)
        if cache_len:
            m, cache = L.apply_mamba(cfg, bp["mamba"], xin, mesh=mesh,
                                     return_state=True)
        else:
            m = L.apply_mamba(cfg, bp["mamba"], xin, mesh=mesh)
        h = h + m
    else:
        raise ValueError(kind)
    return h, aux, cache, stats


def _attn_with_cache(cfg, p, x, *, kind, positions, seg_ids, mesh, cache_len):
    """Prefill: compute attention AND return the kv cache (roped keys)."""
    B, S, _ = x.shape
    q, k, v = L._qkv(cfg, p, x, positions, kind)
    causal = kind != "enc"
    window = cfg.sliding_window if kind == "local" else 0
    from repro.kernels.flash_attention.ops import flash_attention
    o = flash_attention(q, k, v, causal=causal, window=window,
                        softcap=cfg.attn_softcap,
                        scale=cfg.attn_scale or None,
                        seg_q=seg_ids, seg_kv=seg_ids, impl=L._impl(cfg))
    out = o.reshape(B, S, cfg.q_dim) @ L.cast(cfg, p["wo"])
    if L.ring_cache(cfg, kind):
        W = cfg.sliding_window
        take = min(W, S)
        ks, vs = k[:, -take:], v[:, -take:]
        pos_tail = jnp.arange(S - take, S, dtype=jnp.int32)
        slots = pos_tail % W
        ks, vs = L.flat_heads(ks), L.flat_heads(vs)
        kc = jnp.zeros((B, W, ks.shape[-1]), k.dtype).at[:, slots].set(ks)
        vc = jnp.zeros((B, W, vs.shape[-1]), v.dtype).at[:, slots].set(vs)
        pc = jnp.full((W,), -1, jnp.int32).at[slots].set(pos_tail)
        cache = {"k": kc, "v": vc, "pos": pc}
    else:
        pad = ((0, 0), (0, cache_len - S), (0, 0))
        cache = {"k": jnp.pad(L.flat_heads(k), pad),
                 "v": jnp.pad(L.flat_heads(v), pad)}
    return out, cache


def _cross_with_cache(cfg, p, x, mem):
    B, S, _ = x.shape
    Sm = mem.shape[1]
    q = (x @ L.cast(cfg, p["wq"])).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = (mem @ L.cast(cfg, p["wk"])).reshape(B, Sm, cfg.num_kv_heads,
                                             cfg.head_dim)
    v = (mem @ L.cast(cfg, p["wv"])).reshape(B, Sm, cfg.num_kv_heads,
                                             cfg.head_dim)
    from repro.kernels.flash_attention.ops import flash_attention
    o = flash_attention(q, k, v, causal=False, softcap=cfg.attn_softcap,
                        scale=cfg.attn_scale or None, impl=L._impl(cfg))
    out = o.reshape(B, S, cfg.q_dim) @ L.cast(cfg, p["wo"])
    return out, {"xk": L.flat_heads(k), "xv": L.flat_heads(v)}


def decode_block(cfg: ModelConfig, bp: Params, h, cache: Params, kind: str,
                 *, positions, mesh):
    """Single-token step.  h: (B,1,D).  Returns (h, upd, stats): ``upd``
    holds only the cache entries the step changes — the new rows of a
    full-length attention cache (k/v, or MLA's latent ``ckv``;
    ``decode_step`` writes them), or the whole new ring / recurrent
    state; cross-attention ``xk``/``xv`` are read, never returned.
    ``stats`` as ``forward_block``'s."""
    stats = _no_stats()
    if kind in ("global", "local"):
        xin = L.apply_norm(cfg, bp["ln1"], h)
        if cfg.mla:
            a, upd = L.mla_decode(cfg, bp["attn"], xin,
                                  {"ckv": cache["ckv"]}, positions)
        else:
            sub = {k: cache[k] for k in ("k", "v", "pos") if k in cache}
            a, upd = L.attn_decode(cfg, bp["attn"], xin, sub, positions,
                                   kind=kind, mesh=mesh)
        if cfg.post_norms:
            a = L.apply_norm(cfg, bp["ln1_post"], a)
        h = h + a
        if "xattn" in bp and "xk" in cache:
            xin = L.apply_norm(cfg, bp["lnx"], h)
            xa = L.attn_decode_cross(cfg, bp["xattn"], xin,
                                     {"xk": cache["xk"], "xv": cache["xv"]})
            h = h + xa
        xin = L.apply_norm(cfg, bp["ln2"], h)
        if "moe" in bp:
            y, _, stats = L.moe_layer(cfg, bp["moe"], xin, mesh=mesh)
        else:
            y = L.apply_mlp(cfg, bp["mlp"], xin)
        if cfg.post_norms:
            y = L.apply_norm(cfg, bp["ln2_post"], y)
        h = h + y
    elif kind == "rec":
        xin = L.apply_norm(cfg, bp["ln1"], h)
        m, upd = L.rglru_decode(cfg, bp["rec"], xin,
                                {"h": cache["h"], "conv": cache["conv"]})
        h = h + m
        h = h + L.apply_mlp(cfg, bp["mlp"], L.apply_norm(cfg, bp["ln2"], h))
    elif kind == "mamba":
        xin = L.apply_norm(cfg, bp["ln1"], h)
        m, upd = L.mamba_decode(cfg, bp["mamba"], xin,
                                {"h": cache["h"], "conv": cache["conv"]})
        h = h + m
    else:
        raise ValueError(kind)
    return h, upd, stats


# ---------------------------------------------------------------- embed/head

def embed_tokens(cfg: ModelConfig, params: Params, tokens, positions):
    e = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(L._dt(cfg))
    if cfg.emb_scale:
        e = e * jnp.asarray(math.sqrt(cfg.d_model), L._dt(cfg))
    if cfg.rope_theta == 0:  # absolute sinusoidal positions (whisper)
        e = e + L.sinusoidal_pos(positions, cfg.d_model).astype(L._dt(cfg))
    return e


def lm_logits(cfg: ModelConfig, params: Params, h, *, mesh=None):
    """Full logits (serve path; training uses the fused chunked loss)."""
    w = params["embed"]["tok"] if cfg.tie_embeddings else params["head"]
    if cfg.tie_embeddings:
        logits = h.astype(jnp.float32) @ w.astype(jnp.float32).T
    else:
        logits = h.astype(jnp.float32) @ w.astype(jnp.float32)
    logits = constrain_logits(cfg, mesh, logits)
    if cfg.final_softcap:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    return logits


# ---------------------------------------------------------------- encoder

def encode(cfg: ModelConfig, params: Params, enc_frames, *, mesh=None,
           remat: bool = False, batch_kind: str = "train"):
    B, S, _ = enc_frames.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = enc_frames.astype(L._dt(cfg))
    if cfg.rope_theta == 0:
        h = h + L.sinusoidal_pos(pos, cfg.d_model).astype(L._dt(cfg))
    h = constrain_batch(cfg, mesh, h, batch_kind)

    def body(carry, bp):
        hh = carry
        hh, _, _, _ = forward_block(cfg, bp["sub_0"], hh, "enc",
                                    positions=pos, seg_ids=None, mem=None,
                                    mesh=mesh, cache_len=None)
        hh = constrain_batch(cfg, mesh, hh, batch_kind)
        return hh, None

    if remat:
        body = jax.checkpoint(body, policy=_remat_policy(cfg))
    h, _ = lax.scan(body, h, params["enc"]["blocks"])
    return L.apply_norm(cfg, params["enc"]["final_norm"], h)


def _remat_policy(cfg: ModelConfig):
    if cfg.remat == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return None  # "full": save nothing


# ---------------------------------------------------------------- forward

def forward(cfg: ModelConfig, params: Params, tokens, *, positions=None,
            seg_ids=None, vision_embeds=None, enc_frames=None, mesh=None,
            remat: bool = False, cache_len: Optional[int] = None,
            batch_kind: str = "train"):
    """Returns dict with h (B,S,D final-normed), aux (scalar), cache (or None).

    ``cache_len``: when set, collect a decode cache (prefill mode); caches
    for global-attention layers are padded to this length.
    """
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
    h = embed_tokens(cfg, params, tokens, positions)
    if vision_embeds is not None and cfg.vision_tokens:
        vt = vision_embeds.shape[1]
        h = jnp.concatenate([vision_embeds.astype(h.dtype), h[:, vt:]], 1)
    h = constrain_batch(cfg, mesh, h, batch_kind)
    mem = None
    if enc_frames is not None and cfg.encoder_layers:
        mem = encode(cfg, params, enc_frames, mesh=mesh, remat=remat,
                     batch_kind=batch_kind)

    period, G, n_tail = _layout(cfg)
    aux = jnp.zeros((), jnp.float32)
    stats = _no_stats()
    collect = cache_len is not None
    cache: Params = {}

    def unrolled(h, aux, stats, group: str, kinds):
        """Layers kept out of the scan: the leading dense ones and the
        tail."""
        out = {}
        for j, kind in enumerate(kinds):
            blk = lambda hh, bp, kind=kind: forward_block(
                cfg, bp, hh, kind, positions=positions, seg_ids=seg_ids,
                mem=mem, mesh=mesh, cache_len=cache_len)
            if remat:
                blk = jax.checkpoint(blk, policy=_remat_policy(cfg))
            h, a, c, st = blk(h, params[group][f"block_{j}"])
            h = constrain_batch(cfg, mesh, h, batch_kind)
            aux, stats = aux + a, stats + st
            out[f"block_{j}"] = c
        if collect and kinds:
            cache[group] = out
        return h, aux, stats

    h, aux, stats = unrolled(h, aux, stats, "dense", [
        cfg.layer_kind(j) for j in range(cfg.first_dense_layers)])

    if G:
        xs, layer = scan_stack(cfg, params["blocks"])

        def body(carry, bp):
            hh, ax, st = carry
            bp = layer(bp)
            cg = {}
            for s in range(period):
                kind = cfg.layer_pattern[s]
                hh, a, c, sb = forward_block(
                    cfg, bp[f"sub_{s}"], hh, kind, positions=positions,
                    seg_ids=seg_ids, mem=mem, mesh=mesh, cache_len=cache_len)
                ax, st = ax + a, st + sb
                hh = constrain_batch(cfg, mesh, hh, batch_kind)
                if collect:
                    cg[f"sub_{s}"] = c
            return (hh, ax, st), (cg if collect else None)

        if remat:
            body = jax.checkpoint(body, policy=_remat_policy(cfg))
        (h, aux, stats), blocks_cache = lax.scan(body, (h, aux, stats), xs)
        if collect:
            cache["blocks"] = blocks_cache

    h, aux, stats = unrolled(h, aux, stats, "tail", [
        _kind(cfg, G, period, j) for j in range(n_tail)])

    h = L.apply_norm(cfg, params["final_norm"], h)
    return {"h": h, "aux": aux, "cache": cache if collect else None,
            "moe": stats}


# ---------------------------------------------------------------- decode

def _apply_updates(cfg: ModelConfig, kind: str, cache: Params, upd: Params,
                   positions, *, axis: int, mesh) -> Params:
    """One layer's (or scanned sub-layer's) new cache: ``decode_block``'s
    new rows (k/v, or MLA's ``ckv``) written at ``positions`` into a
    full-length attention cache, or its whole new state put in place;
    entries it did not return (cross-attention ``xk``/``xv``) pass
    through."""
    new = dict(cache)
    if kind in ("global", "local") and not L.ring_cache(cfg, kind):
        for name in upd:
            new[name] = L.write_rows(cfg, cache[name], upd[name],
                                     positions, axis=axis, mesh=mesh)
    else:
        new.update(upd)
    return new


def decode_step(cfg: ModelConfig, params: Params, cache: Params, tokens,
                positions, *, mesh=None, stats: bool = False):
    """One token for the whole batch.  tokens: (B,1); positions: (B,) —
    per-row offsets: rows may sit at different sequence positions (see
    layers.attn_decode), which is what lets the continuous-batching server
    admit a freshly prefilled request into a running decode wave.

    The layer scan only reads full-length attention caches and emits each
    layer's new rows; one write per leaf after the scan puts them at
    ``positions`` into the stacked cache, which XLA updates in place when
    the caller donates ``cache`` (``BatchedServer`` does).  Sliding-window
    rings and recurrent states are small and re-emitted whole by the scan.
    Returns (logits (B,1,V), new cache), and with ``stats`` also the
    expert layers' int32 counts summed over layers (``layers.moe_layer``).
    """
    h = embed_tokens(cfg, params, tokens, positions[:, None])
    h = constrain_batch(cfg, mesh, h, "serve")
    period, G, n_tail = _layout(cfg)
    new_cache: Params = {}
    st = _no_stats()

    def unrolled(h, st, group: str, kinds):
        out = {}
        for j, kind in enumerate(kinds):
            c = cache[group][f"block_{j}"]
            h, upd, sb = decode_block(cfg, params[group][f"block_{j}"], h, c,
                                      kind, positions=positions, mesh=mesh)
            st = st + sb
            out[f"block_{j}"] = _apply_updates(cfg, kind, c, upd, positions,
                                               axis=0, mesh=mesh)
        if kinds:
            new_cache[group] = out
        return h, st

    h, st = unrolled(h, st, "dense", [
        cfg.layer_kind(j) for j in range(cfg.first_dense_layers)])

    if G:
        xs, layer = scan_stack(cfg, params["blocks"])

        def body(carry, xs):
            hh, sc = carry
            bp, cg = xs
            bp = layer(bp)
            ug = {}
            for s in range(period):
                kind = cfg.layer_pattern[s]
                hh, ug[f"sub_{s}"], sb = decode_block(
                    cfg, bp[f"sub_{s}"], hh, cg[f"sub_{s}"], kind,
                    positions=positions, mesh=mesh)
                sc = sc + sb
            hh = constrain_batch(cfg, mesh, hh, "serve")
            return (hh, sc), ug

        (h, st), ubc = lax.scan(body, (h, st), (xs, cache["blocks"]))
        new_cache["blocks"] = {
            f"sub_{s}": _apply_updates(
                cfg, cfg.layer_pattern[s], cache["blocks"][f"sub_{s}"],
                ubc[f"sub_{s}"], positions, axis=1, mesh=mesh)
            for s in range(period)}

    h, st = unrolled(h, st, "tail", [
        _kind(cfg, G, period, j) for j in range(n_tail)])

    h = L.apply_norm(cfg, params["final_norm"], h)
    logits = lm_logits(cfg, params, h, mesh=mesh)
    if stats:
        return logits, new_cache, st
    return logits, new_cache


# ---------------------------------------------------------------- cache init

def _block_cache_zeros(cfg: ModelConfig, kind: str, B: int, cache_len: int,
                       cross: bool):
    dt = L._dt(cfg)
    F = cfg.num_kv_heads * cfg.head_dim      # kv heads side by side
    if kind in ("global", "local") and cfg.mla:
        return {"ckv": jnp.zeros((B, cache_len, cfg.cache_width), dt)}
    if kind in ("global", "local"):
        if L.ring_cache(cfg, kind):
            W = min(cfg.sliding_window, cache_len)
            c = {"k": jnp.zeros((B, W, F), dt),
                 "v": jnp.zeros((B, W, F), dt),
                 "pos": jnp.full((W,), -1, jnp.int32)}
        else:
            c = {"k": jnp.zeros((B, cache_len, F), dt),
                 "v": jnp.zeros((B, cache_len, F), dt)}
        if cross:
            c["xk"] = jnp.zeros((B, cfg.encoder_seq, F), dt)
            c["xv"] = jnp.zeros((B, cfg.encoder_seq, F), dt)
        return c
    if kind == "rec":
        W = cfg.lru_width_
        return {"h": jnp.zeros((B, W), jnp.float32),
                "conv": jnp.zeros((B, cfg.ssm_conv - 1, W), dt)}
    if kind == "mamba":
        return {"h": jnp.zeros((B, cfg.d_inner, cfg.ssm_state), jnp.float32),
                "conv": jnp.zeros((B, cfg.ssm_conv - 1, cfg.d_inner), dt)}
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, B: int, cache_len: int) -> Params:
    period, G, n_tail = _layout(cfg)
    cross = cfg.encoder_layers > 0
    cache: Params = {}
    if cfg.first_dense_layers:
        cache["dense"] = {
            f"block_{j}": _block_cache_zeros(cfg, cfg.layer_kind(j), B,
                                             cache_len, cross)
            for j in range(cfg.first_dense_layers)}
    if G:
        def one(_):
            return {f"sub_{s}": _block_cache_zeros(
                cfg, cfg.layer_pattern[s], B, cache_len, cross)
                for s in range(period)}
        cache["blocks"] = jax.vmap(one)(jnp.arange(G))
    if n_tail:
        cache["tail"] = {
            f"block_{j}": _block_cache_zeros(
                cfg, _kind(cfg, G, period, j), B, cache_len, cross)
            for j in range(n_tail)}
    return cache
