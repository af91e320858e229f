"""Plain reference of the DeepSeek-V3 block that Moonlight-16B-A3B uses, in
float32 ``jax.numpy`` at ``highest`` matmul precision, with no kernels,
cache or batching: the whole sequence at once, every head expanded.

It follows the published description (moonshotai/Moonlight-16B-A3B
``config.json``, model_type deepseek_v3), not the program's code:

* pre-norm RMSNorm blocks, an untied head, layer 0 with the dense SwiGLU;
* latent attention without a q latent: q = W_q x split into its unroped
  (128) and roped (64) parts; [c ; k_r] = W_kva x, c RMS-normed; each head's
  unroped key and value W_kvb c; the roped key k_r one head shared by all;
  scores over the concatenated 192 lanes scaled by 192^-0.5, causal;
* expert layers: sigmoid scores over all experts in float32, the top k of
  score + bias choose, the chosen unbiased scores renormalised and scaled
  weigh them; the shared experts (one SwiGLU) are added whole.

A rank that holds a share of the experts is given the same share: the sum
runs over the held experts only (``held``), as the program's does.  Rope
pairs are halves, as the program's ``apply_rope``: the checkpoint's
interleaved rope columns are a fixed permutation of the weight columns,
which random weights do not see.

Parameters are read from the program's tree by name.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def mm(spec, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HI)


def rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x, pos, theta):
    """x (B, S, H, d): rotate the pairs (i, i + d/2) by pos * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(p, x):
    return mm("bsf,fd->bsd", jax.nn.silu(mm("bsd,df->bsf", x, p["wg"]))
              * mm("bsd,df->bsf", x, p["wi"]), p["wo"])


def attention(cfg, a, x, pos):
    B, S, _ = x.shape
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q = mm("bsd,de->bse", x, a["wq"]).reshape(B, S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos,
                                           cfg.rope_theta)], -1)
    kva = mm("bsd,de->bse", x, a["wkv_a"])
    c = rms(kva[..., :r], a["kv_norm"]["scale"], cfg.norm_eps)
    k_r = rope(kva[..., None, r:], pos, cfg.rope_theta)
    kv = mm("bsr,re->bse", c, a["wkv_b"]).reshape(B, S, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_r, (B, S, H, dr))], -1)
    s = mm("bqhd,bkhd->bhqk", q, k) * (dn + dr) ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = mm("bhqk,bkhd->bqhd", w, kv[..., dn:]).reshape(B, S, H * dv)
    return mm("bse,ed->bsd", o, a["wo"])


def route(cfg, m, x):
    """(weights (B,S,k), expert ids (B,S,k)) over all experts."""
    s = jax.nn.sigmoid(mm("bsd,de->bse", x, m["router"]))
    _, idx = jax.lax.top_k(s + m["bias"].astype(jnp.float32),
                           cfg.experts_per_tok)
    w = jnp.take_along_axis(s, idx, -1)
    return w / w.sum(-1, keepdims=True) * cfg.routed_scale, idx


def experts(cfg, m, x, held=None):
    """The routed sum over the experts ``held`` (a range of global ids;
    all by default), each computed on every token and weighted by its
    routing weight where chosen, plus the shared experts."""
    held = range(cfg.num_experts) if held is None else held
    w, idx = route(cfg, m, x)
    y = swiglu(m["shared"], x) if "shared" in m else jnp.zeros_like(x)
    for n, e in enumerate(held):
        we = jnp.sum(jnp.where(idx == e, w, 0.0), -1)[..., None]
        y = y + we * swiglu({k: m[k][n] for k in ("wi", "wg", "wo")}, x)
    return y


def block(cfg, p, h, pos, held=None):
    h = h + attention(cfg, p["attn"], rms(h, p["ln1"]["scale"],
                                          cfg.norm_eps), pos)
    x = rms(h, p["ln2"]["scale"], cfg.norm_eps)
    return h + (experts(cfg, p["moe"], x, held) if "moe" in p
                else swiglu(p["mlp"], x))


def logits(cfg, params, tokens, held=None):
    """Logits (B, S, V) of the whole sequence ``tokens`` (B, S)."""
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(jnp.float32)
    for j in range(cfg.first_dense_layers):
        h = block(cfg, params["dense"][f"block_{j}"], h, pos)
    stack = params["blocks"]["sub_0"]
    for i in range(jax.tree.leaves(stack)[0].shape[0]):
        h = block(cfg, jax.tree.map(lambda a: a[i], stack), h, pos, held)
    h = rms(h, params["final_norm"]["scale"], cfg.norm_eps)
    return mm("bsd,dv->bsv", h, params["head"])
