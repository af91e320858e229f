"""Plain reference of the DeepSeek-V3 block as the Moonlight-16B-A3B cell
runs it, in float32 jax.numpy: the benchmark's own copy, independent of the
program (it imports nothing of it).

It follows the published description (moonshotai/Moonlight-16B-A3B
``config.json``, model_type deepseek_v3): pre-norm RMSNorm blocks; latent
attention without a q latent (q = W_q x split into its unroped and roped
parts; [c ; k_r] = W_kva x with c RMS-normed; each head's unroped key and
value W_kvb c; k_r one roped key shared by every head; scores over the
concatenated q/k lanes scaled by their width^-0.5, causal); layer 0 with
the dense SwiGLU; then sigmoid routing over all experts in float32 (the
top k of score + bias choose, the chosen unbiased scores renormalised and
times ``routed_scaling_factor`` weigh them), the routed sum over the
experts this chip holds (the configuration's ``expert_parallel`` rank),
each computed on every token, and the shared experts (one SwiGLU) whole;
an untied head.  Rope pairs are the halves of each head's rope lanes.

Weights come from the seed by the program's stated initialisation, key for
key (``init_params``), are kept in bfloat16 as the cell serves them, and
are widened to float32 one layer at a time.  ``policy`` is that of
``lib/reference.mm``: ``"f32"`` at ``highest`` precision, ``"fp8"`` the
control.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from lib.reference import mm

BIAS_STD = 0.1


def dims(c: dict) -> dict:
    ep = c["expert_parallel"]
    return dict(
        L=c["num_hidden_layers"], D=c["hidden_size"],
        H=c["num_attention_heads"], r=c["kv_lora_rank"],
        dn=c["qk_nope_head_dim"], dr=c["qk_rope_head_dim"],
        dv=c["v_head_dim"], F=c["intermediate_size"],
        Fe=c["moe_intermediate_size"], k=c["num_experts_per_tok"],
        held=c["n_routed_experts"], E=c["n_routed_experts"] * ep["ranks"],
        base=c["n_routed_experts"] * ep["rank"],
        shared=c["n_shared_experts"] * c["moe_intermediate_size"],
        dense=c["first_k_dense_replace"], V=c["vocab_size"],
        theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
        scale=float(c["routed_scaling_factor"]))


# ------------------------------------------------------------------ init

def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _mlp(key, D, F, std_out, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"wi": _normal(k1, (D, F), 0.02, dtype),
            "wo": _normal(k2, (F, D), std_out, dtype),
            "wg": _normal(k3, (D, F), 0.02, dtype)}


def _attn(key, d, std_out, dtype):
    D, H = d["D"], d["H"]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {"wq": _normal(k1, (D, H * (d["dn"] + d["dr"])), 0.02, dtype),
            "wkv_a": _normal(k2, (D, d["r"] + d["dr"]), 0.02, dtype),
            "kv_norm": {"scale": jnp.ones((d["r"],), dtype)},
            "wkv_b": _normal(k3, (d["r"], H * (d["dn"] + d["dv"])), 0.02,
                             dtype),
            "wo": _normal(k4, (H * d["dv"], D), std_out, dtype)}


def init_params(c: dict, key, dtype=jnp.bfloat16) -> Dict:
    """Weights from ``key``: ``split(key, 5)`` gives the embedding's,
    the head's, the stack's and two unused keys; the dense layer ``j``
    draws from ``fold_in(head key, j + 1)``, expert layer ``i`` from
    ``fold_in(split(stack key, layers)[i], 0)``; in a layer ``split(., 8)``
    gives attention [0] and MLP or experts [2]; expert ``e`` of the held
    ones draws from ``fold_in(k, e)`` of its global id.  Normal(0, 0.02),
    output projections 0.02 / sqrt(2 * layers), norms one, the selection
    bias normal(0, ``BIAS_STD``), router and bias in float32."""
    d = dims(c)
    D, L = d["D"], d["L"]
    kE, kH, kB, _kT, _kEnc = jax.random.split(key, 5)
    std_out = 0.02 / math.sqrt(2 * L)
    norm = lambda: {"scale": jnp.ones((D,), dtype)}  # noqa: E731

    def dense(k):
        ks = jax.random.split(k, 8)
        return {"ln1": norm(), "attn": _attn(ks[0], d, std_out, dtype),
                "ln2": norm(), "mlp": _mlp(ks[2], D, d["F"], std_out, dtype)}

    def moe_layer(k):
        ks = jax.random.split(jax.random.fold_in(k, 0), 8)
        m = jax.random.split(ks[2], 6)
        ids = jnp.arange(d["base"], d["base"] + d["held"])

        def experts(kk, shape, sd):
            return jax.vmap(lambda e: _normal(jax.random.fold_in(kk, e),
                                              shape, sd, dtype))(ids)
        return {"ln1": norm(), "attn": _attn(ks[0], d, std_out, dtype),
                "ln2": norm(),
                "moe": {"router": _normal(m[0], (D, d["E"]), 0.02,
                                          jnp.float32),
                        "wi": experts(m[1], (D, d["Fe"]), 0.02),
                        "wo": experts(m[2], (d["Fe"], D), std_out),
                        "wg": experts(m[3], (D, d["Fe"]), 0.02),
                        "bias": _normal(m[4], (d["E"],), BIAS_STD,
                                        jnp.float32),
                        "shared": _mlp(m[5], D, d["shared"], std_out,
                                       dtype)}}

    return {"embed": {"tok": _normal(kE, (d["V"], D), 0.02, dtype)},
            "head": _normal(kH, (D, d["V"]), 0.02, dtype),
            "final_norm": norm(),
            "dense": {f"block_{j}": dense(jax.random.fold_in(kH, j + 1))
                      for j in range(d["dense"])},
            "blocks": {"sub_0": jax.vmap(moe_layer)(
                jax.random.split(kB, L - d["dense"]))}}


# ------------------------------------------------------------------ math

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(policy, p, x):
    return mm(policy, "bsf,fd->bsd",
              jax.nn.silu(mm(policy, "bsd,df->bsf", x, p["wg"]))
              * mm(policy, "bsd,df->bsf", x, p["wi"]), p["wo"])


def attention(d: dict, policy: str, a: Dict, x, pos):
    """Causal latent attention over the whole sequence, every head
    expanded."""
    B, S, _ = x.shape
    H, r, dn, dr, dv = d["H"], d["r"], d["dn"], d["dr"], d["dv"]
    q = mm(policy, "bsd,de->bse", x, a["wq"]).reshape(B, S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, d["theta"])],
                        -1)
    kva = mm(policy, "bsd,de->bse", x, a["wkv_a"])
    c = _rms(kva[..., :r], a["kv_norm"]["scale"], d["eps"])
    k_r = _rope(kva[..., None, r:], pos, d["theta"])
    kv = mm(policy, "bsr,re->bse", c, a["wkv_b"]).reshape(B, S, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_r, (B, S, H, dr))], -1)
    s = mm(policy, "bqhd,bkhd->bhqk", q, k) / math.sqrt(dn + dr)
    causal = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), -1)
    o = mm(policy, "bhqk,bkhd->bqhd", w, kv[..., dn:]).reshape(B, S, H * dv)
    return mm(policy, "bse,ed->bsd", o, a["wo"])


def route(d: dict, m: Dict, x):
    """(weights (B,S,k), global expert ids (B,S,k)), routed in float32 at
    ``highest`` precision whatever the policy."""
    s = jax.nn.sigmoid(mm("f32", "bsd,de->bse", x, m["router"]))
    _, idx = jax.lax.top_k(s + m["bias"].astype(jnp.float32), d["k"])
    w = jnp.take_along_axis(s, idx, -1)
    return w / (w.sum(-1, keepdims=True) + 1e-20) * d["scale"], idx


def experts(d: dict, policy: str, m: Dict, x):
    """The held experts' routed sum plus the shared experts."""
    w, idx = route(d, m, x)
    y = _swiglu(policy, m["shared"], x)
    for n in range(d["held"]):
        we = jnp.sum(jnp.where(idx == d["base"] + n, w, 0.0), -1)
        y = y + we[..., None] * _swiglu(
            policy, {k: m[k][n] for k in ("wi", "wg", "wo")}, x)
    return y


def block(d: dict, policy: str, p: Dict, h, pos):
    h = h + attention(d, policy, p["attn"],
                      _rms(h, p["ln1"]["scale"], d["eps"]), pos)
    x = _rms(h, p["ln2"]["scale"], d["eps"])
    if "moe" in p:
        return h + experts(d, policy, p["moe"], x)
    return h + _swiglu(policy, p["mlp"], x)


def served_logits(c: dict, policy: str, params: Dict, tokens, start: int):
    """Logits at positions ``start`` .. S-1 of ``tokens`` (B, S), computed
    layer by layer, each layer's weights widened to float32 only while it
    runs."""
    d = dims(c)
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    wide = lambda p: jax.tree.map(lambda x: x.astype(jnp.float32), p)  # noqa: E731

    @jax.jit
    def one(h, p):
        return block(d, policy, wide(p), h, pos)

    @jax.jit
    def head(norm, w, h):
        return mm(policy, "bsd,dv->bsv", _rms(h[:, start:], norm, d["eps"]),
                  w)

    h = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(jnp.float32)
    for j in range(d["dense"]):
        h = one(h, params["dense"][f"block_{j}"])
    stack = params["blocks"]["sub_0"]
    for i in range(d["L"] - d["dense"]):
        h = one(h, jax.tree.map(lambda x: x[i], stack))
    return head(params["final_norm"]["scale"], params["head"], h)
