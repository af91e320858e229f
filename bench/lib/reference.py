"""Plain reference of a llama-like dense decoder, in float32 jax.numpy.

Independent of the program under test: it imports nothing of it and makes
its own weights from the seed.  It follows the published block (pre-norm
RMSNorm, rotary q/k, causal multi-head softmax attention, SwiGLU MLP, tied
embedding head) and the program's stated initialisation, optimizer and
schedule, so that the same seed gives the same weights and the same
training trajectory up to rounding.

``policy`` sets the precision of every matmul: ``"f32"`` computes in
float32 at ``highest`` precision; ``"fp8"`` rounds both operands of every
matmul to float8 (e4m3, scaled per tensor by its largest magnitude; the
gradients flowing back through them to e5m2, scaled alike) and
accumulates in float32.  ``"fp8"`` is the control: the step below bfloat16,
the compute precision the configurations state.

Parameter trees use the program's leaf names (``embed/tok``,
``blocks/sub_0/attn/wq``, ...) so that the two can be compared leaf by
leaf; the layer stack is stored stacked on a leading axis.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
F8_BWD = jnp.float8_e5m2
F8_BWD_MAX = 57344.0
HI = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------------ shapes

def dims(c: dict) -> dict:
    return dict(L=c["num_hidden_layers"], D=c["hidden_size"],
                H=c["num_attention_heads"], KH=c["num_key_value_heads"],
                Dh=c["head_dim"], F=c["intermediate_size"],
                V=c["vocab_size"], theta=float(c["rope_theta"]),
                eps=float(c["rms_norm_eps"]))


# ------------------------------------------------------------------ init

def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_params(c: dict, key, dtype=jnp.float32) -> Dict:
    """Weights from ``key``: normal(0, 0.02) inputs, output projections at
    0.02 / sqrt(2 * layers), norms at one; each layer's key is the
    ``fold_in(split(kB, layers)[i], 0)`` of the program's scheme."""
    d = dims(c)
    D, L = d["D"], d["L"]
    kE, _kH, kB, _kT, _kEnc = jax.random.split(key, 5)
    std_out = 0.02 / math.sqrt(2 * L)

    def layer(k):
        ks = jax.random.split(jax.random.fold_in(k, 0), 8)
        a = jax.random.split(ks[0], 4)
        m = jax.random.split(ks[2], 3)
        return {"sub_0": {
            "ln1": {"scale": jnp.ones((D,), dtype)},
            "attn": {"wq": _normal(a[0], (D, d["H"] * d["Dh"]), 0.02, dtype),
                     "wk": _normal(a[1], (D, d["KH"] * d["Dh"]), 0.02, dtype),
                     "wv": _normal(a[2], (D, d["KH"] * d["Dh"]), 0.02, dtype),
                     "wo": _normal(a[3], (d["H"] * d["Dh"], D), std_out,
                                   dtype)},
            "ln2": {"scale": jnp.ones((D,), dtype)},
            "mlp": {"wi": _normal(m[0], (D, d["F"]), 0.02, dtype),
                    "wo": _normal(m[1], (d["F"], D), std_out, dtype),
                    "wg": _normal(m[2], (D, d["F"]), 0.02, dtype)}}}

    return {"embed": {"tok": _normal(kE, (d["V"], D), 0.02, dtype)},
            "final_norm": {"scale": jnp.ones((D,), dtype)},
            "blocks": jax.vmap(layer)(jax.random.split(kB, L))}


# ------------------------------------------------------------------ math

def _round8(x, dtype, top):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _q8(x):
    """Round to float8 with a per-tensor scale: e4m3 going forward, and
    the gradient that flows back through it to e5m2, as float8 training
    does."""
    return _round8(x.astype(jnp.float32), F8, F8_MAX)


def _q8_fwd(x):
    return _q8(x), None


def _q8_bwd(_, g):
    return (_round8(g, F8_BWD, F8_BWD_MAX),)


_q8.defvjp(_q8_fwd, _q8_bwd)


def mm(policy: str, spec: str, a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if policy == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HI)


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


def block(c: dict, policy: str, p: Dict, h, pos):
    """One pre-norm layer over the whole sequence, causal."""
    d = dims(c)
    B, S, _ = h.shape
    a = p["attn"]
    x = _rms(h, p["ln1"]["scale"], d["eps"])
    q = mm(policy, "bsd,de->bse", x, a["wq"]).reshape(B, S, d["H"], d["Dh"])
    k = mm(policy, "bsd,de->bse", x, a["wk"]).reshape(B, S, d["KH"], d["Dh"])
    v = mm(policy, "bsd,de->bse", x, a["wv"]).reshape(B, S, d["KH"], d["Dh"])
    q, k = _rope(q, pos, d["theta"]), _rope(k, pos, d["theta"])
    rep = d["H"] // d["KH"]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = mm(policy, "bqhd,bkhd->bhqk", q, k) / math.sqrt(d["Dh"])
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = mm(policy, "bhqk,bkhd->bqhd", w, v).reshape(B, S, d["H"] * d["Dh"])
    h = h + mm(policy, "bse,ed->bsd", o, a["wo"])
    m = p["mlp"]
    x = _rms(h, p["ln2"]["scale"], d["eps"])
    g = mm(policy, "bsd,df->bsf", x, m["wg"])
    u = mm(policy, "bsd,df->bsf", x, m["wi"])
    return h + mm(policy, "bsf,fd->bsd", jax.nn.silu(g) * u, m["wo"])


def hidden(c: dict, policy: str, params: Dict, tokens):
    """Final-normed hidden states of ``tokens`` (B, S)."""
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(jnp.float32)

    def body(h, p):
        return block(c, policy, p["sub_0"], h, pos), None

    h, _ = jax.lax.scan(body, h, params["blocks"])
    return _rms(h, params["final_norm"]["scale"], dims(c)["eps"])


def logits(c: dict, policy: str, params: Dict, h):
    return mm(policy, "bsd,vd->bsv", h, params["embed"]["tok"])


def loss(c: dict, policy: str, params: Dict, tokens, labels):
    """Mean next-token cross-entropy over every position."""
    lg = logits(c, policy, params, hidden(c, policy, params, tokens))
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


# ------------------------------------------------------------------ training

def lr_at(step: int, hyper: dict) -> float:
    """Cosine schedule with linear warm-up, in float64 on the host."""
    base, warm, total = hyper["lr"], hyper["warmup"], hyper["total_steps"]
    w = min(1.0, (step + 1) / max(warm, 1))
    t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    cosv = hyper["min_ratio"] + (1 - hyper["min_ratio"]) * 0.5 \
        * (1 + math.cos(math.pi * t))
    return base * w * cosv


def leaf_norms(tree) -> Dict[str, float]:
    """Euclidean norm of each leaf, keyed by its slash-joined path."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in path)
        out[key] = float(jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32)))))
    return out


@partial(jax.jit, static_argnums=(0, 1))
def _grad(cj, policy, params, tokens, labels):
    import json
    c = json.loads(cj)
    return jax.value_and_grad(lambda p: loss(c, policy, p, tokens, labels))(
        params)


@partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd", "clip"))
def _adamw(params, m, v, g, count, lr, *, b1, b2, eps, wd, clip):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-9))
    g = jax.tree.map(lambda x: x * scale, g)
    c1 = 1.0 - b1 ** count
    c2 = 1.0 - b2 ** count

    def one(p, mm_, vv, gg):
        mm_ = b1 * mm_ + (1 - b1) * gg
        vv = b2 * vv + (1 - b2) * gg * gg
        step = (mm_ / c1) / (jnp.sqrt(vv / c2) + eps)
        if p.ndim >= 2:
            step = step + wd * p
        return p - lr * step, mm_, vv

    out = jax.tree.map(one, params, m, v, g)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), g


@partial(jax.jit, static_argnums=(0, 1))
def _loss(cj, policy, params, tokens, labels):
    import json
    return loss(json.loads(cj), policy, params, tokens, labels)


def train_readings(c: dict, policy: str, key, batches: List[dict],
                   lrs: List[float], hyper: dict,
                   after: Optional[dict] = None) -> dict:
    """Drive the reference through ``len(batches)`` AdamW steps from the
    weights ``key`` makes.  Returns each step's loss, the norm of each leaf
    of the first (clipped) gradient, the norm of each leaf's change over
    all the steps, and with ``after`` the loss of that batch once the
    steps are done (``after_loss``)."""
    import json
    cj = json.dumps(c, sort_keys=True)
    p0 = init_params(c, key)
    params = p0
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for i, (b, lr) in enumerate(zip(batches, lrs)):
        lval, g = _grad(cj, policy, params, jnp.asarray(b["tokens"]),
                        jnp.asarray(b["labels"]))
        params, m, v, g = _adamw(
            params, m, v, g, jnp.float32(i + 1), jnp.float32(lr),
            b1=hyper["b1"], b2=hyper["b2"], eps=hyper["eps"],
            wd=hyper["wd"], clip=hyper["clip"])
        losses.append(float(lval))
        if first is None:
            first = leaf_norms(g)
            del g
    change = leaf_norms(jax.tree.map(lambda a, b: a - b, params, p0))
    out = {"losses": losses, "grad_norms": first, "change_norms": change}
    if after is not None:
        out["after_loss"] = float(_loss(cj, policy, params,
                                        jnp.asarray(after["tokens"]),
                                        jnp.asarray(after["labels"])))
    return out


# ------------------------------------------------------------------ serving

def served_logits(c: dict, policy: str, params: Dict, tokens, start: int):
    """Logits at positions ``start`` .. S-1 of ``tokens`` (B, S), computed
    layer by layer from ``params`` kept in their stored dtype, each
    layer's weights widened to float32 only while it runs."""
    B, S = tokens.shape
    d = dims(c)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    @jax.jit
    def embed(table, tokens):
        return jnp.take(table, tokens, axis=0).astype(jnp.float32)

    @jax.jit
    def one(h, p):
        return block(c, policy, jax.tree.map(
            lambda x: x.astype(jnp.float32), p), h, pos)

    @jax.jit
    def head(norm, table, h):
        h = _rms(h[:, start:], norm, d["eps"])
        return mm(policy, "bsd,vd->bsv", h, table)

    h = embed(params["embed"]["tok"], tokens)
    for i in range(d["L"]):
        h = one(h, jax.tree.map(lambda x: x[i], params["blocks"])["sub_0"])
    return head(params["final_norm"]["scale"], params["embed"]["tok"], h)


def token_gaps(ref_logits, tokens) -> np.ndarray:
    """How far each chosen token's reference logit lies below the
    reference's best at that position."""
    ref = np.asarray(ref_logits, np.float64)
    tok = np.asarray(tokens)
    best = ref.max(axis=-1)
    chosen = np.take_along_axis(ref, tok[..., None], -1)[..., 0]
    return best - chosen
