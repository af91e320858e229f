"""Per-arch smoke tests (reduced configs) + the strong correctness test:
prefill-then-decode must match the full forward for every family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_configs, reduced
from repro.models import decode_step, forward, init_params
from repro.models.transformer import lm_logits

ARCHS = list(list_configs())


def _inputs(cfg, B, S, key=2):
    kw = {}
    if cfg.vision_tokens:
        kw["vision_embeds"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(key), (B, cfg.vision_tokens, cfg.d_model))
    if cfg.encoder_layers:
        kw["enc_frames"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(key + 1), (B, cfg.encoder_seq, cfg.d_model))
    return kw


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_smoke(arch):
    cfg = reduced(get_config(arch))
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 32
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    out = forward(cfg, params, tokens, **_inputs(cfg, B, S))
    assert out["h"].shape == (B, S, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(out["h"].astype(jnp.float32))))
    logits = lm_logits(cfg, params, out["h"])
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    from repro.configs.base import ShapeSpec
    from repro.data import SyntheticLM
    from repro.train import TrainHyper, build_train_step, make_train_state
    cfg = reduced(get_config(arch)).replace(microbatches=2)
    state = make_train_state(cfg, jax.random.PRNGKey(0))
    step = jax.jit(build_train_step(cfg, hyper=TrainHyper(warmup=1,
                                                          total_steps=10)))
    batch = SyntheticLM(cfg, ShapeSpec("t", "train", 32, 4)).batch_at(0)
    state, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))
    assert int(jax.device_get(state["step"])) == 1


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch):
    cfg = reduced(get_config(arch))
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S, EXTRA, CLEN = 2, 24, 4, 48
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S + EXTRA), 0,
                                cfg.vocab_size)
    kw = _inputs(cfg, B, S)
    full = lm_logits(cfg, params, forward(cfg, params, tokens, **kw)["h"])
    cache = forward(cfg, params, tokens[:, :S], cache_len=CLEN,
                    **kw)["cache"]
    errs = []
    for t in range(EXTRA):
        pos = jnp.full((B,), S + t, jnp.int32)
        logits, cache = decode_step(cfg, params, cache,
                                    tokens[:, S + t:S + t + 1], pos)
        errs.append(float(jnp.max(jnp.abs(logits[:, 0] - full[:, S + t]))))
    assert max(errs) < 2e-2, (arch, errs)


def _per_row(cfg):
    """Every layer keeps a full-length per-row attention cache (no ring,
    no recurrent state), so rows may sit at unequal positions."""
    from repro.models.layers import ring_cache
    return all(k in ("global", "local") and not ring_cache(cfg, k)
               for k in map(cfg.layer_kind, range(cfg.num_layers)))


PER_ROW = [a for a in ARCHS if _per_row(reduced(get_config(a)))]


@pytest.mark.parametrize("arch,scan", [(a, True) for a in PER_ROW]
                         + [("minicpm-2b", False)])
def test_decode_at_unequal_row_positions_matches_forward(arch, scan):
    """Rows decode at their own positions.  Row 1 is rewound below its
    prefill, whose cache holds k/v of other tokens from there on: the
    step must attend to none of them and write its own rows over them.
    ``scan=False`` unrolls every layer (the ``tail`` cache path)."""
    cfg = reduced(get_config(arch)).replace(scan_layers=scan)
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S, EXTRA, CLEN, BACK = 2, 24, 4, 48, 6
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S + EXTRA), 0,
                                cfg.vocab_size)
    kw = _inputs(cfg, B, S)
    full = lm_logits(cfg, params, forward(cfg, params, tokens, **kw)["h"])
    stale = tokens.at[1, S - BACK:S].set(
        (tokens[1, S - BACK:S] + 1) % cfg.vocab_size)
    cache = forward(cfg, params, stale[:, :S], cache_len=CLEN,
                    **kw)["cache"]
    start = jnp.asarray([S, S - BACK], jnp.int32)
    rows = jnp.arange(B)
    errs = []
    for t in range(EXTRA):
        pos = start + t
        logits, cache = decode_step(cfg, params, cache,
                                    tokens[rows, pos][:, None], pos)
        errs.append(float(jnp.max(jnp.abs(logits[:, 0] - full[rows, pos]))))
    assert max(errs) < 2e-2, (arch, errs)


def test_moe_no_drop_matches_dense_reference():
    """With generous capacity, sorted-dispatch MoE == dense compute-all."""
    from repro.models import layers as L
    cfg = reduced(get_config("qwen3-moe-30b-a3b"))
    key = jax.random.PRNGKey(0)
    p = L.init_moe(cfg, key)
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y, aux, _ = L.moe_layer(cfg.replace(moe_capacity=float(cfg.num_experts)),
                            p, x)

    # dense reference: run every expert on all tokens, weight top-k
    xt = x.reshape(-1, cfg.d_model)
    logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    w, idx = jax.lax.top_k(probs, cfg.experts_per_tok)
    w = w / w.sum(-1, keepdims=True)
    outs = []
    for e in range(cfg.num_experts):
        hi = xt @ p["wi"][e]
        hg = xt @ p["wg"][e]
        h = jax.nn.silu(hg) * hi
        outs.append(h @ p["wo"][e])
    dense = jnp.stack(outs, 1)                     # (T, E, D)
    sel = jnp.take_along_axis(dense, idx[..., None], axis=1)
    y_ref = (sel * w[..., None]).sum(1).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), atol=2e-2)


def test_vision_embeds_change_output():
    cfg = reduced(get_config("internvl2-26b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0,
                                cfg.vocab_size)
    v1 = 0.02 * jax.random.normal(jax.random.PRNGKey(2),
                                  (1, cfg.vision_tokens, cfg.d_model))
    out1 = forward(cfg, params, tokens, vision_embeds=v1)["h"]
    out2 = forward(cfg, params, tokens, vision_embeds=2 * v1)["h"]
    assert float(jnp.max(jnp.abs(out1 - out2))) > 1e-6


def test_encoder_changes_decoder_output():
    cfg = reduced(get_config("whisper-large-v3"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0,
                                cfg.vocab_size)
    f1 = 0.02 * jax.random.normal(jax.random.PRNGKey(2),
                                  (1, cfg.encoder_seq, cfg.d_model))
    out1 = forward(cfg, params, tokens, enc_frames=f1)["h"]
    out2 = forward(cfg, params, tokens, enc_frames=-f1)["h"]
    assert float(jnp.max(jnp.abs(out1 - out2))) > 1e-6
