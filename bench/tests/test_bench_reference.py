"""The plain reference against the program at a small size on the CPU:
the same seed makes the same weights, and in float32 the two give the
same loss and logits to rounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import reference
from lib.ensemble import _paths, synthetic_batch
from lib.model import program_config

TINY = {"hidden_act": "silu", "hidden_size": 64, "intermediate_size": 160,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "num_hidden_layers": 3, "vocab_size": 211, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": True,
        "dtype": "float32", "param_dtype": "float32"}


@pytest.fixture(scope="module")
def cfg():
    return program_config(TINY, "bench-test:tiny-f32")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_same_seed_same_weights(cfg, dtype):
    from repro.models import init_params
    key = jax.random.PRNGKey(2**31 + 3)
    prog = _paths(init_params(cfg.replace(param_dtype=jnp.dtype(dtype).name),
                              key))
    ref = _paths(reference.init_params(TINY, key, dtype))
    assert sorted(prog) == sorted(ref)
    for k in prog:
        assert prog[k].dtype == ref[k].dtype
        np.testing.assert_array_equal(np.asarray(prog[k], np.float32),
                                      np.asarray(ref[k], np.float32))


def test_loss_and_logits_match_program_in_f32(cfg):
    from repro.models import forward, init_params, lm_logits
    key = jax.random.PRNGKey(5)
    params = init_params(cfg.replace(kernel_impl="xla"), key)
    b = synthetic_batch(9, 0, TINY["vocab_size"], 2, 24)
    with jax.default_matmul_precision("highest"):
        h = forward(cfg.replace(kernel_impl="xla"), params,
                    jnp.asarray(b["tokens"]))["h"]
        want = lm_logits(cfg, params, h)
        got = reference.logits(TINY, "f32", params, reference.hidden(
            TINY, "f32", params, jnp.asarray(b["tokens"])))
        lw = jax.nn.logsumexp(want, -1) - jnp.take_along_axis(
            want, jnp.asarray(b["labels"])[..., None], -1)[..., 0]
        loss = reference.loss(TINY, "f32", params, jnp.asarray(b["tokens"]),
                              jnp.asarray(b["labels"]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert float(loss) == pytest.approx(float(jnp.mean(lw)), rel=1e-5)


def test_served_logits_match_whole_forward():
    key = jax.random.PRNGKey(1)
    params = reference.init_params(TINY, key, jnp.bfloat16)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 211, (2, 20)),
                       jnp.int32)
    whole = reference.logits(TINY, "f32", params, reference.hidden(
        TINY, "f32", params, toks))
    part = reference.served_logits(TINY, "f32", params, toks, 15)
    np.testing.assert_allclose(np.asarray(part), np.asarray(whole[:, 15:]),
                               atol=1e-5)
    gaps = reference.token_gaps(part, np.asarray(part).argmax(-1))
    assert gaps.shape == (2, 5) and np.all(gaps == 0)
