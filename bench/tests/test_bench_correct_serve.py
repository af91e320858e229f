"""``correct`` at a size a test run can hold, on the CPU: the harness's
whole run (set-up, window, checks) with the chip check skipped, sound and
with a fault planted under the timed path, and the control in the
program's place.  The limits are the cells' own (``bench/traffic``)."""
import time
from contextlib import nullcontext

import jax
import pytest

from lib import model
from lib.harness import Run

# serving at published widths with 4 layers and a 16,384-row vocabulary:
# at smaller widths the tokens barely depend on the cache, and a cache
# fault would change no token
SERVE_SMALL = dict(num_hidden_layers=4, vocab_size=16384)


def tiny_serve():
    spec = model.cell("serve-minicpm2b.chat")
    mix = dict(spec["mix"], prompt_tokens=16, latency_new_tokens=4,
               throughput_new_tokens=12, decode_slots=4, window_s=0.5,
               rate_rps=6.0, sample={"12": 4, "4": 2})
    return dict(spec, config=dict(spec["config"], **SERVE_SMALL),
                config_entry=dict(spec["config_entry"], name="tiny-serve"),
                mix=mix)


def run_once(spec, seconds, fault=None, planted=None):
    from lib.harness import kind_module
    run = Run(spec, 2**31 + 17, seconds, False, time.perf_counter(),
              devices=jax.devices())
    with planted(fault) if fault else nullcontext():
        kind_module(run.kind).run_cell(run)
    return run


@pytest.mark.parametrize("fault", [None, "token_altered", "cache_unchanged"])
def test_serve_correct_only_when_sound(fault):
    from lib.serving import planted
    run = run_once(tiny_serve(), 1.5, fault, planted)
    assert run.checks.ok == (fault is None), run.checks.items


def test_serve_control_fails():
    """The float8 control in the program's place, at published widths and
    4 layers of a 16,384-row vocabulary: at some position of the same
    tokens, the token it puts first lies further below the float32
    reference's best than the cell's limit allows."""
    import jax.numpy as jnp
    import numpy as np

    from lib import reference
    spec = model.cell("serve-minicpm2b.chat")
    c = dict(spec["config"], num_hidden_layers=4, vocab_size=16384)
    params = jax.jit(lambda k: reference.init_params(c, k, jnp.bfloat16))(
        jax.random.PRNGKey(3))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, c["vocab_size"], (4, 64)), jnp.int32)
    ref = np.asarray(reference.served_logits(c, "f32", params, toks, 31))
    ctl = np.asarray(reference.served_logits(c, "fp8", params, toks, 31))
    assert reference.token_gaps(ref, ref.argmax(-1)).max() == 0.0
    limit = spec["mix"]["limits"]["served_token_gap"]
    assert reference.token_gaps(ref, ctl.argmax(-1)).max() > limit
