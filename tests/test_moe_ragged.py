"""The dropless expert layer's ragged row list against its capacity layout
and the plain reference (``mla_moe_reference.experts``), at a small width
on the CPU, and the ragged grouped matmul's impls against its oracle.

A dropless layer whose capacity layout would hold more than a chunk of
rows (``layers.EXPERT_CHUNK_ROWS``) lays the held experts' rows out one
group after another under the forward-only Pallas kernel (interpreted
here).  The ``ref`` and ``xla`` impls keep the capacity layout in the
program (their loop would have to be differentiable), so their cases
steer the layer onto the ragged path from the test.

Tolerance: as ``test_mla_moe.py`` (float32 throughout; the paths differ
in the order of each token's sum over its experts).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mla_moe_reference as ref
from repro.configs import get_config, reduced
from repro.kernels.moe_gmm.ops import gmm_ragged
from repro.kernels.moe_gmm.ref import BLOCK_ROWS, gmm_ragged_ref
from repro.models import layers as L

TOL = 1e-4
R = L.EXPERT_CHUNK_ROWS
HELD = range(4)         # the rank's experts: 4 of 8


def _cfg(impl):
    return reduced(get_config("moonlight-16b-a3b")).replace(
        num_experts=8, experts_per_tok=2, experts_held=4, kernel_impl=impl)


def _layer(cfg, bias, T):
    """(params, x): routing biased by ``bias`` {expert: bias}."""
    p = L.init_moe(cfg, jax.random.PRNGKey(3))
    for e, b in bias.items():
        p["bias"] = p["bias"].at[e].set(b)
    return p, jax.random.normal(jax.random.PRNGKey(1), (1, T, cfg.d_model))


def _run(cfg, p, x):
    y, _, stats = jax.jit(lambda p, x: L.moe_layer(cfg, p, x))(p, x)
    return np.asarray(y), [int(v) for v in stats]


# (tokens, planted bias, chunks the routed rows fill: None = count them)
CASES = {
    "balanced": (2048, {}, None),
    # every token picks held expert 1 and one more: 2 whole chunks for
    # expert 1 alone, more for the others
    "one_expert": (8192, {1: 100.0}, None),
    # every token picks experts 5 and 6, neither held: no chunk runs
    "no_rows": (4096, {5: 100.0, 6: 100.0}, 0),
    # one held row a token, all in expert 1: exactly two full chunks
    "chunk_boundary": (8192, {1: 100.0, 5: 100.0}, 2),
}


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla", "ref"])
@pytest.mark.parametrize("case", list(CASES))
def test_ragged_rows_match_capacity_layout_and_reference(case, impl,
                                                         monkeypatch):
    T, bias, chunks = CASES[case]
    cap = _cfg("xla")
    p, x = _layer(cap, bias, T)
    y_cap, st_cap = _run(cap, p, x)
    if impl != "pallas_interpret":
        monkeypatch.setattr(L, "_ragged", lambda cfg, E_local, C, cf: (
            not cf and E_local * C > R))
    y, st = _run(_cfg(impl), p, x)

    want = np.asarray(ref.experts(cap, p, x, HELD))
    np.testing.assert_allclose(y, want, atol=TOL, rtol=0)
    np.testing.assert_allclose(y, y_cap, atol=TOL, rtol=0)
    # rows computed, experts hit and drops (none) are the layout's own
    assert st[:3] == st_cap[:3] and st[2] == 0
    assert st_cap[3] == len(HELD) * T            # E_local * C, C = T
    rows, hit = st[0], st[1]
    if chunks is None:                 # the rows and each group's padding
        chunks = -(-rows // R) if hit <= 1 else None
    assert st[3] % R == 0 and rows <= st[3] < rows + hit * BLOCK_ROWS + R
    if chunks is not None:
        assert st[3] == chunks * R
    if case == "one_expert":
        assert rows > 2 * R and st[3] >= 3 * R     # several chunks
    if case == "chunk_boundary":
        assert (rows, hit) == (T, 1)


def test_decode_shape_keeps_capacity_layout():
    """16 tokens: 4 experts x 16 rows fit one chunk, so the Pallas path
    keeps the capacity layout, and counts its rows as E_local * C."""
    p, x = _layer(_cfg("xla"), {}, 16)
    y_cap, st_cap = _run(_cfg("xla"), p, x)
    y, st = _run(_cfg("pallas_interpret"), p, x)
    assert st == st_cap and st[3] == len(HELD) * 16
    np.testing.assert_allclose(y, y_cap, atol=TOL, rtol=0)
    jaxpr = jax.make_jaxpr(lambda p, x: L.moe_layer(
        _cfg("pallas_interpret"), p, x))(p, x)
    assert "while" not in str(jaxpr)               # no chunk loop


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("D,F,live", [(32, 96, 0), (32, 96, 5), (32, 96, 8),
                                      (256, 384, 3)])
def test_gmm_ragged_matches_oracle(impl, D, F, live):
    """Blocks of experts 0, 0, 2, 2, 2, 3, 3, 3 of layer 1's experts
    [1, 5): the first ``live`` computed, the rest zero."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((8 * BLOCK_ROWS, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((2, 5, D, F)), jnp.float32)
    experts = jnp.asarray([0, 0, 2, 2, 2, 3, 3, 3], jnp.int32)
    idx = jnp.asarray([1, 1], jnp.int32)
    got = gmm_ragged(x, w, experts, jnp.int32(live), idx, impl=impl)
    want = gmm_ragged_ref(x, w[1], experts + 1, live)
    np.testing.assert_allclose(got, want, atol=1e-3 * np.sqrt(D), rtol=0)
    assert not np.asarray(want[live * BLOCK_ROWS:]).any()
