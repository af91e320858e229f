"""TPU-compiler rehearsal of the Pallas kernels at the widths of the configs
that use them, plus the loud failures of the forward-only Pallas paths.

The compiles target a described (not attached) TPU v5e: no chip is needed,
and the TPU compiler refuses here what it would refuse on the chip
(misaligned blocks, dynamic slices it cannot lower, VMEM overuse).  The
topology is described inside a fixture, never at import, so every xdist
worker collects the same tests and only the worker running this file loads
the TPU library.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.mamba.ops import selective_scan
from repro.kernels.moe_gmm.ops import gmm, gmm_ragged
from repro.kernels.rglru.ops import linear_scan


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compile cache off: a
    compile for an unattached chip cannot be read back from it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_case(name):
    """(fn, arg dtypes+shapes) of one kernel at its config's real widths."""
    bf, f32 = jnp.bfloat16, jnp.float32
    if name == "flash_attention":            # gemma2-2b local layer
        c = get_config("gemma2-2b")
        S = 1024
        fn = lambda q, k, v: flash_attention(
            q, k, v, window=c.sliding_window, softcap=c.attn_softcap,
            scale=c.attn_scale, impl="pallas")
        return fn, [((1, S, c.num_heads, c.head_dim), bf),
                    ((1, S, c.num_kv_heads, c.head_dim), bf),
                    ((1, S, c.num_kv_heads, c.head_dim), bf)]
    if name == "rglru":                      # recurrentgemma-2b lru_width
        W = get_config("recurrentgemma-2b").lru_width_
        fn = lambda x, a, h0: linear_scan(x, a, h0, impl="pallas")
        return fn, [((2, 1024, W), f32), ((2, 1024, W), f32), ((2, W), f32)]
    if name == "mamba":                      # falcon-mamba-7b d_inner/state
        c = get_config("falcon-mamba-7b")
        d, n, T = c.d_inner, c.ssm_state, 1024
        fn = lambda *a: selective_scan(*a, impl="pallas")
        return fn, [((1, T, d), bf), ((1, T, d), f32), ((d, n), f32),
                    ((1, T, n), bf), ((1, T, n), bf), ((d,), f32),
                    ((1, d, n), f32)]
    if name == "moe_gmm":                    # qwen3-moe-30b-a3b experts
        c = get_config("qwen3-moe-30b-a3b")
        E, D, F = c.num_experts, c.d_model, c.expert_ff
        fn = lambda x, w, s: gmm(x, w, s, impl="pallas")
        return fn, [((E, 128, D), bf), ((E, D, F), bf), ((E,), jnp.int32)]
    if name == "moe_gmm_dropless":           # moonlight: 8 held experts,
        c = get_config("moonlight-16b-a3b")  # a row per token of a prefill
        D, F = c.d_model, c.expert_ff
        fn = lambda x, w, s: gmm(x, w, s, impl="pallas")
        return fn, [((8, 4096, D), bf), ((8, D, F), bf), ((8,), jnp.int32)]
    if name == "moe_gmm_stacked":            # moonlight decode: the layer
        c = get_config("moonlight-16b-a3b")  # scan's whole expert stack
        L, D, F = c.num_layers - 1, c.d_model, c.expert_ff
        fn = lambda x, w, s, i: gmm(x, w, s, i, impl="pallas")
        return fn, [((8, 16, D), bf), ((L, 8, D, F), bf), ((8,), jnp.int32),
                    ((2,), jnp.int32)]
    if name in ("moe_gmm_ragged", "moe_gmm_ragged_down"):
        c = get_config("moonlight-16b-a3b")  # moonlight prefill: a chunk of
        L, D, F = c.num_layers - 1, c.d_model, c.expert_ff  # ragged rows
        if name.endswith("down"):
            D, F = F, D
        fn = lambda x, w, be, n, i: gmm_ragged(x, w, be, n, i, impl="pallas")
        return fn, [((4096, D), bf), ((L, 8, D, F), bf), ((32,), jnp.int32),
                    ((), jnp.int32), ((2,), jnp.int32)]
    if name == "flash_attention_mla":        # moonlight prefill, expanded
        c = get_config("moonlight-16b-a3b")
        S, H = 2048, c.num_heads
        fn = lambda q, k, v: flash_attention(
            q, k, v, scale=c.head_dim ** -0.5, impl="pallas")
        return fn, [((1, S, H, c.head_dim), bf), ((1, S, H, c.head_dim), bf),
                    ((1, S, H, c.v_head_dim), bf)]
    raise ValueError(name)


@pytest.mark.parametrize("name", ["flash_attention", "rglru", "mamba",
                                  "moe_gmm", "moe_gmm_dropless",
                                  "moe_gmm_stacked", "moe_gmm_ragged",
                                  "moe_gmm_ragged_down",
                                  "flash_attention_mla"])
def test_pallas_kernel_compiles_for_v5e(name, one_chip):
    fn, args = _kernel_case(name)
    specs = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
             for s, dt in args]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_attention_refuses_segment_ids():
    q = jnp.zeros((1, 128, 2, 16), jnp.float32)
    k = jnp.zeros((1, 128, 1, 16), jnp.float32)
    seg = jnp.zeros((1, 128), jnp.int32)
    with pytest.raises(NotImplementedError, match="segment ids"):
        flash_attention(q, k, k, seg_q=seg, seg_kv=seg, impl="pallas")


def test_pallas_kernels_refuse_gradients():
    """A forward-only kernel fails loudly under jax.grad; the XLA path
    (what training picks) differentiates."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 128, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 128, 1, 16)), jnp.float32)

    def loss(q, impl):
        return flash_attention(q, k, k, impl=impl).sum()

    with pytest.raises(NotImplementedError, match="forward-only"):
        jax.grad(loss)(q, "pallas_interpret")
    assert np.isfinite(np.asarray(jax.grad(loss)(q, "xla"))).all()
