"""Moonlight-16B-A3B's block (latent attention, sigmoid-routed experts
beside shared experts, a leading dense layer) against the plain reference
in ``mla_moe_reference.py``, at a small size on the CPU, and the expert
layer's share of the experts, its routing and its counts.

Tolerances: program and reference both compute in float32 here (the
reduced config's dtype); the program's matmuls run at the CPU backend's
float32 and the reference's at ``highest``, so they differ only in the
order of sums (the absorbed decode attention sums over the latent, the
reference over each head's expanded key).  Logits of these widths are
O(1e-1); 1e-4 is above that rounding and far below what a wrong expert,
row, position or weight gives (>= 1e-2 at these sizes).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mla_moe_reference as ref
from repro.configs import get_config, reduced
from repro.models import init_params
from repro.models import layers as L
from repro.serve import BatchedServer, Request
from repro.serve.engine import EXPERT_STATS, build_prefill_step

TOL = 1e-4


def _cfg(**kw):
    return reduced(get_config("moonlight-16b-a3b")).replace(**kw)


def _x(cfg, T, key=1):
    return jax.random.normal(jax.random.PRNGKey(key), (1, T, cfg.d_model))


def test_reduced_config_keeps_the_block():
    cfg = _cfg()
    assert cfg.mla and cfg.head_dim == cfg.qk_nope_dim + cfg.qk_rope_dim
    assert cfg.first_dense_layers == 1 and cfg.router == "sigmoid_bias"
    assert cfg.moe_capacity == 0 and cfg.num_shared_experts == 2
    assert cfg.cache_width == cfg.kv_lora_rank + cfg.qk_rope_dim
    full = get_config("moonlight-16b-a3b")
    assert (full.num_layers, full.d_model, full.vocab_size) == \
        (27, 2048, 163_840)
    assert full.cache_width == 576 and full.head_dim == 192


class _Recording(BatchedServer):
    """Keeps every logit row the engine computed for each request: its
    prefill's, then one per decode step while it holds a slot."""

    def __init__(self, cfg, params, **kw):
        from repro.models import decode_step
        self.seen, self.live = {}, []
        prefill = jax.jit(build_prefill_step(cfg, cache_len=kw["max_len"]))
        step = jax.jit(lambda p, c, t, pos: decode_step(cfg, p, c, t, pos,
                                                        stats=True))

        def prefill_fn(p, batch):
            out = prefill(p, batch)
            self.prefill_logits = np.asarray(out["logits"][:, 0])
            return out

        def step_fn(p, c, t, pos):
            logits, c, moe = step(p, c, t, pos)
            for i, r in enumerate(self.live):
                if r is not None:
                    self.seen[r.rid].append(np.asarray(logits[i, 0]))
            return logits, c, moe
        super().__init__(cfg, params, prefill_fn=prefill_fn,
                         step_fn=step_fn, **kw)

    def _admit(self, slots, cache, positions, last):
        self.live = slots
        before = [r and r.rid for r in slots]
        cache = super()._admit(slots, cache, positions, last)
        for i, r in enumerate(slots):
            if r is not None and before[i] != r.rid:
                self.seen[r.rid] = [self.prefill_logits[i]]
        return cache


@pytest.mark.parametrize("held", [0, 2])
def test_served_logits_match_reference(held):
    """Prefill, then decode at unequal row positions through
    ``BatchedServer`` (continuous mode: a request of 2 new tokens frees
    its slot and the next joins the live wave): every logit row the
    engine computed for a request equals the reference's full forward
    over the request's prompt and the tokens served before it.  ``held``
    2: this rank holds experts [2, 4) of 4, and the reference sums those
    alone."""
    cfg = _cfg(experts_held=held, expert_rank=1 if held else 0)
    params = init_params(cfg, jax.random.PRNGKey(0))
    S0, new = 8, [2, 5, 3, 4]
    srv = _Recording(cfg, params, batch=2, prompt_len=S0,
                     max_len=S0 + max(new))
    rng = np.random.default_rng(0)
    srv.submit([Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, S0),
                        max_new_tokens=n) for i, n in enumerate(new)])
    done = srv.run()
    assert sorted(r.rid for r in done) == [0, 1, 2, 3]
    assert srv.stats["prefills"] >= 2          # a joiner mid-wave
    held_ids = range(2, 4) if held else None
    for r in done:
        got = np.stack(srv.seen[r.rid])
        assert got.shape[0] == r.max_new_tokens + 1
        first = int(got[0].argmax())
        toks = np.concatenate([r.prompt, [first], r.out_tokens[:-1]])
        want = ref.logits(cfg, params, jnp.asarray(toks)[None], held_ids)
        np.testing.assert_allclose(got, np.asarray(want[0, S0 - 1:]),
                                   atol=TOL, rtol=0)


def _moe_params(cfg, key=3):
    return L.init_moe(cfg, jax.random.PRNGKey(key))


def test_expert_shares_sum_to_uncut_layer():
    """Ranks holding experts [r*2, r*2+2) of 8: their routed parts, with
    the shared experts counted once, add up to the uncut reference layer;
    each rank's weights are the uncut model's of its experts."""
    cfg = _cfg(num_experts=8, experts_per_tok=3)
    x = _x(cfg, 12)
    full = _moe_params(cfg)
    want = ref.experts(cfg, full, x)
    shared = L.apply_mlp(cfg, full["shared"], x)
    total = -3 * shared
    for r in range(4):
        c = cfg.replace(experts_held=2, expert_rank=r)
        p = _moe_params(c)
        for k in ("wi", "wg", "wo"):
            np.testing.assert_array_equal(p[k], full[k][2 * r:2 * r + 2])
        y, _, stats = L.moe_layer(c, p, x)
        assert int(stats[2]) == 0
        total = total + y
    np.testing.assert_allclose(total, want, atol=TOL, rtol=0)


def test_dropless_under_bias_that_sends_every_token_to_one_expert():
    """A planted bias routes every token to held expert 1 (and one more):
    the dropless layer computes all of them and matches the reference;
    the same routing under a 1.25 capacity factor drops rows."""
    cfg = _cfg(num_experts=8, experts_per_tok=2, experts_held=4)
    T = 64
    x = _x(cfg, T)
    p = _moe_params(cfg)
    p["bias"] = p["bias"].at[1].set(100.0)
    y, _, stats = L.moe_layer(cfg, p, x)
    rows, hit, drops, _ = (int(v) for v in stats)
    assert drops == 0 and rows >= T
    np.testing.assert_allclose(y, ref.experts(cfg, p, x, range(4)),
                               atol=TOL, rtol=0)
    _, _, capped = L.moe_layer(cfg.replace(moe_capacity=1.25), p, x)
    assert int(capped[2]) > 0 and int(capped[0]) + int(capped[2]) == \
        rows


def test_router_bias_changes_choice_not_weights():
    cfg = _cfg(num_experts=8, experts_per_tok=3)
    p = _moe_params(cfg)
    xt = _x(cfg, 64)[0]
    w, idx, _ = L.route(cfg, p, xt)
    w0, idx0, _ = L.route(cfg, {**p, "bias": jnp.zeros_like(p["bias"])}, xt)
    assert bool(jnp.any(jnp.sort(idx, -1) != jnp.sort(idx0, -1)))
    s = jax.nn.sigmoid(xt @ p["router"])
    expect = jnp.take_along_axis(s, idx, -1)
    expect = expect / expect.sum(-1, keepdims=True) * cfg.routed_scale
    np.testing.assert_allclose(w, expect, rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), cfg.routed_scale, rtol=1e-6)


def test_engine_records_expert_counts_on_its_spans():
    from repro.obs import Tracer
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tr = Tracer()
    srv = BatchedServer(cfg, params, batch=2, prompt_len=8, max_len=12,
                        tracer=tr)
    rng = np.random.default_rng(1)
    srv.submit([Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 8),
                        max_new_tokens=2 + i) for i in range(3)])
    srv.run()
    spans = [s for s in tr.spans if s["cat"] == "engine"
             and s["name"] in ("decode", "admit")]
    assert {s["name"] for s in spans} == {"decode", "admit"}
    moe_layers = cfg.num_layers - cfg.first_dense_layers
    for s in spans:
        assert all(k in s for k in EXPERT_STATS), s
        assert s["expert_drops"] == 0 and s["expert_rows"] > 0
        assert 0 < s["experts_hit"] <= cfg.held_experts * moe_layers
        # the capacity layout, C rows for each held expert: C the step's
        # tokens (2 slots, or 2 x 8 prompt tokens) rounded up to 8
        C = 8 if s["name"] == "decode" else 16
        assert s["expert_row_slots"] == cfg.held_experts * C * moe_layers
        assert s["expert_rows"] <= s["expert_row_slots"]
    for k in EXPERT_STATS:
        assert srv.stats[k] == sum(s[k] for s in spans)
    dec = [s for s in spans if s["name"] == "decode"]
    assert all(s["cache_donated"] is not None for s in dec)


def test_engine_without_experts_records_no_counts():
    from repro.obs import Tracer
    cfg = reduced(get_config("minicpm-2b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    tr = Tracer()
    srv = BatchedServer(cfg, params, batch=2, prompt_len=8, max_len=10,
                        tracer=tr)
    srv.submit([Request(rid=0, prompt=np.arange(8), max_new_tokens=2)])
    srv.run()
    assert not any(k in s for s in tr.spans for k in EXPERT_STATS)
    assert not any(k in srv.stats for k in EXPERT_STATS)


@pytest.mark.parametrize("sizes", [[0, 17, 200, 129], [256, 0, 1, 128]])
def test_gmm_kernel_skips_dead_blocks_exactly(sizes):
    """The Pallas grouped matmul (interpreted) with row blocks past each
    group's size pointed at blocks already held equals the oracle."""
    from repro.kernels.moe_gmm.ops import gmm
    from repro.kernels.moe_gmm.ref import gmm_ref
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 256, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, 32, 256)), jnp.float32)
    sz = jnp.asarray(sizes, jnp.int32)
    np.testing.assert_allclose(gmm(x, w, sz, impl="pallas_interpret"),
                               gmm_ref(x, w, sz), atol=1e-4)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_attention_with_values_narrower_than_keys(impl):
    from repro.kernels.flash_attention.ops import flash_attention
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 256, 4, 24)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 256, 4, 24)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 256, 4, 16)), jnp.float32)
    want = flash_attention(q, k, v, impl="ref")
    got = flash_attention(q, k, v, impl=impl, q_chunk=128, kv_chunk=128)
    assert got.shape == (1, 256, 4, 16)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_pallas_path_reads_expert_stacks_in_place():
    """With the Pallas expert kernel (interpreted here) the scan hands it
    the whole expert stacks and the layer index: prefill and decode give
    the XLA path's logits."""
    from repro.models import decode_step, forward
    from repro.models.transformer import lm_logits, scan_stack
    cfg = _cfg(experts_held=2, expert_rank=1)
    fast = cfg.replace(kernel_impl="pallas_interpret")
    params = init_params(cfg, jax.random.PRNGKey(0))
    xs, _ = scan_stack(fast, params["blocks"])
    assert "wi" not in xs[0]["sub_0"]["moe"]          # kept whole
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                cfg.vocab_size)
    out = {}
    for c in (cfg, fast):
        pre = forward(c, params, tokens[:, :8], cache_len=16)
        logits = [lm_logits(c, params, pre["h"][:, -1:])[:, 0]]
        cache = pre["cache"]
        for t in range(8, 12):
            lg, cache = decode_step(c, params, cache, tokens[:, t:t + 1],
                                    jnp.full((2,), t, jnp.int32))
            logits.append(lg[:, 0])
        out[c.kernel_impl] = np.stack(logits)
    np.testing.assert_allclose(out["pallas_interpret"], out[""], atol=TOL,
                               rtol=0)


def test_mesh_server_serves_experts_like_single_device():
    """The sharded branch pins the expert counts' output (replicated)
    beside logits and cache: it serves what the single-device branch
    serves and records the same counts."""
    from repro.launch.mesh import make_host_mesh
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(2))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, 8) for _ in range(3)]

    def serve(mesh):
        srv = BatchedServer(cfg, params, batch=2, prompt_len=8, max_len=12,
                            mesh=mesh)
        srv.submit([Request(rid=i, prompt=p, max_new_tokens=2 + i)
                    for i, p in enumerate(prompts)])
        got = {r.rid: r.out_tokens for r in srv.run()}
        return got, {k: srv.stats[k] for k in EXPERT_STATS}

    assert serve(make_host_mesh((1, 1))) == serve(None)


@pytest.mark.parametrize("arch", ["moonlight-16b-a3b", "qwen3-moe-30b-a3b"])
def test_dryrun_lowers_expert_serving_steps(arch):
    """The dry run lowers an expert config's prefill and decode steps on
    the production mesh, whose out_shardings carry the expert counts.  A
    child process: the dry run describes 512 host devices at import."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = ("from repro.launch.dryrun import lower_cell\n"
            f"for s in ('prefill_32k', 'decode_32k'):\n"
            f"    lower_cell({arch!r}, s, False)\n")
    env = {**os.environ, "PYTHONPATH": src, "JAX_PLATFORMS": "cpu"}
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
