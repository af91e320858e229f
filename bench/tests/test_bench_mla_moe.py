"""The ``serving_mla_moe`` kind's yardstick, on the CPU: its reference
against the program at a small size, its operation counts by hand, its two
kernel readers on synthetic traces, its lookup, and the cut of its
configuration file."""
import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import flops_mla_moe as F
from lib import reference_mla_moe as R
from lib.peaks import peaks

BENCH = Path(__file__).resolve().parents[1]
CONFIG = BENCH / "configs" / "serve-moonlight-16b-a3b.json"


def small(ranks=4, rank=1, **kw) -> dict:
    """The configuration file's keys at a small size, in float32."""
    c = json.loads(CONFIG.read_text())
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, head_dim=24, intermediate_size=96,
             moe_intermediate_size=32, n_routed_experts=2,
             num_experts_per_tok=3, num_hidden_layers=3, vocab_size=257,
             dtype="float32", param_dtype="float32",
             expert_parallel={"ranks": ranks, "rank": rank}, **kw)
    return c


def paths(tree):
    return {"/".join(str(getattr(k, "key", k)) for k in p): x
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("rank", [0, 3])
def test_reference_matches_program(rank):
    """Same seed, same weights leaf by leaf (the program's init scheme,
    key for key); same logits up to float32 rounding (the program's
    absorbed-free prefill and the reference differ only in the order of
    sums: 1e-4 on logits of O(1e-1))."""
    from lib.serving_mla_moe import program_config
    from repro.models import forward, init_params
    from repro.models.transformer import lm_logits
    c = small(rank=rank)
    cfg = program_config(c, f"bench:test-mla-moe-{rank}")
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_rank) == \
        (8, 2, rank)
    key = jax.random.PRNGKey(7)
    prog = init_params(cfg, key)
    ref = R.init_params(c, key, jnp.float32)
    pp, rp = paths(prog), paths(ref)
    assert set(pp) == set(rp)
    for k in rp:
        np.testing.assert_array_equal(pp[k], rp[k], err_msg=k)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                c["vocab_size"])
    want = lm_logits(cfg, prog, forward(cfg, prog, tokens)["h"])[:, 5:]
    got = R.served_logits(c, "f32", ref, tokens, 5)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    ctl = R.served_logits(c, "fp8", ref, tokens, 5)
    assert float(jnp.max(jnp.abs(ctl - got))) > 1e-3


def test_reference_routes_over_all_experts_and_sums_the_held():
    """Biasing the router to an expert another rank holds takes rows
    from this rank's sum; to one it holds, adds them."""
    c = small(rank=0)
    d = R.dims(c)
    params = R.init_params(c, jax.random.PRNGKey(3), jnp.float32)
    m = jax.tree.map(lambda x: x[0], params["blocks"]["sub_0"])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 16, d["D"]))
    _, idx = R.route(d, m, x)
    assert int(jnp.max(idx)) >= d["held"]          # ids beyond this rank
    far = dict(m, bias=m["bias"].at[7].set(50.0))
    near = dict(m, bias=m["bias"].at[0].set(50.0))
    w_far, i_far = R.route(d, far, x)
    assert bool(jnp.all(jnp.any(i_far == 7, -1)))
    base = R.experts(d, "f32", m, x)
    assert float(jnp.max(jnp.abs(R.experts(d, "f32", near, x) - base))) \
        > 1e-3
    np.testing.assert_allclose(w_far.sum(-1), d["scale"], rtol=1e-6)


def test_operation_counts_by_hand():
    c = small()
    f = F.MlaMoe.from_config(c)
    # attention: wq 64*96 + wkv_a 64*40 + wkv_b 32*128 + wo 64*64
    assert f.attn_params() == 6144 + 2560 + 4096 + 4096
    # 3 layers of attention, 1 dense SwiGLU (3*64*96), 2 expert layers
    # (router 64*8, shared 3*64*64), head 257*64
    assert f.token_matmul_params() == (3 * 16896 + 18432
                                       + 2 * (512 + 12288) + 16448)
    assert f.expert_row_flops() == 2 * 3 * 64 * 32
    # prefill: 4 heads * 2 * (16+8+16) lanes * causal pairs of 4 * layers
    assert f.prefill_attn_flops(4) == 3 * 4 * 2 * 40 * 10
    # decode: 4 heads * 2 * (2*32 + 8) lanes * ctx keys * layers
    assert f.decode_attn_flops(5) == 3 * 4 * 2 * 72 * 5
    body = 2 * (f.token_matmul_params() - 257 * 64)
    head = 2 * 257 * 64
    want = (4 * (body + 0.5 * 12288) + head + f.prefill_attn_flops(4)
            + body + head + 0.5 * 12288 + f.decode_attn_flops(5))
    assert f.request_flops(4, 1, 0.5) == pytest.approx(want)
    ops, nbytes = F.mla_attention_cost(2, 4, 3, 24, 16)
    assert ops == 2 * 3 * 2 * 40 * 10
    assert nbytes == 2 * 2 * 4 * 3 * (48 + 32)
    ops, nbytes = F.expert_gmm_cost(10, 2, 64, 32)
    assert ops == 6 * 64 * 32 * 10
    assert nbytes == 2 * (2 * 3 * 64 * 32 + 2 * 10 * 64)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def op(kind, shape):
    return f"%{kind}.1 = {shape}{{2,1,0}} {kind}(bf16[1]{{0}} %p)"


def cell_run(program, ops, runs=1, spans=(), t=(10.0, 16.0)):
    """What the harness hands a reader after a traced serving window:
    the device trace's ops of ``program``, the program spans, and the
    traced span's ends on the tracer's clock (origin 100 s)."""
    c = json.loads(CONFIG.read_text())
    mix = json.loads((BENCH / "traffic" / "chat2k.json").read_text())
    return SimpleNamespace(
        cfg=c, mix=mix, peaks=peaks("TPU v5 lite"),
        device_trace={"program_ops": {program: ops} if ops else {},
                      "program_runs": {program: runs}},
        extra={"spans": list(spans)},
        profiler=SimpleNamespace(t0=100.0 + t[0], t1=100.0 + t[1]),
        tracer=SimpleNamespace(t0=100.0))


def step(t0, t1, rows, hit):
    return {"cat": "engine", "name": "decode", "t0": t0, "t1": t1,
            "expert_rows": rows, "experts_hit": hit, "expert_drops": 0}


def test_expert_gmm_roofline_reads_the_traced_steps():
    read = reader("expert_gmm_roofline")
    c = json.loads(CONFIG.read_text())
    D, Fe = c["hidden_size"], c["moe_intermediate_size"]
    p = peaks("TPU v5 lite")
    rows, hit = 40, 150
    least = max(6 * D * Fe * rows / p.flops,
                2 * (hit * 3 * D * Fe + 2 * rows * D) / p.hbm_bytes_per_s)
    inside = [step(11.0, 11.02, 20, 75), step(12.0, 12.02, 20, 75)]
    outside = [step(9.99, 10.01, 500, 200), step(15.99, 16.1, 500, 200)]
    kern = [(0.0, least, op("custom-call", "bf16[8,16,1408]")),
            (0.0, 1.0, op("fusion", "bf16[16,2048]"))]
    got = read(cell_run("jit_serve_step", kern, 2, inside + outside))
    assert got == pytest.approx(100.0)
    kern[0] = (0.0, 4 * least, kern[0][2])
    assert read(cell_run("jit_serve_step", kern, 2, inside)) == \
        pytest.approx(25.0)


@pytest.mark.parametrize("what", ["no steps", "no counters", "no program",
                                  "no trace"])
def test_expert_gmm_roofline_reads_nothing_without_its_inputs(what):
    read = reader("expert_gmm_roofline")
    kern = [(0.0, 1e-3, op("custom-call", "bf16[8,16,1408]"))]
    spans = [step(11.0, 11.1, 20, 75)]
    if what == "no counters":       # a program whose spans carry none
        spans = [{k: v for k, v in spans[0].items()
                  if not k.startswith("expert")}]
    run = cell_run("jit_serve_step", kern, 1,
                   [] if what == "no steps" else spans)
    if what == "no program":
        run.device_trace["program_ops"] = {}
    if what == "no trace":
        run.device_trace = None
    assert read(run) is None


def test_mla_prefill_attn_roofline_reads_the_attention_ops_alone():
    read = reader("mla_prefill_attn_roofline")
    c = json.loads(CONFIG.read_text())
    mix = json.loads((BENCH / "traffic" / "chat2k.json").read_text())
    B, S = mix["decode_slots"], mix["prompt_tokens"]
    H, dv = c["num_attention_heads"], c["v_head_dim"]
    p = peaks("TPU v5 lite")
    ops, nbytes = F.mla_attention_cost(B, S, H, 192, dv)
    least = max(ops / p.flops, nbytes / p.hbm_bytes_per_s)
    L = c["num_hidden_layers"]
    attn = op("custom-call", f"bf16[{B * H},1,{S},{dv}]")
    gmm = op("custom-call", f"bf16[1,{B * S},1408]")
    kern = [(0.0, least, attn)] * L + [(0.0, 5.0, gmm)] * 3
    assert read(cell_run("jit_prefill_step", kern)) == pytest.approx(100.0)
    kern = [(0.0, 2 * least, attn)] * L + [(0.0, 5.0, gmm)]
    assert read(cell_run("jit_prefill_step", kern)) == pytest.approx(50.0)
    assert read(cell_run("jit_prefill_step", [(0.0, 5.0, gmm)])) is None
    assert read(cell_run("jit_serve_step", kern)) is None


def test_kind_module_lookup():
    from lib.harness import kind_module
    mod = kind_module("serving_mla_moe")
    for name in ("run_cell", "calibrate", "sweep"):
        assert callable(getattr(mod, name))
    mix = json.loads((BENCH / "traffic" / "chat2k.json").read_text())
    assert mix["kind"] == "serving_mla_moe"
    assert mix["limits"]["expert_drops"] == 0


def test_configuration_file_states_its_cut():
    c = json.loads(CONFIG.read_text())
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(e for e in spec["configs"]
                 if e["file"] == "bench/configs/serve-moonlight-16b-a3b.json")
    assert sorted(entry["reduced"]) == sorted(c["published"])
    ep = c["expert_parallel"]
    assert c["n_routed_experts"] * ep["ranks"] == \
        c["published"]["n_routed_experts"]
    assert 0 <= ep["rank"] < ep["ranks"]
    assert c["head_dim"] == c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    d = R.dims(c)
    assert (d["E"], d["held"], d["base"], d["shared"]) == (64, 8, 0, 2816)


def test_planted_faults_restore_the_program():
    from lib.serving_mla_moe import planted
    from repro.models import layers
    from repro.serve import engine
    before = engine.build_serve_step, layers._expert_ffn
    for kind in planted.KINDS:
        with planted(kind):
            assert (engine.build_serve_step, layers._expert_ffn) != before
        assert (engine.build_serve_step, layers._expert_ffn) == before
    with pytest.raises(ValueError):
        planted("nothing")


def test_gap_numbers_sum_up_the_sampled_gaps():
    """The largest gap, the mean over every compared token (not over
    requests) and the share of tokens off the reference's best; zeros
    with nothing compared."""
    from lib.serving_mla_moe import gap_numbers
    got = gap_numbers({1: np.array([0.0, 0.5, 0.0]), 2: np.array([1.5])})
    assert got == {"served_token_gap": 1.5, "served_token_gap_mean": 0.5,
                   "served_token_miss": 0.5}
    assert set(gap_numbers({}).values()) == {0.0}
