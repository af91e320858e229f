"""repro.serving: seedable traffic, the continuous-batching DES cost
model, SLA metrics reconstruction, and the real per-step admit/evict
BatchedServer (token identity vs a sequential reference)."""
import numpy as np
import pytest

from repro.core import AppManager
from repro.runtime.executor import PilotRuntime
from repro.serving import (CLASSES, TrafficModel, build_serving_app,
                           simulate_continuous, sla_class)


def _tiny_cfg(**over):
    from repro.configs.base import ModelConfig
    kw = dict(name="serve-test", family="dense", num_layers=2, d_model=32,
              num_heads=2, num_kv_heads=1, head_dim=16, d_ff=64,
              vocab_size=64, layer_pattern=("global",))
    kw.update(over)
    return ModelConfig(**kw)


# ------------------------------------------------------------- traffic

def test_traffic_windows_deterministic_and_seeded():
    m = TrafficModel(seed=3, window_s=30.0)
    a = m.window(5)
    b = TrafficModel(seed=3, window_s=30.0).window(5)
    assert a == b                                     # pure fn of (seed, k)
    c = TrafficModel(seed=4, window_s=30.0).window(5)
    assert a != c
    # offsets sorted inside the window, rids globally unique, SLAs known
    offs = [r.offset_s for r in a]
    assert offs == sorted(offs)
    assert all(0.0 <= o < 30.0 for o in offs)
    rids = [r.rid for k in range(8) for r in m.window(k)]
    assert len(rids) == len(set(rids))
    assert all(r.sla in CLASSES for r in a)


def test_traffic_rate_is_diurnal_and_bounded():
    m = TrafficModel(base_rps=2.0, peak_rps=8.0, period_s=600.0,
                     window_s=30.0, burst_prob=0.0)
    rates = [m.rate(k) for k in range(20)]            # one full period
    assert all(2.0 - 1e-9 <= r <= 8.0 + 1e-9 for r in rates)
    assert max(rates) > 6.0 and min(rates) < 4.0      # actually swings


def test_traffic_class_split():
    m = TrafficModel(seed=1, latency_frac=0.25, base_rps=20.0,
                     peak_rps=20.0, burst_prob=0.0)
    reqs = [r for k in range(10) for r in m.window(k)]
    lat = [r for r in reqs if r.sla == "latency"]
    both = m.requests(0, "latency") + m.requests(0, "throughput")
    assert sorted(both, key=lambda r: r.rid) == m.window(0)
    assert 0.1 < len(lat) / len(reqs) < 0.4
    # latency requests decode fewer tokens than throughput ones
    assert max(r.max_new_tokens for r in lat) \
        <= min(r.max_new_tokens for r in reqs if r.sla == "throughput")


# ----------------------------------------------------- DES cost model

def test_simulate_continuous_properties():
    m = TrafficModel(seed=0)
    reqs = m.window(2)
    assert reqs
    sim = simulate_continuous(reqs, 4, step_cost_s=0.01,
                              prefill_cost_s=0.1)
    new = [r.max_new_tokens for r in reqs]
    assert max(new) <= sim.steps <= sum(new)
    assert 0.0 < sim.occupancy <= 1.0
    assert sim.prefills == -(-len(reqs) // 4)
    assert sim.makespan_s == pytest.approx(
        sim.steps * 0.01 + sim.prefills * 0.1)
    for r in reqs:
        assert 0.0 < sim.first_s[r.rid] <= sim.finish_s[r.rid]
        assert sim.finish_s[r.rid] <= sim.makespan_s + 1e-9


def test_simulate_continuous_empty():
    sim = simulate_continuous([], 8, step_cost_s=0.01)
    assert (sim.makespan_s, sim.steps, sim.prefills) == (0.0, 0, 0)


def test_simulate_single_slot_is_serial():
    m = TrafficModel(seed=0)
    reqs = m.window(1)
    sim = simulate_continuous(reqs, 1, step_cost_s=1.0)
    assert sim.steps == sum(r.max_new_tokens for r in reqs)
    assert sim.occupancy == pytest.approx(1.0)


# ------------------------------------------------- DES end-to-end app

def test_des_serving_app_collects_metrics():
    m = TrafficModel(seed=7, window_s=10.0, base_rps=3.0, peak_rps=9.0,
                     period_s=120.0)
    pipes, channels, metrics = build_serving_app(
        m, 6, decode_slots=4, step_cost_s=0.01,
        deadlines={"latency": 15.0, "throughput": 600.0})
    am = AppManager(PilotRuntime(slots=8, mode="sim", preempt=True))
    prof = am.run(pipes, validate="error")
    metrics.install(am, prof)
    s = prof.results["serving"]
    total = sum(len(m.window(k)) for k in range(6))
    assert sum(c["n"] for c in s["classes"].values()) == total
    for c in s["classes"].values():
        assert 0.0 < c["p50_latency_s"] <= c["p99_latency_s"]
        assert 0.0 < c["p50_ttft_s"] <= c["p50_latency_s"] + 1e-9
        assert 0.0 < c["occupancy"] <= 1.0
        assert c["dropped_windows"] == 0
    assert s["overall"]["tokens"] == sum(
        c["tokens"] for c in s["classes"].values())
    assert s["overall"]["goodput_tok_s"] <= \
        s["overall"]["throughput_tok_s"] + 1e-9
    # generous deadlines -> every token lands inside its budget
    assert s["classes"]["latency"]["met_tokens"] == \
        s["classes"]["latency"]["tokens"]
    for ch in channels.values():
        assert ch.n_unconsumed() == 0


def test_baseline_mode_strips_sla_annotations():
    m = TrafficModel(seed=7, window_s=10.0)
    pipes, _, _ = build_serving_app(m, 3, prioritize=False)
    specs = [sp for p in pipes for st in p.stages for sp in st.tasks]
    assert specs and all(sp.sla is None for sp in specs)
    pipes, _, _ = build_serving_app(m, 3, prioritize=True)
    slas = {sp.sla for p in pipes for st in p.stages for sp in st.tasks}
    assert slas == {"latency", "throughput"}


def test_sla_registry():
    assert sla_class("latency").priority > sla_class("throughput").priority
    assert sla_class("latency").preempts
    assert not sla_class("throughput").preempts
    with pytest.raises(KeyError):
        sla_class("gold")


# ------------------------------------------- real continuous batching

def test_continuous_batching_token_identity_and_backfill():
    """Per-step admit/evict serves the same tokens as a sequential
    B=1 reference, in fewer decode steps than wave scheduling."""
    jax = pytest.importorskip("jax")
    from repro.models import init_params
    from repro.serve import BatchedServer, Request

    cfg = _tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    S0, new = 4, [3, 5, 2, 4, 3]
    prompts = [rng.integers(0, cfg.vocab_size, S0) for _ in new]

    def serve(batch, reqs):
        srv = BatchedServer(cfg, params, batch=batch, prompt_len=S0,
                            max_len=S0 + max(new))
        srv.submit(reqs)
        return srv, {r.rid: r.out_tokens for r in srv.run()}

    srv, got = serve(2, [Request(rid=i, prompt=p, max_new_tokens=n)
                         for i, (p, n) in enumerate(zip(prompts, new))])
    assert srv.continuous
    # sequential reference: each request alone in a B=1 server
    for i, (p, n) in enumerate(zip(prompts, new)):
        _, ref = serve(1, [Request(rid=i, prompt=p, max_new_tokens=n)])
        assert got[i] == ref[i], f"rid {i} diverged from B=1 reference"
        assert len(got[i]) == n
    # backfill: steps bound is the continuous makespan, not wave sum
    waves_steps = 5 + 4 + 3                 # max-per-wave under B=2
    assert srv.stats["decode_steps"] < waves_steps
    assert srv.stats["decode_steps"] == simulate_continuous(
        [type("R", (), {"rid": i, "max_new_tokens": n})()
         for i, n in enumerate(new)], 2, step_cost_s=1.0).steps


def test_mid_wave_admission_matches_b1_and_forward_with_cache_donated():
    """Requests admitted into a live wave decode at unequal per-row
    positions and serve the tokens of a B=1 server and of greedy decoding
    by the full forward pass; every step consumes (donates) the cache it
    is given, and each traced ``engine.decode`` span says so."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.models import forward, init_params, lm_logits
    from repro.obs import Tracer
    from repro.serve import BatchedServer, Request

    cfg = _tiny_cfg(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)
    S0, new = 4, [6, 2, 5, 3, 4]
    prompts = [rng.integers(0, cfg.vocab_size, S0) for _ in new]

    def serve(batch, reqs, tracer=None):
        srv = BatchedServer(cfg, params, batch=batch, prompt_len=S0,
                            max_len=S0 + max(new), tracer=tracer)
        step, passed = srv.step, []

        def spy(p, cache, tokens, positions):
            passed.append(cache)
            return step(p, cache, tokens, positions)
        srv.step = spy
        srv.submit(reqs)
        return srv, passed, {r.rid: r.out_tokens for r in srv.run()}

    tr = Tracer()
    srv, passed, got = serve(2, [Request(rid=i, prompt=p, max_new_tokens=n)
                                 for i, (p, n) in enumerate(zip(prompts,
                                                                new))], tr)
    assert srv.stats["prefills"] > 1          # admissions into a live wave
    assert len(passed) == srv.stats["decode_steps"]
    for cache in passed:
        assert all(x.is_deleted() for x in jax.tree_util.tree_leaves(cache))
    flags = [s["cache_donated"] for s in tr.spans
             if s["cat"] == "engine" and s["name"] == "decode"]
    assert flags == [True] * srv.stats["decode_steps"]
    for i, (p, n) in enumerate(zip(prompts, new)):
        _, _, ref = serve(1, [Request(rid=i, prompt=p, max_new_tokens=n)])
        assert got[i] == ref[i], f"rid {i} diverged from B=1 reference"
        seq = [int(t) for t in p]
        for _ in range(n + 1):                # the prefill's token first
            h = forward(cfg, params, jnp.asarray([seq], jnp.int32))["h"]
            seq.append(int(jnp.argmax(lm_logits(cfg, params, h)[0, -1])))
        assert got[i] == seq[S0 + 1:], f"rid {i} diverged from forward"


def test_mesh_server_writes_rows_per_shard_like_single_device():
    """The sharded branch (cache pinned to ``cache_shardings``, each
    device writing its own rows) serves what the single-device branch
    serves, and also consumes each step's cache."""
    jax = pytest.importorskip("jax")
    from repro.launch.mesh import make_host_mesh
    from repro.models import init_params
    from repro.obs import Tracer
    from repro.serve import BatchedServer, Request

    cfg = _tiny_cfg(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(4))
    rng = np.random.default_rng(4)
    new = [3, 5, 2, 4]
    prompts = [rng.integers(0, cfg.vocab_size, 4) for _ in new]

    def serve(mesh):
        tr = Tracer()
        srv = BatchedServer(cfg, params, batch=2, prompt_len=4,
                            max_len=4 + max(new), mesh=mesh, tracer=tr)
        srv.submit([Request(rid=i, prompt=p, max_new_tokens=n)
                    for i, (p, n) in enumerate(zip(prompts, new))])
        got = {r.rid: r.out_tokens for r in srv.run()}
        assert all(s["cache_donated"] for s in tr.spans
                   if s["name"] == "decode")
        return got

    assert serve(make_host_mesh((1, 1))) == serve(None)


def test_request_clock_stamps_and_submit_guard():
    jax = pytest.importorskip("jax")
    from repro.models import init_params
    from repro.serve import BatchedServer, Request

    cfg = _tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tick = iter(range(100))
    srv = BatchedServer(cfg, params, batch=2, prompt_len=4, max_len=8,
                        clock=lambda: float(next(tick)))
    with pytest.raises(ValueError):
        srv.submit([Request(rid=9, prompt=np.zeros(4, int),
                            max_new_tokens=99)])
    reqs = [Request(rid=i, prompt=np.arange(4), max_new_tokens=2)
            for i in range(3)]
    srv.submit(reqs)
    done = srv.run()
    assert len(done) == 3
    for r in done:
        assert r.done_at > r.submitted_at >= 0.0   # session clock, ordered


def test_sliding_window_cfg_falls_back_to_waves():
    jax = pytest.importorskip("jax")
    from repro.models import init_params
    from repro.serve import BatchedServer

    cfg = _tiny_cfg(layer_pattern=("local", "global"), sliding_window=4)
    srv = BatchedServer(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                        batch=2, prompt_len=4, max_len=8)
    assert not srv.continuous


# ------------------------------------------------- engine program spans

def _stand_ins(B, V=16):
    """Deterministic prefill/step stand-ins: each token is the last one
    plus one (mod V); the cache is a per-row position counter."""
    import jax.numpy as jnp

    def prefill(params, batch):
        toks = batch["tokens"]
        logits = jnp.eye(V)[(toks[:, -1] + 1) % V][:, None]
        return {"logits": logits, "cache": {"tail": {
            "n": jnp.zeros((toks.shape[0],), jnp.int32)}}}

    def step(params, cache, tokens, positions):
        logits = jnp.eye(V)[(tokens[:, 0] + 1) % V][:, None]
        return logits, {"tail": {"n": cache["tail"]["n"] + 1}}
    return prefill, step


def _stand_in_server(tracer=None, B=2, new=(3, 5, 2, 4, 3)):
    from repro.serve import BatchedServer, Request
    prefill, step = _stand_ins(B)
    srv = BatchedServer(_tiny_cfg(), None, batch=B, prompt_len=4,
                        max_len=4 + max(new), prefill_fn=prefill,
                        step_fn=step, tracer=tracer)
    srv.submit([Request(rid=100 + i, prompt=np.arange(4) + i,
                        max_new_tokens=n) for i, n in enumerate(new)])
    return srv, srv.run()


def test_engine_spans_one_decode_per_step_with_one_wait_each():
    from repro.obs import Tracer
    tr = Tracer()
    srv, done = _stand_in_server(tr)
    spans = tr.spans
    decode = {s["id"]: s for s in spans if s["cat"] == "engine"
              and s["name"] == "decode"}
    assert len(decode) == srv.stats["decode_steps"]
    waits = [s for s in spans if s["name"] == "wait"]
    for sid in decode:
        assert sum(1 for w in waits if w["parent"] == sid) == 1
    admits = {s["id"]: s for s in spans if s["name"] == "admit"}
    assert len(admits) == srv.stats["prefills"]
    assert sum(a["joiners"] for a in admits.values()) == len(done)
    assert all(sum(1 for w in waits if w["parent"] == a) == 1
               for a in admits)
    assert sorted(r for a in admits.values() for r in a["rids"]) == \
        sorted(r.rid for r in done)
    assert [s["name"] for s in spans if s["name"] == "build"] == ["build"]
    # decode steps hold their own occupancy; admissions are siblings
    assert sum(s["slots"] for s in decode.values()) == \
        srv.stats["slot_steps"]
    assert all(s["parent"] is None for s in decode.values())
    req = {s["rid"]: s for s in spans if s["name"] == "request"}
    assert set(req) == {r.rid for r in done}
    for r in done:
        s = req[r.rid]
        assert (s["t0"], s["first_token_at"], s["t1"]) == \
            (r.submitted_at, r.first_token_at, r.done_at)
        assert s["t0"] <= s["first_token_at"] <= s["t1"] <= tr.now()


def test_engine_tokens_and_counts_do_not_depend_on_the_tracer():
    jax = pytest.importorskip("jax")
    from repro.models import init_params
    from repro.obs import Tracer
    from repro.serve import BatchedServer, Request

    cfg = _tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    new = [3, 5, 2, 4]
    prompts = [rng.integers(0, cfg.vocab_size, 4) for _ in new]

    def serve(tracer):
        srv = BatchedServer(cfg, params, batch=2, prompt_len=4,
                            max_len=4 + max(new), tracer=tracer)
        srv.submit([Request(rid=i, prompt=p, max_new_tokens=n)
                    for i, (p, n) in enumerate(zip(prompts, new))])
        return {r.rid: r.out_tokens for r in srv.run()}, srv.stats

    assert serve(None) == serve(Tracer())


def test_serve_decode_returns_per_request_times_on_the_tracer_clock():
    import dataclasses
    import time
    from types import SimpleNamespace

    from repro.obs import Tracer
    from repro.plugins.serve import serve_decode
    m = TrafficModel(seed=5, window_s=2.0, base_rps=3.0, peak_rps=3.0,
                     burst_prob=0.0, prompt_tokens=4,
                     latency_new_tokens=2, throughput_new_tokens=3)
    k = next(k for k in range(50) if m.requests(k, None))
    args = {"model": dataclasses.asdict(m), "window": k, "sla": None,
            "prompt_len": 4, "decode_slots": 2}
    tr = Tracer()
    out = serve_decode(args, {"runtime": SimpleNamespace(tracer=tr)})
    want = sorted(r.rid for r in m.requests(k, None))
    assert sorted(e["rid"] for e in out["requests"]) == want
    req = {s["rid"]: s for s in tr.spans if s["name"] == "request"}
    for e in out["requests"]:
        assert e["first_token_at"] == req[e["rid"]]["first_token_at"]
        assert e["done_at"] == req[e["rid"]]["t1"]
        assert 0.0 <= e["first_token_at"] <= e["done_at"] <= tr.now()
    # untraced: the same requests, timed on time.perf_counter
    t0 = time.perf_counter()
    plain = serve_decode(args, {})
    assert [e["rid"] for e in plain["requests"]] == \
        [e["rid"] for e in out["requests"]]
    assert all(t0 <= e["first_token_at"] <= e["done_at"]
               <= time.perf_counter() for e in plain["requests"])
    assert plain["stats"] == out["stats"]
