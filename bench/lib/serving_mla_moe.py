"""Serving members with latent attention and a share of the experts: job
kind ``serving_mla_moe`` (the Moonlight-16B-A3B configuration).

The cell runs as the ``serving`` kind does (``lib/serving.py``): open-loop
windows from the paced source, ``serve.decode`` through the program's
``BatchedServer`` in continuous mode, the served tokens recorded at the
engine's boundary and a sample of them teacher-forced through a plain
reference.  What differs is the configuration and what the checks compare
it with:

* the configuration file keeps the published ``config.json`` keys, with
  ``n_routed_experts`` the experts this chip holds and ``expert_parallel``
  the ranks that share each layer and this chip's rank; ``program_config``
  turns it into the program's ``ModelConfig``;
* the reference is ``lib/reference_mla_moe.py`` (latent attention
  expanded, the held experts' routed sum and the shared experts);
* the engine's expert counters (``expert_rows``, ``experts_hit``,
  ``expert_drops`` in ``serve.decode``'s ``stats``) add an exact check that
  no routed row was dropped, and give the operations of the held experts
  (``lib/flops_mla_moe.py``).
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from lib import reference_mla_moe as reference
from lib import serving
from lib.checks import Checks
from lib.flops_mla_moe import MlaMoe
from lib.reference import token_gaps
from lib.stats import latencies, percentile, rate


def program_config(c: dict, name: str):
    """Register the configuration file ``c`` with the program under
    ``name`` (once) and return the program's ``ModelConfig``."""
    from repro.configs.base import ModelConfig, get_config, register
    try:
        return get_config(name)
    except KeyError:
        pass
    if c["hidden_act"] != "silu" or c["scoring_func"] != "sigmoid" \
            or c["q_lora_rank"] is not None or c["n_group"] != 1:
        raise ValueError("this adapter describes DeepSeek-V3 blocks with "
                         "SwiGLU, sigmoid routing in one group and no q "
                         "latent")
    ep = c["expert_parallel"]
    return register(ModelConfig(
        name=name, family="moe",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        layer_pattern=("global",), mlp="swiglu", norm="rmsnorm",
        norm_eps=float(c["rms_norm_eps"]),
        rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        kv_lora_rank=c["kv_lora_rank"], qk_nope_dim=c["qk_nope_head_dim"],
        qk_rope_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        num_experts=c["n_routed_experts"] * ep["ranks"],
        experts_held=c["n_routed_experts"], expert_rank=ep["rank"],
        experts_per_tok=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"],
        first_dense_layers=c["first_k_dense_replace"],
        num_shared_experts=c["n_shared_experts"], router="sigmoid_bias",
        routed_scale=float(c["routed_scaling_factor"]), moe_capacity=0.0,
        dtype=c["dtype"], param_dtype=c["param_dtype"]))


EXPERT_STATS = ("expert_rows", "experts_hit", "expert_drops")


class MlaMoeCell(serving.ServeCell):
    def __init__(self, run):
        program_config(run.cfg, f"bench:{run.entry['name']}")
        super().__init__(run)

    def reference_gaps(self, rids: List[int], policy: str = "f32") -> dict:
        """As ``ServeCell.reference_gaps``, through this kind's reference."""
        c, served = self.run.cfg, self.rec.served
        params = jax.jit(lambda k: reference.init_params(c, k))(
            jax.random.PRNGKey(self.param_seed))
        out = {}
        groups: Dict[int, List[int]] = {}
        for rid in rids:
            groups.setdefault(len(served[rid]["tokens"]), []).append(rid)
        for n, group in groups.items():
            seqs = np.stack([np.concatenate(
                [served[r]["prompt"][:self.S0], [served[r]["first"]],
                 served[r]["tokens"][:-1]]) for r in group]).astype(np.int32)
            chosen = np.stack([[served[r]["first"]] + served[r]["tokens"]
                               for r in group])
            ref = np.asarray(reference.served_logits(
                c, "f32", params, jnp.asarray(seqs), self.S0 - 1))
            gaps = token_gaps(ref, chosen)
            if policy == "fp8":
                ctl = np.asarray(reference.served_logits(
                    c, "fp8", params, jnp.asarray(seqs), self.S0 - 1))
                gaps = token_gaps(ref, ctl.argmax(-1))
            for r, g in zip(group, gaps):
                out[r] = g
            del ref
        del params
        return out

    def expert_counts(self) -> Dict[str, int]:
        return {k: sum(int(s.get(k, 0)) for s in self.stats)
                for k in EXPERT_STATS}

    def checks(self, records) -> Checks:
        """As ``ServeCell.checks``, but the sampled tokens' gaps are judged
        by their mean (``served_token_gap_mean``): under bf16 a routing
        choice near a tie flips now and then and moves a few tokens by up
        to ≈ 1, so the largest gap of a sound run comes near the float8
        control's, while the mean keeps them far apart (PERF.md §6).  The
        largest gap and the share of tokens off the reference's best are
        kept as counters.  Adds the exact ``expert_drops``."""
        ck = Checks()
        ck.add("tasks_not_done", self.not_done, 0)
        ck.add("requests_not_served",
               sum(1 for r in records if not r["served"]), 0)
        ck.add("tokens_missing", sum(
            1 for s in self.rec.served.values()
            if len(s["tokens"]) != s["max_new"] or s["first"] is None), 0)
        gaps = self.reference_gaps(self.sample())
        self.compared_tokens = int(sum(len(g) for g in gaps.values()))
        self.gaps = gap_numbers(gaps)
        limits = self.mix["limits"]
        ck.add("served_token_gap_mean", self.gaps["served_token_gap_mean"],
               float(limits["served_token_gap_mean"]))
        ck.add("expert_drops", self.expert_counts()["expert_drops"],
               float(limits["expert_drops"]))
        return ck


def run_cell(run):
    """Set-up, window and checks of one ``serving_mla_moe`` cell: as
    ``lib/serving.run_cell``, with this kind's cell and operation count."""
    from contextlib import nullcontext

    from lib.harness import annotate_kernels, log
    cell = MlaMoeCell(run)
    n = max(1, int(math.floor(run.seconds / cell.ws)))
    cell.setup(n)
    run.mark_setup_done()
    spans = annotate_kernels(["serve.decode", serving.SOURCE]) \
        if run.trace else nullcontext()
    with spans:
        records = cell.window(n, tracer=run.tracer, profile=run.profiler)
    t0 = cell.t0
    t_close = t0 + n * cell.ws
    deadline = t_close + float(run.mix["drain_s"])
    lat, failed = latencies([r["due"] for r in records],
                            [r["done"] for r in records], deadline)
    lat += [deadline - r["due"] for r in records
            if r["done"] is None or r["done"] > deadline]
    ok = [r for r in records if r["done"] is not None
          and r["done"] <= deadline]
    last = max((r["done"] for r in ok), default=t_close)
    tokens = sum(r["tokens"] for r in ok)
    run.window = (t0, last)
    run.e2e["latency_p50_s"] = percentile(lat, 50)
    run.e2e["latency_p95_s"] = percentile(lat, 95)
    run.e2e["serve_tokens_per_s"] = rate(tokens, t0, last)
    stats = cell.stats
    steps = sum(s.get("decode_steps", 0) for s in stats)
    prefills = sum(s.get("prefills", 0) for s in stats)
    experts = cell.expert_counts()
    computed = cell.slots * (prefills * cell.S0 + steps)
    per_token = experts["expert_rows"] / computed if computed else 0.0
    flops = MlaMoe.from_config(run.cfg)
    run.counters.update(
        requests=len(records), failed=failed, windows=n,
        output_tokens=tokens, source_late_s=cell.clock["late"],
        decode_steps=steps,
        slot_steps=sum(s.get("slot_steps", 0) for s in stats),
        prefills=prefills, slots=cell.slots,
        expert_rows_per_token=per_token, **experts,
        flops=sum(flops.request_flops(cell.S0, r["tokens"], per_token)
                  for r in ok))
    log(f"latency samples={len(lat)} (p95 has "
        f"{len(lat) - math.ceil(0.95 * len(lat))} beyond it); "
        f"generator late by up to {cell.clock['late']!r}s")
    if run.trace:
        from repro.obs.report import decompose, segment_from_tracer
        run.profiler.stop()
        run.device_trace = run.profiler.reduce()
        run.obs = decompose(segment_from_tracer(run.tracer))
        run.extra["spans"] = run.tracer.spans
    run.read_memory_peak()
    cell.free()
    run.checks = cell.checks(records)
    run.counters["tokens_compared"] = cell.compared_tokens
    run.counters.update(cell.gaps)
    run.attempted = len(records)
    run.failed = failed
    return run


# ---------------------------------------------------------------- faults

class planted:
    """A fault planted in the program for the calibration and the tests:
    ``token_altered`` (every seventh decode step's token, in every slot, is
    the one the logits rank last, so that any sampled request holds
    some), ``cache_unchanged`` (each decode step returns the cache it was
    given) or ``experts_ignored`` (the held experts add nothing; the shared
    experts still do).  The engine's jitted steps are dropped on entry
    and exit, so that each side traces its own."""

    KINDS = ("token_altered", "cache_unchanged", "experts_ignored")

    def __init__(self, kind: str):
        if kind not in self.KINDS:
            raise ValueError(kind)
        self.kind = kind

    def __enter__(self):
        from repro.models import layers
        from repro.serve import engine
        self.saved = engine.build_serve_step, layers._expert_ffn
        engine._STEPS.clear()       # steps traced before the fault was in
        real = engine.build_serve_step
        if self.kind == "experts_ignored":
            layers._expert_ffn = lambda cfg, p, xt, *a: jnp.zeros(
                xt.shape, jnp.float32)
            return self
        kind = self.kind

        def build(cfg, mesh=None):
            step = real(cfg, mesh)

            def bad(params, cache, tokens, positions):
                logits, new, *rest = step(params, cache, tokens, positions)
                if kind == "cache_unchanged":
                    return (logits, cache, *rest)
                flip = (positions % 7 == 3)[:, None, None]
                return (jnp.where(flip, -logits, logits), new, *rest)
            return bad
        engine.build_serve_step = build
        return self

    def __exit__(self, *exc):
        from repro.models import layers
        from repro.serve import engine
        engine.build_serve_step, layers._expert_ffn = self.saved
        engine._STEPS.clear()
        return False


def _short_window(run, n_windows: int):
    cell = MlaMoeCell(run)
    cell.setup(n_windows)
    records = cell.window(n_windows)
    cell.free()
    return cell, records


def gap_numbers(gaps: dict) -> Dict[str, float]:
    """The sampled requests' token gaps summed up: the largest, their
    mean, and the share of tokens that are not the reference's best (0
    with no token compared)."""
    g = np.concatenate([np.asarray(v, np.float64).ravel()
                        for v in gaps.values()] + [np.zeros(0)])
    if not g.size:
        return dict.fromkeys(("served_token_gap", "served_token_gap_mean",
                              "served_token_miss"), 0.0)
    return {"served_token_gap": float(g.max()),
            "served_token_gap_mean": float(g.mean()),
            "served_token_miss": float(np.mean(g > 0))}


def calibrate(run, control: bool = False, faults: bool = False):
    """As ``lib/serving.calibrate``, with this kind's cell and faults."""
    n = int(run.mix["calibration_windows"])
    cell, _ = _short_window(run, n)
    rids = cell.sample()
    gaps = cell.reference_gaps(rids)
    yield {"what": "program", "numbers": {
        **gap_numbers(gaps),
        "tokens": int(sum(len(g) for g in gaps.values())),
        **cell.expert_counts()}}
    if control:
        yield {"what": "control",
               "numbers": gap_numbers(cell.reference_gaps(rids, "fp8"))}
    if faults:
        for kind in planted.KINDS:
            with planted(kind):
                bad, _ = _short_window(run, n)
            yield {"what": kind,
                   "numbers": gap_numbers(bad.reference_gaps(bad.sample()))}


def sweep(run, seconds: float) -> dict:
    """As ``lib/serving.sweep``, with this kind's cell."""
    cell = MlaMoeCell(run)
    n = max(1, int(seconds // cell.ws))
    cell.setup(n)
    records = cell.window(n)
    tasks = cell.am.session.graph.tasks
    windows = []
    for j, k in enumerate(cell.order_):
        t = tasks.get(f"dec.w{j:05d}")
        if t is None:
            continue
        windows.append({"window": k, "requests": len(cell.model.window(k)),
                        "serve_s": t.t_finished - t.t_started,
                        "lag_s": t.t_finished - (cell.t0 + (j + 1) * cell.ws)})
    cell.free()
    lat = [r["done"] - r["due"] for r in records if r["done"]]
    return {"rate_rps": float(run.mix["rate_rps"]), "requests": len(records),
            "latency_p50_s": percentile(lat, 50),
            "latency_p95_s": percentile(lat, 95),
            "serve_s_max": max(w["serve_s"] for w in windows),
            "windows": windows}
