"""Serving kernels: traffic-window source + continuous-batch decode.

Both kernels regenerate their window's requests from the seedable
TrafficModel carried in ``arguments["model"]`` (a dataclass dict) — no
request payloads travel through the graph.  In DES mode neither function
body runs (the task's ``sim_duration`` models it); in real mode
``serve.decode`` drives an actual jitted BatchedServer over a small model.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import numpy as np

from repro.core.kernel_plugin import register_kernel

# real-mode decode params cache: one model per (arch, seed, placement),
# shared across the many per-window decode tasks of a run
_PARAMS_CACHE: Dict[Any, Any] = {}


def serving_params(cfg, seed: int, sharding=None):
    """Random weights for ``cfg`` made from ``seed`` in its compute dtype
    (``repro.serve.serving_config``), placed on ``sharding`` (default
    device when ``None``) and cached for the rest of the process."""
    import jax

    from repro.models import init_params
    from repro.serve import serving_config
    cfg = serving_config(cfg)
    key = (cfg.name, seed, sharding)
    if key not in _PARAMS_CACHE:
        _PARAMS_CACHE[key] = jax.jit(partial(init_params, cfg),
                                     out_shardings=sharding)(
            jax.random.PRNGKey(seed))
    return _PARAMS_CACHE[key]


def _serve_cfg(arch):
    if arch:
        from repro.plugins.lm import resolve_cfg
        return resolve_cfg(arch)
    from repro.configs.base import ModelConfig
    return ModelConfig(name="serve-tiny", family="dense", num_layers=2,
                       d_model=32, num_heads=2, num_kv_heads=1, head_dim=16,
                       d_ff=64, vocab_size=256, layer_pattern=("global",))


@register_kernel("serve.source",
                 description="regenerate one traffic window's requests")
def serve_source(args, ctx):
    from repro.serving.traffic import TrafficModel
    m = TrafficModel(**args["model"])
    sla = args.get("sla")
    reqs = m.requests(int(args["window"]), sla)
    return {"window": int(args["window"]), "sla": sla, "n": len(reqs),
            "prompt_tokens": sum(r.prompt_tokens for r in reqs),
            "nbytes": m.batch_nbytes(reqs)}


@register_kernel("serve.decode",
                 description="continuous-batch decode one traffic window")
def serve_decode(args, ctx):
    """Serve one window's requests; returns counts, the engine's
    ``stats`` and, per request, its ``rid`` and the times its first token
    was read back and it was done, on the run's tracer's clock (on
    ``time.perf_counter`` in an untraced run)."""
    from repro.plugins.lm import submesh_sharding
    from repro.serve import BatchedServer, Request
    from repro.serving.traffic import TrafficModel

    m = TrafficModel(**args["model"])
    reqs = m.requests(int(args["window"]), args.get("sla"))
    if not reqs:
        return {"served": 0, "tokens": 0}
    cfg = _serve_cfg(args.get("arch"))
    params = serving_params(cfg, int(args.get("param_seed", 0)),
                            submesh_sharding(ctx))
    S0 = int(args.get("prompt_len", 8))
    max_new = max(r.max_new_tokens for r in reqs)
    srv = BatchedServer(cfg, params,
                        batch=int(args.get("decode_slots", 4)),
                        prompt_len=S0, max_len=S0 + max_new,
                        tracer=getattr(ctx.get("runtime"), "tracer", None))
    srv.submit([Request(rid=r.rid,
                        prompt=np.random.default_rng(r.rid).integers(
                            0, cfg.vocab_size, S0),
                        max_new_tokens=r.max_new_tokens, sla=r.sla)
                for r in reqs])
    done = srv.run()
    return {"served": len(done),
            "tokens": sum(len(r.out_tokens) for r in done),
            "stats": srv.stats,
            "requests": [{"rid": r.rid, "first_token_at": r.first_token_at,
                          "done_at": r.done_at} for r in done]}
