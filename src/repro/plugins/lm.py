"""LM kernel plugins: the real science workloads of this reproduction.

The paper's MD engines (Amber/Gromacs) become JAX model steps on the
assigned architectures.  Reduced configs run on CPU; full configs are what
the dry-run lowers.  Step functions and live train states are cached in
module stores keyed by (ensemble, member) — the in-memory analogue of the
paper's staged files.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import flags
from repro.configs import get_config, reduced
from repro.configs.base import ShapeSpec
from repro.core.kernel_plugin import register_kernel
from repro.data import SyntheticLM
from repro.train import TrainHyper, build_eval_step, build_train_step, \
    make_train_state

# live member states (the "staging area"); keyed by (ensemble_id, member_id)
STATE_STORE: Dict[Tuple[str, int], Any] = {}
_STEP_CACHE: Dict[Tuple, Any] = {}


def resolve_cfg(name: str):
    if name.startswith("reduced:"):
        return reduced(get_config(name.split(":", 1)[1]))
    return get_config(name)


def _steps(cfg, kind: str, hyper: TrainHyper = TrainHyper()):
    key = (cfg.name, kind, hyper)
    if key not in _STEP_CACHE:
        if kind == "train":
            _STEP_CACHE[key] = jax.jit(build_train_step(cfg, hyper=hyper))
        else:
            _STEP_CACHE[key] = jax.jit(build_eval_step(cfg))
    return _STEP_CACHE[key]


def _shape(args, cfg) -> ShapeSpec:
    return ShapeSpec("task", "train",
                     int(args.get("seq", 64)), int(args.get("batch", 4)))


def submesh_sharding(ctx) -> Optional[NamedSharding]:
    """Placement on the device submesh the runtime granted this task
    (``ctx["submesh"]``), replicated over its devices; ``None`` when no
    submesh was granted (work then lands on the default device)."""
    mesh = ctx.get("submesh")
    return None if mesh is None else NamedSharding(mesh, P())


def _put(tree, sharding):
    return tree if sharding is None else jax.device_put(tree, sharding)


def _device_of(tree) -> str:
    return str(next(iter(jax.tree.leaves(tree)[0].devices())))


@register_kernel("lm.train", description="train an LM for n steps")
def lm_train(args, ctx):
    """Train member ``args["member"]`` for ``args["steps"]`` steps.  With
    the run's tracer: a ``member.train`` span over the body, with a
    ``member.data`` child per step's batch and placement and a
    ``member.wait`` child around each blocking read-back; step dispatch
    is what the children leave of it."""
    tr = getattr(ctx.get("runtime"), "tracer", None)
    if tr is None:
        return _train(args, ctx, None)
    with tr.span("member", "train", member=int(args.get("member", 0))):
        return _train(args, ctx, tr)


def _train(args, ctx, tr):
    cfg = resolve_cfg(args.get("arch", "reduced:gemma2-2b"))
    hyper = TrainHyper(base_lr=float(args.get("lr", 3e-4)), warmup=2,
                       total_steps=int(args.get("total_steps", 1000)),
                       schedule=args.get("schedule", "cosine"))
    sid = (args.get("ensemble", "default"), int(args.get("member", 0)))
    sh = submesh_sharding(ctx)
    state = STATE_STORE.get(sid)
    if state is None:
        state = jax.jit(partial(make_train_state, cfg), out_shardings=sh)(
            jax.random.PRNGKey(int(args.get("seed", 0)) + sid[1]))
    state = _put(state, sh)
    step = _steps(cfg, "train", hyper)
    data = SyntheticLM(cfg, _shape(args, cfg),
                       seed=int(args.get("data_seed", 0)))
    if tr is not None:
        wait = tr.open("member", "wait")
    start = int(jax.device_get(state["step"]))
    if tr is not None:
        tr.close(wait)
    losses = []
    for i in range(int(args.get("steps", 2))):
        if tr is not None:
            prep = tr.open("member", "data")
        batch = _put(data.batch_at(start + i), sh)
        if tr is not None:
            tr.close(prep)
        state, m = step(state, batch)
        losses.append(m["loss"])
    STATE_STORE[sid] = state
    if tr is not None:
        wait = tr.open("member", "wait")
    losses = [float(x) for x in jax.device_get(losses)]
    if tr is not None:
        tr.close(wait)
        wait = tr.open("member", "wait")
    end = int(jax.device_get(state["step"]))
    if tr is not None:
        tr.close(wait)
    return {"loss": losses[-1] if losses else float(np.nan),
            "losses": losses,
            "step": end,
            "member": sid[1],
            "impl": flags.TRAIN_IMPL,
            "device": _device_of(state["params"])}


@register_kernel("lm.eval", description="eval an LM member")
def lm_eval(args, ctx):
    cfg = resolve_cfg(args.get("arch", "reduced:gemma2-2b"))
    sid = (args.get("ensemble", "default"), int(args.get("member", 0)))
    state = STATE_STORE.get(sid)
    if state is None:
        raise RuntimeError(f"no live state for member {sid}")
    sh = submesh_sharding(ctx)
    step = _steps(cfg, "eval")
    data = SyntheticLM(cfg, _shape(args, cfg),
                       seed=int(args.get("data_seed", 1)))
    out = step(_put(state["params"], sh),
               _put(data.batch_at(int(args.get("batch_idx", 0))), sh))
    return {"loss": float(out["loss"]), "member": sid[1]}


@register_kernel("lm.checkpoint", description="checkpoint a member state")
def lm_checkpoint(args, ctx):
    from repro.checkpoint import Checkpointer
    sid = (args.get("ensemble", "default"), int(args.get("member", 0)))
    state = STATE_STORE[sid]
    ck = Checkpointer(args["dir"], keep=int(args.get("keep", 2)))
    path = ck.save(state, int(jax.device_get(state["step"])))
    return {"path": path}


@register_kernel("lm.decode", description="batched greedy decode")
def lm_decode(args, ctx):
    """Serve ``requests`` random prompts made from ``seed`` with a trained
    member's params, or with weights made from ``seed`` in the compute
    dtype."""
    from repro.plugins.serve import serving_params
    from repro.serve import BatchedServer, Request
    cfg = resolve_cfg(args.get("arch", "reduced:gemma2-2b"))
    sid = (args.get("ensemble", "default"), int(args.get("member", 0)))
    sh = submesh_sharding(ctx)
    seed = int(args.get("seed", 0))
    state = STATE_STORE.get(sid)
    if state is None:
        params = serving_params(cfg, seed, sh)
    else:
        params = _put(state["params"], sh)
    S0 = int(args.get("prompt_len", 8))
    B = int(args.get("batch", 2))
    srv = BatchedServer(cfg, params, batch=B, prompt_len=S0,
                        max_len=S0 + int(args.get("new_tokens", 4)) + 1)
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, S0),
                    max_new_tokens=int(args.get("new_tokens", 4)))
            for i in range(int(args.get("requests", 2)))]
    srv.submit(reqs)
    done = srv.run()
    return {"served": len(done), "stats": srv.stats,
            "attn_impl": flags.attn_impl(cfg.kernel_impl or None),
            "device": _device_of(params),
            "requests": [{"rid": r.rid, "prompt": r.prompt.tolist(),
                          "tokens": list(r.out_tokens)} for r in done]}
