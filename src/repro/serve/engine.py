"""Serving substrate: prefill/decode step builders, cache specs, and a
host-side continuous-batching scheduler (per-step admit/evict over a live
decode wave) used by the serving example and the ensemble serving plugins
(repro.serving builds whole PST applications on top of it).
"""
from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field
from typing import Any, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import decode_step, forward, init_cache, lm_logits
from repro.models.layers import ring_cache


def serving_config(cfg: ModelConfig) -> ModelConfig:
    """``cfg`` with parameters kept in the compute dtype: serving holds no
    f32 master copy (a full-width bf16 gemma2-2b is ~5.2 GB, f32 ~10.4)."""
    return cfg.replace(param_dtype=cfg.dtype)


EXPERT_STATS = ("expert_rows", "experts_hit", "expert_drops",
                "expert_row_slots")

# jitted single-device steps, kept for the process (``BatchedServer``)
_STEPS: dict = {}


def build_prefill_step(cfg: ModelConfig, mesh=None,
                       cache_len: Optional[int] = None):
    """Prefill: the last position's logits and, with ``cache_len``, the
    decode cache; for a config with experts also ``moe``, int32
    [``EXPERT_STATS``] summed over layers (``layers.moe_layer``)."""
    def prefill_step(params, batch):
        out = forward(cfg, params, batch["tokens"],
                      vision_embeds=batch.get("vision_embeds"),
                      enc_frames=batch.get("enc_frames"),
                      mesh=mesh, cache_len=cache_len, batch_kind="serve")
        logits = lm_logits(cfg, params, out["h"][:, -1:], mesh=mesh)
        res = {"logits": logits}
        if cache_len is not None:
            res["cache"] = out["cache"]
        if cfg.num_experts:
            res["moe"] = out["moe"]
        return res
    return prefill_step


def build_serve_step(cfg: ModelConfig, mesh=None):
    """decode: one new token for the whole batch against the cache;
    (logits, cache), and for a config with experts also its ``moe``
    counts as ``build_prefill_step``'s."""
    def serve_step(params, cache, tokens, positions):
        return decode_step(cfg, params, cache, tokens, positions, mesh=mesh,
                           stats=bool(cfg.num_experts))
    return serve_step


def step_out_shardings(cfg: ModelConfig, cache_sh):
    """out_shardings of the prefill and the serve step with the cache
    pinned to ``cache_sh``: logits and any expert counts replicated."""
    prefill = {"logits": None, "cache": cache_sh}
    serve = (None, cache_sh)
    if cfg.num_experts:
        prefill["moe"] = None
        serve += (None,)
    return prefill, serve


def cache_specs(cfg: ModelConfig, B: int, cache_len: int):
    """ShapeDtypeStructs of the decode cache (no allocation)."""
    return jax.eval_shape(lambda: init_cache(cfg, B, cache_len))


# ---------------------------------------------------------------- requests

@dataclass
class Request:
    rid: int
    prompt: Any                      # token array (S,)
    max_new_tokens: int = 16
    out_tokens: List[int] = field(default_factory=list)
    submitted_at: float = 0.0
    first_token_at: float = 0.0      # its first token on the host
    done_at: float = 0.0
    sla: str = "throughput"          # serving SLA class (repro.serving.sla)


def _merge_rows(old, new, mask, *, axis):
    """Select ``new``'s batch rows where ``mask`` is set, ``old``'s
    elsewhere, for every leaf of a cache subtree (``axis`` is the batch
    axis: 1 for the scanned ``blocks`` subtree, 0 for ``tail``)."""
    def sel(o, n):
        shape = [1] * o.ndim
        shape[axis] = o.shape[axis]
        return jnp.where(mask.reshape(shape), n, o)
    return jax.tree_util.tree_map(sel, old, new)


def _deleted(tree) -> bool:
    """Whether every leaf of ``tree`` is a deleted ``jax.Array``: a cache
    that a step took as a donated argument, whose buffers the step's new
    cache now holds."""
    leaves = jax.tree_util.tree_leaves(tree)
    return bool(leaves) and all(
        isinstance(x, jax.Array) and x.is_deleted() for x in leaves)


class BatchedServer:
    """Host-side continuous-batching server over fixed decode slots.

    ``run()`` keeps ONE decode wave alive for the whole queue: each step it
    (1) admits queued requests into free slots — group prefill, then merge
    only the joiner rows into the live cache — (2) decodes one token for
    every occupied slot at its own per-row cache position, and (3) evicts
    each request the step it reaches its ``max_new_tokens``, freeing the
    slot for the next admission.  Per-row positions come from
    ``models.layers.attn_decode``; sliding-window local layers keep a
    batch-synchronized ring cache (one position vector, no batch dim), so
    configs containing them fall back to the legacy synchronized-wave loop
    (evict-at-own-length still holds; no mid-wave admission).  The jitted
    decode step, on one device or a mesh, takes its cache as a donated
    argument: each step rebinds ``cache`` to the step's output, and the
    step writes the new k/v rows into the old buffers in place
    (``models.decode_step``).

    ``clock`` stamps Request.submitted_at/first_token_at/done_at: the
    tracer's clock when a ``tracer`` (repro.obs.Tracer) is given, else
    ``time.perf_counter``; a virtual-time callable in DES runs
    (repro.serving).  ``prefill_fn``/``step_fn`` let tests inject
    deterministic stand-ins for the jitted model functions.

    With a ``tracer``, continuous batching records program spans:
    ``engine.build`` (this constructor), ``engine.admit`` (an admission
    with joiners: prefill dispatch, row merge, first-token read-back),
    ``engine.decode`` (one decode step after admission, through its
    bookkeeping; ``cache_donated``: whether the cache passed in was
    consumed, i.e. every leaf deleted), ``engine.wait`` (each blocking
    read-back, inside those two) and ``engine.request`` (submit to
    eviction, per ``rid``).  For a config with experts, ``engine.admit``
    and ``engine.decode`` carry the step's ``EXPERT_STATS`` (rows the
    held experts computed, held experts with a row, rows dropped, rows of
    the expert layout the grouped matmuls ran; summed over layers), read
    back with the step's tokens, and ``stats`` sums them over the run.
    """

    def __init__(self, cfg: ModelConfig, params, *, batch: int, prompt_len: int,
                 max_len: int, mesh=None, clock=None,
                 prefill_fn=None, step_fn=None, tracer=None):
        self.tracer = tracer
        if tracer is not None:
            build = tracer.open("engine", "build")
        self.cfg, self.params, self.mesh = cfg, params, mesh
        self.B, self.S0, self.Smax = batch, prompt_len, max_len
        if clock is None:
            clock = time.perf_counter if tracer is None else tracer.now
        self.clock = clock
        # sliding-window ring caches are batch-synchronized -> wave mode
        self.continuous = not any(ring_cache(cfg, cfg.layer_kind(i))
                                  for i in range(cfg.num_layers))
        if prefill_fn is not None or step_fn is not None:
            self.prefill, self.step = prefill_fn, step_fn
        elif mesh is not None:
            # pin the distributed layout: params/cache stay sharded across
            # decode steps, logits replicated for sampling
            from repro.dist.sharding import cache_shardings, state_shardings
            p_sh = state_shardings(cfg, mesh, params)
            c_sh = cache_shardings(cfg, mesh,
                                   cache_specs(cfg, batch, max_len))
            pre_sh, step_sh = step_out_shardings(cfg, c_sh)
            self.prefill = jax.jit(
                build_prefill_step(cfg, mesh, cache_len=max_len),
                in_shardings=(p_sh, None), out_shardings=pre_sh)
            self.step = jax.jit(
                build_serve_step(cfg, mesh),
                in_shardings=(p_sh, c_sh, None, None),
                out_shardings=step_sh, donate_argnums=(1,))
        else:
            # one pair of jitted steps per (builders, config, cache length)
            # for the process: a server per traffic window reuses them
            # instead of tracing both steps again
            key = (build_prefill_step, build_serve_step, cfg, max_len)
            if key not in _STEPS:
                _STEPS[key] = (
                    jax.jit(build_prefill_step(cfg, cache_len=max_len)),
                    jax.jit(build_serve_step(cfg), donate_argnums=(1,)))
            self.prefill, self.step = _STEPS[key]
        self.queue: collections.deque = collections.deque()
        self.stats = {"served": 0, "decode_steps": 0, "prefills": 0,
                      "slot_steps": 0}
        if cfg.num_experts:
            self.stats.update(dict.fromkeys(EXPERT_STATS, 0))
        if tracer is not None:
            tracer.close(build)

    def submit(self, reqs: List[Request]):
        for r in reqs:
            if self.S0 + r.max_new_tokens > self.Smax:
                raise ValueError(
                    f"request {r.rid}: prompt_len {self.S0} + "
                    f"max_new_tokens {r.max_new_tokens} exceeds cache "
                    f"length {self.Smax}")
            r.submitted_at = self.clock()
            self.queue.append(r)

    def run(self) -> List[Request]:
        return self._run_continuous() if self.continuous \
            else self._run_waves()

    # -------------------------------------------------- continuous batching
    def _admit(self, slots, cache, positions, last):
        """Fill free slots from the queue: one group prefill for all
        joiners, merged row-wise into the live cache."""
        joiners = []
        for i in range(self.B):
            if slots[i] is None and self.queue:
                slots[i] = self.queue.popleft()
                joiners.append(i)
        if not joiners:
            return cache
        tr = self.tracer
        if tr is not None:
            admit = tr.open("engine", "admit", joiners=len(joiners),
                            rids=[slots[i].rid for i in joiners])
        joinset = set(joiners)
        tokens = jnp.stack(
            [jnp.asarray(slots[i].prompt[:self.S0])
             if i in joinset else jnp.zeros((self.S0,), jnp.int32)
             for i in range(self.B)])
        out = self.prefill(self.params, {"tokens": tokens})
        self.stats["prefills"] += 1
        fresh = out["cache"]
        if cache is None:
            cache = fresh
        else:
            mask = jnp.asarray([i in joinset for i in range(self.B)])
            # the scanned stack's leaves are (G, B, ...), the unrolled
            # layers' (``dense``, ``tail``) (B, ...)
            cache = {k: _merge_rows(cache[k], fresh[k], mask,
                                    axis=1 if k == "blocks" else 0)
                     for k in cache}
        first = jnp.argmax(out["logits"][:, 0], axis=-1)
        if tr is not None:
            wait = tr.open("engine", "wait")
        first, moe = jax.device_get((first, out.get("moe")))
        if tr is not None:
            tr.close(wait)
        now = self.clock()
        for i in joiners:
            last[i] = int(first[i])
            positions[i] = self.S0
            slots[i].first_token_at = now
        counts = self._count_experts(moe)
        if tr is not None:
            tr.close(admit, **counts)
        return cache

    def _count_experts(self, moe) -> dict:
        """A step's expert counts as span attributes, added to ``stats``
        (none for a config without experts)."""
        if moe is None:
            return {}
        counts = {k: int(v) for k, v in zip(EXPERT_STATS, moe)}
        for k, v in counts.items():
            self.stats[k] += v
        return counts

    def _run_continuous(self) -> List[Request]:
        done: List[Request] = []
        slots: List[Optional[Request]] = [None] * self.B
        positions = [0] * self.B     # next cache write offset per slot
        last = [0] * self.B          # last decoded token per slot (host)
        cache = None
        tr = self.tracer
        while self.queue or any(s is not None for s in slots):
            cache = self._admit(slots, cache, positions, last)
            if tr is not None:
                step = tr.open("engine", "decode", slots=sum(
                    s is not None for s in slots))
                evicted = len(done)
                passed = cache
            logits, cache, *moe = self.step(
                self.params, cache, jnp.asarray(last, jnp.int32)[:, None],
                jnp.asarray(positions, jnp.int32))
            self.stats["decode_steps"] += 1
            nxt = jnp.argmax(logits[:, 0], axis=-1)
            if tr is not None:
                wait = tr.open("engine", "wait")
            nxt, moe = jax.device_get((nxt, moe[0] if moe else None))
            if tr is not None:
                tr.close(wait)
            counts = self._count_experts(moe)
            for i, r in enumerate(slots):
                if r is None:
                    continue
                r.out_tokens.append(int(nxt[i]))
                last[i] = int(nxt[i])
                positions[i] += 1
                self.stats["slot_steps"] += 1
                if len(r.out_tokens) >= r.max_new_tokens:
                    r.done_at = self.clock()
                    done.append(r)
                    self.stats["served"] += 1
                    slots[i] = None      # evict: slot free next admission
            if tr is not None:
                tr.close(step, cache_donated=_deleted(passed), **counts)
                for r in done[evicted:]:
                    tr.record("engine", "request", r.submitted_at,
                              r.done_at, rid=r.rid,
                              first_token_at=r.first_token_at)
        return done

    # -------------------------------------------------- legacy wave loop
    def _run_waves(self) -> List[Request]:
        done: List[Request] = []
        while self.queue:
            wave = [self.queue.popleft()
                    for _ in range(min(self.B, len(self.queue)))]
            tokens = jnp.stack(
                [jnp.asarray(r.prompt[:self.S0]) for r in wave] +
                [jnp.zeros((self.S0,), jnp.int32)] * (self.B - len(wave)))
            out = self.prefill(self.params, {"tokens": tokens})
            self.stats["prefills"] += 1
            cache = out["cache"]
            last = jnp.argmax(out["logits"][:, 0], axis=-1)
            nsteps = max(r.max_new_tokens for r in wave)
            for t in range(nsteps):
                pos = jnp.full((self.B,), self.S0 + t, jnp.int32)
                logits, cache, *_ = self.step(self.params, cache,
                                              last[:, None], pos)
                last = jnp.argmax(logits[:, 0], axis=-1)
                self.stats["decode_steps"] += 1
                host = jax.device_get(last)
                if t == 0:           # the wave's first tokens on the host
                    now = self.clock()
                    for r in wave:
                        r.first_token_at = now
                for i, r in enumerate(wave):
                    if t < r.max_new_tokens:
                        r.out_tokens.append(int(host[i]))
                        self.stats["slot_steps"] += 1
            for r in wave:
                r.done_at = self.clock()
            done.extend(wave)
            self.stats["served"] += len(wave)
        return done
