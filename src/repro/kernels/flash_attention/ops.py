"""jit'd public wrapper for flash attention with impl dispatch.

impl:
  "xla"              - chunked online-softmax in pure jnp (CPU + dry-run path)
  "ref"              - naive oracle (tests only; O(Sq*Sk) memory)
  "pallas"           - Pallas TPU kernel (real-hardware hot path)
  "pallas_interpret" - Pallas kernel body interpreted on CPU (validation)
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from repro import flags
from repro.kernels import forward_only
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flash_attention.xla import attention_xla


@partial(jax.jit, static_argnames=("causal", "window", "softcap", "scale",
                                   "q_offset", "impl", "q_chunk", "kv_chunk"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: Optional[float] = None,
                    q_offset: int = 0, seg_q=None, seg_kv=None,
                    impl: Optional[str] = None,
                    q_chunk: int = 512, kv_chunk: int = 512):
    impl = flags.attn_impl(impl)
    kw = dict(causal=causal, window=window, softcap=softcap, scale=scale,
              q_offset=q_offset, seg_q=seg_q, seg_kv=seg_kv)
    if impl == "ref":
        return attention_ref(q, k, v, **kw)
    if impl == "xla":
        return attention_xla(q, k, v, q_chunk=q_chunk, kv_chunk=kv_chunk, **kw)
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels.flash_attention.pallas_kernel import flash_attention_pallas
        return forward_only("flash_attention", partial(
            flash_attention_pallas, interpret=(impl == "pallas_interpret"),
            block_q=q_chunk, block_kv=kv_chunk, **kw), q, k, v)
    raise ValueError(f"unknown attention impl {impl!r}")
