"""Operations and bytes the algorithm needs, computed from shapes.

Counts are of multiply-adds as 2 operations.  The dense decoder here is a
llama-like block: q, k, v, o projections and a gated (SwiGLU) MLP per layer
and a head of ``vocab x d_model``.  Embedding gathers, norms, rope and
softmax exponentials are not counted (each is under 1% of a layer's
operations at these widths).  Causal attention counts only the scores and
values a causal mask keeps: query ``t`` of a sequence attends to ``t + 1``
keys.  Operations recomputed under rematerialisation are not counted.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Dense:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> "Dense":
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"])

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    def layer_matmul_params(self) -> int:
        d = self.d_model
        return 2 * d * self.q_dim + 2 * d * self.kv_dim + 3 * d * self.d_ff

    def matmul_params(self) -> int:
        """Weights that take part in a matmul per token: every layer's
        projections and MLP, and the head (tied or not)."""
        return self.layers * self.layer_matmul_params() \
            + self.vocab * self.d_model

    def params(self, tied: bool = True) -> int:
        """All parameters: embedding, head when untied, layers with their
        two norms, and the final norm."""
        d = self.d_model
        n = self.vocab * d * (1 if tied else 2) + d
        return n + self.layers * (self.layer_matmul_params() + 2 * d)

    # ---------------------------------------------------------- attention
    def causal_attn_flops(self, seq: int) -> float:
        """Forward scores and weighted values of one causal sequence."""
        pairs = seq * (seq + 1) / 2
        return self.layers * 2 * 2 * self.q_dim * pairs

    def decode_attn_flops(self, ctx: int) -> float:
        """Forward attention of one decoded token over ``ctx`` keys."""
        return self.layers * 2 * 2 * self.q_dim * ctx

    # ---------------------------------------------------------- per step
    def train_flops(self, batch: int, seq: int) -> float:
        """Forward and backward of one training step (3x the forward)."""
        fwd = 2 * self.matmul_params() * batch * seq \
            + batch * self.causal_attn_flops(seq)
        return 3 * fwd

    def prefill_flops(self, batch: int, seq: int) -> float:
        """Forward of ``batch`` prompts of ``seq`` tokens; the head runs on
        the last position only."""
        body = 2 * self.layers * self.layer_matmul_params() * batch * seq
        head = 2 * self.vocab * self.d_model * batch
        return body + head + batch * self.causal_attn_flops(seq)

    def decode_token_flops(self, ctx: int) -> float:
        """One decoded token that attends to ``ctx`` keys."""
        return 2 * self.matmul_params() + self.decode_attn_flops(ctx)


def flash_attention_cost(batch: int, seq: int, heads: int, kv_heads: int,
                         head_dim: int, itemsize: int = 2) -> tuple:
    """(operations, bytes) of one causal self-attention call: q.k scores
    and p.v products over the causal pairs, and q, k, v read and the
    output written once."""
    ops = batch * heads * 2 * 2 * head_dim * seq * (seq + 1) / 2
    nbytes = itemsize * batch * seq * head_dim * (2 * heads + 2 * kv_heads)
    return ops, nbytes


def least_time(ops: float, nbytes: float, peak_flops: float,
               peak_bytes_per_s: float) -> tuple:
    """(seconds, bound) of the roofline: the larger of operations over the
    peak rate and bytes over the peak bandwidth."""
    t_ops, t_mem = ops / peak_flops, nbytes / peak_bytes_per_s
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
