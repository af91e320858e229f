"""Operations and bytes of the Moonlight-16B-A3B serving cell, from shapes
and the engine's expert counters.

Multiply-adds count 2 operations, as in ``lib/flops.py``.  Per token every
layer's latent attention projections (``wq``, ``wkv_a``, ``wkv_b``, ``wo``:
the absorbed decode multiplies by ``wkv_b``'s two halves once per token
too), the dense layer's SwiGLU, each expert layer's router and shared
experts, and the head.  The held experts count per row they computed
(``expert_rows`` of the engine's counters): a SwiGLU of width
``moe_intermediate_size`` a row.  Attention: prefill expands the latent,
so its causal scores run over the q/k width (nope + rope) and its values
over ``v_head_dim``; decode is absorbed, so a token's scores and weighted
sum run over the cache's latent row (rank + rope lanes, and rank lanes of
values) for each head.  Norms, rope, softmax, routing's top-k and the
embedding gather are not counted.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class MlaMoe:
    layers: int
    dense_layers: int
    d_model: int
    heads: int
    rank: int
    nope: int
    rope: int
    v: int
    d_ff: int
    expert_ff: int
    experts: int
    shared_ff: int
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> "MlaMoe":
        ep = c["expert_parallel"]
        return cls(layers=c["num_hidden_layers"],
                   dense_layers=c["first_k_dense_replace"],
                   d_model=c["hidden_size"], heads=c["num_attention_heads"],
                   rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
                   rope=c["qk_rope_head_dim"], v=c["v_head_dim"],
                   d_ff=c["intermediate_size"],
                   expert_ff=c["moe_intermediate_size"],
                   experts=c["n_routed_experts"] * ep["ranks"],
                   shared_ff=c["n_shared_experts"]
                   * c["moe_intermediate_size"],
                   vocab=c["vocab_size"])

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    def attn_params(self) -> int:
        d, H = self.d_model, self.heads
        return (d * H * (self.nope + self.rope) + d * (self.rank + self.rope)
                + self.rank * H * (self.nope + self.v) + H * self.v * d)

    def token_matmul_params(self) -> int:
        """Weights a token multiplies by, the held experts left out."""
        d = self.d_model
        return (self.layers * self.attn_params()
                + self.dense_layers * 3 * d * self.d_ff
                + self.moe_layers * (d * self.experts
                                     + 3 * d * self.shared_ff)
                + self.vocab * d)

    def expert_row_flops(self) -> float:
        return 2 * 3 * self.d_model * self.expert_ff

    # ---------------------------------------------------------- attention
    def prefill_attn_flops(self, seq: int) -> float:
        pairs = seq * (seq + 1) / 2
        return self.layers * self.heads * 2 * (self.nope + self.rope
                                               + self.v) * pairs

    def decode_attn_flops(self, ctx: int) -> float:
        return self.layers * self.heads * 2 * (2 * self.rank
                                               + self.rope) * ctx

    # ---------------------------------------------------------- requests
    def request_flops(self, prompt: int, new: int,
                      rows_per_token: float) -> float:
        """One request: its prompt's prefill (head on the last position),
        the token that chooses, and ``new`` decoded tokens attending to
        the cache; the held experts at ``rows_per_token`` rows a token
        (over all expert layers)."""
        body = 2 * (self.token_matmul_params() - self.vocab * self.d_model)
        head = 2 * self.vocab * self.d_model
        experts = rows_per_token * self.expert_row_flops()
        prefill = prompt * (body + experts) + head \
            + self.prefill_attn_flops(prompt)
        decode = sum(body + head + experts
                     + self.decode_attn_flops(prompt + t + 1)
                     for t in range(new))
        return prefill + decode


def mla_attention_cost(batch: int, seq: int, heads: int, qk_dim: int,
                       v_dim: int, itemsize: int = 2) -> tuple:
    """(operations, bytes) of one causal prefill attention call over
    expanded heads: scores over ``qk_dim`` and weighted values over
    ``v_dim`` for the causal pairs; q, k and v read and the output
    written once."""
    ops = batch * heads * 2 * (qk_dim + v_dim) * seq * (seq + 1) / 2
    nbytes = itemsize * batch * seq * heads * (2 * qk_dim + 2 * v_dim)
    return ops, nbytes


def expert_gmm_cost(rows: float, experts_hit: float, d_model: int,
                    expert_ff: int, itemsize: int = 2) -> tuple:
    """(operations, bytes) of the grouped expert matmuls (gate, up, down)
    for ``rows`` routed rows over ``experts_hit`` experts with a row: each
    such expert's three weights read once, each row read in and written
    out once."""
    ops = 2 * 3 * d_model * expert_ff * rows
    nbytes = itemsize * (experts_hit * 3 * d_model * expert_ff
                         + 2 * rows * d_model)
    return ops, nbytes
