"""Operations and bytes a dense GQA decoder needs, from its published shapes.

These count the work the algorithm needs, whatever implements it: heads are
counted unpadded, the weights are read once per step, and the cache is read
only for the tokens that are live. So a change that removes padding or a
copy raises a share computed against them, and no implementation can beat
them. The parameter arithmetic is a copy of ``repro.configs.param_count``
for the dense GQA family (norm weights are left out there and here), kept
with the benchmark so that no later change to the program can move it.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Dims:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    bytes_per_param: int = 2  # bf16, as served and trained

    @classmethod
    def from_published(cls, hf: dict, repo_fields: dict) -> "Dims":
        """``repo_fields``, the file's values that no published key states,
        change nothing that a dense decoder counts."""
        heads = hf["num_attention_heads"]
        return cls(
            d=hf["hidden_size"],
            layers=hf["num_hidden_layers"],
            heads=heads,
            kv_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
            d_ff=hf["intermediate_size"],
            vocab=hf["vocab_size"],
            tied=bool(hf["tie_word_embeddings"]),
        )

    # -- parameters ------------------------------------------------------------

    @property
    def embed_params(self) -> int:
        return self.vocab * self.d * (1 if self.tied else 2)

    @property
    def layer_params(self) -> int:
        d, h, kv, hd = self.d, self.heads, self.kv_heads, self.head_dim
        return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * self.d_ff

    @property
    def non_embedding_params(self) -> int:
        return self.layers * self.layer_params

    @property
    def total_params(self) -> int:
        return self.embed_params + self.non_embedding_params

    @property
    def matmul_params(self) -> int:
        """Parameters a token multiplies through: every layer plus the head."""
        return self.non_embedding_params + self.vocab * self.d

    # -- per-token work --------------------------------------------------------

    def attn_flops(self, query_tokens: int, context: int) -> float:
        """QK^T and PV for ``query_tokens`` queries over ``context`` keys each."""
        return 4.0 * self.layers * self.heads * self.head_dim * query_tokens * context

    def decode_flops(self, context: int) -> float:
        """One decoded token whose query sees ``context`` keys (itself included)."""
        return 2.0 * self.matmul_params + self.attn_flops(1, context)

    def prefill_flops(self, prompt: int) -> float:
        """A causal prefill of ``prompt`` tokens with logits at its last position."""
        causal_pairs = prompt * (prompt + 1) / 2
        return (
            2.0 * self.non_embedding_params * prompt
            + 2.0 * self.vocab * self.d
            + 4.0 * self.layers * self.heads * self.head_dim * causal_pairs
        )

    def train_flops_per_token(self, seq_len: int) -> float:
        """Forward and backward of one trained token (causal attention over
        ``seq_len``); recomputed operations do not count."""
        return 6.0 * self.matmul_params + 3.0 * self.attn_flops(1, (seq_len + 1) / 2)

    # -- bytes -----------------------------------------------------------------

    @property
    def weight_bytes(self) -> int:
        """Weights one decode step has to read: every layer and the head."""
        return self.bytes_per_param * self.matmul_params

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * self.bytes_per_param
