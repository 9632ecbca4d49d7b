"""Operations and bytes that each step of a dense decoder needs, from the
configuration's published sizes alone.

These count the work a step cannot do without, not what the program does
today: the weights are read once per step, the KV cache only up to each
sequence's live length (not the padded slot pool), causal attention only
over the lower triangle, and the LM head only at the positions whose
logits are used. A later program that stops doing wasted work then reads
a higher share of its roofline, never one above 100%.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The widths a step's cost depends on, in the published config's terms."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    weight_bytes: int = 2  # bytes per served weight element (bf16)
    kv_bytes: int = 2  # bytes per cached K or V element (bf16)

    @classmethod
    def from_config(cls, hf: dict, weight_bytes: int = 2) -> Sizes:
        heads = hf["num_attention_heads"]
        return cls(
            layers=hf["num_hidden_layers"],
            d=hf["hidden_size"],
            heads=heads,
            kv_heads=hf.get("num_key_value_heads", heads),
            head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
            d_ff=hf["intermediate_size"],
            vocab=hf["vocab_size"],
            weight_bytes=weight_bytes,
        )

    # -- parameters -----------------------------------------------------
    @property
    def layer_matmul_params(self) -> int:
        """q, k, v, o projections and the gated FFN of one layer."""
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d * (q + 2 * kv) + q * self.d + 3 * self.d * self.d_ff

    @property
    def matmul_params(self) -> int:
        """Every weight a token multiplies: all layers plus the LM head
        (the embedding is a gather, not a matmul)."""
        return self.layers * self.layer_matmul_params + self.d * self.vocab

    @property
    def weight_bytes_read(self) -> int:
        """Bytes of the weights a step reads once: layer matrices, the
        head, and the norm vectors (the embedding table is gathered by row
        and counted per token)."""
        norms = self.layers * 2 * self.d + self.d
        return (self.matmul_params + norms) * self.weight_bytes

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * self.kv_bytes

    # -- attention --------------------------------------------------------
    def attn_flops(self, query_keys: int) -> int:
        """QK^T and PV over ``query_keys`` (query, key) pairs in all layers."""
        return 4 * self.layers * self.heads * self.head_dim * query_keys

    # -- steps --------------------------------------------------------------
    def prefill(self, length: int) -> tuple[int, int]:
        """(FLOPs, bytes) of prefilling one prompt of ``length`` tokens and
        producing the logits of its last position."""
        pairs = length * (length + 1) // 2  # causal: the lower triangle
        flops = (2 * self.layers * self.layer_matmul_params * length
                 + 2 * self.d * self.vocab + self.attn_flops(pairs))
        nbytes = (self.weight_bytes_read
                  + length * self.d * self.weight_bytes  # embedding rows
                  + length * self.kv_bytes_per_token  # KV written
                  + 4 * self.vocab)  # last-position logits, f32
        return flops, nbytes

    def decode(self, kv_lens) -> tuple[int, int]:
        """(FLOPs, bytes) of one decode tick for active sequences that
        attend ``kv_lens`` positions each (the new token's included)."""
        kv_lens = list(kv_lens)
        n = len(kv_lens)
        flops = 2 * self.matmul_params * n + self.attn_flops(sum(kv_lens))
        nbytes = (self.weight_bytes_read
                  + n * self.d * self.weight_bytes  # embedding rows
                  + sum(kv_lens) * self.kv_bytes_per_token  # KV read (and the new row)
                  + n * 4 * self.vocab)  # logits, f32
        return flops, nbytes

    def train_flops_per_token(self, seq: int) -> float:
        """Forward and backward FLOPs per token of next-token training on
        sequences of ``seq`` tokens: 6 per matmul weight, and causal
        attention over (seq + 1) / 2 keys on average, three times (forward,
        and the two products of its backward). Recomputation is not
        counted."""
        return (6 * self.matmul_params
                + 3 * self.attn_flops(1) * (seq + 1) / 2)


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind`` from ``peaks.json``. A device
    that is not in the table is an error, never a default."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]
