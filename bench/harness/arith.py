"""Operations and bytes that the work needs, counted from shapes.

These are what the algorithm requires, not what the program happens to
execute: causal attention counts the keys at or before each query, a
decode step reads the cache up to its position, prefill computes logits at
the last position only.  A roofline or ``mfu`` share divides them by a time
from the device trace and a peak from ``peaks.json``.
"""
from __future__ import annotations

from dataclasses import dataclass

BF16 = 2
F32 = 4


@dataclass(frozen=True)
class Dense:
    """The shapes of a dense GQA decoder (internlm2's family)."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    weight_bytes: int = BF16
    cache_bytes: int = BF16

    @classmethod
    def from_config(cls, c: dict) -> "Dense":
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], d_ff=c["intermediate_size"],
                   vocab=c["vocab_size"])

    @property
    def layer_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * self.heads * hd * 2 + d * self.kv_heads * hd * 2
        return attn + 3 * d * self.d_ff + 2 * d

    @property
    def params(self) -> int:
        """Every weight: layers, embedding, head, final norm."""
        return (self.layers * self.layer_params
                + 2 * self.vocab * self.d_model + self.d_model)

    def _attn_flops(self, keys: int) -> int:
        """Scores and weighted values of one query over ``keys`` keys."""
        return 4 * self.layers * self.heads * self.head_dim * keys

    def prefill_flops(self, batch: int, seq: int) -> int:
        """One prefill of ``batch`` prompts of ``seq`` tokens: the layers'
        matmuls for every token, causal attention, logits at the last
        position."""
        mm = 2 * self.layers * (self.layer_params - 2 * self.d_model)
        causal_keys = seq * (seq + 1) // 2
        return batch * (mm * seq + self._attn_flops(causal_keys)
                        + 2 * self.d_model * self.vocab)

    def decode_flops(self, batch: int, pos: int) -> int:
        """One decode step of ``batch`` sequences whose new token sits at
        position ``pos`` (it attends to ``pos + 1`` keys)."""
        mm = 2 * self.layers * (self.layer_params - 2 * self.d_model)
        return batch * (mm + self._attn_flops(pos + 1)
                        + 2 * self.d_model * self.vocab)

    def decode_bytes(self, batch: int, pos: int) -> int:
        """Least HBM traffic of one decode step: every weight but the
        embedding table once, the embedding rows of the batch, the cache up
        to ``pos`` read and one position written, the logits written."""
        weights = (self.layers * self.layer_params + self.d_model * self.vocab
                   + self.d_model) * self.weight_bytes
        embed = batch * self.d_model * self.weight_bytes
        per_pos = self.layers * 2 * self.kv_heads * self.head_dim
        cache = batch * per_pos * (pos + 2) * self.cache_bytes
        logits = batch * self.vocab * BF16
        return weights + embed + cache + logits


def decode_step_floor_s(m: Dense, batch: int, pos: int, peaks: dict) -> float:
    """The least time one decode step can take on the chip:
    ``max(flops / peak, bytes / bandwidth)``."""
    return max(m.decode_flops(batch, pos) / peaks["bf16_flops_per_s"],
               m.decode_bytes(batch, pos) / peaks["hbm_bytes_per_s"])


def stream_bytes(kind: str, shape: tuple[int, ...], itemsize: int) -> int:
    """HBM bytes one stream launch needs: the array read, and for the
    ``write`` (copy) kernel written again."""
    n = itemsize
    for s in shape:
        n *= int(s)
    return n * (2 if kind == "write" else 1)
