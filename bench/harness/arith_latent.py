"""Operations and bytes that latent attention with an expert FFN needs
(DeepSeek-V2's family), counted from shapes.

As in ``harness.arith``: what the algorithm requires, not what the program
happens to execute.  Prefill takes the expanded form (K and V per head
from the latent, causal attention over the keys at or before each query,
logits at the last position only); a decode step the absorbed form (the
query folded into latent space, scores and the weighted latent against the
cache's ``rank + rope`` values a position, read up to the step's
position).  The routed experts' operations come from the pairs the program
counted (``moe_pairs``): every other count is fixed by the shapes.
"""
from __future__ import annotations

from dataclasses import dataclass

from harness.arith import BF16


@dataclass(frozen=True)
class Latent:
    """The shapes of a DeepSeek-V2-style decoder, with the experts held
    on this chip."""

    layers: int
    dense_layers: int
    d_model: int
    heads: int
    rank: int
    nope: int
    rope: int
    v_dim: int
    d_ff: int
    expert_ff: int
    shared_ff: int
    held: int
    router_experts: int
    vocab: int
    weight_bytes: int = BF16
    cache_bytes: int = BF16

    @classmethod
    def from_config(cls, c: dict) -> "Latent":
        return cls(layers=c["num_hidden_layers"],
                   dense_layers=c["first_k_dense_replace"],
                   d_model=c["hidden_size"], heads=c["num_attention_heads"],
                   rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
                   rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
                   d_ff=c["intermediate_size"],
                   expert_ff=c["moe_intermediate_size"],
                   shared_ff=c["n_shared_experts"]
                   * c["moe_intermediate_size"],
                   held=c["n_routed_experts"],
                   router_experts=c["deployment"]["routed_experts"],
                   vocab=c["vocab_size"])

    @property
    def expert_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def row(self) -> int:
        """Cache values a position and layer: the latent and the rotary
        key."""
        return self.rank + self.rope

    @property
    def attn_params(self) -> int:
        d, h = self.d_model, self.heads
        return (d * h * (self.nope + self.rope) + d * self.row + self.rank
                + self.rank * h * (self.nope + self.v_dim)
                + h * self.v_dim * d)

    @property
    def expert_params(self) -> int:
        """One held routed expert."""
        return 3 * self.d_model * self.expert_ff

    @property
    def params(self) -> int:
        """Every weight held: layers (all held experts), embedding, head,
        final norm."""
        d = self.d_model
        ffn = (self.dense_layers * 3 * d * self.d_ff
               + self.expert_layers * (self.held * self.expert_params
                                       + d * self.router_experts
                                       + 3 * d * self.shared_ff))
        return (self.layers * (self.attn_params + 2 * d) + ffn
                + 2 * self.vocab * d + d)

    def _ffn_flops(self) -> int:
        """One token through every layer's FFN but the routed experts."""
        d = self.d_model
        return 2 * (self.dense_layers * 3 * d * self.d_ff
                    + self.expert_layers * (d * self.router_experts
                                            + 3 * d * self.shared_ff))

    def routed_flops(self, pairs: float) -> float:
        """``pairs`` (token, held expert) pairs through their expert."""
        return 2 * self.expert_params * pairs

    def prefill_flops(self, batch: int, seq: int) -> int:
        """One prefill of ``batch`` prompts of ``seq`` tokens, the routed
        experts left out: the projections of every token (the expanded
        K and V among them), causal attention, logits at the last
        position."""
        d, h = self.d_model, self.heads
        proj = 2 * self.layers * (self.attn_params - self.rank)
        keys = seq * (seq + 1) // 2
        attn = 2 * self.layers * h * (self.nope + self.rope + self.v_dim)
        return batch * ((proj + self._ffn_flops()) * seq + attn * keys
                        + 2 * d * self.vocab)

    def decode_flops(self, batch: int, pos: int, pairs_per_token: float
                     ) -> float:
        """One decode step of ``batch`` sequences whose new token sits at
        position ``pos`` (it attends to ``pos + 1`` positions), absorbed:
        the query's projections and its fold into latent space, scores
        over ``rank + rope`` values and the weighted latent over ``rank``
        a position, the latent's way out, the FFNs, the logits."""
        d, h = self.d_model, self.heads
        proj = (d * h * (self.nope + self.rope) + d * self.row
                + h * self.nope * self.rank + h * self.rank * self.v_dim
                + h * self.v_dim * d)
        attn = h * (self.row + self.rank) * (pos + 1)
        return batch * (2 * self.layers * (proj + attn) + self._ffn_flops()
                        + self.routed_flops(pairs_per_token)
                        + 2 * d * self.vocab)

    def decode_bytes(self, batch: int, pos: int) -> int:
        """Least HBM traffic of one decode step: every weight but the
        embedding table once, all held experts among them; the embedding
        rows of the batch; the cache up to ``pos`` read and one position
        written; the logits written."""
        weights = (self.params - self.vocab * self.d_model) \
            * self.weight_bytes
        embed = batch * self.d_model * self.weight_bytes
        cache = batch * self.layers * self.row * (pos + 1) * self.cache_bytes
        logits = batch * self.vocab * BF16
        return weights + embed + cache + logits

    def decode_step_floor_s(self, batch: int, pos: int,
                            pairs_per_token: float, peaks: dict) -> float:
        """The least time one decode step can take on the chip."""
        return max(self.decode_flops(batch, pos, pairs_per_token)
                   / peaks["bf16_flops_per_s"],
                   self.decode_bytes(batch, pos) / peaks["hbm_bytes_per_s"])
