"""Architecture + shape configuration system.

Every assigned architecture is a ``ModelConfig`` in ``repro.configs.<id>``;
``--arch <id>`` resolves through ``repro.configs.get_config``. Each config
also provides a reduced ``smoke()`` variant of the same family for real
CPU execution in tests; the full configs are exercised via the dry-run only.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

__all__ = ["ModelConfig", "ShapeSpec", "SHAPES", "Yarn", "shape_for"]


@dataclass(frozen=True)
class Yarn:
    """YaRN rotary scaling (arXiv:2309.00071), in DeepSeek-V2's form:
    frequencies blended between interpolated and original over a ramp of
    dimensions, and a softmax scale raised by ``mscale_all_dim``."""

    factor: float
    original_max_pos: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class ModelConfig:
    # identity
    name: str
    family: str                 # dense | moe | mla | ssm | hybrid | audio | vlm
    source: str = ""            # provenance tag from the assignment table

    # transformer backbone
    n_layers: int = 0
    d_model: int = 0
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0           # 0 -> d_model // n_heads
    d_ff: int = 0
    vocab_size: int = 0
    qk_norm: bool = False
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    logit_softcap: float = 0.0          # gemma-style; 0 = off
    embed_scale: bool = False           # gemma multiplies embeds by sqrt(d)

    # MoE
    moe_experts: int = 0                # routed experts the router scores
    moe_top_k: int = 0
    moe_capacity_factor: float = 1.25   # sharded experts, moe_ep slots
    moe_d_ff: int = 0                   # expert width; 0 -> d_ff
    moe_shared_d_ff: int = 0            # shared experts as one SwiGLU; 0 = none
    moe_held: int = 0                   # experts held here; 0 -> all
    moe_held_first: int = 0             # id of the first expert held here
    moe_norm_topk: bool = True          # renormalize the top-k weights
    dense_layers: int = 0               # leading layers with a dense FFN

    # latent attention (mla): q and a latent kv, no query LoRA
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    yarn: Yarn | None = None

    # SSM / RWKV
    ssm_state: int = 0                  # mamba2 N
    ssm_head_dim: int = 64              # mamba2 P / rwkv head size
    ssm_expand: int = 2                 # mamba2 d_inner = expand * d_model
    ssm_conv_width: int = 4
    rwkv_decay_lora: int = 64

    # hybrid (zamba2)
    shared_attn_every: int = 6          # apply the shared block every N layers

    # audio (musicgen)
    n_codebooks: int = 0

    # vlm (paligemma)
    vision_embed_dim: int = 0           # SigLIP output width (stub frontend)
    n_patches: int = 0
    prefix_lm: bool = False

    # numerics / training
    dtype: str = "bfloat16"
    subquadratic: bool = False          # can run long_500k

    # ---------------------------------------------------------------- util
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def expert_range(self) -> tuple[int, int]:
        """``(first, count)`` of the routed experts held here."""
        return self.moe_held_first, self.moe_held or self.moe_experts

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def smoke(self) -> "ModelConfig":
        """Reduced same-family config: runs one real step on CPU."""
        kw = dict(
            name=self.name + "-smoke",
            n_layers=2,
            d_model=64,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 4) or 0,
            head_dim=16,
            d_ff=128,
            vocab_size=256,
        )
        if self.family == "moe":
            kw.update(moe_experts=4, moe_top_k=2)
        if self.family == "mla":
            kw.update(n_layers=3, n_kv_heads=4, d_ff=96, moe_experts=8,
                      moe_top_k=3, moe_d_ff=32, moe_shared_d_ff=64,
                      moe_held=min(self.moe_held, 4), kv_lora_rank=32,
                      qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16)
        if self.family in ("ssm", "hybrid"):
            kw.update(ssm_state=8, ssm_head_dim=8, rwkv_decay_lora=8)
        if self.family == "hybrid":
            kw.update(shared_attn_every=2)
        if self.family == "audio":
            kw.update(n_codebooks=self.n_codebooks, vocab_size=64)
        if self.family == "vlm":
            kw.update(vision_embed_dim=24, n_patches=8, head_dim=16)
        return self.replace(**kw)

    # parameter count (for MODEL_FLOPS = 6 N D)
    def param_count(self, active_only: bool = False) -> int:
        d, ff, v, L = self.d_model, self.d_ff, self.vocab_size, self.n_layers
        hd = self.resolved_head_dim
        nq, nkv = self.n_heads, self.n_kv_heads
        emb = v * d * (1 if self.tie_embeddings else 2)
        if self.family == "audio":
            emb = self.n_codebooks * v * d * 2
        if self.family == "vlm":
            emb += self.vision_embed_dim * d
        attn = d * nq * hd + 2 * d * nkv * hd + nq * hd * d
        mlp = 3 * d * ff
        if self.family == "moe":
            e = self.moe_top_k if active_only else self.moe_experts
            mlp = 3 * d * ff * e + d * self.moe_experts  # experts + router
        if self.family == "mla":
            h, r = self.n_heads, self.kv_lora_rank
            attn = (d * h * (self.qk_nope_dim + self.qk_rope_dim)
                    + d * (r + self.qk_rope_dim) + r
                    + r * h * (self.qk_nope_dim + self.v_head_dim)
                    + h * self.v_head_dim * d + 2 * d)
            e = self.moe_top_k if active_only else self.expert_range[1]
            moe = (3 * d * self.moe_d_ff * e + d * self.moe_experts
                   + 3 * d * self.moe_shared_d_ff)
            dl = self.dense_layers
            return (emb + d + L * attn + dl * 3 * d * ff
                    + (L - dl) * moe)
        if self.family == "ssm":                          # rwkv6
            att_like = 4 * d * d + 2 * d * self.rwkv_decay_lora * 2
            mlp_like = 2 * d * ff
            return emb + L * (att_like + mlp_like)
        if self.family == "hybrid":                       # zamba2
            d_in = self.ssm_expand * d
            mamba = d * (2 * d_in + 2 * self.ssm_state) + d_in * d
            shared = (2 * d) * d + attn + mlp             # projector + block
            return emb + L * mamba + shared
        per_layer = attn + mlp
        if self.family == "hybrid":
            per_layer = mlp
        return emb + L * per_layer


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str                   # train | prefill | decode

    @property
    def tokens(self) -> int:
        if self.kind == "decode":
            return self.global_batch      # one new token per sequence
        return self.global_batch * self.seq_len


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


def shape_for(name: str) -> ShapeSpec:
    try:
        return SHAPES[name]
    except KeyError as e:
        raise KeyError(f"unknown shape '{name}'; known: {sorted(SHAPES)}") from e
