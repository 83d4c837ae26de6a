"""Architecture configuration schema.

One ``ArchConfig`` per assigned architecture (exact public-literature
configuration) plus a ``smoke()`` reduction of the same family for CPU tests.
"""

from __future__ import annotations

import dataclasses
from typing import Literal


Family = Literal["dense", "vlm", "encdec", "griffin", "xlstm", "moe"]


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: Family
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0            # 0 -> d_model // n_heads
    qkv_bias: bool = False
    mlp: str = "swiglu"          # swiglu | geglu | gelu
    norm: str = "rms"            # rms | ln
    rope_theta: float = 500_000.0
    use_rope: bool = True
    tie_embeddings: bool = False

    # --- MoE ---------------------------------------------------------------
    n_experts: int = 0           # routed experts the router scores
    experts_held: int = 0        # of them, experts 0..held-1 live here (0 = all)
    n_shared_experts: int = 0
    top_k: int = 0
    norm_topk_prob: bool = True  # renormalise the top-k gates to sum to 1
    router_aux_weight: float = 0.01
    first_dense_layers: int = 0  # leading dense layers (first_k_dense_replace)
    dense_d_ff: int = 0          # their MLP width (0 -> d_ff)

    # --- latent attention (MLA; kv_lora_rank 0 = standard attention) --------
    kv_lora_rank: int = 0        # width of the joint k/v latent
    qk_nope_head_dim: int = 0    # per-head query/key width without rotation
    qk_rope_head_dim: int = 0    # rotated width, the key part shared by heads
    v_head_dim: int = 0

    # --- YaRN rope scaling (yarn_factor 0 = plain rope) ----------------------
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # --- griffin / local attention ------------------------------------------
    window: int = 0              # local-attention window (0 = full)
    pattern: tuple[str, ...] = ()  # block pattern, e.g. ("rec","rec","attn")
    lru_width: int = 0           # RG-LRU channel count (0 -> d_model)
    conv_width: int = 4

    # --- vlm ------------------------------------------------------------------
    cross_interval: int = 0      # 1 cross-attn layer after every N self layers
    n_vision_tokens: int = 1024  # stub frontend output length

    # --- encdec -----------------------------------------------------------------
    n_encoder_layers: int = 0
    n_audio_frames: int = 1500   # stub conv frontend output length

    # --- xlstm -------------------------------------------------------------------
    slstm_every: int = 0         # one sLSTM block per this many layers
    expand: float = 2.0          # mLSTM up-projection factor

    # --- serving / shapes ----------------------------------------------------
    max_seq: int = 32768
    sub_quadratic: bool = False  # eligible for long_500k

    # --- distribution hints ---------------------------------------------------
    notes: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def held_experts(self) -> int:
        """Routed experts whose weights this model holds (the first ones)."""
        return self.experts_held or self.n_experts

    @property
    def moe_layers(self) -> int:
        """Layers with routed experts: all but the leading dense ones."""
        return self.n_layers - self.first_dense_layers if self.n_experts else 0

    def param_count(self) -> int:
        """Approximate total parameter count (for partition-size heuristic and
        MODEL_FLOPS).  Computed from the layout builders, so exact: see
        models/build.py:param_count which sums the real layouts; this is the
        quick analytic version used before layouts exist."""
        from repro.models.build import exact_param_count

        return exact_param_count(self)


def smoke_variant(cfg: ArchConfig) -> ArchConfig:
    """Reduced same-family config for CPU smoke tests."""
    kw: dict = dict(
        n_layers=min(cfg.n_layers, 4),
        d_model=64,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2) if cfg.n_kv_heads < cfg.n_heads else 4,
        d_ff=128,
        vocab=256,
        head_dim=16,
        max_seq=128,
    )
    if cfg.family == "moe":
        # half of the 8 experts held, as a chip holds its share of them
        kw.update(n_experts=8, experts_held=4, top_k=2, d_ff=32,
                  first_dense_layers=min(cfg.first_dense_layers, 1),
                  dense_d_ff=128 if cfg.dense_d_ff else 0)
    if cfg.kv_lora_rank:
        # the published MLA widths' ratios (512 : 128 : 64 : 128), at 1/16
        kw.update(kv_lora_rank=32, qk_nope_head_dim=8, qk_rope_head_dim=4,
                  v_head_dim=8, head_dim=12)
    if cfg.family == "griffin":
        kw.update(window=32, lru_width=64, n_layers=min(cfg.n_layers, 6))
    if cfg.family == "xlstm":
        kw.update(n_layers=4, n_heads=2, n_kv_heads=2)
    if cfg.family == "vlm":
        kw.update(n_layers=5, n_vision_tokens=16)
    if cfg.family == "encdec":
        kw.update(n_encoder_layers=2, n_layers=2, n_audio_frames=16)
    return dataclasses.replace(cfg, name=cfg.name + "-smoke", **kw)
