"""Pallas flash attention (GQA-aware) + pure-jnp reference."""

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ops import (
    causal_attention, flash_attention_gqa)
from repro.kernels.flash_attention.ref import attention_ref

__all__ = ["flash_attention", "flash_attention_gqa", "causal_attention",
           "attention_ref"]
