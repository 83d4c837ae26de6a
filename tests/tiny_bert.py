"""The tiny bert-10b stand-in the chip-smoke tests run on the CPU."""

import dataclasses

from repro.configs import get_config, smoke_variant

# chip_smoke's phase sizes, cut to CPU scale
TINY = dict(steps=8, global_batch=8, seq=64, micro_steps=2)


def tiny_bert():
    """bert-10b's family at d_model 64.  The vocab stays large enough that a
    random-init loss sits within chip_smoke's 10% of ln(vocab) (about
    ln(vocab) + 1/2 for unit-variance logits)."""
    return dataclasses.replace(smoke_variant(get_config("bert-10b")),
                               vocab=4096)
