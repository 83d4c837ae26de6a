"""Names of the training step's device work, one per layer.

Each name is a ``jax.named_scope`` around the code that does that work.  It
reaches the compiled HLO's ``metadata={op_name=...}``, and through the
instruction names every operation of a profiler trace.  Where scopes nest
(a gather inside the layer scan, hop 2 inside the optimizer), the innermost
one names the operation; a backward operation carries the scope of the
forward code it transposes (``transpose(jvp(model.mlp))``).

A scope changes nothing but debug locations: the step's lowered module with
debug info stripped is the same with and without them, and so is the
persistent compilation cache's default key (``compile_cache``).
"""

GATHER = "mics.gather"          # core/comm: wire cast and all-gather stages
HOP1 = "mics.hop1"              # core/comm: the gather's adjoint (hop 1)
HOP2 = "mics.hop2"              # core/comm: replica all-reduce (hop 2)
CARRY = "mics.carry"            # models/lm: the layer scan and its residuals
GRAD_ACCUM = "mics.grad_accum"  # core/mics: fp32 sum over micro-steps
OPTIMIZER = "mics.optimizer"    # core/schedule: norm, clip, AdamW
ATTENTION = "model.attention"   # models/blocks: QKV to the out projection
MLP = "model.mlp"               # models/blocks: dense MLP and experts
EMBED = "model.embed"           # models/lm: token and position lookup
HEAD = "model.head"             # models/lm: final norm, logits and the loss

ALL = (GATHER, HOP1, HOP2, CARRY, GRAD_ACCUM, OPTIMIZER, ATTENTION, MLP,
       EMBED, HEAD)

# Inside MLP, in an expert layer: a reader of ALL still finds MLP for them.
MOE_ROUTE = "moe.route"         # router, top-k, expert order and back, combine
MOE_EXPERTS = "moe.experts"     # the held experts' grouped products
MOE = (MOE_ROUTE, MOE_EXPERTS)
