"""Model assembly: pools of stacked layers + embedding/head, with all
parameter-gather collectives routed through the MiCS ``CommEngine``.

A ``Pool`` is a stack of identical superblocks whose parameters live in one
flat buffer per layer (``[stack, tp, flat_len]`` globally).  The forward pass
scans over the stack; each layer's flat shard is gathered across the
partition group (one collective per layer — the paper's coalesced gather),
unflattened, and applied under ``jax.checkpoint`` so the backward pass
re-gathers (ZeRO-3 semantics + activation checkpointing).

Two schedules exist (``CommEngine.prefetch`` selects):

* **serial** — gather layer i, compute layer i (the seed behaviour; every
  gather blocks compute).
* **double-buffered prefetch** — the scan carries layer i's gathered flat
  buffer while its body *issues layer i+1's all-gather before running layer
  i's compute*.  The gather has no data dependency on the current layer's
  math, so XLA's scheduler can overlap it with the matmuls — the ZeRO-3
  style prefetch MiCS assumes.  Loss is bitwise identical to the serial
  schedule (same gathers, same compute, same order of adds).

A pool's route (:func:`pool_route`) follows from what it can observe:

* ``'remat'``, every training pool of the prefetch schedule — the whole
  pool scan runs under a custom VJP (:func:`_apply_pool_prefetch_remat`):
  the forward is the *identical* double-buffered scan (bitwise-equal
  losses and gradients), but only the layer-input activations and the
  parameter shards are kept; the backward re-issues each layer's
  all-gather (through the same CommEngine gather and its exact adjoint)
  and re-linearizes the layer on the fly.  Costs one extra all-gather per
  layer per micro-step and only O(layers x shard) HBM.
* ``'stored'`` (:func:`_apply_pool_prefetch`) — the carried gathered
  buffer becomes a per-layer scan residual, so the backward never
  re-gathers; costs O(layers x flat_len) HBM per scanned pool
  (DESIGN.md §4), and the time to write that residual, read it back and
  carry its cotangent through the transposed scan.  Only the pools the
  remat VJP cannot run take it: serving pools (caches, no backward) and
  enc-dec decoder pools (their encoder output carries gradient that a
  custom VJP closure would drop).
* ``'serial'`` — no prefetch, or a one-layer pool.

Why training re-gathers rather than keeping the stored residual: on a
TPU v5e the residual's data movement costs more than the re-gather it
spares.  For bert-10b at 4 layers (hidden 2560, 8 x 512 tokens a chip,
two micro-steps) the remat step trains 14310 tokens/s a chip against
10906 stored on one chip (+31%), where a re-gather is a local bf16 cast
(8 of them, 12.8 ms a step), and 11972 against 9993 on a repl=2 x
shard=2 mesh (+20%), where the backward's re-gathers add about 40 ms a
step (PERF.md §5, §6).  The stored residual also always takes more HBM
(core/memplan.py), so no memory budget needs it either.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import scopes
from repro.configs.base import ArchConfig
from repro.core.flat_param import FlatLayout
from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class Pool:
    name: str
    layout: FlatLayout
    stack: int
    # apply(tensors, x, ctx, cache) -> ((x, aux), new_cache)
    apply: Callable
    # make_cache(batch, cache_len) -> cache pytree for ONE stacked row
    make_cache: Callable | None = None
    # shape of a layer's aux: () or, in an expert layer, (2,) -- its balance
    # loss and its experts' load max over mean (blocks.moe_routed)
    aux_shape: tuple[int, ...] = ()


def _aux0(pool: Pool):
    """The zero a pool's scan starts its summed aux from."""
    return np.zeros(pool.aux_shape, np.float32)


@dataclasses.dataclass(frozen=True)
class ModelDef:
    cfg: ArchConfig
    tp: int
    pools: tuple[Pool, ...]
    embed: Pool
    head: Pool
    vocab_padded: int

    def pool(self, name: str) -> Pool:
        for p in (*self.pools, self.embed, self.head):
            if p.name == name:
                return p
        raise KeyError(name)

    def all_pools(self) -> tuple[Pool, ...]:
        return (self.embed, *self.pools, self.head)

    def global_flat_shapes(self) -> dict[str, tuple[int, int, int]]:
        return {
            p.name: (p.stack, self.tp, p.layout.flat_len) for p in self.all_pools()
        }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _row(x, idx=(0,)):
    """Index every leaf (flat pools may be {'q','s'} dicts when quantized)."""
    return jax.tree.map(lambda a: a[idx], x)


def pool_route(stack: int, comm, *, serving: bool = False,
               enc_out: bool = False) -> str:
    """The schedule :func:`_apply_pool` runs a pool of ``stack`` layers
    on: ``'serial'``, ``'stored'`` or ``'remat'`` (the module docstring
    says what each keeps for the backward).

    ``comm`` is the CommEngine or its GatherPolicy (``prefetch``);
    ``serving`` says the pool scans its caches (no backward), ``enc_out``
    that it reads the encoder output (gradient a custom VJP closure would
    drop).  Both keep the stored carry.
    """
    if not (getattr(comm, "prefetch", False) and stack > 1):
        return "serial"
    if serving or enc_out:
        return "stored"
    return "remat"


def is_encoder_pool(cfg, name: str) -> bool:
    """An enc-dec model's encoder pool: :func:`forward` runs it first, and
    its output is every other pool's ``ctx.enc_out``."""
    return getattr(cfg, "family", None) == "encdec" and name.startswith("enc")


def train_route(cfg, name: str, stack: int, comm) -> str:
    """The route of pool ``name`` in a training step: what :func:`forward`
    hands :func:`pool_route` (no caches; an enc-dec model's non-encoder
    pools read the encoder output)."""
    enc_out = (getattr(cfg, "family", None) == "encdec"
               and not is_encoder_pool(cfg, name))
    return pool_route(stack, comm, enc_out=enc_out)


def train_routes(model: ModelDef, comm) -> dict[str, str]:
    """:func:`train_route` of each scanned pool of ``model``."""
    return {pool.name: train_route(model.cfg, pool.name, pool.stack, comm)
            for pool in model.pools}


def attention_paths(model: ModelDef, seq: int) -> collections.Counter:
    """How many of a training forward's attention calls take each path of
    :func:`layers.attention` (``'kernel'``, ``'jnp'``; its
    ``attention_path``), over every layer at ``seq`` tokens.  Each pool's
    layer is traced once, abstractly (``jax.eval_shape``, one row, over an
    abstract mesh of the model axis), and counted ``stack`` times."""
    cfg = model.cfg
    bf16 = jnp.bfloat16
    paths = collections.Counter()
    for pool in model.pools:
        encoder = is_encoder_pool(cfg, pool.name)
        x = jax.ShapeDtypeStruct(
            (1, cfg.n_audio_frames if encoder else seq, cfg.d_model), bf16)
        src = {}
        if cfg.family == "vlm":
            src["vision"] = (1, cfg.n_vision_tokens, cfg.d_model)
        if cfg.family == "encdec" and not encoder:
            src["enc_out"] = (1, cfg.n_audio_frames, cfg.d_model)
        src = {k: jax.ShapeDtypeStruct(s, bf16) for k, s in src.items()}
        tensors = {}
        for s in pool.layout.segments:
            shape = list(s.shape)
            shape[s.model_gather_dim] *= s.model_gather
            tensors[s.name] = jax.ShapeDtypeStruct(tuple(shape), bf16)
        ctx = L.Ctx(mode="train", tp=model.tp)

        def layer(tensors, x, src):
            return pool.apply(tensors, x, dataclasses.replace(ctx, **src), None)

        mesh = jax.sharding.AbstractMesh((model.tp,), (ctx.tp_axis,))
        with L.count_attention_paths() as layer_paths:
            jax.eval_shape(jax.shard_map(
                layer, mesh=mesh, in_specs=P(), out_specs=P(),
                check_vma=False), tensors, x, src)
        for path, n in layer_paths.items():
            paths[path] += n * pool.stack
    return paths


def _apply_pool(
    pool: Pool, flat_rows, x: jax.Array, ctx: L.Ctx,
    comm, caches=None,
):
    """Scan a pool over its stack.  flat_rows: [stack, 1, S_local] leaves.

    ``comm`` is the CommEngine owning every gather collective;
    :func:`pool_route` picks the schedule from its policy.
    """
    route = pool_route(pool.stack, comm, serving=caches is not None,
                       enc_out=ctx.enc_out is not None)
    if route == "remat":
        return _apply_pool_prefetch_remat(pool, flat_rows, x, ctx, comm)
    if route == "stored":
        return _apply_pool_prefetch(pool, flat_rows, x, ctx, comm, caches)
    return _apply_pool_serial(pool, flat_rows, x, ctx, comm, caches)


def _apply_pool_serial(pool, flat_rows, x, ctx, comm, caches):
    """Reference schedule: gather layer i, then compute layer i."""

    def inner(x, row, cache):
        tensors = comm.gather(pool, _row(row), seed=ctx.step_seed)
        (x, aux), new_cache = pool.apply(tensors, x, ctx, cache)
        return x, aux, new_cache

    inner = jax.checkpoint(inner)

    if caches is None:

        def body(carry, row):
            x, aux_tot = carry
            x, aux, _ = inner(x, row, None)
            return (x, aux_tot + aux), None

        with jax.named_scope(scopes.CARRY):
            (x, aux), _ = lax.scan(body, (x, _aux0(pool)), flat_rows)
        return x, aux, None

    def body(carry, xs):
        x, aux_tot = carry
        row, cache = xs
        x, aux, new_cache = inner(x, row, cache)
        return (x, aux_tot + aux), new_cache

    with jax.named_scope(scopes.CARRY):
        (x, aux), new_caches = lax.scan(
            body, (x, _aux0(pool)), (flat_rows, caches))
    return x, aux, new_caches


def _apply_pool_prefetch(pool, flat_rows, x, ctx, comm, caches):
    """Double-buffered schedule: the carry holds layer i's gathered flat
    buffer; the body issues layer i+1's all-gather *before* layer i's
    compute, so the collective overlaps the matmuls.  The scanned inputs are
    the rows rotated one slot left (iteration i sees row i+1); the prologue
    gathers row 0.  The final iteration's wrap-around gather of row 0 is the
    one redundant collective of the schedule (its result is discarded).

    Bitwise equivalence to the serial schedule: the same gather policy runs
    on the same shards, unflatten/compute run in the same order, and the
    aux accumulation order is unchanged.  ``jax.checkpoint`` wraps the body,
    so the backward pass recomputes unflatten+compute from the carried
    buffer (and the lookahead gather) instead of storing activations.
    """
    with jax.named_scope(scopes.CARRY):
        nxt_rows = jax.tree.map(lambda a: jnp.roll(a, -1, axis=0), flat_rows)
    cur0 = comm.gather_flat(_row(flat_rows, (0, 0)), seed=ctx.step_seed)

    def inner(x, cur_full, nxt_row, cache):
        nxt_full = comm.gather_flat(
            _row(nxt_row), seed=ctx.step_seed)      # layer i+1, issued first
        tensors = comm.unflatten(pool, cur_full)     # layer i, from the carry
        (x, aux), new_cache = pool.apply(tensors, x, ctx, cache)
        return x, aux, nxt_full, new_cache

    inner = jax.checkpoint(inner)

    if caches is None:

        def body(carry, nxt_row):
            x, aux_tot, cur = carry
            x, aux, nxt, _ = inner(x, cur, nxt_row, None)
            return (x, aux_tot + aux, nxt), None

        with jax.named_scope(scopes.CARRY):
            (x, aux, _), _ = lax.scan(
                body, (x, _aux0(pool), cur0), nxt_rows)
        return x, aux, None

    def body(carry, xs):
        x, aux_tot, cur = carry
        nxt_row, cache = xs
        x, aux, nxt, new_cache = inner(x, cur, nxt_row, cache)
        return (x, aux_tot + aux, nxt), new_cache

    with jax.named_scope(scopes.CARRY):
        (x, aux, _), new_caches = lax.scan(
            body, (x, _aux0(pool), cur0), (nxt_rows, caches))
    return x, aux, new_caches


def _apply_pool_prefetch_remat(pool, flat_rows, x, ctx, comm):
    """Double-buffered prefetch with a rematerialized backward residual
    (the ``'remat'`` route of :func:`pool_route`).

    The forward is the *same* double-buffered scan as
    :func:`_apply_pool_prefetch` — same gathers on the same shards in the
    same order, so losses are bitwise identical to the stored schedule.
    The difference is what survives for the backward pass: the whole scan
    runs under a ``jax.custom_vjp`` whose residuals are only the parameter
    shards (``flat_rows``, which already live in HBM — O(layers x shard))
    and the stacked per-layer input activations (the activation checkpoint
    any schedule keeps).  The carried gathered buffer is *not* a residual.
    The backward is a hand-rolled reverse scan that re-issues each layer's
    all-gather (``comm.gather_flat`` — the CommEngine's custom-VJP gather,
    so the row cotangent is still the exact staged hop-1 reduce-scatter)
    and linearizes the layer on the fly, exactly what ``jax.checkpoint``
    would recompute, minus the stored carry.  Cost: one extra all-gather
    per layer per micro-step (the re-gather); saving: the O(layers x
    flat_len) carry residual (DESIGN.md §4, core/memplan.py).

    Cache-carrying (serving) and encoder-output-consuming pools never take
    this path (:func:`pool_route` sends them to the stored carry): serving
    has no backward, and ``ctx.enc_out`` carries gradient that a custom VJP
    closure would silently drop.
    """
    seed = ctx.step_seed

    @jax.checkpoint
    def layer(row, x_in):
        """One layer from its shard: gather -> unflatten -> apply.

        Checkpointed so its VJP is the same recompute-then-transpose the
        stored schedule's ``jax.checkpoint(inner)`` runs — gradients stay
        bitwise identical between the two carries, not just losses.
        """
        full = comm.gather_flat(_row(row), seed=seed)
        tensors = comm.unflatten(pool, full)
        (x_out, aux), _ = pool.apply(tensors, x_in, ctx, None)
        return x_out, aux

    def fwd_scan(x, flat_rows):
        """The double-buffered forward; also stacks per-layer inputs.
        Row i+1 is read from the stack in place (the last layer's
        lookahead wraps to row 0, as the stored schedule's roll does): a
        rolled copy of the stack would hold it twice, and in the wire dtype
        once more (1.17 GiB of bert-10b-l4's step compiled for a v5e)."""
        cur0 = comm.gather_flat(_row(flat_rows, (0, 0)), seed=seed)

        def body(carry, i):
            xc, aux_tot, cur = carry
            nxt_row = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(
                    a, (i + 1) % pool.stack, keepdims=False), flat_rows)
            nxt = comm.gather_flat(_row(nxt_row), seed=seed)  # layer i+1
            tensors = comm.unflatten(pool, cur)
            (x_out, aux), _ = pool.apply(tensors, xc, ctx, None)
            return (x_out, aux_tot + aux, nxt), xc            # stash input

        with jax.named_scope(scopes.CARRY):
            (x_out, aux, _), x_ins = lax.scan(
                body, (x, _aux0(pool), cur0), jnp.arange(pool.stack))
        return (x_out, aux), x_ins

    @jax.custom_vjp
    def scan_fn(x, flat_rows):
        return fwd_scan(x, flat_rows)[0]

    def scan_fwd(x, flat_rows):
        out, x_ins = fwd_scan(x, flat_rows)
        return out, (flat_rows, x_ins)

    def scan_bwd(res, cts):
        flat_rows, x_ins = res
        ct_x, ct_aux = cts

        def body(ct_x, xs):
            row, x_in = xs
            _, vjp = jax.vjp(layer, row, x_in)   # re-gathers the layer
            d_row, d_x = vjp((ct_x, ct_aux))
            return d_x, d_row

        with jax.named_scope(scopes.CARRY):
            ct_x, d_rows = lax.scan(body, ct_x, (flat_rows, x_ins),
                                    reverse=True)
        return ct_x, d_rows

    scan_fn.defvjp(scan_fwd, scan_bwd)
    x, aux = scan_fn(x, flat_rows)
    return x, aux, None


@jax.named_scope(scopes.EMBED)
def embed_tokens(model: ModelDef, t_embed, tokens, ctx: L.Ctx, *, pos=None):
    cfg = model.cfg
    x = L.embed_lookup(t_embed["emb.table"], tokens, ctx)
    if "emb.pos" in t_embed:
        if pos is None:
            positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        elif getattr(pos, "ndim", 0) == 1:
            # per-request positions [b] (continuous batching)
            positions = pos[:, None] + jnp.arange(tokens.shape[1])[None, :]
        else:
            positions = jnp.broadcast_to(pos, tokens.shape)
        pe = L.embed_lookup(t_embed["emb.pos"], positions, ctx)
        x = x + pe
    return x.astype(ctx.compute_dtype)


def encode_audio(model: ModelDef, t_embed, audio, ctx: L.Ctx):
    """Whisper stub frontend: precomputed frame embeddings + learned pos."""
    frames = audio.shape[1]
    positions = jnp.broadcast_to(jnp.arange(frames), audio.shape[:2])
    pe = L.embed_lookup(t_embed["emb.audio_pos"], positions, ctx)
    return (audio + pe).astype(ctx.compute_dtype)


@jax.named_scope(scopes.HEAD)
def lm_logits(model: ModelDef, t_head, x, ctx: L.Ctx):
    cfg = model.cfg
    if cfg.norm == "ln":
        x = L.layer_norm(x, t_head["final.scale"], t_head["final.bias"])
    else:
        x = L.rms_norm(x, t_head["final.scale"])
    return x @ t_head["head.w"]


def forward(
    model: ModelDef,
    flat: dict[str, jax.Array],
    comm,
    ctx: L.Ctx,
    batch: dict[str, jax.Array],
    caches: dict | None = None,
):
    """Run embedding -> pools -> final hidden states.

    ``comm`` is the CommEngine (core/comm.py) that owns every gather.
    Returns (hidden, aux_loss, new_caches, t_head).
    """
    cfg = model.cfg
    t_embed = comm.gather(model.embed, _row(flat["embed"], (0, 0)),
                          seed=ctx.step_seed)
    aux_total = jnp.float32(0.0)
    new_caches: dict[str, Any] = {}

    if cfg.family == "encdec" and ctx.mode != "decode":
        enc_x = encode_audio(model, t_embed, batch["audio"], ctx)
        enc_ctx = dataclasses.replace(ctx, mode="train", pos=None)
        for pool in model.pools:
            if not is_encoder_pool(cfg, pool.name):
                continue
            enc_x, aux, _ = _apply_pool(
                pool, flat[pool.name], enc_x, enc_ctx, comm, None)
            aux_total = aux_total + aux
        ctx = dataclasses.replace(ctx, enc_out=enc_x)
    if cfg.family == "vlm" and ctx.mode != "decode":
        ctx = dataclasses.replace(
            ctx, vision=batch["vision"].astype(ctx.compute_dtype))

    x = embed_tokens(model, t_embed, batch["tokens"], ctx, pos=ctx.pos)
    for pool in model.pools:
        if is_encoder_pool(cfg, pool.name):
            continue
        pool_cache = caches.get(pool.name) if caches is not None else None
        x, aux, nc = _apply_pool(
            pool, flat[pool.name], x, ctx, comm, pool_cache)
        aux_total = aux_total + aux
        if nc is not None:
            new_caches[pool.name] = nc

    t_head = comm.gather(model.head, _row(flat["head"], (0, 0)),
                         seed=ctx.step_seed)
    return x, aux_total, new_caches, t_head


def loss_fn(
    model: ModelDef,
    flat: dict[str, jax.Array],
    comm,
    ctx: L.Ctx,
    batch: dict[str, jax.Array],
):
    """Token cross-entropy + MoE aux.  batch: tokens/targets/mask [b, T]."""
    hidden, aux, _, t_head = forward(model, flat, comm, ctx, batch)
    logits = lm_logits(model, t_head, hidden, ctx)
    with jax.named_scope(scopes.HEAD):
        ce = L.tp_cross_entropy(
            logits, batch["targets"], batch["mask"].astype(jnp.float32),
            vocab_real=model.cfg.vocab, vocab_padded=model.vocab_padded,
            ctx=ctx,
        )
    metrics = {"loss": ce, "aux": aux}
    if model.cfg.moe_layers:
        # the expert layers' aux: balance loss, and experts' load max over
        # mean, reported as its mean over those layers
        metrics = {"loss": ce, "aux": aux[0],
                   COUNTERS[0]: aux[1] / model.cfg.moe_layers}
    loss = ce + model.cfg.router_aux_weight * metrics["aux"]
    return loss, metrics


# the loss function's metrics beside "loss" and "aux", in a model with
# expert layers
COUNTERS = ("expert_load_max_over_mean",)


def counters(model: ModelDef) -> tuple[str, ...]:
    """The names of :func:`loss_fn`'s metrics beside "loss" and "aux"."""
    return COUNTERS if model.cfg.moe_layers else ()


# ---------------------------------------------------------------------------
# serving entry points
# ---------------------------------------------------------------------------

def prefill(
    model: ModelDef,
    flat: dict[str, jax.Array],
    comm,
    ctx: L.Ctx,
    batch: dict[str, jax.Array],
):
    """Forward over the prompt, returning per-pool caches + last logits."""
    ctx = dataclasses.replace(ctx, mode="prefill")
    caches = init_caches(model, batch["tokens"].shape[0], ctx.cache_len, prefill=True)
    hidden, _, new_caches, t_head = forward(
        model, flat, comm, ctx, batch, caches)
    logits = lm_logits(model, t_head, hidden[:, -1:], ctx)
    return logits, new_caches


def decode_step(
    model: ModelDef,
    flat: dict[str, jax.Array],
    comm,
    ctx: L.Ctx,
    tokens: jax.Array,          # [b, tq] current token ids (tq=1 rectangular)
    pos: jax.Array,             # scalar absolute position, or [b] per-request
    caches: dict,
    *,
    pages=None,                 # runtime/paged.PageState for paged KV caches
):
    ctx = dataclasses.replace(ctx, mode="decode", pos=pos, pages=pages)
    batch = {"tokens": tokens}
    hidden, _, new_caches, t_head = forward(
        model, flat, comm, ctx, batch, caches)
    logits = lm_logits(model, t_head, hidden, ctx)
    return logits, new_caches


def init_caches(model: ModelDef, batch: int, cache_len: int, *, prefill: bool = False):
    """Zero caches for every pool (stacked along the pool's stack dim).

    In prefill mode the scan still needs cache *inputs* with the right
    structure; their values are ignored and replaced by the computed caches.
    """
    caches = {}
    for pool in model.pools:
        if pool.make_cache is None:
            continue
        one = pool.make_cache(batch, cache_len)
        caches[pool.name] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (pool.stack, *a.shape)), one)
    return caches


def greedy_sample(logits_local: jax.Array, ctx: L.Ctx, vocab_real: int) -> jax.Array:
    """Argmax over the vocab-parallel logits."""
    vl = logits_local.shape[-1]
    lg = logits_local.astype(jnp.float32)
    start = ctx.tp_index() * vl
    col = start + jnp.arange(vl)
    lg = jnp.where(col[None, None, :] < vocab_real, lg, L.NEG_INF)
    local_max = jnp.max(lg, axis=-1)
    local_arg = jnp.argmax(lg, axis=-1) + start
    if ctx.tp == 1:
        return local_arg
    gmax = lax.pmax(local_max, ctx.tp_axis)
    cand = jnp.where(local_max >= gmax, local_arg, jnp.iinfo(jnp.int32).max)
    return lax.pmin(cand, ctx.tp_axis)


def sample_tokens(
    logits_local: jax.Array,    # [b, V/tp] vocab-parallel logits
    ctx: L.Ctx,
    vocab_real: int,
    *,
    seed: jax.Array,            # [b] int32 per-request seeds
    pos: jax.Array,             # [b] int32 position of the sampled token
    temperature: jax.Array,     # [b] f32; 0.0 = greedy
    top_k: int = 0,             # static; 0 = full vocab
) -> jax.Array:
    """Seeded categorical sampler over vocab-parallel logits -> [b] ids.

    Exact Gumbel-max: argmax(logits/T + G) with G ~ Gumbel(0, 1) drawn from
    a key folded over (request seed, token position, tp shard index) — the
    same step-varying fold-in discipline as the qgZ dither seed, so decoding
    is reproducible per (seed, position) and distinct across both.  Rows
    with ``temperature == 0`` take the noiseless argmax (== greedy_sample).
    Under tp > 1 each shard draws noise for its own vocab columns and the
    global argmax uses the pmax/pmin index trick; ``top_k`` is applied
    per shard, i.e. the union of per-shard top-k — a superset of the true
    top-k (exact when tp == 1).
    """
    b, vl = logits_local.shape
    lg = logits_local.astype(jnp.float32)
    start = ctx.tp_index() * vl
    col = start + jnp.arange(vl)
    lg = jnp.where(col[None, :] < vocab_real, lg, L.NEG_INF)
    if top_k:
        thr = lax.top_k(lg, min(top_k, vl))[0][:, -1]
        lg = jnp.where(lg < thr[:, None], L.NEG_INF, lg)

    tpi = ctx.tp_index()

    def noise_row(s, p):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.fold_in(
                jax.random.PRNGKey(0), s), p), tpi)
        return jax.random.gumbel(key, (vl,), jnp.float32)

    g = jax.vmap(noise_row)(seed.astype(jnp.int32), pos.astype(jnp.int32))
    t = jnp.maximum(temperature, 1e-6)[:, None]
    # masked lanes stay masked: NEG_INF/T + G is still < any real score
    scores = jnp.where(temperature[:, None] > 0.0, lg / t + g, lg)

    local_max = jnp.max(scores, axis=-1)
    local_arg = jnp.argmax(scores, axis=-1).astype(jnp.int32) + start
    if ctx.tp == 1:
        return local_arg
    gmax = lax.pmax(local_max, ctx.tp_axis)
    cand = jnp.where(local_max >= gmax, local_arg, jnp.iinfo(jnp.int32).max)
    return lax.pmin(cand, ctx.tp_axis)
