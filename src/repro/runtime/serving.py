"""Serving runtime: prefill + KV-cache decode steps under MiCS sharding.

Inference uses the same flat-pool parameter gathering as training (memory
scales 1/p like ZeRO-3 inference) minus optimizer state.  The KV cache is
sharded batch-over-data and heads-over-model; for GQA archs whose KV head
count is below the model-axis width, each rank caches the one head its Q
group attends to (global cache carries tp "head slots" — the vLLM-style
replication documented in DESIGN.md §3).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.autotune import resolve_config
from repro.core.comm import CommEngine
from repro.core.mics import MiCSConfig, state_pspecs
from repro.core.topology import MODEL_AXIS, MiCSTopology
from repro.models import layers as L
from repro.models import lm
from repro.models.lm import ModelDef


def _cache_pspec_for(leaf_path: str, leaf) -> P:
    """PartitionSpec for one cache leaf (stack, batch, ...) by convention.

    kv/cross caches: [stack, b, len, heads, dh]   -> heads over model
    rec conv:        [stack, b, cw-1, channels]   -> channels over model
    rec h:           [stack, b, channels]         -> channels over model
    xlstm leaves (replicated compute): batch only.
    """
    name = leaf_path.split("/")[-1]
    nd = leaf.ndim
    if name in ("k", "v") and nd == 5:
        return P(None, "data_all", None, MODEL_AXIS, None)
    if name == "conv" and nd == 4:
        return P(None, "data_all", None, MODEL_AXIS)
    if name == "h" and nd == 3:
        return P(None, "data_all", MODEL_AXIS)
    return P(None, "data_all", *([None] * (nd - 2)))


def batch_axes_for(topo: MiCSTopology, global_batch: int):
    """Data axes the batch shards over.

    Ragged batches (``global_batch`` not a multiple of the data-parallel
    size) are padded up to the next multiple with masked dummy rows by
    :func:`pad_ragged_batch` — they used to fall back to replicating the
    whole batch on every data rank, which made a 5-row batch on dp=4 cost
    as much as 20 rows.
    """
    del global_batch  # padding, not replication, handles raggedness now
    return topo.data_axes


def pad_ragged_batch(topo: MiCSTopology, batch: dict):
    """Pad every batch leaf to the next multiple of dp with dummy rows.

    Returns ``(padded_batch, row_mask)`` where ``row_mask`` is a bool [B]
    marking real rows; dummy rows must be masked out of sampling (the
    serve decode step emits token ``-1`` for them).
    """
    dp = topo.data_parallel_size
    b = batch["tokens"].shape[0]
    pad = (-b) % dp
    mask = jnp.arange(b + pad) < b
    if pad:
        batch = {k: jnp.concatenate(
            [v, jnp.zeros((pad,) + v.shape[1:], v.dtype)], axis=0)
            for k, v in batch.items()}
    return batch, mask


def cache_pspecs(model: ModelDef, topo: MiCSTopology, batch_axes=None):
    """Specs for the full cache pytree (built from a tiny local template)."""
    template = lm.init_caches(model, batch=1, cache_len=max(model.cfg.window, 8))
    xlstm = model.cfg.family == "xlstm"
    baxes = topo.data_axes if batch_axes is None else batch_axes

    def spec(path, leaf):
        pathstr = "/".join(str(getattr(p, "key", p)) for p in path)
        ps = _cache_pspec_for(pathstr, leaf)
        if xlstm:  # replicated-compute states: batch sharding only
            ps = P(None, "data_all", *([None] * (leaf.ndim - 2)))
        # replace the placeholder with the real batch axes tuple
        parts = [baxes if p == "data_all" else p for p in ps]
        return P(*parts)

    return jax.tree_util.tree_map_with_path(spec, template)


def global_cache_shapes(model: ModelDef, topo: MiCSTopology,
                        global_batch: int, cache_len: int, batch_axes=None):
    """Global ShapeDtypeStructs for the cache pytree (no allocation)."""
    baxes = topo.data_axes if batch_axes is None else batch_axes
    dp = 1
    for a in baxes:
        dp *= topo.axis_size(a)
    local_b = global_batch // dp
    template = lm.init_caches(model, batch=local_b, cache_len=cache_len)
    specs = cache_pspecs(model, topo, baxes)

    def scale(leaf, ps):
        shape = list(leaf.shape)
        for i, ax in enumerate(ps):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            for a in axes:
                shape[i] *= topo.axis_size(a)
        return jax.ShapeDtypeStruct(tuple(shape), leaf.dtype)

    return jax.tree.map(scale, template, specs,
                        is_leaf=lambda x: isinstance(x, jnp.ndarray)), specs


def build_serve_steps(model: ModelDef, topo: MiCSTopology, mcfg: MiCSConfig,
                      cache_len: int, batch_axes=None, *, top_k: int = 0):
    """Returns (prefill_fn, decode_fn) jitted for the topo's mesh.

    Weight gathers (bf16 or int8-quantized, serial or prefetched) run
    through the same CommEngine as training — decode re-gathers every
    layer each step, so the prefetch schedule matters most here.
    ``policy="auto"`` configs are resolved by the link-model autotuner
    first (serving mode: forward gathers only, no gradient sync).

    ``decode_fn(params, caches, tokens, pos, seeds, temps, row_mask)``
    samples with per-request seeded Gumbel noise (``lm.sample_tokens``):
    ``temps == 0`` rows take the noiseless argmax (exact greedy), masked
    rows (``row_mask`` False — :func:`pad_ragged_batch` padding) emit -1.
    """
    mcfg, _ = resolve_config(mcfg, model, topo, mode="serve")
    comm = CommEngine.from_config(topo, mcfg)
    ctx = L.Ctx(mode="decode", tp=topo.model_size, tp_axis=MODEL_AXIS,
                cache_len=cache_len, window=model.cfg.window,
                compute_dtype=jnp.dtype(mcfg.gather_dtype),
                scores_bf16=mcfg.scores_bf16, mlstm_chunk=mcfg.mlstm_chunk)
    baxes = topo.data_axes if batch_axes is None else batch_axes
    flat_specs = state_pspecs(model, topo)["params"]
    if mcfg.quant_gather:  # int8 weights + per-block scales, same sharding
        flat_specs = {name: {"q": spec, "s": spec}
                      for name, spec in flat_specs.items()}
    c_specs = cache_pspecs(model, topo, baxes)
    tok_spec = P(baxes, None)
    logit_spec = P(baxes, None, MODEL_AXIS)

    def sharded_prefill(params, batch):
        pctx = dataclasses.replace(ctx, mode="prefill")
        logits, caches = lm.prefill(model, params, comm, pctx, batch)
        return logits, caches

    def sharded_decode(params, caches, tokens, pos, seeds, temps, row_mask):
        logits, new_caches = lm.decode_step(
            model, params, comm, ctx, tokens, pos, caches)
        b = tokens.shape[0]
        pos_b = jnp.broadcast_to(pos, (b,)).astype(jnp.int32)
        nxt = lm.sample_tokens(logits[:, -1], ctx, model.cfg.vocab,
                               seed=seeds, pos=pos_b + 1,
                               temperature=temps, top_k=top_k)
        nxt = jnp.where(row_mask, nxt, -1)
        return logits, nxt[:, None], new_caches

    ns = lambda spec: jax.tree.map(
        lambda s_: NamedSharding(topo.mesh, s_), spec,
        is_leaf=lambda x: isinstance(x, P))

    batch_specs = {"tokens": tok_spec}
    if model.cfg.family == "vlm":
        batch_specs["vision"] = P(baxes, None, None)
    if model.cfg.family == "encdec":
        batch_specs["audio"] = P(baxes, None, None)

    prefill_sm = shard_map(
        sharded_prefill, mesh=topo.mesh,
        in_specs=(flat_specs, batch_specs),
        out_specs=(logit_spec, c_specs),
        check_vma=False,
    )
    prefill_fn = jax.jit(
        prefill_sm,
        in_shardings=(ns(flat_specs), ns(batch_specs)),
        out_shardings=(ns(logit_spec), ns(c_specs)),
    )

    row_spec = P(baxes)
    decode_sm = shard_map(
        sharded_decode, mesh=topo.mesh,
        in_specs=(flat_specs, c_specs, tok_spec, P(), row_spec, row_spec,
                  row_spec),
        out_specs=(logit_spec, tok_spec, c_specs),
        check_vma=False,
    )
    decode_jit = jax.jit(
        decode_sm,
        in_shardings=(ns(flat_specs), ns(c_specs), ns(tok_spec),
                      NamedSharding(topo.mesh, P()), ns(row_spec),
                      ns(row_spec), ns(row_spec)),
        out_shardings=(ns(logit_spec), ns(tok_spec), ns(c_specs)),
        donate_argnums=(1,),
    )

    def decode_fn(params, caches, tokens, pos, seeds=None, temps=None,
                  row_mask=None):
        b = tokens.shape[0]
        if seeds is None:
            seeds = jnp.zeros((b,), jnp.int32)
        if temps is None:
            temps = jnp.zeros((b,), jnp.float32)  # greedy
        if row_mask is None:
            row_mask = jnp.ones((b,), bool)
        return decode_jit(params, caches, tokens, pos, seeds, temps, row_mask)

    decode_fn.lower = decode_jit.lower  # AOT path (launch/dryrun.py)
    return prefill_fn, decode_fn


def resize_for_serve_world(model, mcfg: MiCSConfig, n_devices: int, *,
                           tp: int = 1, partition_size: int | None = None,
                           seq: int = 0, arrival_rate: float = 0.0
                           ) -> tuple[MiCSTopology, MiCSConfig, dict]:
    """(topology, config, ledger info) for serving on an ``n_devices`` world.

    The serving analog of ``train_loop.resize_for_world``, and the one
    rebuild path the resilient serve loop (runtime/resilient.py) uses on
    every :class:`repro.core.faults.WorldChangeError`:

    1. ``autotune.resolve_world(mode="serve")`` re-picks the partition
       group for the survivors (the paper's §3.1 rule under
       ``mcfg.hbm_budget_gb``; the keep rule without a budget);
    2. ``topology.elastic_host_topology`` re-meshes them contiguously
       (TP stays pinned — flat layouts are TP-local);
    3. ``autotune.rerank_serve_world`` re-ranks the serve decode grid on
       the new link geometry with numerics pinned, so the re-ranked
       policy cannot break the bitwise replay contract.

    ``info`` is ledger-friendly: the §3.1 decision plus the re-ranked
    serve policy summary.
    """
    from repro.core.autotune import rerank_serve_world, resolve_world
    from repro.core.topology import elastic_host_topology

    p, mcfg2, info = resolve_world(
        model, mcfg, n_devices=n_devices, tp=tp,
        partition_size=partition_size, mode="serve", seq=seq)
    topo = elastic_host_topology(n_devices, p, tp)
    mcfg3, plan = rerank_serve_world(model, topo, mcfg2, seq=seq,
                                     arrival_rate=arrival_rate)
    chosen = plan.chosen
    info = dict(info, serve_rerank={
        "gather": chosen.gather.topology,
        "wire": chosen.gather.wire_dtype,
        "prefetch": chosen.gather.prefetch,
        "kv_dtype": mcfg3.kv_dtype,            # pinned, not chosen.kv_dtype
        "max_resident_requests": mcfg3.max_resident_requests,
        "t_decode_s": chosen.t_decode_s,
        "tokens_per_s": chosen.tokens_per_s,
    })
    return topo, mcfg3, info
