"""Paged KV-cache allocator + paged decode step (vLLM/MaxText-style).

Contiguous serving caches reserve ``cache_len`` slots per request up front —
ragged traffic at wildly different sequence lengths turns most of that HBM
into dead slots.  The paged layout replaces the per-request axis with a
shared pool of fixed-size blocks:

    contiguous:  k [stack, batch, cache_len, heads, dh]
    paged:       k [stack, n_blocks, block_size, heads, dh]
                 block_tables [batch, max_blocks] int32  (rank-local ids)

Each request owns a list of blocks; table entry ``i`` maps token positions
``[i*block_size, (i+1)*block_size)`` to a physical block.  Blocks return to
the free list the moment a request completes, so resident batch is bounded
by *live tokens*, not worst-case length.  Sharding is unchanged from the
contiguous layout: the block pool is sharded over the data axes (each data
rank owns its own allocator and ``n_blocks_local`` blocks — table entries
are rank-local ids) and heads over the model axis, including the GQA
head-slot replication of DESIGN.md §3.

Physical block 0 of every rank is reserved as the *garbage block*: it is
never allocated, unset table entries point at it, and the decode step
redirects writes from padding rows out of range (dropped), so reads through
an unset table entry are deterministic zeros that the per-request
``kv_valid_len`` mask excludes from the softmax.

Bitwise discipline: the paged decode step gathers the pool back into a
contiguous ``[b, max_blocks*block_size, heads, dh]`` view with the *same*
key-axis length as a contiguous cache of that capacity, so the fp32 softmax
reduction tree is identical and paged decode is **bitwise-equal** to the
contiguous reference (tests/serve_harness.py pins this for fp32 and bf16
KV across block sizes).

Int8 KV blocks (``kv_dtype='int8'``) reuse ``core/quant.py``'s absmax
block quantizer — the serving-side analogue of the qgZ gradient wire.  Each
token row is quantized once on write, per (token, head, 128-block of
head_dim), so scale pages shard over the model axis exactly like k/v and
blocks are never re-quantized.  Documented error bound: per-element relative
error ≤ 1/254 of the row's per-block absmax (round-to-nearest at 127 levels);
end-to-end logits stay within a few percent of the fp32 reference
(serve_harness ``int8_kv_error``).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import quant as Q
from repro.core.autotune import resolve_config
from repro.core.comm import CommEngine
from repro.core.mics import MiCSConfig, state_pspecs
from repro.core.topology import MODEL_AXIS, MiCSTopology
from repro.models import layers as L
from repro.models import lm
from repro.models.lm import ModelDef

KV_DTYPES = ("fp32", "bf16", "int8")
_KV_JNP = {"fp32": jnp.float32, "bf16": jnp.bfloat16}


@dataclasses.dataclass(frozen=True)
class PageState:
    """Per-step paged-cache state threaded through ``Ctx.pages``.

    block_tables: [b, max_blocks] int32 rank-local block ids (traced).
    block_size:   static tokens per block.
    n_new:        [b] int32 tokens consumed per slot this tick (traced), or
                  None (all ``tq`` rows valid — plain decode).
    """

    block_tables: Any
    block_size: int
    n_new: Any = None


def blocks_for(n_tokens: int, block_size: int) -> int:
    """Physical blocks needed to hold ``n_tokens`` cache positions."""
    return -(-max(n_tokens, 0) // block_size)


class PagedKVAllocator:
    """Host-side free-list allocator for one data rank's block pool.

    Block 0 is the reserved garbage block (never handed out).  Allocation
    is lowest-id-first so refilled slots reuse just-freed blocks — the
    pool's steady-state working set stays compact.
    """

    def __init__(self, n_blocks: int, block_size: int):
        if n_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is reserved)")
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        self.n_blocks = n_blocks
        self.block_size = block_size
        self._free = list(range(n_blocks - 1, 0, -1))  # pop() -> lowest id

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> list[int] | None:
        """n blocks, or None (and no change) if the pool can't supply them."""
        if n < 0:
            raise ValueError("negative block count")
        if n > len(self._free):
            return None
        got = [self._free.pop() for _ in range(n)]
        return got

    def free(self, blocks: list[int]) -> None:
        for b in blocks:
            if not 0 < b < self.n_blocks:
                raise ValueError(f"bad block id {b}")
            if b in self._free:
                raise ValueError(f"double free of block {b}")
        self._free.extend(sorted(blocks, reverse=True))
        self._free.sort(reverse=True)

    def reset(self) -> None:
        """Return every block to the pool (outstanding tables invalid).

        The world-change rebuild path: after a preemption/degradation the
        KV pool arrays are re-initialized and every resident request
        replays from its prompt, so the allocator forgets all outstanding
        allocations in one step instead of requiring each to be freed."""
        self._free = list(range(self.n_blocks - 1, 0, -1))


# ---------------------------------------------------------------------------
# paged cache pytree (global arrays + pspecs)
# ---------------------------------------------------------------------------

def _check_paged_support(model: ModelDef) -> None:
    if model.cfg.window:
        raise NotImplementedError(
            "paged KV serving requires window == 0 (no rolling caches)")
    for pool in model.pools:
        if pool.make_cache is None:
            raise NotImplementedError(
                f"pool {pool.name!r} has no KV cache (family "
                f"{model.cfg.family!r} is not paged-servable)")
        one = pool.make_cache(1, 8)
        if set(one) != {"k", "v"} or one["k"].ndim != 4:
            raise NotImplementedError(
                f"pool {pool.name!r} cache is not a plain k/v dict "
                f"(family {model.cfg.family!r} is not paged-servable)")


def paged_cache_local(model: ModelDef, n_blocks_local: int, block_size: int,
                      kv_dtype: str = "bf16"):
    """One data rank's paged cache pytree (stacked over each pool's layers).

    Leaves per pool: k/v [stack, n_blocks, block_size, h_local, dh]
    (+ f32 scale pages ks/vs [stack, n_blocks, block_size, h_local, n_scale]
    when ``kv_dtype='int8'``).
    """
    _check_paged_support(model)
    if kv_dtype not in KV_DTYPES:
        raise ValueError(f"kv_dtype must be one of {KV_DTYPES}")
    caches = {}
    for pool in model.pools:
        one = pool.make_cache(n_blocks_local, block_size)
        shape = one["k"].shape  # [n_blocks, block_size, h_local, dh]
        if kv_dtype == "int8":
            nsc = Q.n_blocks(shape[-1])
            one = {
                "k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "ks": jnp.zeros((*shape[:-1], nsc), jnp.float32),
                "vs": jnp.zeros((*shape[:-1], nsc), jnp.float32),
            }
        else:
            dt = _KV_JNP[kv_dtype]
            one = {"k": one["k"].astype(dt), "v": one["v"].astype(dt)}
        caches[pool.name] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (pool.stack, *a.shape)), one)
    return caches


def paged_cache_pspecs(model: ModelDef, topo: MiCSTopology, batch_axes=None,
                       *, kv_dtype: str = "bf16"):
    """[stack, blocks, block_size, heads, ...]: blocks over the data axes
    (each rank owns its pool), heads over model — same placement rules as
    the contiguous cache; int8 scale pages shard identically."""
    baxes = topo.data_axes if batch_axes is None else batch_axes
    spec = P(None, baxes, None, MODEL_AXIS, None)
    names = ("k", "v", "ks", "vs") if kv_dtype == "int8" else ("k", "v")
    return {pool.name: {n: spec for n in names} for pool in model.pools}


def init_paged_caches(model: ModelDef, topo: MiCSTopology,
                      n_blocks_local: int, block_size: int,
                      kv_dtype: str = "bf16", batch_axes=None):
    """Global zero-filled paged caches + their pspecs.

    ``n_blocks_local`` is per data rank (allocators are rank-local); the
    global blocks axis is ``n_blocks_local * dp``.
    """
    baxes = topo.data_axes if batch_axes is None else batch_axes
    local = paged_cache_local(model, n_blocks_local, block_size, kv_dtype)
    specs = paged_cache_pspecs(model, topo, baxes, kv_dtype=kv_dtype)

    def globalize(leaf, ps):
        shape = list(leaf.shape)
        for i, ax in enumerate(ps):
            if ax is None:
                continue
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                shape[i] *= topo.axis_size(a)
        sharding = NamedSharding(topo.mesh, ps)
        return jax.device_put(jnp.zeros(tuple(shape), leaf.dtype), sharding)

    caches = jax.tree.map(globalize, local, specs,
                          is_leaf=lambda x: isinstance(x, jnp.ndarray))
    return caches, specs


# ---------------------------------------------------------------------------
# host-side cache conversion (prefill once, then serve paged)
# ---------------------------------------------------------------------------

def pages_from_contiguous(model: ModelDef, topo: MiCSTopology, contig,
                          paged, tables, lengths, *, block_size: int,
                          kv_dtype: str = "bf16", batch_axes=None):
    """Copy a contiguous prefill cache into an allocated paged pool.

    contig: the ``lm.prefill`` cache pytree (k/v [stack, B, cap, H, dh],
    slot of position a == a for window-free archs); paged: global paged
    caches from :func:`init_paged_caches`; tables [B, max_blocks] rank-local
    block ids; lengths [B] prompt lengths.  Returns updated paged caches.
    Host-side (numpy) — runs once per admission wave, not per step.
    """
    import numpy as np

    baxes = topo.data_axes if batch_axes is None else batch_axes
    dp = 1
    for a in baxes:
        dp *= topo.axis_size(a)
    tables = np.asarray(tables)
    lengths = np.asarray(lengths)
    bsz = tables.shape[0]
    b_local = bsz // dp
    out = {}
    specs = paged_cache_pspecs(model, topo, baxes, kv_dtype=kv_dtype)
    for pool in model.pools:
        src_k = np.asarray(contig[pool.name]["k"], dtype=np.float32)
        src_v = np.asarray(contig[pool.name]["v"], dtype=np.float32)
        dst = {name: np.array(leaf) for name, leaf in paged[pool.name].items()}
        nb_local = dst["k"].shape[1] // dp
        for b in range(bsz):
            n = int(lengths[b])
            if n == 0:
                continue
            rank = b // b_local
            posn = np.arange(n)
            gblk = rank * nb_local + tables[b, posn // block_size]
            off = posn % block_size
            if kv_dtype == "int8":
                qk, sk = Q.quantize_flat(jnp.asarray(src_k[:, b, :n]))
                qv, sv = Q.quantize_flat(jnp.asarray(src_v[:, b, :n]))
                dst["k"][:, gblk, off] = np.asarray(qk)
                dst["v"][:, gblk, off] = np.asarray(qv)
                dst["ks"][:, gblk, off] = np.asarray(sk)
                dst["vs"][:, gblk, off] = np.asarray(sv)
            else:
                dst["k"][:, gblk, off] = src_k[:, b, :n].astype(dst["k"].dtype)
                dst["v"][:, gblk, off] = src_v[:, b, :n].astype(dst["v"].dtype)
        out[pool.name] = {
            name: jax.device_put(
                jnp.asarray(leaf),
                NamedSharding(topo.mesh, specs[pool.name][name]))
            for name, leaf in dst.items()}
    return out


# ---------------------------------------------------------------------------
# the paged decode/chunk step
# ---------------------------------------------------------------------------

def build_paged_step(model: ModelDef, topo: MiCSTopology, mcfg: MiCSConfig,
                     *, max_blocks: int, block_size: int | None = None,
                     chunk: int = 1, kv_dtype: str | None = None,
                     top_k: int = 0, batch_axes=None):
    """Jitted continuous-batching step over a paged KV pool.

    step(params, caches, tokens [B, chunk], pos [B], n_new [B],
         tables [B, max_blocks], seeds [B], temps [B])
      -> (next_tok [B], logits_row [B, vocab_padded], new_caches)

    One call advances every slot by up to ``chunk`` tokens: decode slots
    consume 1 (``n_new=1``), prefill slots up to ``chunk`` (chunked prefill
    interleaved into decode ticks — TTFT and steady-state tokens/s both
    bounded), idle slots 0.  The sampled token comes from the logit row of
    each slot's last consumed token; the scheduler ignores it mid-prompt.
    The key-axis length of every attention is ``max_blocks * block_size``
    regardless of the chunking, so a request's hidden states — and its
    sampled tokens — are bitwise-independent of where its chunk boundaries
    fall for a fixed ``chunk`` width (one compiled executable).  Across
    *different* chunk widths the kernels tile the token matmuls
    differently, so equality is only up to last-ulp rounding — the serve
    harness checks both regimes.
    """
    mcfg, plan = resolve_config(mcfg, model, topo, mode="serve")
    block_size = block_size if block_size is not None else mcfg.kv_block_size
    kv_dtype = kv_dtype if kv_dtype is not None else mcfg.kv_dtype
    _check_paged_support(model)
    comm = CommEngine.from_config(topo, mcfg)
    cache_len = max_blocks * block_size
    ctx = L.Ctx(mode="decode", tp=topo.model_size, tp_axis=MODEL_AXIS,
                cache_len=cache_len, window=0,
                compute_dtype=jnp.dtype(mcfg.gather_dtype),
                scores_bf16=mcfg.scores_bf16, mlstm_chunk=mcfg.mlstm_chunk)
    baxes = topo.data_axes if batch_axes is None else batch_axes
    flat_specs = state_pspecs(model, topo)["params"]
    if mcfg.quant_gather:
        flat_specs = {name: {"q": spec, "s": spec}
                      for name, spec in flat_specs.items()}
    kv_spec = P(None, baxes, None, MODEL_AXIS, None)
    names = ("k", "v", "ks", "vs") if kv_dtype == "int8" else ("k", "v")
    c_specs = {pool.name: {n: kv_spec for n in names} for pool in model.pools}
    tok_spec = P(baxes, None)
    row_spec = P(baxes)
    tbl_spec = P(baxes, None)
    logit_spec = P(baxes, MODEL_AXIS)

    def sharded_step(params, caches, tokens, pos, n_new, tables, seeds, temps):
        pages = PageState(block_tables=tables, block_size=block_size,
                          n_new=n_new)
        logits, new_caches = lm.decode_step(
            model, params, comm, ctx, tokens, pos, caches, pages=pages)
        b = tokens.shape[0]
        row = jnp.maximum(n_new - 1, 0)
        lgt = logits[jnp.arange(b), row]            # [b, V/tp] last-consumed row
        next_tok = lm.sample_tokens(
            lgt, ctx, model.cfg.vocab, seed=seeds, pos=pos + n_new,
            temperature=temps, top_k=top_k)
        return next_tok, lgt, new_caches

    ns = lambda spec: jax.tree.map(
        lambda s_: NamedSharding(topo.mesh, s_), spec,
        is_leaf=lambda x: isinstance(x, P))

    step_sm = shard_map(
        sharded_step, mesh=topo.mesh,
        in_specs=(flat_specs, c_specs, tok_spec, row_spec, row_spec,
                  tbl_spec, row_spec, row_spec),
        out_specs=(row_spec, logit_spec, c_specs),
        check_vma=False,
    )
    step_fn = jax.jit(
        step_sm,
        in_shardings=(ns(flat_specs), ns(c_specs), ns(tok_spec), ns(row_spec),
                      ns(row_spec), ns(tbl_spec), ns(row_spec), ns(row_spec)),
        out_shardings=(ns(row_spec), ns(logit_spec), ns(c_specs)),
        donate_argnums=(1,),
    )
    return step_fn


def build_contiguous_step(model: ModelDef, topo: MiCSTopology,
                          mcfg: MiCSConfig, cache_len: int, *,
                          top_k: int = 0, batch_axes=None):
    """Vector-position contiguous-cache decode step: the bitwise reference
    for the paged engine (same per-request positions and sampler, regular
    [stack, b, cache_len, h, dh] caches, one token per slot per call).

    step(params, caches, tokens [B, 1], pos [B], seeds [B], temps [B])
      -> (next_tok [B], logits_row [B, vocab_padded], new_caches)
    """
    from repro.runtime.serving import cache_pspecs

    mcfg, _ = resolve_config(mcfg, model, topo, mode="serve")
    comm = CommEngine.from_config(topo, mcfg)
    ctx = L.Ctx(mode="decode", tp=topo.model_size, tp_axis=MODEL_AXIS,
                cache_len=cache_len, window=model.cfg.window,
                compute_dtype=jnp.dtype(mcfg.gather_dtype),
                scores_bf16=mcfg.scores_bf16, mlstm_chunk=mcfg.mlstm_chunk)
    baxes = topo.data_axes if batch_axes is None else batch_axes
    flat_specs = state_pspecs(model, topo)["params"]
    if mcfg.quant_gather:
        flat_specs = {name: {"q": spec, "s": spec}
                      for name, spec in flat_specs.items()}
    c_specs = cache_pspecs(model, topo, baxes)
    tok_spec = P(baxes, None)
    row_spec = P(baxes)
    logit_spec = P(baxes, MODEL_AXIS)

    def sharded_step(params, caches, tokens, pos, seeds, temps):
        logits, new_caches = lm.decode_step(
            model, params, comm, ctx, tokens, pos, caches)
        lgt = logits[:, 0]
        next_tok = lm.sample_tokens(
            lgt, ctx, model.cfg.vocab, seed=seeds, pos=pos + 1,
            temperature=temps, top_k=top_k)
        return next_tok, lgt, new_caches

    ns = lambda spec: jax.tree.map(
        lambda s_: NamedSharding(topo.mesh, s_), spec,
        is_leaf=lambda x: isinstance(x, P))

    step_sm = shard_map(
        sharded_step, mesh=topo.mesh,
        in_specs=(flat_specs, c_specs, tok_spec, row_spec, row_spec, row_spec),
        out_specs=(row_spec, logit_spec, c_specs),
        check_vma=False,
    )
    return jax.jit(
        step_sm,
        in_shardings=(ns(flat_specs), ns(c_specs), ns(tok_spec), ns(row_spec),
                      ns(row_spec), ns(row_spec)),
        out_shardings=(ns(row_spec), ns(logit_spec), ns(c_specs)),
        donate_argnums=(1,),
    )
