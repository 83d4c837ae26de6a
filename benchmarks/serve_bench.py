"""Closed-loop continuous-batching serving bench on the 8-device host mesh.

Two engines over the same seeded request trace:

* **paged** — the continuous-batching engine (runtime/batching.py scheduler
  over runtime/paged.py block-pool KV): chunked prefill interleaved with
  decode, FIFO admission under the free-block budget, per-request seeded
  sampling;
* **fixed** — the static baseline (runtime/serving.build_serve_steps):
  requests grouped in arrival order into full batches, prompts padded to
  the global max, the whole group decoded to its longest request
  (head-of-line blocking + padding waste — what continuous batching
  exists to beat).

Both engines get the SAME per-rank KV memory budget: the fixed cache
reserves ``FIXED_ROWS_LOCAL * CAP`` token slots per rank, the paged pool
``(NB_LOCAL - 1) * BLOCK_SIZE`` — equal by construction.  Because real
sequences never fill CAP, block-granular allocation turns that budget
into more resident requests (6 slots/rank vs 4 rows/rank under full
reservation), which is the whole vLLM-style argument: fragmentation
becomes throughput.  On top of that, continuous batching retires each
request the tick it finishes, while the static baseline decodes every
group to its longest member (head-of-line padding waste).

The arrival-rate sweep offers ``rate`` requests per scheduler tick; the
tick -> wall-clock mapping comes from the measured engine steps, so each
cell reports real p50/p99 TTFT + end-to-end latency seconds and generated
tokens/s, plus the link-model predicted decode-step time
(``core/autotune.cost_decode_step``) against the measured mean.

Two correctness/overhead sections ride along:

* ``equivalence`` replays the paged-vs-contiguous bitwise check (fp32 KV,
  block-straddling prompts, GQA head-slot replication) — the engine
  property every throughput number rests on;
* ``step_overhead`` times every step kind both engines issue with
  alternating interleaved reps (same-process back-to-back, so JIT and
  machine-drift bias cancels).  The regression gate is the per-ROW decode
  ratio — the paged step pushes 1.5x the rows per call, so raw step
  times are not directly comparable.  The same controlled prices feed the
  ``normalized`` tokens/s in every sweep cell: wall clocks on this
  oversubscribed CPU harness drift 2-3x between cells, but the scheduler
  tick/step counts are deterministic, so pricing them with interleaved
  timings is the noise-immune throughput comparison.

An ``overload`` cell rides along: a tick-0 burst through the resilient
serve loop (runtime/resilient.py) with a bounded queue, tight deadlines
and the memplan-priced degradation ladder — the overload-control contract
(typed shedding, ladder engage/restore, 100%-accounted lifecycle ledger,
no deadlock) gated on the real engine.  Every sweep cell also carries the
batcher's request-lifecycle ledger (queue-depth and wait-age percentiles,
shed/evict/replay counters).

  PYTHONPATH=src python benchmarks/serve_bench.py [--smoke] [--check]

This script is the ``serve`` suite of the declarative perf matrix
(``benchmarks/matrix.py``); its ``cells`` section carries the standard
per-cell records (repro.bench.measure) — the four interleaved step kinds
as timing cells (the paged/fixed decode comparison is per-ROW,
``normalize_by="rows"``, because the paged step pushes 1.5x the rows),
the bitwise equivalence and every sweep/overload cell as contract cells.
``--check`` is a thin shim applying exactly the gates
``repro.bench.matrixdef`` declares for this suite: it fails on any
paged-vs-contiguous mismatch, when the per-row decode overhead regresses
significantly (variance-aware, vs the same-run fixed reference), or when
the overload cell breaks the shed/ladder/ledger contract; the full run
must additionally show paged normalized tokens/s beating the baseline in
the saturation cell (``rate=inf`` — every request offered at tick 0).
Output JSON is saved as BENCH_serve.json (BENCH_serve_smoke.json in CI).
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.bench import measure as MS
from repro.bench.matrixdef import (
    SERVE_RATES_FULL, SERVE_RATES_SMOKE, SERVE_STEP_KINDS,
)
from repro.configs import get_config, smoke_variant
from repro.core.autotune import cost_decode_step
from repro.core.comm import policies_from_config
from repro.core.linkmodel import get_profile
from repro.core.mics import MiCSConfig, init_state
from repro.core.topology import MiCSTopology, make_host_mesh
from repro.models.build import build_model
from repro.runtime import paged as PG
from repro.core.memplan import degradation_levels
from repro.runtime.batching import ContinuousBatcher, DegradationLadder, Request
from repro.runtime.resilient import ResilientServeLoop, ServeLoopConfig
from repro.runtime.serving import build_serve_steps, global_cache_shapes

BLOCK_SIZE = 8
MAX_BLOCKS = 4
CAP = BLOCK_SIZE * MAX_BLOCKS          # positions per request (both engines)
FIXED_ROWS_LOCAL = 4                   # baseline batch rows per data rank
SLOTS_LOCAL = 6                        # paged slots per rank (1.5x the rows:
#   what the shared block budget sustains for the chat-shaped trace)
CHUNK = 8                              # prefill tokens per tick (>= max plen)
# equal KV budget: usable pool slots/rank == the fixed cache's token slots
NB_LOCAL = FIXED_ROWS_LOCAL * CAP // BLOCK_SIZE + 1  # +1: garbage block 0
# offered requests per tick; inf = the saturation cell (all at tick 0).
# Labels pinned by repro.bench.matrixdef.SERVE_RATES_* — the declared
# matrix cells — so coverage drift fails the matrix loudly.
RATES = tuple(float(r) for r in SERVE_RATES_FULL)
SMOKE_RATES = tuple(float(r) for r in SERVE_RATES_SMOKE)
N_REQUESTS = 32
SMOKE_REQUESTS = 10
PROFILE = "v5e"


def make_trace(n: int, vocab: int, rng: np.random.Generator) -> list[Request]:
    """Seeded decode-dominated workload (chat-shaped: short prompts, long
    variable generations); positions always fit CAP."""
    reqs = []
    for i in range(n):
        plen = int(rng.integers(4, 9))
        max_new = int(rng.integers(4, 25))
        reqs.append(Request(
            rid=i,
            prompt=rng.integers(1, vocab, plen).astype(int).tolist(),
            max_new_tokens=max_new,
            temperature=0.7,
            seed=1000 + i,
        ))
    return reqs


def build_engines(model, topo, mcfg):
    """Two paged steps share one pool: a chunked one for ticks with prefill
    rows in flight and a chunk=1 decode-only fast path for steady state
    (most ticks — paying chunk x compute on pure-decode ticks is what made
    naive chunked prefill lose to the static baseline)."""
    step_chunk = PG.build_paged_step(
        model, topo, mcfg, max_blocks=MAX_BLOCKS, block_size=BLOCK_SIZE,
        chunk=CHUNK, top_k=8)
    step_one = PG.build_paged_step(
        model, topo, mcfg, max_blocks=MAX_BLOCKS, block_size=BLOCK_SIZE,
        chunk=1, top_k=8)
    prefill_fn, decode_fn = build_serve_steps(
        model, topo, mcfg, cache_len=CAP, top_k=8)
    return step_chunk, step_one, prefill_fn, decode_fn


def run_continuous(model, topo, mcfg, step_chunk, step_one, reqs,
                   arrival_ticks):
    """One closed-loop paged cell.  Returns the stats + wall timeline."""
    dp = topo.data_parallel_size
    batcher = ContinuousBatcher(
        dp=dp, slots_local=SLOTS_LOCAL, nb_local=NB_LOCAL,
        block_size=BLOCK_SIZE, max_blocks=MAX_BLOCKS, chunk=CHUNK,
        reserve="full")
    caches, _ = PG.init_paged_caches(
        model, topo, NB_LOCAL, BLOCK_SIZE, mcfg.kv_dtype)
    state = init_state(model, topo, seed=7)
    params = state["params"]

    # warm both compile caches outside the timed loop (donation: rebuild)
    B = batcher.batch
    zero = lambda shape, dt: jnp.zeros(shape, dt)
    for step, c in ((step_chunk, CHUNK), (step_one, 1)):
        out = step(params, caches, zero((B, c), jnp.int32),
                   zero((B,), jnp.int32), zero((B,), jnp.int32),
                   zero((B, MAX_BLOCKS), jnp.int32),
                   zero((B,), jnp.int32), zero((B,), jnp.float32))
        jax.block_until_ready(out[0])
        caches = out[2]
    caches, _ = PG.init_paged_caches(
        model, topo, NB_LOCAL, BLOCK_SIZE, mcfg.kv_dtype)

    pending = sorted(zip(arrival_ticks, reqs), key=lambda p: (p[0], p[1].rid))
    wall = [0.0]
    step_times = []
    decode_step_times = []
    resident_rows = []
    while pending or not batcher.idle:
        while pending and pending[0][0] <= batcher.tick:
            _, req = pending.pop(0)
            req.arrival = batcher.tick
            batcher.submit(req)
        plan = batcher.plan_step()
        if plan.active_rows == 0:
            # nothing resident yet: an idle tick costs no wall time
            batcher.commit(plan, np.zeros(batcher.batch, np.int64))
            wall.append(wall[-1])
            continue
        decode_only = int(plan.n_new.max()) <= 1
        step = step_one if decode_only else step_chunk
        tokens = plan.tokens[:, :1] if decode_only else plan.tokens
        t0 = time.perf_counter()
        tok, _logits, caches = step(
            params, caches,
            jnp.asarray(tokens), jnp.asarray(plan.pos),
            jnp.asarray(plan.n_new), jnp.asarray(plan.tables),
            jnp.asarray(plan.seeds), jnp.asarray(plan.temps))
        tok = np.asarray(tok)
        dt = time.perf_counter() - t0
        step_times.append(dt)
        if decode_only:
            decode_step_times.append(dt)
        resident_rows.append(plan.active_rows)
        wall.append(wall[-1] + dt)
        batcher.commit(plan, tok)

    ttft, lat = [], []
    for r in batcher.finished:
        ttft.append(wall[min(r.first_token_tick + 1, len(wall) - 1)]
                    - wall[r.arrival])
        lat.append(wall[min(r.finish_tick + 1, len(wall) - 1)]
                   - wall[r.arrival])
    tokens = sum(len(r.generated) for r in batcher.finished)
    stats = batcher.stats()
    stats.update(
        wall_s=wall[-1],
        tokens_per_s=tokens / wall[-1] if wall[-1] else 0.0,
        ttft_s_p50=float(np.percentile(ttft, 50)) if ttft else 0.0,
        ttft_s_p99=float(np.percentile(ttft, 99)) if ttft else 0.0,
        latency_s_p50=float(np.percentile(lat, 50)) if lat else 0.0,
        latency_s_p99=float(np.percentile(lat, 99)) if lat else 0.0,
        measured_step_s_mean=float(np.mean(step_times)) if step_times else 0.0,
        measured_decode_step_s_mean=float(np.mean(decode_step_times))
        if decode_step_times else 0.0,
        ticks_active=len(step_times),
        decode_only_ticks=len(decode_step_times),
        mean_resident_rows=float(np.mean(resident_rows))
        if resident_rows else 0.0,
        ledger=batcher.ledger(),
    )
    return stats


def run_fixed(model, topo, mcfg, prefill_fn, decode_fn, reqs, arrival_s,
              params, max_plen):
    """Static baseline: arrival-order groups of B, padded, head-of-line."""
    B = topo.data_parallel_size * FIXED_ROWS_LOCAL
    groups = [reqs[i:i + B] for i in range(0, len(reqs), B)]
    t_end = 0.0
    step_times = []
    lat, ttft = [], []
    tokens = 0
    decode_steps = 0
    for gi, group in enumerate(groups):
        idx = list(range(gi * B, gi * B + len(group)))
        start = max([t_end] + [arrival_s[i] for i in idx])
        toks = np.zeros((B, max_plen), np.int32)
        temps = np.zeros(B, np.float32)
        seeds = np.zeros(B, np.int32)
        for j, r in enumerate(group):
            toks[j, :len(r.prompt)] = r.prompt
            temps[j] = r.temperature
            seeds[j] = r.seed
        t0 = time.perf_counter()
        logits, caches = prefill_fn(params, {"tokens": jnp.asarray(toks)})
        vocab = model.cfg.vocab
        tok = jnp.argmax(jnp.asarray(logits[:, -1:, :vocab], jnp.float32),
                         axis=-1).astype(jnp.int32)
        jax.block_until_ready(tok)
        t_pre = time.perf_counter() - t0
        elapsed = t_pre
        n_steps = max(r.max_new_tokens for r in group)
        decode_steps += n_steps - 1
        row_mask = jnp.arange(B) < len(group)
        for i in range(n_steps - 1):
            t0 = time.perf_counter()
            _lg, tok, caches = decode_fn(
                params, caches, tok, jnp.int32(max_plen + i),
                jnp.asarray(seeds), jnp.asarray(temps), row_mask)
            tok = jnp.asarray(np.asarray(tok))  # block; feed back
            dt = time.perf_counter() - t0
            if gi > 0 or i > 0:   # first decode step pays the compile
                step_times.append(dt)
            elapsed += dt
        t_end = start + elapsed
        for j, r in enumerate(group):
            ttft.append(start + t_pre - arrival_s[idx[j]])
            lat.append(t_end - arrival_s[idx[j]])
            tokens += r.max_new_tokens
    return {
        "wall_s": t_end,
        "tokens_per_s": tokens / t_end if t_end else 0.0,
        "ttft_s_p50": float(np.percentile(ttft, 50)),
        "ttft_s_p99": float(np.percentile(ttft, 99)),
        "latency_s_p50": float(np.percentile(lat, 50)),
        "latency_s_p99": float(np.percentile(lat, 99)),
        "measured_step_s_mean": float(np.mean(step_times))
        if step_times else 0.0,
        "groups": len(groups),
        "decode_steps": decode_steps,
        "tokens": tokens,
    }


def step_overhead(model, topo, mcfg, step_chunk, step_one, prefill_fn,
                  decode_fn, params, max_plen: int, reps: int = 20,
                  warmup: int = 2):
    """Interleaved timing of every step kind both engines issue.

    All four step kinds run back-to-back inside each rep, so JIT/allocator
    warmup and machine drift hit them equally — these are the controlled
    per-step prices the normalized throughput gate uses.  The regression
    gate is the per-ROW decode ratio: the paged step pushes
    ``SLOTS_LOCAL/FIXED_ROWS_LOCAL`` times the rows per call, so raw step
    times are not directly comparable (``normalize_by="rows"`` in the
    matrix gate).  Returns ``(summary, timings)`` where ``timings`` maps
    each step kind to its :class:`repro.bench.measure.TimingStats` (the
    matrix's ``serve/step/*`` cells).
    """
    dp = topo.data_parallel_size
    Bp, Bf = dp * SLOTS_LOCAL, dp * FIXED_ROWS_LOCAL
    pool, _ = PG.init_paged_caches(
        model, topo, NB_LOCAL, BLOCK_SIZE, mcfg.kv_dtype)
    tmpl, _ = global_cache_shapes(model, topo, Bf, CAP)
    cc = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tmpl)
    tok1 = jnp.ones((Bp, 1), jnp.int32)
    tokc = jnp.ones((Bp, CHUNK), jnp.int32)
    tokf = jnp.ones((Bf, 1), jnp.int32)
    pref_batch = {"tokens": jnp.ones((Bf, max_plen), jnp.int32)}
    zp_i = jnp.zeros(Bp, jnp.int32)
    one_p = jnp.ones(Bp, jnp.int32)
    full_p = jnp.full((Bp,), CHUNK, jnp.int32)
    tabs = jnp.ones((Bp, MAX_BLOCKS), jnp.int32)
    zp_f = jnp.zeros(Bp, jnp.float32)
    zf_i = jnp.zeros(Bf, jnp.int32)
    zf_f = jnp.zeros(Bf, jnp.float32)
    mask = jnp.ones(Bf, bool)
    acc = {kind: [] for kind in SERVE_STEP_KINDS}
    for i in range(reps + warmup):
        t0 = time.perf_counter()
        t, _lg, pool = step_one(params, pool, tok1, zp_i, one_p, tabs,
                                zp_i, zp_f)
        jax.block_until_ready(t)
        d_pd = time.perf_counter() - t0
        t0 = time.perf_counter()
        t, _lg, pool = step_chunk(params, pool, tokc, zp_i, full_p, tabs,
                                  zp_i, zp_f)
        jax.block_until_ready(t)
        d_pc = time.perf_counter() - t0
        t0 = time.perf_counter()
        _lg, t2, cc = decode_fn(params, cc, tokf, jnp.int32(3),
                                zf_i, zf_f, mask)
        jax.block_until_ready(t2)
        d_fd = time.perf_counter() - t0
        t0 = time.perf_counter()
        lg, _caches = prefill_fn(params, pref_batch)
        jax.block_until_ready(lg)
        d_fp = time.perf_counter() - t0
        if i >= warmup:  # first interleaved rounds pay the compiles
            acc["paged_decode"].append(d_pd)
            acc["paged_chunk"].append(d_pc)
            acc["fixed_decode"].append(d_fd)
            acc["fixed_prefill"].append(d_fp)
    timings = {k: MS.TimingStats(tuple(v), warmup=warmup)
               for k, v in acc.items()}
    out = {k + "_s": float(np.mean(v)) for k, v in acc.items()}
    out.update(paged_rows=Bp, fixed_rows=Bf, reps=reps, warmup=warmup,
               timing={k: t.to_dict() for k, t in timings.items()})
    out["per_row_ratio"] = ((out["paged_decode_s"] / Bp)
                            / (out["fixed_decode_s"] / Bf)
                            if out["fixed_decode_s"] else float("inf"))
    return out, timings


def normalized_throughput(cont: dict, fixed: dict, so: dict) -> dict:
    """Price each engine's deterministic schedule with the controlled
    interleaved step timings — raw wall clocks on the oversubscribed
    8-virtual-device CPU harness drift 2-3x between cells, but the
    scheduler's tick/step counts are exact, so this is the noise-immune
    tokens/s comparison the gate uses."""
    chunk_ticks = cont["ticks_active"] - cont["decode_only_ticks"]
    pt = (cont["decode_only_ticks"] * so["paged_decode_s"]
          + chunk_ticks * so["paged_chunk_s"])
    ft = (fixed["decode_steps"] * so["fixed_decode_s"]
          + fixed["groups"] * so["fixed_prefill_s"])
    paged_tps = cont["tokens_generated"] / pt if pt else 0.0
    fixed_tps = fixed["tokens"] / ft if ft else 0.0
    return {"paged_compute_s": pt, "fixed_compute_s": ft,
            "paged_tokens_per_s": paged_tps, "fixed_tokens_per_s": fixed_tps,
            "ratio": paged_tps / fixed_tps if fixed_tps else float("inf")}


def bitwise_equivalence(model, topo, params) -> dict:
    """Paged decode vs the contiguous vector-position reference, bitwise.

    fp32 KV, block size 4 (prompt 7 straddles a block boundary), greedy;
    the mesh's tp=4 > n_kv_heads exercises GQA head-slot replication.
    """
    BS, MB = 4, 4
    cap = BS * MB
    prompt_lens = [3, 7, 5, 9]
    B, steps = 4, 4
    mcfg = MiCSConfig(gather_dtype=jnp.float32, kv_dtype="fp32",
                      kv_block_size=BS)
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, model.cfg.vocab, (B, max(prompt_lens)))

    prefill_fn, _ = build_serve_steps(model, topo, mcfg, cap)
    tmpl, _specs = global_cache_shapes(model, topo, B, cap)
    caches_ref = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32), tmpl)
    last_logits = np.zeros((B, model.vocab_padded), np.float32)
    for b in range(B):
        n = prompt_lens[b]
        row = {"tokens": jnp.asarray(
            np.broadcast_to(prompts[b:b + 1, :n], (B, n)).astype(np.int32))}
        logits, caches_b = prefill_fn(params, row)

        def put(dst, src):
            return dst.at[:, b].set(
                jnp.asarray(np.asarray(src)[:, b]).astype(dst.dtype))
        caches_ref = jax.tree.map(put, caches_ref, caches_b)
        last_logits[b] = np.asarray(logits)[b, -1]

    step_ref = PG.build_contiguous_step(model, topo, mcfg, cap)
    step_paged = PG.build_paged_step(model, topo, mcfg, max_blocks=MB,
                                     block_size=BS, chunk=1, kv_dtype="fp32")
    dp = topo.data_parallel_size
    nbl = 16
    allocs = [PG.PagedKVAllocator(nbl, BS) for _ in range(dp)]
    tables = np.zeros((B, MB), np.int32)
    for b in range(B):
        blocks = allocs[b // (B // dp)].alloc(
            PG.blocks_for(prompt_lens[b] + steps, BS))
        tables[b, :len(blocks)] = blocks
    pg_caches, _ = PG.init_paged_caches(model, topo, nbl, BS, "fp32")
    pg_caches = PG.pages_from_contiguous(
        model, topo, caches_ref, pg_caches, tables, prompt_lens,
        block_size=BS, kv_dtype="fp32")

    tok0 = np.argmax(last_logits[:, :model.cfg.vocab], -1).astype(np.int32)
    pos = np.asarray(prompt_lens, np.int32)
    seeds = np.arange(B, dtype=np.int32) * 101
    temps = np.zeros(B, np.float32)
    tok_r = tok_p = jnp.asarray(tok0[:, None])
    ok_tok = ok_log = True
    for s in range(steps):
        p = jnp.asarray(pos + s)
        tr, lr, caches_ref = step_ref(params, caches_ref, tok_r, p,
                                      jnp.asarray(seeds), jnp.asarray(temps))
        tp_, lp, pg_caches = step_paged(
            params, pg_caches, tok_p, p, jnp.ones(B, jnp.int32),
            jnp.asarray(tables), jnp.asarray(seeds), jnp.asarray(temps))
        ok_tok &= bool(np.array_equal(np.asarray(tr), np.asarray(tp_)))
        ok_log &= bool(np.array_equal(
            np.asarray(lr).view(np.uint32), np.asarray(lp).view(np.uint32)))
        tok_r = tr[:, None].astype(jnp.int32)
        tok_p = tp_[:, None].astype(jnp.int32)
    return {"tokens_bitwise": ok_tok, "logits_bitwise": ok_log,
            "block_size": BS, "kv_dtype": "fp32", "steps": steps}


def overload_cell(model, topo, mcfg, n: int) -> dict:
    """Burst overload through the resilient serve loop: ``n`` requests at
    tick 0 against 4 resident rows and a 12-deep bounded queue, with tight
    deadlines on a few and the degradation ladder armed.

    The gate (``check``) asserts the overload-control contract end to end
    on the real engine: typed shedding engages (queue-full + deadline),
    the ladder tightens residency under pressure and restores when it
    clears, the lifecycle ledger accounts 100% of submissions, and the
    loop drains — no deadlock, no silent drops.

    The ladder levels are priced by ``memplan.degradation_levels`` but
    truncated to the residency-tightening rung so the cell stays at the
    configured KV dtype (a kv downshift would recompile the engine and
    change numerics — exercised by tests/serve_chaos_harness.py instead).
    """
    gp, sp = policies_from_config(mcfg)
    levels = degradation_levels(
        model, topo, gp, sp, hbm_bytes=2 * (1 << 30), ctx_len=CAP,
        kv_block_size=BLOCK_SIZE, kv_ceiling=mcfg.kv_dtype)[:2]
    ladder = DegradationLadder(levels, high_water=0.6, low_water=0.2,
                               dwell=2)
    sc = ServeLoopConfig(
        slots_local=2, nb_local=NB_LOCAL, block_size=BLOCK_SIZE,
        max_blocks=MAX_BLOCKS, chunk=CHUNK, top_k=8, reserve="full",
        max_queue=12, evict_cap=2, backoff_base=2, backoff_seed=11, seed=7)
    reqs = make_trace(n, model.cfg.vocab, np.random.default_rng(43))
    for r in reqs[2:5]:
        r.deadline_tick = 2              # unreachable: typed shed at submit
    loop = ResilientServeLoop(model, topo, mcfg, sc, ladder=ladder)
    rep = loop.run(reqs, [0] * len(reqs))
    return {
        "offered": len(reqs),
        "completed_rids": sorted(rep["completions"]),
        "shed": rep["shed"],
        "ledger": rep["ledger"],
        "ladder_levels": levels,
        "ladder_transitions": rep["ladder_transitions"],
        "ladder_max_level": rep["ladder_max_level"],
        "ladder_level": rep["ladder_level"],
        "ticks": rep["ticks"],
    }


def run(smoke: bool) -> dict:
    cfg = smoke_variant(get_config("llama3.2-1b"))
    # GQA path: tp=4 over 2 KV heads -> head-slot replication; dp=2
    topo = MiCSTopology(make_host_mesh(1, 1, 2, 4))
    model = build_model(cfg, tp=topo.model_size)
    state = init_state(model, topo, seed=7)
    params = state["params"]

    mcfg = MiCSConfig(kv_dtype="bf16", kv_block_size=BLOCK_SIZE)
    step_chunk, step_one, prefill_fn, decode_fn = build_engines(
        model, topo, mcfg)

    n = SMOKE_REQUESTS if smoke else N_REQUESTS
    rates = SMOKE_RATES if smoke else RATES
    vocab = model.cfg.vocab
    trace = make_trace(n, vocab, np.random.default_rng(42))
    max_plen = max(len(r.prompt) for r in trace)

    gp, _sp = policies_from_config(mcfg)
    profile = get_profile(PROFILE)
    eq = bitwise_equivalence(model, topo, params)
    so, so_timings = step_overhead(model, topo, mcfg, step_chunk, step_one,
                                   prefill_fn, decode_fn, params, max_plen)
    out = {"mesh": {"data": topo.data_parallel_size,
                    "model": topo.model_size},
           "block_size": BLOCK_SIZE, "max_blocks": MAX_BLOCKS,
           "chunk": CHUNK, "slots": topo.data_parallel_size * SLOTS_LOCAL,
           "fixed_rows": topo.data_parallel_size * FIXED_ROWS_LOCAL,
           "kv_token_slots_per_rank": {
               "paged": (NB_LOCAL - 1) * BLOCK_SIZE,
               "fixed": FIXED_ROWS_LOCAL * CAP},
           "n_requests": n, "kv_dtype": mcfg.kv_dtype,
           "equivalence": eq,
           "step_overhead": so,
           "sweep": {}}
    for rate in rates:
        arrival_ticks = [int(i / rate) for i in range(n)]
        reqs = make_trace(n, vocab, np.random.default_rng(42))  # fresh state
        cont = run_continuous(model, topo, mcfg, step_chunk, step_one, reqs,
                              arrival_ticks)
        # the offered-load timeline in seconds, shared by both engines
        n_ticks = max(cont["ticks"], 1)
        t_tick = cont["wall_s"] / n_ticks
        arrival_s = [t * t_tick for t in arrival_ticks]
        fixed = run_fixed(model, topo, mcfg, prefill_fn, decode_fn,
                          make_trace(n, vocab, np.random.default_rng(42)),
                          arrival_s, params, max_plen)
        pred = cost_decode_step(
            model, topo, profile, gp,
            resident=SLOTS_LOCAL, ctx_len=CAP, kv_dtype=mcfg.kv_dtype,
            chunk=1)
        out["sweep"][str(rate)] = {
            "rate_req_per_tick": rate,
            "paged": cont,
            "fixed": fixed,
            "normalized": normalized_throughput(cont, fixed,
                                                out["step_overhead"]),
            "tokens_per_s_ratio": (
                cont["tokens_per_s"] / fixed["tokens_per_s"]
                if fixed["tokens_per_s"] else float("inf")),
            "predicted_decode_step_s": pred["t_step_s"],
            "predicted_breakdown": pred,
            "measured_decode_step_s": cont["measured_decode_step_s_mean"],
        }
    top = out["sweep"][str(rates[-1])]   # the saturation cell
    out["paged_beats_fixed_at_peak"] = top["normalized"]["ratio"] > 1.0
    out["overload"] = overload_cell(model, topo, mcfg,
                                    n=16 if smoke else 24)
    out["cells"] = matrix_cells(out, cfg, mcfg, so_timings, rates, smoke)
    return out


def matrix_cells(out, cfg, mcfg, so_timings, rates, smoke) -> dict:
    """The serve suite's standard per-cell records (repro.bench.measure):
    the four interleaved step kinds as timing cells, the bitwise
    equivalence + every sweep/overload cell as contract cells, each
    carrying its verdict and the metrics the matrix gates read
    (``rows`` for the per-row decode ratio, ``normalized_ratio`` for the
    saturation throughput bound)."""
    so = out["step_overhead"]
    base = dict(suite="serve", mesh=out["mesh"], model=cfg.name,
                block_size=BLOCK_SIZE, max_blocks=MAX_BLOCKS, chunk=CHUNK,
                kv_dtype=mcfg.kv_dtype, n_requests=out["n_requests"],
                smoke=smoke)
    rows = {"paged_decode": so["paged_rows"], "paged_chunk": so["paged_rows"],
            "fixed_decode": so["fixed_rows"],
            "fixed_prefill": so["fixed_rows"]}
    cells = {}
    for kind in SERVE_STEP_KINDS:
        cells[f"serve/step/{kind}"] = MS.timing_cell(
            dict(base, section="step", cell=kind, reps=so["reps"],
                 warmup=so["warmup"]),
            so_timings[kind], metrics={"rows": rows[kind]})
    eq = out["equivalence"]
    eq_ok = eq["tokens_bitwise"] and eq["logits_bitwise"]
    cells["serve/equivalence"] = MS.contract_cell(
        dict(base, section="equivalence", cell="bitwise",
             eq_block_size=eq["block_size"], eq_kv_dtype=eq["kv_dtype"],
             eq_steps=eq["steps"]),
        eq_ok, detail=None if eq_ok else "paged diverged from contiguous")
    for rate in rates:
        cell = out["sweep"][str(rate)]
        led = cell["paged"]["ledger"]
        ok = (cell["paged"]["finished"] == out["n_requests"]
              and cell["predicted_decode_step_s"] > 0
              and bool(led["accounted"]))
        cells[f"serve/rate/{rate}"] = MS.contract_cell(
            dict(base, section="rate", cell=str(rate)),
            ok,
            metrics={
                "normalized_ratio": cell["normalized"]["ratio"],
                "tokens_per_s_ratio": cell["tokens_per_s_ratio"],
                "predicted_decode_step_s": cell["predicted_decode_step_s"],
                "measured_decode_step_s": cell["measured_decode_step_s"],
            },
            detail=None if ok else
            "unfinished requests or unaccounted ledger")
    ov = out["overload"]
    led = ov["ledger"]
    ov_ok = (bool(led["accounted"]) and led["in_flight"] == 0
             and led["shed"] > 0 and led["completed"] > 0
             and sum(led["shed_by_reason"].values()) == led["shed"]
             and ov["ladder_max_level"] >= 1 and ov["ladder_level"] == 0)
    cells["serve/overload"] = MS.contract_cell(
        dict(base, section="overload", cell="burst", offered=ov["offered"]),
        ov_ok,
        metrics={"shed": led["shed"], "completed": led["completed"],
                 "ladder_max_level": ov["ladder_max_level"]},
        detail=None if ov_ok else "shed/ladder/ledger contract broke")
    return cells


def check(out: dict, smoke: bool) -> None:
    """The standalone gate shim: apply exactly the matrix's declared gates
    for the ``serve`` suite (contracts + the variance-aware per-row decode
    ratio; the saturation throughput bound only in full runs)."""
    from repro.bench.runner import check_suite

    failures = check_suite("serve", out, smoke=smoke)
    if failures:
        print("serve bench gate FAILED:\n  " + "\n  ".join(failures),
              file=sys.stderr)
        sys.exit(1)


def main() -> None:
    # Virtual CPU devices for the host mesh; set before JAX first touches
    # a backend, and only when run as a script, so importing this module
    # leaves the device set alone.
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", "")
    )
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: fewer requests and rates")
    ap.add_argument("--check", action="store_true",
                    help="assert the gate invariants after printing JSON")
    args = ap.parse_args()
    result = run(args.smoke)
    print(json.dumps(result, indent=1))
    if args.check:
        check(result, args.smoke)


if __name__ == "__main__":
    main()
