"""Serving launcher: fixed-batch greedy decode or resilient continuous
batching.

Weight gathers run through the same CommEngine as training (decode
re-gathers every layer each step); ``--policy auto`` lets the link-model
autotuner pick the gather topology/wire dtype for ``--link-profile``
(serving mode: forward gathers only, so int8 wire wins once
``--quant-gather`` permits it) and prints the ranked serve table —
candidates now carry the decode axes too: KV dtype (up to the
``--kv-dtype`` numerics ceiling), block size and planner-derived
residency, priced by ``cost_decode_step`` at ``--arrival-rate``.

``--continuous`` switches to the fault-tolerant continuous-batching
engine (runtime/resilient.py): a seeded request trace through the paged
scheduler with deadline-aware admission (``--deadline-ms``, mapped to
scheduler ticks via the measured warm step time), a bounded queue
(``--max-queue``), graceful degradation (``--shed-policy degrade``) and a
scripted fault timeline (``--fault-plan "preempt@20x4,grow@40x4,crash@60"``
— see ``core/faults.FaultPlan.parse``; world-change faults need a
multi-device mesh, e.g. under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  At exit the
request-lifecycle ledger is printed: where every submission ended up
(completed / shed-with-reason / replayed), latency and queue-depth
percentiles in ticks, world changes and ladder transitions.

Runnable on this host with reduced configs:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --prompt-len 16 --decode-tokens 8 --policy auto --arrival-rate 0.5
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --continuous --requests 8 --max-queue 6 --deadline-ms 2000 \
      --shed-policy degrade --fault-plan crash@6
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, smoke_variant
from repro.core.autotune import resolve_config
from repro.core.faults import FaultPlan
from repro.core.mics import MiCSConfig, init_state
from repro.core.quant import quantize_state
from repro.core.topology import (
    MiCSTopology, elastic_host_topology, make_host_mesh,
)
from repro.models.build import build_model
from repro.runtime.serving import build_serve_steps


def serve_continuous(cfg, mcfg, args) -> None:
    """The resilient continuous-batching path (runtime/resilient.py)."""
    from repro.runtime.batching import DegradationLadder, Request
    from repro.runtime.resilient import ResilientServeLoop, ServeLoopConfig

    # the mesh spans every ambient device (dp = world, tp = 1), so scripted
    # world-change faults have devices to lose
    n_dev = len(jax.devices())
    topo = elastic_host_topology(n_dev, 1, tp=1)
    model = build_model(cfg, tp=1)

    block_size = mcfg.kv_block_size
    positions = args.prompt_len + args.decode_tokens
    max_blocks = -(-positions // block_size)
    sc = ServeLoopConfig(
        slots_local=4, nb_local=4 * max_blocks + 1, block_size=block_size,
        max_blocks=max_blocks, chunk=min(8, args.prompt_len), top_k=8,
        reserve="full", max_queue=args.max_queue, backoff_base=2,
        arrival_rate=args.arrival_rate)
    ladder = None
    if args.shed_policy == "degrade":
        ladder = DegradationLadder(
            [{"kv_dtype": mcfg.kv_dtype, "resident_cap": 0,
              "label": "configured"},
             {"kv_dtype": mcfg.kv_dtype, "resident_cap": 2,
              "label": "tightened"}],
            high_water=0.75, low_water=0.25, dwell=4)
    fault = FaultPlan.parse(args.fault_plan) if args.fault_plan else None
    loop = ResilientServeLoop(model, topo, mcfg, sc,
                              fault_injector=fault, ladder=ladder)

    # warm the decode step and measure it: the tick -> wall-time price that
    # turns --deadline-ms into a scheduler-tick deadline
    B = loop.batcher.batch
    zero = lambda s, d: jnp.zeros(s, d)
    for _ in range(3):
        t0 = time.time()
        tok, _lg, caches = loop.step_one(
            loop.params, loop.caches, zero((B, 1), jnp.int32),
            zero((B,), jnp.int32), zero((B,), jnp.int32),
            zero((B, max_blocks), jnp.int32), zero((B,), jnp.int32),
            zero((B,), jnp.float32))
        jax.block_until_ready(tok)
        loop.caches = caches
        tick_s = time.time() - t0
    deadline_ticks = (max(1, int(args.deadline_ms / 1e3 / tick_s))
                      if args.deadline_ms > 0 else None)
    print(f"warm decode step: {tick_s*1e3:.1f} ms/tick"
          + (f" -> deadline {deadline_ticks} ticks" if deadline_ticks
             else ""))

    rng = np.random.default_rng(0)
    reqs = [Request(
        rid=i,
        prompt=rng.integers(1, cfg.vocab, args.prompt_len).astype(int)
        .tolist(),
        max_new_tokens=args.decode_tokens, temperature=0.7, seed=1000 + i,
        deadline_tick=deadline_ticks)
        for i in range(args.requests)]
    arrivals = ([int(i / args.arrival_rate) for i in range(len(reqs))]
                if args.arrival_rate > 0 else None)

    t0 = time.time()
    rep = loop.run(reqs, arrivals)
    dt = time.time() - t0
    tokens = sum(len(t) for t in rep["completions"].values())
    print(f"served {rep['ledger']['completed']}/{len(reqs)} requests, "
          f"{tokens} tokens in {dt:.2f}s ({tokens/dt:.1f} tok/s), "
          f"{rep['ticks']} ticks on a {rep['world']}-device world")
    print("lifecycle ledger:", json.dumps(rep["ledger"], indent=1))
    if rep["world_changes"]:
        print("world changes:", json.dumps(rep["world_changes"], indent=1,
                                           default=str))
    if rep["ladder_transitions"]:
        print("ladder transitions:",
              json.dumps(rep["ladder_transitions"], indent=1))
    if rep["shed"]:
        print("shed:", rep["shed"])
    assert rep["ledger"]["accounted"], "lifecycle ledger lost a request"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--decode-tokens", type=int, default=8)
    ap.add_argument("--policy", choices=["manual", "auto"], default="manual",
                    help="'auto' picks the gather policy from --link-profile")
    ap.add_argument("--link-profile", default="v5e")
    ap.add_argument("--quant-gather", action="store_true",
                    help="int8 wire gathers (a permission under --policy "
                         "auto)")
    ap.add_argument("--prefetch", type=int, default=1,
                    help="1 = double-buffered lookahead gathers, 0 = serial")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="offered load (requests/s/replica) the serve "
                         "autotuner prices decode policies against; 0 = "
                         "throughput-saturated")
    ap.add_argument("--kv-dtype", choices=["fp32", "bf16", "int8"],
                    default="bf16",
                    help="KV-cache storage dtype; under --policy auto this "
                         "is the numerics ceiling the planner may narrow to")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="paged-KV block size in token positions")
    ap.add_argument("--max-resident-requests", type=int, default=0,
                    help="cap on concurrently resident requests per "
                         "replica; 0 = planner-derived from the HBM budget")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching through the resilient serve "
                         "loop instead of the fixed-batch path")
    ap.add_argument("--requests", type=int, default=8,
                    help="[--continuous] synthetic requests to serve")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="[--continuous] per-request completion SLO; "
                         "mapped to scheduler ticks via the measured warm "
                         "step time (0 = no deadline)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="[--continuous] waiting-queue bound; submissions "
                         "beyond it are shed with reason queue_full "
                         "(0 = unbounded)")
    ap.add_argument("--shed-policy", choices=["reject", "degrade"],
                    default="reject",
                    help="[--continuous] 'reject' sheds typed on overload; "
                         "'degrade' also walks the degradation ladder "
                         "(residency tightening) under queue pressure")
    ap.add_argument("--fault-plan", default="",
                    help="[--continuous] scripted fault timeline, e.g. "
                         "'preempt@20x4,grow@40x4,crash@60' "
                         "(kind@tick[xN]; kinds: preempt notice grow slow "
                         "evict crash)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    topo = MiCSTopology(make_host_mesh(1, 1, 1, 1))
    model = build_model(cfg, tp=topo.model_size)
    state = init_state(model, topo)
    params = state["params"]

    cache_len = args.prompt_len + args.decode_tokens
    mcfg = MiCSConfig(policy=args.policy, link_profile=args.link_profile,
                      quant_gather=args.quant_gather,
                      prefetch=bool(args.prefetch),
                      kv_dtype=args.kv_dtype,
                      kv_block_size=args.kv_block_size,
                      max_resident_requests=args.max_resident_requests)
    mcfg, plan = resolve_config(mcfg, model, topo, mode="serve",
                                seq=cache_len,
                                arrival_rate=args.arrival_rate)
    if plan is not None:
        print(plan.table())
        print(f"serve policy: kv_dtype={mcfg.kv_dtype} "
              f"kv_block_size={mcfg.kv_block_size} "
              f"max_resident_requests={mcfg.max_resident_requests}")
    if args.continuous:
        if mcfg.quant_gather:
            # the resilient loop's params provider reloads fp weights on
            # every world change; int8 wire stays a fixed-path feature
            mcfg = dataclasses.replace(mcfg, quant_gather=False)
        serve_continuous(cfg, mcfg, args)
        return
    if mcfg.quant_gather:  # deployment-time int8 conversion (quant.py)
        params = quantize_state(params)
    prefill_fn, decode_fn = build_serve_steps(
        model, topo, mcfg, cache_len)

    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)}
    if cfg.family == "vlm":
        batch["vision"] = jnp.asarray(
            rng.normal(size=(args.batch, cfg.n_vision_tokens, cfg.d_model)),
            jnp.bfloat16)
    if cfg.family == "encdec":
        batch["audio"] = jnp.asarray(
            rng.normal(size=(args.batch, cfg.n_audio_frames, cfg.d_model)),
            jnp.bfloat16)

    t0 = time.time()
    logits, caches = prefill_fn(params, batch)
    print(f"prefill {args.batch}x{args.prompt_len}: {time.time()-t0:.2f}s")

    # greedy continuation
    tok = jnp.argmax(jnp.asarray(logits[:, -1:]), axis=-1).astype(jnp.int32)
    outs = []
    t0 = time.time()
    for i in range(args.decode_tokens):
        pos = jnp.int32(args.prompt_len + i)
        logits, tok, caches = decode_fn(params, caches, tok, pos)
        tok = tok.astype(jnp.int32)
        outs.append(np.asarray(tok)[:, 0])
    dt = time.time() - t0
    print(f"decoded {args.decode_tokens} tokens x{args.batch} in {dt:.2f}s "
          f"({args.decode_tokens*args.batch/dt:.1f} tok/s)")
    print("sampled ids:", np.stack(outs, axis=1).tolist())


if __name__ == "__main__":
    main()
