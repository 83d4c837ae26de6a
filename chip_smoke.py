"""Smoke run of the training path on TPU.

Trains the paper's BERT-10B (Table 1: hidden 2560, FFN 10240, 40 heads,
vocab 32008) at its published widths, depth cut to 4 of 127 layers so one
v5e chip holds the weights, the AdamW state and the activations, for a few
steps of seeded synthetic data — through the construction path of
``repro.launch.train`` and ``runtime.train_loop.train``.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # repl=2 x shard=2 mesh vs one chip

This is a smoke run, not a benchmark: the times it prints are single
untuned samples, and the first step's includes compilation.  It exits
non-zero, with no ``ok`` line, unless JAX finds a TPU and every check
holds.  The last line of a passing run is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "bert-10b"
LAYERS = 4          # of 127: what one 16 GiB chip holds with AdamW state
GLOBAL_BATCH = 8
SEQ = 512           # the config's max_seq
MICRO_STEPS = 2
STEPS = 8
# Peak learning rate x d_model.  Adam's first steps move every weight by
# about the learning rate, so the change to a layer's output grows with
# its width: the rate is scaled by 1/d_model (about 1e-4 at 2560; 1e-3
# made the loss rise there).
LR_X_WIDTH = 0.25

# --four-chips agreement with the one-chip reference.  Both runs start from
# the same init and see the same batches, but they are not bitwise equal.
# Both gather bf16 weights (the bf16 wire), but one chip sums the whole
# batch's weight gradient inside each matmul, while the mesh sums
# per-device bf16 cotangents in its hop-1 reduce-scatter and the replicas
# in hop 2, in another order: gradients differ by bf16 rounding.  Losses must
# agree to within LOSS_RTOL, relative.  Parameters must agree to within
# PARAM_RTOL of how far training moved them (||p_mesh - p_ref|| over
# ||p_ref - p_init||, per pool): a shard gathered from or written to the
# wrong device moves a pool by the size of its update or more.
LOSS_RTOL = 5e-3
PARAM_RTOL = 0.2


def smoke_config():
    """bert-10b at published widths, depth cut to ``LAYERS``."""
    from repro.configs import get_config

    return dataclasses.replace(get_config(ARCH), n_layers=LAYERS)


def train_once(cfg, topo, ckpt_dir, *, steps, global_batch, seq,
               micro_steps):
    """Train into the empty ``ckpt_dir`` with step retries off; prints the
    loop's wall time beside the time its steps took."""
    from repro.core.mics import MiCSConfig
    from repro.launch.train import build_training

    run = build_training(
        cfg, topo, MiCSConfig(micro_steps=micro_steps), steps=steps,
        global_batch=global_batch, seq=seq, lr=LR_X_WIDTH / cfg.d_model,
        checkpoint_dir=ckpt_dir, checkpoint_every=0, max_step_retries=0)
    t0 = time.perf_counter()
    stats = run.train()
    wall = time.perf_counter() - t0
    print(f"smoke run: train() {wall:.1f}s, of which steps "
          f"{sum(stats.step_times):.1f}s (the rest: init, data, final save)")
    return run, stats


def final_params(run, ckpt_dir):
    """The params the run's final checkpoint holds, as host arrays."""
    from repro.checkpoint.checkpointer import Checkpointer

    return Checkpointer(ckpt_dir).load_host(run.model)[0]["params"]


def training_failures(stats, steps: int, vocab: int) -> list[str]:
    """Why a run does not count as training; empty when it does."""
    losses = stats.losses
    fails = []
    if len(losses) != steps:
        fails.append(f"{len(losses)} losses for {steps} steps")
    if not all(math.isfinite(x) for x in losses):
        fails.append(f"non-finite loss in {losses}")
    elif losses:
        uniform = math.log(vocab)
        if abs(losses[0] - uniform) > 0.1 * uniform:
            fails.append(f"first loss {losses[0]:.4f} not within 10% of "
                         f"ln(vocab) = {uniform:.4f}")
        if not losses[-1] < losses[0]:
            fails.append(f"loss did not fall: {losses[0]:.4f} -> "
                         f"{losses[-1]:.4f}")
    if stats.restarts:
        fails.append(f"{stats.restarts} step restarts")
    if stats.save_failures:
        fails.append(f"{stats.save_failures} checkpoint save failures")
    return fails


def one_chip_phase(cfg, *, steps=STEPS, global_batch=GLOBAL_BATCH, seq=SEQ,
                   micro_steps=MICRO_STEPS) -> list[str]:
    """Train on the first device; returns the failed checks."""
    import jax

    from repro.core.topology import elastic_host_topology

    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as d:
        _, stats = train_once(
            cfg, elastic_host_topology(1, 1), d, steps=steps,
            global_batch=global_batch, seq=seq, micro_steps=micro_steps)
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print("smoke run (not a benchmark): one device, "
          f"losses {[round(x, 4) for x in stats.losses]}")
    print(f"smoke run: first step (compile + run) {stats.step_times[0]:.2f}s;"
          f" later steps {[round(t, 4) for t in stats.step_times[1:]]} s")
    print(f"smoke run: peak_bytes_in_use {peak}")
    return training_failures(stats, steps, cfg.vocab)


def _rel_param_diff(p_mesh, p_ref, p_init) -> dict[str, float]:
    import numpy as np

    out = {}
    for pool in p_ref:
        moved = np.linalg.norm(p_ref[pool] - p_init[pool])
        out[pool] = float(np.linalg.norm(p_mesh[pool] - p_ref[pool])
                          / max(moved, 1e-30))
    return out


def four_chip_phase(cfg, *, steps=STEPS, global_batch=GLOBAL_BATCH, seq=SEQ,
                    micro_steps=MICRO_STEPS) -> list[str]:
    """The same run on a repl=2 x shard=2 mesh and on one of its chips;
    returns the failed checks."""
    import jax

    from repro.core.mics import init_state
    from repro.core.topology import elastic_host_topology

    one = elastic_host_topology(1, 1)
    kw = dict(steps=steps, global_batch=global_batch, seq=seq,
              micro_steps=micro_steps)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as d:
        run, ref = train_once(cfg, one, d, **kw)
        p_ref = final_params(run, d)
    p_init = jax.device_get(init_state(run.model, one)["params"])
    held = [(d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()[:4]]
    print(f"smoke run: bytes_in_use per device before the mesh run {held}")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as d:
        mesh, got = train_once(cfg, elastic_host_topology(4, 2), d, **kw)
        p_mesh = final_params(mesh, d)
    shape = dict(zip(mesh.topo.mesh.axis_names, mesh.topo.mesh.devices.shape))
    print(f"smoke run (not a benchmark): mesh {shape} vs one device")
    print(f"smoke run: mesh losses {[round(x, 4) for x in got.losses]}")
    print(f"smoke run: ref  losses {[round(x, 4) for x in ref.losses]}")
    print(f"smoke run: mesh step times {[round(t, 4) for t in got.step_times]}"
          " s (first includes compile)")
    fails = [f"mesh: {f}" for f in training_failures(got, steps, cfg.vocab)]
    fails += [f"ref: {f}" for f in training_failures(ref, steps, cfg.vocab)]
    worst = max((abs(a - b) / abs(b) for a, b in zip(got.losses, ref.losses)),
                default=math.inf)
    print(f"smoke run: worst loss mismatch {worst:.3e} (tolerance "
          f"{LOSS_RTOL:g})")
    if not worst <= LOSS_RTOL:
        fails.append(f"losses disagree by {worst:.3e} > {LOSS_RTOL:g}")
    rel = _rel_param_diff(p_mesh, p_ref, p_init)
    print(f"smoke run: param mismatch / update, per pool {rel} (tolerance "
          f"{PARAM_RTOL:g})")
    fails += [f"pool {k} params disagree by {v:.3e} of the update > "
              f"{PARAM_RTOL:g}" for k, v in rel.items() if not v <= PARAM_RTOL]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:4]]
    print(f"smoke run: peak_bytes_in_use per device {peaks}")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the repl=2 x shard=2 mesh and its "
                         "one-chip reference (needs four chips)")
    args = ap.parse_args(argv)

    import jax

    from repro.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU, JAX found {dev.platform}", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    cfg = smoke_config()
    print(f"smoke config: {cfg.name} d_model={cfg.d_model} d_ff={cfg.d_ff} "
          f"heads={cfg.n_heads} vocab={cfg.vocab} at published widths; "
          f"reduced: n_layers 127 -> {cfg.n_layers}; global batch "
          f"{GLOBAL_BATCH} x seq {SEQ}, micro_steps {MICRO_STEPS}, "
          f"{STEPS} steps")
    t0 = time.perf_counter()
    phase = four_chip_phase if args.four_chips else one_chip_phase
    fails = phase(cfg)
    print(f"smoke run: wall {time.perf_counter() - t0:.1f}s")
    if fails:
        print("chip_smoke FAILED:\n  " + "\n  ".join(fails), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
