"""Fault-tolerant, elastic training driver.

Production posture (DESIGN.md §6; scales the same way at 1000+ nodes):

* **Checkpoint/restart** — periodic async sharded checkpoints; on start the
  loop resumes from the newest complete checkpoint, including the data
  cursor (the synthetic pipeline is seekable, so no sample is replayed or
  skipped).
* **Failure handling** — any step raising a device/runtime error triggers
  rollback-and-retry from the last checkpoint; repeated failures of the same
  step re-raise (poison-step guard).  On real clusters the same hook is
  where a missing-heartbeat / SPMD barrier timeout lands.
* **Elastic world changes** — with an :class:`ElasticConfig`, a
  :class:`repro.core.faults.WorldChangeError` (preemption / grow-back) is
  survived in-loop: an emergency checkpoint is taken while the old world is
  still intact (when the event came with notice), the surviving device set
  is re-factored into a fresh ``MiCSTopology``
  (``core/topology.elastic_host_topology``), ``autotune.resolve_world``
  re-picks partition-group size + carry for the new world (the paper's
  §3.1 rule re-run on the survivors, when ``hbm_budget_gb`` is set), the
  step function is rebuilt, and the newest complete checkpoint is restored
  cross-topology.  Every change lands in the ``LoopStats.world_changes``
  ledger; the retry budget and backoff are bounded
  (``ElasticConfig.max_world_changes`` / ``backoff_s``).  The resumed
  trajectory is bitwise identical to a cold restore of the same checkpoint
  on the same surviving topology (tests/elastic_harness.py).
* **Straggler mitigation** — on TPU SPMD a straggler stalls the collective,
  so mitigation happens at the *input* layer: the loader prefetches ahead on
  a worker thread and the loop tracks a step-time EWMA, flagging steps
  slower than `straggler_factor` x the EWMA; an injected
  :class:`repro.core.faults.StragglerError` (the production evict decision)
  rides the rollback-and-retry path.

Deterministic fault injection: pass a ``core/faults.FaultPlan`` as
``fault_injector`` — the loop binds its crash-during-save leg to the
checkpointer automatically, and every scripted event fires exactly once.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.checkpoint.checkpointer import Checkpointer
from repro.core.autotune import resolve_world
from repro.core.faults import FaultError, WorldChangeError
from repro.core.mics import MiCSConfig, build_train_step, init_state
from repro.core.topology import MiCSTopology, elastic_host_topology
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models.build import build_model
from repro.models.lm import ModelDef
from repro.optim.adamw import OptConfig

log = logging.getLogger("repro.train")


@dataclasses.dataclass
class LoopConfig:
    total_steps: int = 100
    checkpoint_every: int = 50
    checkpoint_dir: str = "checkpoints"
    log_every: int = 10
    straggler_factor: float = 3.0
    max_step_retries: int = 2
    seed: int = 0


@dataclasses.dataclass
class ElasticConfig:
    """How the loop survives world changes (preemptible/spot capacity).

    ``max_world_changes`` bounds the rebuild budget — a flapping cluster
    re-raises rather than thrashing forever.  ``backoff_s`` sleeps
    ``backoff_s * attempt`` before each rebuild (keep 0 in tests; on a real
    cluster this is where the coordinator's membership settles)."""

    max_world_changes: int = 8
    backoff_s: float = 0.0


@dataclasses.dataclass
class LoopStats:
    losses: list
    step_times: list
    straggler_steps: list
    restarts: int = 0
    world_changes: list = dataclasses.field(default_factory=list)
    emergency_saves: int = 0
    save_failures: int = 0


def train(model: ModelDef, topo: MiCSTopology, mcfg: MiCSConfig,
          oc: OptConfig, dc: DataConfig, lc: LoopConfig,
          fault_injector: Callable[[int], None] | None = None,
          elastic: ElasticConfig | None = None) -> LoopStats:
    ckpt = Checkpointer(lc.checkpoint_dir)
    if hasattr(fault_injector, "bind"):   # a core/faults.FaultPlan
        fault_injector.bind(ckpt)
    source = SyntheticLM(dc)
    stats = LoopStats([], [], [], 0)

    topo_cur, mcfg_cur = topo, mcfg
    tp = topo.model_size
    world = topo.world_size
    step_fn = build_train_step(model, topo_cur, mcfg_cur, oc)

    def _try_save(state, step, cursor, *, blocking, emergency=False) -> bool:
        """Checkpoint, absorbing writer crashes into the stats ledger.

        A held failure from a previous async save surfaces here too (the
        checkpointer re-raises it from ``save``'s internal ``wait``); one
        retry keeps the checkpoint cadence after a crashed writer."""
        for attempt in (0, 1):
            try:
                with TraceAnnotation("train.save"):
                    ckpt.save(state, step, topo=topo_cur, data_cursor=cursor,
                              blocking=blocking, emergency=emergency)
                return True
            except Exception as e:  # noqa: BLE001 - failure domain boundary
                stats.save_failures += 1
                log.warning("checkpoint save at step %d failed (%s)%s",
                            step, e, "; retrying" if attempt == 0 else "")
        return False

    start = ckpt.latest_step()
    if start is not None:
        state, meta = ckpt.restore(model, topo_cur)
        cursor = meta["data_cursor"]
        log.info("resumed from step %d", start)
    else:
        state = init_state(model, topo_cur, seed=lc.seed)
        cursor = 0

    ewma = None
    measured = 0   # steps timed since the last (re)compile
    step = int(np.asarray(state["step"]))
    retries = 0
    while step < lc.total_steps:
        with jax.profiler.StepTraceAnnotation("train", step_num=step):
            with TraceAnnotation("train.batch"):
                batch = jax.tree.map(
                    jax.numpy.asarray, source.global_step_batch(cursor))
            t0 = time.time()
            try:
                if fault_injector is not None:
                    fault_injector(step)
                with TraceAnnotation("train.step"):
                    state, metrics = step_fn(state, batch)
                with TraceAnnotation("train.loss_read"):
                    loss = float(metrics["loss"])  # blocks; surfaces device errors
            except WorldChangeError as e:
                stats.restarts += 1
                if elastic is None:
                    raise
                if len(stats.world_changes) >= elastic.max_world_changes:
                    log.error("world changed %d times; giving up",
                              len(stats.world_changes))
                    raise
                new_world = world - e.lost + e.gained
                fired_step = step
                log.warning("world change at step %d (%s): %d -> %d devices",
                            step, e, world, new_world)
                if e.notice:
                    # the old world is still intact (preemption notice / grow
                    # announcement): emergency-save so zero steps are lost.
                    if _try_save(state, step, cursor, blocking=True,
                                 emergency=True):
                        stats.emergency_saves += 1
                else:
                    try:
                        ckpt.wait()   # let an in-flight periodic save land
                    except FaultError as we:
                        stats.save_failures += 1
                        log.warning("in-flight save lost to the crash (%s)", we)
                if elastic.backoff_s:
                    time.sleep(elastic.backoff_s * (len(stats.world_changes) + 1))
                topo_cur, mcfg_cur, info = resize_for_world(
                    model, mcfg, new_world, tp=tp,
                    partition_size=topo_cur.partition_size)
                step_fn = build_train_step(model, topo_cur, mcfg_cur, oc)
                if ckpt.latest_step() is not None:
                    state, meta = ckpt.restore(model, topo_cur)
                    cursor = meta["data_cursor"]
                else:
                    state = init_state(model, topo_cur, seed=lc.seed)
                    cursor = 0
                step = int(np.asarray(state["step"]))
                world = new_world
                stats.world_changes.append({
                    "at_step": int(fired_step),
                    "kind": "grow" if e.gained else "preempt",
                    "lost": e.lost, "gained": e.gained, "notice": e.notice,
                    "world": new_world, "resumed_step": step, **info,
                })
                log.warning("resumed at step %d on %d devices (p=%d, %s)",
                            step, new_world, topo_cur.partition_size,
                            info["rule"])
                ewma = None
                measured = 0   # the rebuilt step_fn recompiles on first use
                retries = 0
                continue
            except Exception as e:  # noqa: BLE001 - failure domain boundary
                stats.restarts += 1
                retries += 1
                if retries > lc.max_step_retries:
                    raise
                log.warning("step %d failed (%s); rolling back", step, e)
                prev = ckpt.latest_step()
                if prev is not None:
                    state, meta = ckpt.restore(model, topo_cur)
                    cursor = meta["data_cursor"]
                    step = int(np.asarray(state["step"]))
                else:
                    state = init_state(model, topo_cur, seed=lc.seed)
                    cursor = 0
                    step = 0
                continue
            retries = 0
            dt = time.time() - t0
            measured += 1
            if measured > 1:
                # the first step after a (re)compile pays tracing+compilation;
                # seeding the EWMA with it would mask real stragglers for many
                # steps, so the detector warms up from the second step on.
                ewma = dt if ewma is None else 0.9 * ewma + 0.1 * dt
            if ewma is not None and dt > lc.straggler_factor * ewma \
                    and len(stats.step_times) > 3:
                stats.straggler_steps.append(step)
                log.warning("straggler: step %d took %.2fs (ewma %.2fs)",
                            step, dt, ewma)
            stats.losses.append(loss)
            stats.step_times.append(dt)
            cursor += 1
            step += 1
            if lc.log_every and step % lc.log_every == 0:
                log.info("step %d loss %.4f (%.2fs)", step, loss, dt)
            if lc.checkpoint_every and step % lc.checkpoint_every == 0:
                _try_save(state, step, cursor, blocking=False)

    try:
        ckpt.wait()
    except Exception as e:  # noqa: BLE001
        stats.save_failures += 1
        log.warning("final wait surfaced a crashed save (%s)", e)
    _try_save(state, step, cursor, blocking=True)
    return stats


def resize_for_world(model, mcfg: MiCSConfig, n_devices: int, *, tp: int = 1,
                     partition_size: int | None = None,
                     local_batch: int = 0, seq: int = 0
                     ) -> tuple[MiCSTopology, MiCSConfig, dict]:
    """(topology, config, ledger info) for an ``n_devices`` world.

    The one rebuild path both the in-loop world-change handler and a cold
    :func:`elastic_restart` share, so the two are bitwise-interchangeable:
    ``autotune.resolve_world`` re-picks the partition-group size
    (§3.1 re-run on the survivors under ``mcfg.hbm_budget_gb``; without a
    budget the previous ``partition_size`` is kept where it divides), then
    the survivors are re-meshed contiguously
    (``core/topology.elastic_host_topology``).
    """
    p, mcfg2, info = resolve_world(
        model, mcfg, n_devices=n_devices, tp=tp,
        partition_size=partition_size, local_batch=local_batch, seq=seq)
    return elastic_host_topology(n_devices, p, tp), mcfg2, info


def elastic_restart(checkpoint_dir: str, cfg, new_topo: MiCSTopology,
                    mcfg: MiCSConfig, oc: OptConfig, step: int | None = None):
    """Resume a run on a different topology (pod loss / regrowth).

    Returns (model, state, step_fn, meta) resharded for `new_topo`.
    ``step=None`` restores the newest complete checkpoint; pass an explicit
    step to cold-restore the exact checkpoint an in-loop world change
    resumed from (the bitwise-equivalence reference of the kill-a-device
    test).  Pair with :func:`resize_for_world` to pick ``new_topo`` and the
    matching config the in-loop path would have chosen.
    """
    model = build_model(cfg, tp=new_topo.model_size)
    ckpt = Checkpointer(checkpoint_dir)
    state, meta = ckpt.restore(model, new_topo, step)
    step_fn = build_train_step(model, new_topo, mcfg, oc)
    return model, state, step_fn, meta
