"""Sharded checkpointing with elastic resharding.

Fault-tolerance model (DESIGN.md §6; targets 1000+ nodes):

* **Sharded save** — each host writes only the shards it owns (here: the
  process-local addressable shards) as one .npz per pool plus a JSON
  manifest carrying the step, mesh descriptor, partition-group size and data
  -pipeline cursor.  No host ever materializes the full model.
* **Atomicity** — writes go to ``step_XXXXXX.tmp/`` and are renamed into
  place only after the manifest is fsync'd; a crashed save can never corrupt
  the latest valid checkpoint.  Restart scans for the newest *complete* one:
  ``latest_step`` skips ``.tmp`` dirs, stray non-numeric ``step_*`` names,
  and dirs whose manifest/state blob is missing or truncated (the
  kill-the-writer scenarios tests/test_checkpoint.py +
  tests/elastic_harness.py script via ``fault_hook``).
* **Elastic resharding** — restore may target a *different* topology
  (partition-group size, replication degree, or pod count).  Because model
  states are flat vectors, resharding is pure index arithmetic: the global
  [stack, tp, flat_len] array is reassembled logically and re-partitioned
  under the new topology's NamedShardings.  This is what lets the framework
  resume after losing a pod (512 -> 256 chips) or growing back.
* **Emergency save** — a preemption notice (runtime/train_loop.py elastic
  path) triggers a blocking ``save(..., emergency=True)`` of the still-
  intact state, tagged in the manifest, so a world change with notice loses
  zero steps.
* **Async save** — serialization happens on a worker thread; the train loop
  only blocks if a second save is requested before the first lands.  A
  writer-thread failure is held and re-raised from the next ``wait()`` /
  ``save()`` — never silently swallowed.
"""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import threading
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mics import state_shardings
from repro.core.topology import MiCSTopology
from repro.models.lm import ModelDef

MANIFEST = "manifest.json"
STATE_BLOB = "state.npz"


def _fsync(path: pathlib.Path) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


class Checkpointer:
    def __init__(self, directory: str | pathlib.Path):
        self.dir = pathlib.Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self._worker: threading.Thread | None = None
        self._exc: BaseException | None = None
        # Test/fault-injection hook (core/faults.FaultPlan.bind): called as
        # fault_hook(phase, tmp_dir, meta) from the writer thread; raising
        # simulates the writer dying mid-save.
        self.fault_hook: Callable[[str, pathlib.Path, dict], None] | None = None

    # -- save ---------------------------------------------------------------
    def save(self, state, step: int, *, topo: MiCSTopology,
             data_cursor: int = 0, blocking: bool = True,
             emergency: bool = False):
        """Snapshot `state` at `step`.  Arrays are fetched to host first (so
        the device buffers donate-rotate freely) and written by a worker.
        ``emergency=True`` tags a preemption-triggered save in the manifest
        (the train loop's response to a world-change notice)."""
        host_state = jax.tree.map(np.asarray, state)
        meta = {
            "step": int(step),
            "data_cursor": int(data_cursor),
            "time": time.time(),
            "mesh_axes": dict(zip(topo.mesh.axis_names, topo.mesh.devices.shape)),
            "partition_axes": list(topo.partition_axes),
            "replication_axes": list(topo.replication_axes),
            "emergency": bool(emergency),
        }
        self.wait()
        self._worker = threading.Thread(
            target=self._write_guarded, args=(host_state, meta), daemon=True)
        self._worker.start()
        if blocking:
            self.wait()

    def wait(self):
        """Join the in-flight save; re-raise its failure, if any."""
        if self._worker is not None:
            self._worker.join()
            self._worker = None
        if self._exc is not None:
            exc, self._exc = self._exc, None
            raise exc

    def _write_guarded(self, host_state, meta):
        try:
            self._write(host_state, meta)
        except BaseException as e:  # noqa: BLE001 - held for wait()
            self._exc = e

    def _write(self, host_state, meta):
        step = meta["step"]
        tmp = self.dir / f"step_{step:08d}.tmp"
        final = self.dir / f"step_{step:08d}"
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        flat, treedef = jax.tree_util.tree_flatten_with_path(host_state)
        names = []
        arrays = {}
        for i, (path, leaf) in enumerate(flat):
            key = f"leaf_{i:04d}"
            names.append("/".join(str(getattr(p, "key", p)) for p in path))
            arrays[key] = leaf
        np.savez(tmp / STATE_BLOB, **arrays)
        meta["leaves"] = names
        if self.fault_hook is not None:
            # state blob is on disk, manifest is not: the mid-save kill
            # window the atomicity contract is tested against.
            self.fault_hook("pre_manifest", tmp, meta)
        mpath = tmp / MANIFEST
        mpath.write_text(json.dumps(meta, indent=1))
        _fsync(mpath)
        if final.exists():
            shutil.rmtree(final)
        tmp.rename(final)

    # -- restore --------------------------------------------------------------
    def _complete(self, path: pathlib.Path) -> bool:
        """True iff `path` is a fully-written ``step_<N>`` checkpoint dir."""
        if path.name.endswith(".tmp") or not path.name[len("step_"):].isdigit():
            return False
        if not (path / STATE_BLOB).exists():
            return False
        try:
            json.loads((path / MANIFEST).read_text())
        except (OSError, ValueError):
            return False   # missing or truncated manifest (crashed writer)
        return True

    def latest_step(self) -> int | None:
        """Newest *complete* checkpoint step (None if there is none).

        Skips ``.tmp`` dirs, malformed names (a stray ``step_old`` must not
        crash the scan), and dirs with a missing/truncated manifest or
        state blob — everything a crashed writer can leave behind.
        """
        steps = sorted(
            int(p.name[len("step_"):]) for p in self.dir.glob("step_*")
            if self._complete(p)
        )
        return steps[-1] if steps else None

    def restore(self, model: ModelDef, topo: MiCSTopology,
                step: int | None = None):
        """Load a checkpoint onto (possibly different) `topo`.

        Returns (state, meta).  Cross-topology restores reshard via the flat
        layout — the on-disk representation is topology-agnostic global
        arrays (params and both AdamW moments), so nothing special is
        needed beyond new out-shardings.
        """
        state_host, meta = self.load_host(model, step)
        shardings = state_shardings(model, topo)
        with topo.mesh:
            state = jax.tree.map(
                lambda a, s: jax.device_put(jnp.asarray(a), s),
                state_host, shardings,
                is_leaf=lambda x: isinstance(x, np.ndarray),
            )
        return state, meta

    def load_host(self, model: ModelDef, step: int | None = None):
        """(state, meta) of a checkpoint as global host numpy arrays, in the
        pytree structure of ``init_state_shapes`` — no device is touched.
        ``step=None`` loads the newest complete checkpoint."""
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.dir}")
        path = self.dir / f"step_{step:08d}"
        if not self._complete(path):
            raise FileNotFoundError(
                f"checkpoint {path} is missing or incomplete "
                f"(newest complete step: {self.latest_step()})")
        meta = json.loads((path / MANIFEST).read_text())
        data = np.load(path / STATE_BLOB)
        leaves = [data[f"leaf_{i:04d}"] for i in range(len(meta["leaves"]))]

        # rebuild the pytree structure from a template
        from repro.core.mics import init_state_shapes

        template = init_state_shapes(model)
        flat_t, treedef = jax.tree_util.tree_flatten(template)
        if len(flat_t) != len(leaves):
            raise ValueError(
                f"checkpoint has {len(leaves)} leaves, model needs {len(flat_t)}")
        for want, got in zip(flat_t, leaves):
            if tuple(want.shape) != tuple(got.shape):
                raise ValueError(
                    f"leaf shape mismatch {got.shape} vs {want.shape}: elastic "
                    f"restore reshards pods/partition/replication freely but "
                    f"the TP degree is fixed (flat layouts are TP-local)")
        return jax.tree_util.tree_unflatten(treedef, leaves), meta
