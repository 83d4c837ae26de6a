"""Checkpoint roundtrip, crash-safe atomicity (incl. the injected
crash-mid-save writer kill), fault-tolerant train loop with injected
failures, data-pipeline determinism/seekability and exact restart-boundary
continuity.  The multi-device save->restore->save reshard roundtrip
(p=2 -> p=4 -> p=2) is pinned from the elastic harness run
(tests/elastic_harness.py)."""

import dataclasses
import hashlib
import json

import jax
import numpy as np
import pytest

import repro.runtime.train_loop as TL
from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import get_config, smoke_variant
from repro.core.faults import CrashDuringSaveError, FaultPlan
from repro.core.mics import MiCSConfig, init_state
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models.build import build_model
from repro.optim.adamw import OptConfig
from repro.runtime.train_loop import LoopConfig, train


def test_checkpoint_roundtrip(tmp_path, topo1):
    cfg = smoke_variant(get_config("llama3.2-1b"))
    model = build_model(cfg, tp=1)
    state = init_state(model, topo1, seed=5)
    ck = Checkpointer(tmp_path)
    ck.save(state, step=7, topo=topo1, data_cursor=123)

    restored, meta = ck.restore(model, topo1)
    assert meta["step"] == 7 and meta["data_cursor"] == 123
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        state, restored)


def test_checkpoint_latest_and_atomicity(tmp_path, topo1):
    cfg = smoke_variant(get_config("llama3.2-1b"))
    model = build_model(cfg, tp=1)
    state = init_state(model, topo1)
    ck = Checkpointer(tmp_path)
    ck.save(state, step=1, topo=topo1)
    ck.save(state, step=2, topo=topo1)
    # a stale .tmp dir (simulated crash) must be ignored
    (tmp_path / "step_00000099.tmp").mkdir()
    assert ck.latest_step() == 2


def test_latest_step_skips_malformed_and_incomplete_dirs(tmp_path, topo1):
    cfg = smoke_variant(get_config("llama3.2-1b"))
    model = build_model(cfg, tp=1)
    state = init_state(model, topo1)
    ck = Checkpointer(tmp_path)
    ck.save(state, step=3, topo=topo1)
    # a stray non-numeric step_* name (e.g. a hand-made step_old backup)
    # must not crash the scan, let alone win it
    (tmp_path / "step_old").mkdir()
    (tmp_path / "step_12xy").mkdir()
    # a numeric dir missing its state blob (writer died before the state)
    (tmp_path / "step_00000007").mkdir()
    # a numeric dir with a truncated manifest (writer died mid-manifest)
    crashed = tmp_path / "step_00000009"
    crashed.mkdir()
    np.savez(crashed / "state.npz", leaf_0000=np.zeros(3))
    (crashed / "manifest.json").write_text('{"step": 9, "data_c')
    assert ck.latest_step() == 3
    # restore() follows the same completeness rule
    _, meta = ck.restore(model, topo1)
    assert meta["step"] == 3
    with pytest.raises(FileNotFoundError, match="incomplete"):
        ck.restore(model, topo1, step=9)


def test_crash_mid_save_leaves_tmp_and_restores_newest_complete(
        tmp_path, topo1):
    cfg = smoke_variant(get_config("llama3.2-1b"))
    model = build_model(cfg, tp=1)
    state = init_state(model, topo1, seed=2)
    ck = Checkpointer(tmp_path)
    plan = FaultPlan().crash_during_save(2).bind(ck)
    ck.save(state, step=1, topo=topo1, data_cursor=1)
    with pytest.raises(CrashDuringSaveError):
        ck.save(state, step=2, topo=topo1, data_cursor=2)   # blocking: raises
    # the kill window leaves the .tmp corpse: state blob + truncated manifest
    corpse = tmp_path / "step_00000002.tmp"
    assert corpse.exists() and (corpse / "state.npz").exists()
    with pytest.raises(ValueError):
        json.loads((corpse / "manifest.json").read_text())
    assert not (tmp_path / "step_00000002").exists()
    # restore picks the newest COMPLETE step
    assert ck.latest_step() == 1
    restored, meta = ck.restore(model, topo1)
    assert meta["step"] == 1
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        state, restored)
    # a later save recovers the cadence (the fired event is one-shot)
    ck.save(state, step=2, topo=topo1, data_cursor=2)
    assert ck.latest_step() == 2 and not corpse.exists()
    assert [e["kind"] for e in plan.log] == ["crash_during_save"]


def test_async_save_failure_surfaces_at_wait(tmp_path, topo1):
    cfg = smoke_variant(get_config("llama3.2-1b"))
    model = build_model(cfg, tp=1)
    state = init_state(model, topo1)
    ck = Checkpointer(tmp_path)
    FaultPlan().crash_during_save(4).bind(ck)
    ck.save(state, step=4, topo=topo1, blocking=False)   # crash held...
    with pytest.raises(CrashDuringSaveError):
        ck.wait()                                        # ...surfaced here
    ck.wait()   # the failure is re-raised once, not forever


def test_train_loop_recovers_from_injected_fault(tmp_path, topo1):
    cfg = smoke_variant(get_config("llama3.2-1b"))
    model = build_model(cfg, tp=1)
    mcfg = MiCSConfig(micro_steps=2)
    oc = OptConfig(total_steps=8, warmup_steps=0, lr_max=1e-3)
    dc = DataConfig(vocab=cfg.vocab, seq=32, global_batch=4, micro_steps=2)
    lc = LoopConfig(total_steps=8, checkpoint_every=2, log_every=0,
                    checkpoint_dir=str(tmp_path))

    crashed = {"done": False}

    def injector(step):
        if step == 5 and not crashed["done"]:
            crashed["done"] = True
            raise RuntimeError("injected node failure")

    stats = train(model, topo1, mcfg, oc, dc, lc, fault_injector=injector)
    assert stats.restarts == 1
    assert len(stats.losses) >= 8
    assert np.isfinite(stats.losses[-1])
    ck = Checkpointer(tmp_path)
    assert ck.latest_step() == 8


def test_train_loop_resume_continues_data_cursor(tmp_path, topo1):
    cfg = smoke_variant(get_config("llama3.2-1b"))
    model = build_model(cfg, tp=1)
    mcfg = MiCSConfig(micro_steps=2)
    dc = DataConfig(vocab=cfg.vocab, seq=32, global_batch=4, micro_steps=2)

    lc1 = LoopConfig(total_steps=4, checkpoint_every=2, log_every=0,
                     checkpoint_dir=str(tmp_path))
    train(model, topo1, mcfg, OptConfig(total_steps=8, warmup_steps=0),
          dc, lc1)
    lc2 = dataclasses.replace(lc1, total_steps=6)
    stats = train(model, topo1, mcfg,
                  OptConfig(total_steps=8, warmup_steps=0), dc, lc2)
    assert len(stats.losses) == 2  # resumed at 4, ran to 6


def test_restart_boundary_replays_and_skips_no_batch(tmp_path, topo1,
                                                     monkeypatch):
    """Satellite (d): the resumed ``data_cursor`` continues the stream
    exactly — batch fingerprints across the restart boundary show neither a
    replayed nor a skipped batch."""
    served = []

    class RecordingLM(SyntheticLM):
        def global_step_batch(self, step):
            b = super().global_step_batch(step)
            served.append(
                (int(step), hashlib.sha1(b["tokens"].tobytes()).hexdigest()))
            return b

    monkeypatch.setattr(TL, "SyntheticLM", RecordingLM)
    cfg = smoke_variant(get_config("llama3.2-1b"))
    model = build_model(cfg, tp=1)
    mcfg = MiCSConfig(micro_steps=2)
    dc = DataConfig(vocab=cfg.vocab, seq=32, global_batch=4, micro_steps=2)
    oc = OptConfig(total_steps=8, warmup_steps=0)
    lc1 = LoopConfig(total_steps=4, checkpoint_every=2, log_every=0,
                     checkpoint_dir=str(tmp_path))
    train(model, topo1, mcfg, oc, dc, lc1)
    boundary = len(served)
    train(model, topo1, mcfg, oc, dc,
          dataclasses.replace(lc1, total_steps=8))

    cursors = [c for c, _ in served]
    assert cursors[:boundary] == [0, 1, 2, 3]
    assert cursors[boundary:] == [4, 5, 6, 7]   # no replay, no skip
    # the fingerprints are the stream's, not an artifact of the restart:
    # a fresh loader reproduces every one, and they are pairwise distinct
    fresh = SyntheticLM(dc)
    for c, h in served:
        want = hashlib.sha1(
            fresh.global_step_batch(c)["tokens"].tobytes()).hexdigest()
        assert h == want, f"batch {c} changed across the restart boundary"
    assert len({h for _, h in served}) == len(served)


def test_reshard_roundtrip_across_topologies(elastic_results):
    """Satellite (c), multi-device half: save -> restore -> save across
    p=2 -> p=4 -> p=2 is bitwise lossless (run in the elastic harness)."""
    res = elastic_results["reshard_roundtrip"]
    assert res["ok"], f"{res.get('err')}\n{res.get('tb', '')}"


def test_data_pipeline_deterministic_and_seekable():
    dc = DataConfig(vocab=128, seq=16, global_batch=8, micro_steps=2)
    src1, src2 = SyntheticLM(dc), SyntheticLM(dc)
    b1 = src1.global_step_batch(3)
    b2 = src2.global_step_batch(3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # host sharding partitions the global batch exactly
    h0 = src1.host_step_batch(3, 0, 2)
    h1 = src1.host_step_batch(3, 1, 2)
    merged = np.concatenate([h0["tokens"], h1["tokens"]], axis=1)
    np.testing.assert_array_equal(merged, b1["tokens"])
    # targets are inputs shifted by one
    np.testing.assert_array_equal(b1["tokens"][:, :, 1:],
                                  b1["targets"][:, :, :-1])
