"""A profile of a training run names the loop's host work on the
profiler's clock: each step is a ``train`` step annotation carrying its
``step_num``, and inside it ``train.batch``, ``train.step``,
``train.loss_read`` and, on a checkpoint step, ``train.save`` run in that
order (docs/profiling.md)."""

import glob

import jax
from jax.profiler import ProfileData

from repro.configs import get_config, smoke_variant
from repro.core.mics import MiCSConfig
from repro.data.pipeline import DataConfig
from repro.models.build import build_model
from repro.optim.adamw import OptConfig
from repro.runtime.train_loop import LoopConfig, train

INSIDE = ["train.batch", "train.step", "train.loss_read", "train.save"]


def _host_spans(trace_dir) -> list[tuple[str, float, float, dict]]:
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats))
                    for line in plane.lines for e in line.events
                    if e.name == "train" or e.name.startswith("train.")]
    return sorted(out, key=lambda s: s[1])


def test_profiled_steps_hold_their_spans_in_order(tmp_path, topo1):
    cfg = smoke_variant(get_config("llama3.2-1b"))
    dc = DataConfig(vocab=cfg.vocab, seq=32, global_batch=4, micro_steps=2)
    lc = LoopConfig(total_steps=2, checkpoint_every=1, log_every=0,
                    checkpoint_dir=str(tmp_path / "ckpt"))
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        train(build_model(cfg, tp=1), topo1, MiCSConfig(micro_steps=2),
              OptConfig(total_steps=2, warmup_steps=0), dc, lc)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path / "trace")
    steps = [s for s in spans if s[0] == "train"]
    assert [s[3].get("step_num") for s in steps] == [0, 1]
    for _, lo, hi, _ in steps:
        inside = [n for n, a, b, _ in spans if n != "train" and lo <= a
                  and b <= hi]
        assert inside == INSIDE, inside
    # the last save runs after the loop, outside every step
    assert spans[-1][0] == "train.save" and spans[-1][1] >= steps[-1][2]
