"""Device time by the program's scopes (``chipbench.scoped``), on hand-made
intervals, on compiled-module text, and on traces recorded on the chip with
the op names of their operations (``data/``, from ``scoped_run.py``)."""

import json
import pathlib

import pytest

from chipbench import scoped, tracing

DATA = pathlib.Path(__file__).parent / "data"
MS = 1_000_000  # ns

NAMES = {
    "fusion.1": "jit(step)/while/body/jvp(mics.carry)/while/body/"
                "model.attention/dot_general",
    "fusion.2": "jit(step)/while/body/transpose(jvp(mics.carry))/while/body/"
                "checkpoint/mics.gather/mics.hop1/reduce_scatter",
    "fusion.3": "jit(step)/while/body/transpose(jvp(model.mlp))/dot_general",
    "copy.4": "jit(step)/while/body/jvp(mics.carry)/while/body/"
              "dynamic_update_slice",
    "all-gather-start.5": "jit(step)/jvp(mics.gather)/all_gather",
    "all-reduce.6": "jit(step)/mics.optimizer/mics.hop2/psum",
    "fusion.7": "jit(step)/mics.gatherer/add",   # a renamed scope
}


def test_innermost_known_scope_names_the_operation():
    got = {n: scoped.scope_of(op) for n, op in NAMES.items()}
    assert got == {"fusion.1": "model.attention", "fusion.2": "mics.hop1",
                   "fusion.3": "model.mlp", "copy.4": "mics.carry",
                   "all-gather-start.5": "mics.gather",
                   "all-reduce.6": "mics.hop2", "fusion.7": "unscoped"}
    assert scoped.scope_of(None) == "unscoped"


def _events():
    # window 0-100 ms.  Device 0: a while loop 0-60 enclosing fusion.1 0-20
    # and copy.4 15-30 (overlapping leaves), fusion.3 30-40; an all-gather
    # 50-70 (exposed 60-70), in flight 40-75 on the async line (exposed
    # 70-75); fusion.7 80-90.  Device 1: all-reduce.6 10-30, fusion.2 20-40.
    host = [("bench.trace", 0, 100 * MS)]
    device = {0: [("while.9", 0, 60 * MS), ("fusion.1", 0, 20 * MS),
                  ("copy.4", 15 * MS, 30 * MS), ("fusion.3", 30 * MS, 40 * MS),
                  ("all-gather-start.5", 50 * MS, 70 * MS),
                  ("fusion.7", 80 * MS, 90 * MS)],
              1: [("all-reduce.6", 10 * MS, 30 * MS),
                  ("fusion.2", 20 * MS, 40 * MS)]}
    device_async = {0: [("all-gather-start.5", 40 * MS, 75 * MS)]}
    return tracing.Events(device, host, device_async)


def test_scopes_and_unscoped_add_up_to_busy():
    ev = _events()
    r = scoped.reduce(ev, NAMES)
    busy = tracing.reduce(ev)["busy_s"]
    assert r["busy_s"] == pytest.approx(busy)
    assert sum(r["scope_s"].values()) + r["unscoped_s"] == pytest.approx(busy)
    # overlapping leaves: copy.4 keeps only what fusion.1 left (20-30)
    assert r["scope_s"]["model.attention"] == pytest.approx(0.020 / 2)
    assert r["scope_s"]["mics.carry"] == pytest.approx(0.010 / 2)
    assert r["scope_s"]["mics.hop2"] == pytest.approx(0.020 / 2)
    assert r["scope_s"]["mics.hop1"] == pytest.approx(0.010 / 2)
    assert r["unscoped_s"] == pytest.approx(0.010 / 2)
    assert r["unscoped_ops"] == [["fusion.7", pytest.approx(0.005)]]
    assert r["devices"] == 2


def test_exposed_collective_time_by_scope():
    r = scoped.reduce(_events(), NAMES)
    # gather: 40-75 in flight, 50-70 running; other work 0-40 and 80-90
    assert r["exposed_s"]["mics.gather"] == pytest.approx(0.035 / 2)
    # hop 2 on device 1: 10-30, with fusion.2 from 20
    assert r["exposed_s"]["mics.hop2"] == pytest.approx(0.010 / 2)
    assert r["exposed_comm_s"] == pytest.approx(0.045 / 2)
    assert r["exposed_comm_s"] == pytest.approx(
        tracing.reduce(_events())["exposed_collective_s"])


def test_no_window_reads_nothing():
    ev = _events()
    assert scoped.reduce(tracing.Events(ev.device, []), NAMES) is None


HLO = """\
HloModule jit_step, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %add.1 = f32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(step)/mics.grad_accum/add"}
}

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %copy.3 = f32[4]{0} copy(%param_0.1)
}

ENTRY %main.9 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation
  %fusion.1 = f32[4]{0} fusion(%fusion), kind=kLoop, calls=%fused_computation.1
  ROOT %all-reduce.2 = f32[4]{0} all-reduce(%fusion.1), channel_id=1, replica_groups={{0,1}}, to_apply=%add, metadata={op_name="jit(step)/mics.optimizer/mics.hop2/psum" source_file="comm.py" source_line=1}
}
"""


def test_op_names_of_compiled_text():
    names = scoped.op_names(HLO)
    assert names["all-reduce.2"] == "jit(step)/mics.optimizer/mics.hop2/psum"
    # a fusion with no metadata of its own takes its root's
    assert names["fusion"] == "jit(step)/mics.grad_accum/add"
    # XLA's own copy has none anywhere
    assert "fusion.1" not in names and "x" not in names
    assert scoped.carries_scopes(names)
    assert not scoped.carries_scopes({"x": "jit(step)/add"})


# Four traced steps of each cell on TPU v5e, recorded by scoped_run.py: the
# device seconds by scope (mean over the devices) as first reduced from the
# whole trace.
RECORDED = {
    "bert10b_s512_1chip": {
        "mics.gather": 0.179775439, "mics.hop1": 0.0, "mics.hop2": 0.0,
        "mics.carry": 0.502128142, "mics.grad_accum": 0.070511239,
        "mics.optimizer": 0.174576972, "model.attention": 0.150065859,
        "model.mlp": 0.131587419, "model.embed": 0.006997147,
        "model.head": 0.055307768, "unscoped": 0.18722457},
    "bert10b_s512_r2x2_4chip": {
        "mics.gather": 0.146731608, "mics.hop1": 0.0,
        "mics.hop2": 0.08082806325, "mics.carry": 0.46200198275,
        "mics.grad_accum": 0.035277146, "mics.optimizer": 0.086947123,
        "model.attention": 0.15846022475, "model.mlp": 0.13099625175,
        "model.embed": 0.00605516675, "model.head": 0.052637036,
        "unscoped": 0.41291402125},
}
# Operations that XLA makes with no op_name, among the ten that take most
# time: the stored carry's relayout loop and its zero fill, and on the mesh
# the all-reduces over the partition group that the TPU compiler makes of
# hop 1's reduce-scatters.
NO_OP_NAME = {
    "bert10b_s512_1chip": {"reshape_dynamic-update-slice_fusion",
                           "broadcast.1196"},
    "bert10b_s512_r2x2_4chip": {"reshape_dynamic-update-slice_fusion",
                                "broadcast.883", "all-reduce.37",
                                "all-reduce.38", "all-reduce.40"},
}


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_recorded_trace_by_scope(cell):
    ev = tracing.load(str(DATA / "scoped" / f"{cell}.xplane.pb"))
    with open(DATA / "scoped" / f"{cell}.op_names.json") as f:
        names = json.load(f)
    r = scoped.reduce(ev, names)
    whole = tracing.reduce(ev)
    got = {**r["scope_s"], "unscoped": r["unscoped_s"]}
    assert got == pytest.approx(RECORDED[cell])
    assert sum(got.values()) == pytest.approx(whole["busy_s"])
    for name, _, scope in r["ops"]:
        assert scope != scoped.UNSCOPED or name in NO_OP_NAME[cell], name
    # the exposed collective time under the scopes and under none is the
    # reducer's; on this v5e no collective overlaps other work
    exposed = r["exposed_comm_s"] + r["exposed_s"].get(scoped.UNSCOPED, 0.0)
    assert exposed == pytest.approx(whole["exposed_collective_s"])
    assert whole["exposed_collective_s"] == pytest.approx(
        whole["collective_s"])
