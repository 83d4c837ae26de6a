"""The trace reducer, on hand-made intervals and on a trace recorded on the
chip (``data/``: a traced run of ``bert10b_s512_1chip``)."""

import pathlib

import pytest

from chipbench import tracing

DATA = pathlib.Path(__file__).parent / "data"
MS = 1_000_000  # ns


def _events():
    # window 0-100 ms; device 0: compute 0-40, all-gather 30-50 (exposed
    # 40-50), compute 60-90; device 1: compute 10-20
    host = [("bench.trace", 0, 100 * MS), ("bench.dispatch", 50 * MS, 55 * MS),
            ("bench.loss_read", 90 * MS, 100 * MS)]
    device = {0: [("fusion.1", 0, 40 * MS),
                  ("all-gather-start.3", 30 * MS, 50 * MS),
                  ("fusion.2", 60 * MS, 90 * MS)],
              1: [("fusion.1", 10 * MS, 20 * MS)]}
    return tracing.Events(device, host)


def test_busy_collective_and_exposed():
    r = tracing.reduce(_events())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx((0.08 + 0.01) / 2)
    assert r["collective_s"] == pytest.approx(0.02 / 2)
    assert r["exposed_collective_s"] == pytest.approx(0.01 / 2)
    assert r["devices"] == 2


def test_idle_gaps_are_named_by_host_spans():
    r = tracing.reduce(_events())
    assert r["idle_gaps"] == [["bench.dispatch", pytest.approx(0.01)],
                              ["bench.loss_read", pytest.approx(0.01)]]
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.025)]


def test_no_window_or_no_device_reads_nothing():
    ev = _events()
    assert tracing.reduce(tracing.Events({}, ev.host)) is None
    assert tracing.reduce(tracing.Events(ev.device, ev.host[1:])) is None


def test_recorded_one_chip_trace():
    """Four traced steps of bert10b_s512_1chip on a TPU v5e, trimmed by
    ``trim_trace.py``: the numbers as first reduced from the whole trace."""
    r = tracing.reduce(tracing.load(str(DATA / "bert10b_s512_1chip.xplane.pb")))
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(1.505488533)
    assert r["busy_s"] == pytest.approx(1.468132594)
    assert r["collective_s"] == r["exposed_collective_s"] == 0
    assert r["device_ops"][0] == ["dynamic_update_slice.22",
                                  pytest.approx(0.154789428)]
    # between steps the device waits while the host reads the loss back
    assert r["idle_gaps"][0] == ["bench.loss_read",
                                 pytest.approx(0.004806822)]
    assert sum(t for _, t in r["idle_gaps"]) < r["window_s"] - r["busy_s"]
