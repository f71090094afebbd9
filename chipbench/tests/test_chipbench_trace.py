"""The trace reduction: busy union, idle gaps by host span and top device
operations, on hand-made events and on a small trace recorded on a TPU
v5e by ``record_trace.py`` (``data/trace_small.*``)."""
import json

import pytest

from chipbench import spec, tracing

DATA = spec.HERE / "tests" / "data"


def _raw(ops, spans, modules=()):
    return dict(spans=spans, devices=[dict(name="/device:TPU:0", ops=ops,
                                           modules=list(modules))])


def test_union_and_leaves():
    assert tracing.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    ev = [(0, 10, "%while.1 = x"), (1, 3, "%fusion.2 = y"),
          (4, 9, "%fusion.3 = z"), (12, 13, "%copy.4 = w")]
    assert [n for _, _, n in tracing.leaves(ev)] == [
        "%fusion.2 = y", "%fusion.3 = z", "%copy.4 = w"]


def test_gaps_named_by_the_last_opened_span():
    spans = [(0, 100, tracing.WINDOW_SPAN), (0, 100, "chipbench.wait"),
             (20, 60, "chipbench.step"), (30, 50, "chipbench.run_batch")]
    ops = [(10, 20, "%a = 1"), (40, 45, "%b = 2"), (70, 80, "%c = 3")]
    red = tracing.reduce(_raw(ops, spans, [(10, 20, "jit_x"),
                                           (40, 45, "jit_y")]))
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(25e-9)
    idle = dict(red["breakdown"]["idle_gaps"])
    # gaps 0-10, 20-40, 45-70, 80-100; only "wait" is open in 0-10,
    # 60-70 and 80-100; step alone in 20-30 and 50-60; run_batch in
    # 30-40 and 45-50
    assert idle["chipbench.wait"] == pytest.approx(40e-9)
    assert idle["chipbench.run_batch"] == pytest.approx(15e-9)
    assert idle["chipbench.step"] == pytest.approx(20e-9)
    ops_s = dict(red["breakdown"]["device_ops"])
    assert ops_s == pytest.approx({"jit_x/a": 10e-9, "jit_y/b": 5e-9,
                                   "?/c": 10e-9})


@pytest.fixture(scope="module")
def small():
    raw = tracing.read(str(DATA / "trace_small.xplane.pb"))
    return (tracing.reduce(raw),
            json.loads((DATA / "trace_small.json").read_text()))


def test_small_trace_window_and_busy(small):
    red, exp = small
    assert red["window_s"] == pytest.approx(exp["window_s"], abs=2e-3)
    assert 0 < red["busy_s"] < red["window_s"]
    idle = sum(v for _, v in red["breakdown"]["idle_gaps"])
    assert red["busy_s"] + idle == pytest.approx(red["window_s"], rel=1e-6)


def test_small_trace_sleep_is_idle(small):
    red, exp = small
    idle = dict(red["breakdown"]["idle_gaps"])
    assert exp["sleep_s"] * 0.9 <= idle["chipbench.sleep"] <= \
        exp["sleep_s"] * 1.3


def test_small_trace_programs(small):
    red, exp = small
    assert len(red["modules"]["jit_work"]) == exp["work_runs"]
    top = red["breakdown"]["device_ops"]
    assert top and all(name.startswith("jit_work/") for name, _ in top[:3])
    # the matmuls' device time fits inside the host's wait for them
    dev = sum(b - a for a, b in red["modules"]["jit_work"]) / 1e9
    assert dev <= sum(exp["work_wall_s"])
    # and a bf16 matmul program runs below the chip's peak
    assert exp["flops_per_run"] * exp["work_runs"] / dev < 197e12
