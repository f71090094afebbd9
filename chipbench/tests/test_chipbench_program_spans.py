"""The program-span metrics (``chipbench/program_spans.py``): on
hand-made spans, on a small program trace recorded on the CPU by
``record_program_trace.py`` (``data/program_trace.*``), and through the
harness on a live CPU run at the test size."""
import json
import lzma
import math
import time
from types import SimpleNamespace

import pytest

from chipbench import program_spans as ps
from chipbench import spec, tracing

DATA = spec.HERE / "tests" / "data"
FIXTURE = json.loads((DATA / "program_trace.json").read_text())
METRICS = ("submit_lag_p50_ms.lat", "sched_wait_p50_ms.lat",
           "est_err_p50_pct.lat", "slice_host_share.lat")


def _run(name, trace_window, requests=()):
    return SimpleNamespace(cell=SimpleNamespace(name=name),
                           trace_window=tuple(trace_window),
                           requests=list(requests))


@pytest.fixture()
def recorded(tmp_path, monkeypatch):
    """The recorded run: its trace where the readers look, and a ``Run``
    with the requests and the traced window the harness had."""
    d = tmp_path / FIXTURE["cell"]
    d.mkdir()
    (d / "t.xplane.pb").write_bytes(
        lzma.decompress((DATA / "program_trace.xplane.pb.xz").read_bytes()))
    monkeypatch.setattr(ps, "TRACE_ROOT", tmp_path)
    reqs = [SimpleNamespace(due=due, rid=rid, submit=sub)
            for due, rid, sub in FIXTURE["requests"]]
    return _run(FIXTURE["cell"], FIXTURE["trace_window"], reqs)


@pytest.mark.parametrize("name", METRICS)
def test_recorded_metric_reads_what_the_run_read(recorded, name):
    got = spec.reader(name)(recorded)
    assert got is not None and math.isfinite(got)
    assert got == pytest.approx(FIXTURE["metrics"][name], rel=1e-9)


def test_due_to_submit_plus_submit_to_slice_is_due_to_dispatch(recorded):
    """Per request, the front end's part and the scheduler's part add up
    to ``SliceLog``'s due-to-first-dispatch, to within 1 ms."""
    P = ps.load(recorded)
    lags = ps.submit_lags_ms(recorded, P)
    waits = ps.sched_waits_ms(P)
    submits = {int(s["args"]["rid"]): s for s in ps.named(P, "scls.submit")}
    due = {q.rid: q.due for q in recorded.requests}
    both = sorted(set(lags) & set(waits))
    assert len(both) >= 10
    for rid in both:
        s = submits[rid]
        total = lags[rid] + (s["b"] - s["a"]) / 1e6 + waits[rid]
        first = 1e3 * (FIXTURE["first_dispatch"][str(rid)] - due[rid])
        assert total == pytest.approx(first, abs=1.0)
        # the submit span starts where the client called submit
        assert P["host"](s["a"]) <= dict(
            (q.rid, q.submit) for q in recorded.requests)[rid]


def test_recorded_slices_split_into_device_stages_and_host(recorded):
    P = ps.load(recorded)
    sl = ps.window_slices(P)
    assert len(sl) >= 10
    for s in sl:
        a = s["args"]
        rids = ps.ints(a["rids"])
        assert len(rids) == a["rows"] == len(ps.ints(a["valid"])) \
            == len(ps.ints(a["fresh"])) == len(ps.floats(a["waited"]))
        stages = ps.children(P, s, ps.DEVICE_STAGES)
        assert [c["name"] for c in stages].count(ps.DECODE_SPAN) == 1
    share = ps.slice_host_share(recorded)
    assert 0 < share < 100
    errs = ps.est_errors(P)
    assert len(errs) == len(sl)
    assert ps.est_err_p50_pct(recorded) == pytest.approx(
        100 * sorted(abs(e) for e in errs)[math.ceil(len(errs) / 2) - 1])


def test_no_program_spans_read_as_nothing(tmp_path, monkeypatch):
    """A program without spans (the TPU trace of ``record_trace.py`` holds
    the benchmark's spans only), an untraced run and a missing trace give
    ``None`` for every metric."""
    d = tmp_path / "cell"
    d.mkdir()
    (d / "t.xplane.pb").write_bytes(
        (DATA / "trace_small.xplane.pb").read_bytes())
    monkeypatch.setattr(ps, "TRACE_ROOT", tmp_path)
    for run in (_run("cell", (0.0, 1.0)), _run("absent", (0.0, 1.0))):
        assert all(spec.reader(n)(run) is None for n in METRICS)
    untraced = _run("cell", (0.0, 1.0))
    untraced.trace_window = None
    assert all(spec.reader(n)(untraced) is None for n in METRICS)


def _hand_made():
    def span(name, a, b, **args):
        return dict(name="scls." + name, a=a, b=b, args=args)
    spans = [span("pacer.step", 0, 100), span("sched.tick", 10, 90),
             span("slice", 20, 80, rids="1 2", fresh="1 0",
                  waited="0.5 0.25", est_time=66e-9, wall=50e-9, rows=2),
             span("engine.plan", 20, 25), span("engine.prefill", 25, 40),
             span("engine.decode", 45, 75), span("engine.retain", 76, 80),
             span("submit", 120, 130, rid=3)]
    ops = [(26, 39, "%p = 1", "mlp"), (46, 60, "%d = 1", "kv_gather"),
           (60, 74, "%e = 1", None)]
    mods = [(46, 74, "jit_serve"), (150, 160, "jit_serve")]
    return dict(spans=spans, window=(0, 200), ops=ops, modules=mods, w0=0,
                w1=200, host=lambda ns: 10.0 + ns / 1e9)


def test_hand_made_spans():
    P = _hand_made()
    assert ps.sched_waits_ms(P) == {1: 500.0}
    run = _run("x", (10.0, 10.0 + 200e-9),
               [SimpleNamespace(due=10.0 + 20e-9, rid=3)])
    assert ps.submit_lags_ms(run, P) == pytest.approx({3: 100e-6})
    assert ps.est_errors(P) == pytest.approx([0.1])
    assert ps.est_errors(P, "wall") == pytest.approx([0.32])
    idle = ps.idle_by_span(P)
    # device busy 26-39 and 46-74; the rest of 0-200 is idle, named by
    # the innermost span open in each piece
    assert sum(idle.values()) == pytest.approx((200 - 13 - 28) / 1e9)
    assert idle["scls.engine.plan"] == pytest.approx(5e-9)
    assert idle["scls.engine.prefill"] == pytest.approx(2e-9)
    assert idle["scls.engine.decode"] == pytest.approx(2e-9)
    assert idle["scls.submit"] == pytest.approx(10e-9)
    assert ps.decode_coverage(P) == (2, 1)
    assert ps.device_by_scope(P) == pytest.approx(
        {"mlp": 13e-9, "kv_gather": 14e-9, "unscoped": 14e-9})
    # an operation's scope: the innermost known one in its op-name stat
    assert ps._scope([("tf_op", "jit(serve)/while/body/attention/"
                                "kv_gather/dot_general"),
                      ("hlo_op", "fusion.3")]) == "kv_gather"
    assert ps._scope([("tf_op", "jit(serve)/head/dot")]) == "head"
    assert ps._scope([("hlo_op", "fusion.3")]) is None
    assert tracing.union([(a, b) for a, b, *_ in P["ops"]]) == [
        (26, 39), (46, 74)]


def test_live_cpu_run_reports_the_four_metrics(tmp_path, monkeypatch):
    """Through ``spec`` and the harness, as ``run.py --trace 1`` runs
    them, at the test size on the CPU (not a device number)."""
    from chipbench.harness import CompileClock, run_cell
    monkeypatch.setattr(ps, "TRACE_ROOT", tmp_path)
    bench = {"workloads": [{"name": "tiny.cell", "config": "tiny",
                            "traffic": "tiny", "chips": 1}],
             "end_to_end": [{"name": "ttft_p95_ms", "unit": "ms"}],
             "per_layer": []}
    cell = spec.cell("tiny.cell", bench, root=DATA)
    for n in METRICS:
        cell.per_layer.append({"name": n, "unit": "x"})
        cell.readers[n] = spec.reader(n)
    res = run_cell(cell, 2 ** 31 + 5, 3.0, True, time.perf_counter(),
                   CompileClock(), require_tpu=False, log=lambda *a: None,
                   trace_dir=str(tmp_path / "tiny.cell"))
    assert res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(got) == set(METRICS)
    assert all(math.isfinite(v) for v in got.values())
    assert 0 < got["slice_host_share.lat"] < 100
