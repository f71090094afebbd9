"""The ``mfu`` and ``decode_roofline`` readers on a synthetic traced run:
they take their counts from the configuration's ``counts`` module, and
for the Mistral cell they read what they read before that module was
the configuration's."""
import pytest

from chipbench import readers, spec
from chipbench.harness import Run
from chipbench.peaks import peaks

DATA = spec.HERE / "tests" / "data"


def _rec(t0, t1, ctx, fresh, valid, reprefill=0):
    return dict(t0=t0, t1=t1, rows=len(ctx), ctx=ctx, fresh=fresh,
                valid=valid, steps=max(valid), reprefill=reprefill)


#: slices of a traced window of 8 s: fresh and resident rows, a
#: re-prefill, a slice with no valid token, one matched by two
#: ``jit_serve`` runs (left out of the roofline), one after the window
RECORDS = [
    _rec(0.5, 1.9, [300, 1000], [True, False], [128, 40]),
    _rec(2.0, 3.4, [1040, 17, 555], [False, True, True], [88, 128, 3],
         reprefill=700),
    _rec(3.5, 4.9, [1128, 60], [False, True], [0, 0]),
    _rec(5.0, 6.2, [1, 2047], [True, True], [128, 1]),
    _rec(6.3, 7.9, [129, 2048], [False, False], [5, 128]),
    _rec(9.0, 11.0, [400], [True], [128]),
]
JIT_SERVE = [(0.6, 1.85), (2.1, 3.3), (3.6, 3.7), (5.1, 5.5), (5.6, 6.1),
             (6.4, 7.8), (9.1, 10.9)]


def _run(cell):
    return Run(cell=cell, model=cell.config["model"], records=RECORDS,
               requests=[], first_dispatch={}, window=(0.0, 12.0),
               trace_window=(0.0, 8.0),
               trace=dict(window_s=8.0, module_host={"jit_serve": JIT_SERVE}),
               peaks=peaks("TPU v5 lite"))


def test_mistral_reads_what_it_read():
    """Pinned to the values that the readers gave before the counts
    became the configuration's (``_slice_flops`` and the per-step sum in
    ``readers.py``, on this same run): 31,116,512,460,800 model FLOPs in
    the five slices inside the window over 8 s x 197 TFLOP/s; and
    2,813,791,567,872 least bytes of the four slices that one
    ``jit_serve`` run matches, over 819 GB/s, over the 3.95 s those runs
    took (summed in float in the order of the slices)."""
    run = _run(spec.cell("mistral-7b-v0.3-l16.codefuse.steady"))
    counts = run.cell.counts
    m = run.model
    s = run.traced_slices()
    assert sum(counts.slice_flops(m, r) for r in s) == 31116512460800
    assert sum(counts.decode_slice_min_bytes(m, r)
               for r in (s[0], s[1], s[2], s[4])) == 2813791567872
    assert readers.mfu(run) == 1.974397998781726
    assert readers.decode_roofline(run) == 86.97830227885197


def test_readers_call_the_configuration_counts():
    """``tiny-planted`` names ``counts_by_row.py``: a million FLOPs per
    row, a thousand bytes per valid token."""
    bench = {"workloads": [{"name": "own", "config": "tiny-planted",
                            "traffic": "tiny", "chips": 1}],
             "end_to_end": [], "per_layer": []}
    run = _run(spec.cell("own", bench, root=DATA))
    rows = 2 + 3 + 2 + 2 + 2
    assert readers.mfu(run) == pytest.approx(
        100.0 * rows * 1e6 / (8.0 * 197e12), rel=1e-12)
    valid = (128 + 40) + (88 + 128 + 3) + 0 + (5 + 128)
    dev_s = 1.25 + 1.2 + 0.1 + 1.4
    assert readers.decode_roofline(run) == pytest.approx(
        100.0 * valid * 1000 / 819e9 / dev_s, rel=1e-12)
