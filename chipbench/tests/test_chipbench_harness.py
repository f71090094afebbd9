"""A whole run of the harness on the CPU at a test size (``tests/data``:
the dense family at 2 layers of width 64), past the look for a chip: a
sound run is ``correct``; the same run is not with the served tokens
altered where the engine produces them, nor with decode steps that leave
the K/V pool as they found it; and with the float8 control put in the
program's place, the same verdict comes out not ``correct``.  A
configuration that names a plain reference of its own (``tiny-planted``,
a new file with a planted fault) is judged by that reference."""
import time

import numpy as np
import pytest

from chipbench import spec
from chipbench.harness import CompileClock, run_cell

ROOT = spec.HERE / "tests" / "data"
BENCH = {"workloads": [{"name": "tiny.cell", "config": "tiny",
                        "traffic": "tiny", "chips": 1},
                       {"name": "tiny-planted.cell", "config": "tiny-planted",
                        "traffic": "tiny", "chips": 1}],
         "end_to_end": [{"name": n, "unit": "x"} for n in
                        ("out_tok_s", "ttft_p95_ms", "norm_lat_p95_ms",
                         "slo_met_share", "setup_s")],
         "per_layer": []}
SEED = 2 ** 31 + 11


@pytest.fixture(scope="module")
def compiles():
    return CompileClock()


def _run(compiles, control=False, name="tiny.cell"):
    cell = spec.cell(name, BENCH, root=ROOT)
    return run_cell(cell, SEED, 4.0, False, time.perf_counter(), compiles,
                    require_tpu=False, log=lambda *a: None,
                    control=control)


def test_sound_run_is_correct(compiles):
    res = _run(compiles)
    assert res["correct"] is True
    assert res["check"]["length_errors"]["value"] == 0
    assert res["check"]["logit_gap"]["value"] <= \
        res["check"]["logit_gap"]["limit"]
    assert res["attempted"] > 10 and res["failed"] == 0
    assert set(res["metrics"]) == {"out_tok_s", "ttft_p95_ms",
                                   "norm_lat_p95_ms", "slo_met_share",
                                   "setup_s"}
    assert res["metrics"]["out_tok_s"]["value"] > 0
    assert res["device"]["platform"] == "cpu"


def test_altered_tokens_are_not_correct(compiles, monkeypatch):
    """Every token the engine produces is replaced by its successor in
    the vocabulary, where it is produced: the scheduler, the streams and
    the lengths are untouched, only the tokens are wrong."""
    from repro.engine.static_engine import StaticEngine
    serve = StaticEngine.serve_batch_paged

    def altered(self, *a, **kw):
        res = serve(self, *a, **kw)
        V = self.model.cfg.vocab_size
        for r in res.results:
            r["tokens"] = [(t + 1) % V for t in r["tokens"]]
        return res

    monkeypatch.setattr(StaticEngine, "serve_batch_paged", altered)
    res = _run(compiles)
    assert res["correct"] is False
    assert res["check"]["length_errors"]["value"] == 0
    assert res["check"]["logit_gap"]["value"] > \
        res["check"]["logit_gap"]["limit"]


def test_kv_state_left_unchanged_is_not_correct(compiles, monkeypatch):
    """Each decode step attends as it should but hands back the K/V pool
    it was given, so the cache never holds the tokens decoded after the
    prefill: the served tokens' logits fall behind the reference's."""
    from repro.models import attention
    decode = attention.attention_decode_paged

    def stale(p, x, q_pos, k_pages, v_pages, *a, **kw):
        out, _, _ = decode(p, x, q_pos, k_pages, v_pages, *a, **kw)
        return out, k_pages, v_pages

    monkeypatch.setattr(attention, "attention_decode_paged", stale)
    res = _run(compiles)
    assert res["correct"] is False
    assert res["check"]["length_errors"]["value"] == 0
    assert res["check"]["logit_gap"]["value"] > \
        res["check"]["logit_gap"]["limit"]


def test_float8_control_fails_the_limit(compiles):
    res = _run(compiles, control=True)
    limit = res["check"]["logit_gap"]["limit"]
    assert res["correct"] is False
    assert res["program_gap"] <= limit
    assert res["check"]["logit_gap"]["value"] > limit
    assert np.isfinite(res["check"]["logit_gap"]["value"])


def test_configuration_names_its_own_reference(compiles):
    """The same run, judged by the reference that its configuration
    names: that reference puts every position's best 1.0 higher, so the
    widest gap passes the limit although the program is sound."""
    res = _run(compiles, name="tiny-planted.cell")
    assert res["correct"] is False
    assert res["check"]["length_errors"]["value"] == 0
    assert res["check"]["logit_gap"]["value"] >= 1.0 > \
        res["check"]["logit_gap"]["limit"]
