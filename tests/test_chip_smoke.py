"""chip_smoke.py's phases on the CPU at the reduced preset.

The script itself refuses to run without a TPU; here its phases run on the
toy llama3.2-1b preset (float32) with Pallas kernels in interpret mode, so
its wiring, traffic and checks are guarded without the chip.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

M_AVAILABLE = 32e6  # KV bytes per worker: ~28k tokens of the toy preset


@pytest.fixture(scope="module")
def server():
    cfg = chip_smoke.serving_config(0, workers=1, reduced=True,
                                    m_available=M_AVAILABLE)
    return chip_smoke.build_server(cfg)


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_requests_span_slices_and_share_a_prefix():
    reqs = chip_smoke.make_requests(0, vocab=512)
    assert len(reqs) == 8
    lens = [len(p) for p, _ in reqs]
    assert min(lens) == 32 and max(lens) == 512
    assert all(48 <= g <= 64 for _, g in reqs)  # >= 3 slices of 16
    (a, _), (b, _) = reqs[:2]
    assert (a[:chip_smoke.PREFIX] == b[:chip_smoke.PREFIX]).all()
    assert a[chip_smoke.PREFIX] != b[chip_smoke.PREFIX]
    again = chip_smoke.make_requests(0, vocab=512)
    assert all((p == q).all() and g == h
               for (p, g), (q, h) in zip(reqs, again))


def test_numerics_phase_matches_reference(server):
    srv, _ = server
    out = chip_smoke.numerics_phase(srv.core.backend.engines[0], seed=0)
    # float32 served against float32 reference: rounding noise only
    assert out["max_err_over_std"] < 1e-3


def test_serve_phase(server):
    srv, vocab = server
    metrics = chip_smoke.serve_phase(srv, chip_smoke.make_requests(0, vocab))
    assert metrics["n_completed"] == 8
    assert metrics["prefix_hit_tokens"] >= chip_smoke.PREFIX
    json.dumps(metrics)  # printed as a bring-up line


def test_kernel_phase_small():
    errs = chip_smoke.kernel_phase(0, B=2, Hq=4, Hkv=2, D=16, pg=8, nb=3,
                                   T=24)
    assert set(errs) == {"paged_decode_attention", "paged_prefill_write",
                         "fused_rope_prefill_write",
                         "fused_rope_decode_append"}
    assert errs["paged_prefill_write"] == 0.0


def test_four_chip_phase_on_the_devices_there_are():
    n = len(jax.devices())
    out = chip_smoke.four_chip_phase(0, n_chips=n, reduced=True,
                                     m_available=M_AVAILABLE)
    assert out["workers4"]["n_completed"] == 8
    assert out["workers1"]["n_completed"] == 8
