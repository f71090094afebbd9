"""DeepSeek-V2-Lite's counts (``counts_deepseek_v2.py``) against the
program's parameter tree and hand counts, and ``moe_decode_roofline.lat``
on a synthetic traced run."""
import jax
import pytest

from chipbench import program_spans, spec
from chipbench.harness import Run, model_config
from chipbench.peaks import peaks

CELL = "deepseek-v2-lite-e8.azure-conv.steady"


@pytest.fixture(scope="module")
def cell():
    return spec.cell(CELL)


def test_params_and_latent_match_the_program(cell):
    """The program builds exactly as many parameters as the counts say
    (8 of the 64 routed experts held), and its Eq. 5 Δ is the latent
    record: 27 x (512 + 64) x 2 B."""
    from repro.models.registry import get_model
    model = get_model(model_config(cell.config))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    m = cell.config["model"]
    assert n == cell.counts.total_params(m) == 3110989312
    assert model.kv_bytes_per_token() == \
        cell.counts.latent_bytes_per_token(m) == 31104


def test_decode_bytes_leave_out_the_routed_experts(cell):
    """Least bytes of a decode step: every weight but the routed experts
    and the embedding table once (the router in float32), the rows looked
    up, and each row's latent records."""
    c, m = cell.counts, cell.config["model"]
    p = c.param_counts(m)
    other = (c.total_params(m) - p["embed"] - 26 * (p["routed"] + p["router"]))
    want = other * 2 + 26 * p["router"] * 4 + 2 * 2048 * 2 + 300 * 31104
    assert c.decode_step_min_bytes(m, [100, 200]) == want
    assert p["expert"] * 2 == 17301504  # 3 x 2048 x 1408 x 2 B


def test_each_phase_counts_its_cheaper_form(cell):
    """A prefill counts the expanded form (up projections once per key)
    and a decode step the absorbed form (once per query), each the fewer
    FLOPs at these lengths."""
    c, m = cell.counts, cell.config["model"]
    H, r, dn, dr, dv, L = 16, 512, 128, 64, 128, 27
    up = 2 * L * r * H * (dn + dv)
    expanded_pair = L * 2 * H * (dn + dr + dv)
    absorbed_pair = L * (2 * H * (r + dr) + 2 * H * r)
    head = 2 * 2048 * 102400
    tok = c._token_flops(m)
    assert c.decode_step_flops(m, [1000]) == tok + up + 1000 * absorbed_pair \
        + head
    n = 1000
    assert c.prefill_flops(m, [n]) == n * tok + n * up \
        + n * (n + 1) // 2 * expanded_pair + head


def _rec(t0, t1, ctx, fresh, valid):
    return dict(t0=t0, t1=t1, rows=len(ctx), ctx=ctx, fresh=fresh,
                valid=valid, steps=max(valid), reprefill=0)


RECORDS = [_rec(0.5, 1.9, [1000, 3000], [True, False], [128, 40]),
           _rec(2.0, 3.4, [1040, 17, 555], [False, True, True], [88, 128, 3]),
           _rec(9.0, 11.0, [400], [True], [128])]
JIT_SERVE = [(0.6, 1.85), (2.1, 3.3), (9.1, 10.9)]


def _run(cell, monkeypatch, reads):
    spans = [dict(name=program_spans.DECODE_SPAN, a=a, b=b, args=arg)
             for (a, b), arg in zip(JIT_SERVE, reads)]
    monkeypatch.setattr(program_spans, "load",
                        lambda run: dict(spans=spans, host=lambda t: t))
    return Run(cell=cell, model=cell.config["model"], records=RECORDS,
               requests=[], first_dispatch={}, window=(0.0, 12.0),
               trace_window=(0.0, 8.0),
               trace=dict(window_s=8.0, module_host={"jit_serve": JIT_SERVE}),
               peaks=peaks("TPU v5 lite"))


def test_moe_roofline_adds_the_experts_read(cell, monkeypatch):
    """Per slice in the traced window: the counts' least bytes plus the
    decode span's ``expert_reads`` times one expert's bytes, over
    819 GB/s, over that slice's ``jit_serve`` time."""
    run = _run(cell, monkeypatch, [{"expert_reads": 4000},
                                   {"expert_reads": 3000},
                                   {"expert_reads": 10}])
    m = run.model
    need = sum(cell.counts.decode_slice_min_bytes(m, r) for r in RECORDS[:2])
    need += (4000 + 3000) * 17301504
    want = 100.0 * need / 819e9 / (1.25 + 1.2)
    assert cell.readers["moe_decode_roofline.lat"](run) == \
        pytest.approx(want, rel=1e-12)


def test_moe_roofline_is_silent_without_expert_reads(cell, monkeypatch):
    """A program whose decode spans carry no ``expert_reads`` (one that
    predates them) gives nothing, and raises nothing."""
    run = _run(cell, monkeypatch, [{"steps": 128}] * 3)
    assert cell.readers["moe_decode_roofline.lat"](run) is None
