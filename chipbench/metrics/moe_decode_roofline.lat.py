"""Kernels: the MoE decode-slice program's least HBM time over its device time, counting the routed experts the program says it read; moves ttft_p95_ms.

Per traced slice: the configuration's ``counts.decode_slice_min_bytes``
(every weight but the routed experts once per step, the latent records)
plus the matched ``scls.engine.decode`` span's ``expert_reads`` (per step
and MoE layer, the held experts a live row was routed to) times one
expert's bytes (gate, up and down, ``3 * d_model * d_ff_expert`` in
bf16), over the HBM peak, over the device time of that slice's
``jit_serve`` run.  A program whose decode spans carry no
``expert_reads`` gives nothing.
"""
from typing import Optional

from chipbench import program_spans
from chipbench.readers import DECODE_MODULE


def _expert_bytes(m) -> int:
    return 3 * m["d_model"] * m["d_ff_expert"] * 2


def read(run) -> Optional[float]:
    if run.trace_window is None or run.trace is None or run.peaks is None:
        return None
    P = program_spans.load(run)
    if P is None:
        return None
    decodes = [(P["host"]((s["a"] + s["b"]) / 2), s["args"]["expert_reads"])
               for s in program_spans.named(P, program_spans.DECODE_SPAN)
               if "expert_reads" in s["args"]]
    runs = run.trace["module_host"].get(DECODE_MODULE, [])
    if not decodes or not runs:
        return None
    need, dev_s = 0.0, 0.0
    for r in run.traced_slices():
        # matched by midpoint, as ``readers.decode_roofline`` matches: the
        # trace's device clock runs about a millisecond apart from the host's
        mine = [(a, b) for a, b in runs if r["t0"] <= (a + b) / 2 <= r["t1"]]
        reads = [int(n) for t, n in decodes if r["t0"] <= t <= r["t1"]]
        if len(mine) != 1 or len(reads) != 1:
            continue
        dev_s += mine[0][1] - mine[0][0]
        need += (run.cell.counts.decode_slice_min_bytes(run.model, r)
                 + reads[0] * _expert_bytes(run.model))
    if dev_s <= 0:
        return None
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / dev_s
