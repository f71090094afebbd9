"""The shapes a cell can reach, so that set-up compiles every one of them
and nothing compiles inside the measured window.

The persistent paged engine (``StaticEngine.serve_batch_paged``) runs two
programs per slice: the stage-A prefill of the rows that are not resident,
shaped (rows rounded up to a power of two, longest row rounded up to
``len_bucket``), and the decode slice, shaped (rows rounded up to a power
of two, block-table width rounded up to ``NB_BUCKET`` pages).  The
scheduler's Eq. 5-9 bound (``packing: batch-max``) admits a batch of N
rows whose longest effective input is L only if
N * ceil((bucket(L) + S) / page_tokens) <= the budget in pages, so a
power-of-two bucket of B rows is reachable only if B / 2 + 1 rows of the
shortest length that rounds into the length bucket fit.  Set-up drives
one batch through the engine for every reachable (rows, length) pair;
each such batch also compiles the decode slice of its (rows, pages)
bucket.
"""
from __future__ import annotations

from typing import List, Mapping, Tuple

#: the engine's block-table rounding (``engine.static_engine.NB_BUCKET``)
NB_BUCKET = 4


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def bucket(n: int, unit: int) -> int:
    return ceil_div(max(n, 1), unit) * unit


def pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def min_rows(b: int) -> int:
    """Fewest rows that round up to the power-of-two bucket ``b``."""
    return 1 if b == 1 else b // 2 + 1


def fits(sv: Mapping, rows: int, longest: int) -> bool:
    """Eq. 5-9 (batch-max): ``rows`` rows charged the envelope of the
    longest effective input ``longest`` fit the budget."""
    blocks = ceil_div(bucket(longest, sv["mem_bucket"]) + sv["slice_len"],
                      sv["page_tokens"])
    return rows * blocks <= sv["budget_pages"]


def _row_buckets(sv: Mapping) -> List[int]:
    out, b = [], 1
    while fits(sv, min_rows(b), 1):
        out.append(b)
        b *= 2
    return out


def prefill_shapes(sv: Mapping, max_len: int) -> List[Tuple[int, int]]:
    """(rows bucket, length bucket) of the stage-A prefills the scheduler
    can dispatch, for effective inputs of up to ``max_len`` tokens."""
    lb = sv["len_bucket"]
    return [(b, L) for b in _row_buckets(sv)
            for L in range(lb, bucket(max_len, lb) + 1, lb)
            if fits(sv, min_rows(b), L - lb + 1)]


def decode_pages(sv: Mapping, row_tokens: int) -> int:
    """The decode slice's block-table width when the longest row holds
    ``row_tokens`` resident tokens."""
    return bucket(ceil_div(row_tokens + sv["slice_len"], sv["page_tokens"]),
                  NB_BUCKET)


def _shortest_with_pages(sv: Mapping, nb: int) -> int:
    """The fewest resident tokens that give a block table of ``nb``."""
    return max(1, (nb - NB_BUCKET) * sv["page_tokens"] - sv["slice_len"] + 1)


def decode_shapes(sv: Mapping, max_len: int) -> List[Tuple[int, int]]:
    """(rows bucket, block-table width) of the decode slices the
    scheduler can dispatch."""
    out = []
    for b in _row_buckets(sv):
        nb = NB_BUCKET
        while _shortest_with_pages(sv, nb) <= max_len:
            if fits(sv, min_rows(b), _shortest_with_pages(sv, nb)):
                out.append((b, nb))
            nb += NB_BUCKET
    return out


def warm_batches(sv: Mapping, max_len: int) -> List[List[int]]:
    """Row lengths of the batches set-up serves so that every reachable
    prefill and decode shape compiles: the fewest rows of each rows
    bucket, one of them as short as its length or page bucket allows and
    the rest of one token, so that each batch is itself one the scheduler
    could dispatch."""
    lb = sv["len_bucket"]
    batches, decodes = [], set()
    for b, L in prefill_shapes(sv, max_len):
        row = L - lb + 1
        batches.append([row] + [1] * (min_rows(b) - 1))
        decodes.add((b, decode_pages(sv, row)))
    for b, nb in decode_shapes(sv, max_len):
        if (b, nb) not in decodes:
            row = _shortest_with_pages(sv, nb)
            batches.append([row] + [1] * (min_rows(b) - 1))
    return batches
