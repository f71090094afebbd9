"""The warm-up grid covers every shape the scheduler's bound admits, and
every warm-up batch is one the bound admits itself."""
import itertools

import pytest

from chipbench import grid, spec


def _config(name):
    """A configuration of the benchmark, or of the tests' own data."""
    data = spec.HERE / "tests" / "data"
    root = data if (data / "configs" / f"{name}.json").exists() else spec.HERE
    return spec.config(name, root)


NAMES = ["qwen1.5-7b-l16", "mistral-7b-v0.3-l16"]


def _batches_reaching(sv, max_len, rows_cap=200):
    """Every (rows, longest) the batch-max bound admits, by brute force."""
    for n in range(1, rows_cap):
        if not grid.fits(sv, n, 1):
            break
        for L in range(1, max_len + 1):
            if grid.fits(sv, n, L):
                yield n, L


@pytest.mark.parametrize("name", NAMES)
def test_grid_covers_reachable_shapes(name):
    conf = _config(name)
    sv, max_len = conf["serving"], conf["max_effective_input"]
    pre, dec = set(grid.prefill_shapes(sv, max_len)), set()
    for lens in grid.warm_batches(sv, max_len):
        dec.add((grid.pow2(len(lens)), grid.decode_pages(sv, max(lens))))
        pre.add((grid.pow2(len(lens)), grid.bucket(max(lens),
                                                   sv["len_bucket"])))
    for n, L in _batches_reaching(sv, max_len):
        b = grid.pow2(n)
        assert (b, grid.bucket(L, sv["len_bucket"])) in pre, (n, L)
        assert (b, grid.decode_pages(sv, L)) in dec, (n, L)


@pytest.mark.parametrize("name", NAMES)
def test_warm_batches_are_admissible(name):
    conf = _config(name)
    sv, max_len = conf["serving"], conf["max_effective_input"]
    for lens in grid.warm_batches(sv, max_len):
        assert grid.fits(sv, len(lens), max(lens))
        assert max(lens) <= max_len


def test_bucket_helpers():
    assert [grid.pow2(n) for n in (1, 2, 3, 5, 9)] == [1, 2, 4, 8, 16]
    assert [grid.min_rows(b) for b in (1, 2, 4, 8)] == [1, 2, 3, 5]
    sv = dict(page_tokens=128, slice_len=32)
    assert grid.decode_pages(sv, 480) == 4
    assert grid.decode_pages(sv, 481) == 8
    assert list(itertools.islice(grid.prefill_shapes(
        dict(sv, len_bucket=256, budget_pages=4, mem_bucket=1), 600), 10)) \
        == [(1, 256), (1, 512), (2, 256), (4, 256)]
