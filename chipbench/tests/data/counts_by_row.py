"""Counts of the tests' own, told apart from the dense ones by their
values: a million FLOPs per row of a slice, and a thousand bytes per
valid output token."""


def slice_flops(m, r):
    return 10 ** 6 * len(r["ctx"])


def decode_slice_min_bytes(m, r):
    return 1000 * sum(r["valid"])
