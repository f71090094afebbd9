"""A plain reference with a planted fault, for the tests: the dense
reference whose ``best`` is 1.0 higher at every position, so every
served token's gap grows by 1.0.  A configuration that names this module
by ``check.reference`` has to come out not ``correct``."""
from chipbench import reference


def score(params, m, seq, first, alt=None, precision="f32"):
    out = reference.score(params, m, seq, first, alt=alt,
                          precision=precision)
    out["best"] = out["best"] + 1.0
    return out
