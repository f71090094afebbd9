"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the completed requests, drawn from the seed and always holding the one
with the most served tokens, is run through the reference
(``chipbench.reference``) over its prompt and its served tokens.  For
each served token the reference gives the gap by which that token's
logit lies below its best at that position; the number compared is the
widest gap in the sample (``logit_gap``).  Every token was chosen
greedily by the program in bfloat16, so a sound program's gaps are
rounding near-ties; a wrong cache page, position, mask or weight puts
tokens far below the best.

A second number needs no reference: every completed request must have
exactly the output length the traffic gave it (``length_errors``).

With ``control``, the reference in float8 (``precision="fp8"``) takes
the program's place: at the same positions, the token the float8 forward
puts first is scored as if it had been served, and the verdict is
reached on its widest gap by the same rule, so a sound control comes out
not ``correct``.  The program's own reading is kept beside it.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List

import numpy as np


def sample(recs: List, seed: int, min_tokens: int, min_requests: int,
           max_requests: int) -> List:
    """Completed requests: the one with the most served tokens, then
    others in an order drawn from the seed until both ``min_tokens``
    served tokens and ``min_requests`` requests, or ``max_requests``."""
    done = [q for q in recs if q.ok and q.tokens]
    if not done:
        return []
    longest = max(done, key=lambda q: (len(q.tokens), -q.idx))
    rest = [q for q in done if q is not longest]
    order = np.random.default_rng(seed + 7).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if (n >= min_tokens and len(out) >= min_requests) \
                or len(out) >= max_requests:
            break
        out.append(rest[int(i)])
        n += len(rest[int(i)].tokens)
    return out


def check(conf: Dict, params, recs: List, seed: int, log: Callable,
          control: bool = False) -> Dict:
    """The ``correct`` verdict and the numbers it compared."""
    from chipbench import reference

    ck = conf["check"]
    m = conf["model"]
    length_errors = sum(1 for q in recs
                        if q.ok and len(q.tokens or []) != q.gen_len)
    picked = sample(recs, seed, ck["sample_tokens"], ck["min_requests"],
                    ck["sample_requests"])
    widest, n_tok, ctl = math.nan, 0, math.nan
    per = []
    for q in picked:
        seq = np.concatenate([q.prompt, np.asarray(q.tokens, np.int32)])
        first = len(q.prompt)
        alt = None
        if control:
            low = reference.score(params, m, seq, first, precision="fp8")
            alt = low["top"]
        sc = reference.score(params, m, seq, first, alt=alt)
        g = sc["best"] - sc["served"]
        per.append(float(g.max()))
        n_tok += len(g)
        if control:
            c = float((sc["best"] - sc["alt"]).max())
            ctl = c if math.isnan(ctl) else max(ctl, c)
    if per:
        widest = max(per)
    gap = ctl if control else widest
    limit = ck.get("logit_gap_limit")
    lim = math.inf if limit is None else float(limit)
    ok = (bool(picked) and length_errors == 0 and gap <= lim)
    log(f"check: {len(picked)} requests, {n_tok} served tokens; widest "
        f"logit gap {widest!r} (per request {per}) against limit {limit}; "
        f"length errors {length_errors}"
        + (f"; control (float8) in its place: widest gap {ctl!r}, correct "
           f"{ok}" if control else ""))
    out = dict(correct=ok,
               check={"logit_gap": dict(value=gap, limit=limit),
                      "length_errors": dict(value=length_errors, limit=0)})
    if control:
        out["program_gap"] = widest
    return out
