"""The one traffic generator: a mix file of parameters in, a schedule of
requests out.

A mix (``chipbench/traffic/<name>.json``) states the arrival process and
rate, the prompt and output length distributions with their bounds, the
lead before the window, the drain rule and the latency limits.  The
length distributions and the ``*Spec`` fits of ShareGPT and CodeFuse are
the paper's Fig. 6 models, copied from ``repro.cluster.trace`` so that no
change to the program moves them.

Every seed gets the same work.  Lengths and inter-arrival gaps are the
distribution's quantiles at evenly spaced probabilities (a stratified
draw), so the multiset of prompt lengths, output lengths and gaps is fixed
by the mix and the horizon.  Their order is drawn from the mix's
``order_seed`` where it states one, so that every run sends the same
lengths at the same times and runs differ only in the token ids and the
weights, which ``--seed`` draws; without it ``--seed`` draws the order
too.  Either way runs on different seeds are asked to do the same work.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List, Mapping

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Planned:
    """One request of the schedule."""

    idx: int
    due: float            # seconds after the traffic starts
    prompt: np.ndarray    # int32 token ids
    gen_len: int


def _quantile(dist: Mapping, p: float) -> float:
    kind = dist["dist"]
    if kind == "lognormal":
        return math.exp(dist["mu"] + dist["sigma"] * _NORMAL.inv_cdf(p))
    if kind == "uniform":
        return dist["min"] + p * (dist["max"] - dist["min"])
    if kind == "fixed":
        return float(dist["value"])
    raise ValueError(f"unknown length distribution {kind!r}")


def stratified_lengths(dist: Mapping, n: int) -> np.ndarray:
    """``n`` lengths at the distribution's quantiles (i + 0.5) / n,
    rounded and clipped to [min, max]."""
    ps = (np.arange(n) + 0.5) / n
    x = np.array([_quantile(dist, float(p)) for p in ps])
    return np.clip(np.round(x), dist["min"], dist["max"]).astype(np.int64)


def _gaps(arrivals: Mapping, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at evenly spaced quantiles."""
    rate = float(arrivals["rate_rps"])
    ps = (np.arange(n) + 0.5) / n
    proc = arrivals.get("process", "poisson")
    if proc == "poisson":
        return -np.log1p(-ps) / rate
    if proc == "gamma":
        # burstier than Poisson for shape < 1, same mean; a fixed draw
        # (the mix's pool_seed), so every run's seed gets the same gaps
        k = float(arrivals["shape"])
        rng = np.random.default_rng(int(arrivals.get("pool_seed", 0)))
        return rng.gamma(k, 1.0 / (k * rate), size=n)
    raise ValueError(f"unknown arrival process {proc!r}")


def n_requests(mix: Mapping, horizon_s: float) -> int:
    return max(1, int(round(float(mix["arrivals"]["rate_rps"]) * horizon_s)))


def schedule(mix: Mapping, seed: int, horizon_s: float,
             vocab: int) -> List[Planned]:
    """The requests due in the first ``horizon_s`` seconds of traffic,
    in due order."""
    n = n_requests(mix, horizon_s)
    rng = np.random.default_rng(seed)
    order = (np.random.default_rng(int(mix["order_seed"]))
             if "order_seed" in mix else rng)
    prompts = order.permutation(stratified_lengths(mix["prompt"], n))
    gens = order.permutation(stratified_lengths(mix["gen"], n))
    gaps = order.permutation(_gaps(mix["arrivals"], n))
    # the stratified gaps sum to about n / rate; scale them so the n
    # arrivals span the horizon exactly, whatever the permutation
    dues = np.cumsum(gaps) - gaps[0]
    dues *= horizon_s * (n - 1) / n / max(dues[-1], 1e-9)
    lo = int(mix.get("token_min", 2))  # 0 pads, 1 ends a sequence
    out = []
    for i in range(n):
        toks = rng.integers(lo, vocab, size=int(prompts[i]), dtype=np.int64)
        out.append(Planned(idx=i, due=float(dues[i]),
                           prompt=toks.astype(np.int32),
                           gen_len=int(gens[i])))
    return out


def summary(plan: List[Planned]) -> Dict[str, float]:
    p = np.array([len(r.prompt) for r in plan])
    g = np.array([r.gen_len for r in plan])
    return dict(n=len(plan), prompt_mean=float(p.mean()),
                prompt_max=int(p.max()), gen_mean=float(g.mean()),
                gen_max=int(g.max()), out_tokens=int(g.sum()))
