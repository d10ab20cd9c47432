"""One general generator for every traffic mix.

A mix is a JSON file of parameters (``bench/traffic/<mix>.json``):

``loop``      ``"open"`` (requests due on a schedule, whatever the
              server does) or ``"closed"`` (``clients`` callers, each
              sending its next request when the last one ends);
``arrival``   open loop: ``"poisson"`` (exponential gaps);
``rate_rps``  open loop: mean arrival rate;
``prompt``, ``output``: a length distribution each, ``{"dist":
              "lognormal", "median", "sigma", "min", "max"}``,
              ``{"dist": "uniform", "min", "max"}`` or ``{"dist":
              "fixed", "value"}``;
``ids``       token ids drawn uniformly from ``[low, high)``;
``order_seed`` (optional): where given, the order of gaps and lengths
              comes from it instead of the run's seed;
``source``    (optional): where the mix's numbers come from.

Any other key is an error, so a mix never sets a knob that nothing
reads.  Every request is greedy (the check compares served tokens
with the reference's best).

Every seed gets the same multiset of gaps and lengths, drawn at
stratified quantiles of the distributions, in an order of its own (or
the mix's ``order_seed`` order); the token ids are the seed's own.  So
the work of a run is fixed, and seeds differ only in what the model
sees and, without ``order_seed``, in which request comes when.  An
open-loop tail depends on that order (on one chip a burst of long
prompts moved the chat mix's p90 from 0.40 to 0.76 s), so a mix whose
tail is judged fixes it.
Seeded with ``numpy``'s PCG64, which takes any non-negative integer.

The arrival arithmetic follows ``benchmarks/traces.py``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

KEYS = {"loop", "clients", "arrival", "rate_rps", "prompt", "output", "ids",
        "order_seed", "source"}
PERIOD = 8            # closed loop: stratified sizes each caller cycles


def check_mix(traffic: dict) -> None:
    """Refuse a mix with a key the generator does not read."""
    extra = set(traffic) - KEYS
    if extra:
        raise ValueError(f"traffic mix has keys nothing reads: {sorted(extra)}")
    if traffic["loop"] not in ("open", "closed"):
        raise ValueError(f"unknown loop {traffic['loop']!r}")


@dataclass(frozen=True)
class Req:
    idx: int
    due_s: float          # offset from the window's start (open loop)
    prompt: Tuple[int, ...]
    max_new: int


def _quantile(dist: dict, u: np.ndarray) -> np.ndarray:
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(u.shape, float(dist["value"]))
    if kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        return lo + u * (hi + 1 - lo) - 0.5
    if kind == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        return dist["median"] * np.exp(dist["sigma"] * z)
    raise ValueError(f"unknown length distribution {kind!r}")


def lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths at stratified quantiles ``(i + 0.5) / n``, clipped to
    ``[min, max]``, in the order ``rng`` gives."""
    u = (np.arange(n) + 0.5) / n
    x = np.rint(_quantile(dist, u)).astype(np.int64)
    lo = dist.get("min", dist.get("value"))
    hi = dist.get("max", dist.get("value"))
    x = np.clip(x, lo, hi)
    return x[rng.permutation(n)]


def gaps(traffic: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps of mean ``1 / rate_rps``, at stratified
    quantiles, in the order ``rng`` gives."""
    mean = 1.0 / traffic["rate_rps"]
    u = (np.arange(n) + 0.5) / n
    if traffic["arrival"] != "poisson":
        raise ValueError(f"unknown arrival {traffic['arrival']!r}")
    g = -np.log1p(-u) * mean
    return g[rng.permutation(n)]


def _prompts(traffic: dict, lens: np.ndarray,
             rng: np.random.Generator) -> List[Tuple[int, ...]]:
    lo, hi = traffic["ids"]["low"], traffic["ids"]["high"]
    return [tuple(int(t) for t in rng.integers(lo, hi, size=int(n)))
            for n in lens]


def open_schedule(traffic: dict, seed: int, seconds: float) -> List[Req]:
    """Requests due in ``[0, seconds)``: ``round(rate * seconds)`` of
    them, the first due at 0."""
    n = max(1, int(round(traffic["rate_rps"] * seconds)))
    rng = np.random.Generator(np.random.PCG64(seed))
    order = np.random.Generator(np.random.PCG64(
        traffic.get("order_seed", seed)))
    g = gaps(traffic, n, order)
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    # the multiset of gaps is the same for every seed, so the span is
    # too; scale it onto the window so that every request is due in it
    span = float(np.sum(g))
    due = due * (seconds / span) if span > seconds else due
    p = lengths(traffic["prompt"], n, order)
    o = lengths(traffic["output"], n, order)
    prompts = _prompts(traffic, p, rng)
    return [Req(i, float(due[i]), prompts[i], int(o[i])) for i in range(n)]


def closed_streams(traffic: dict, seed: int, per_client: int
                   ) -> List[List[Req]]:
    """For each of ``clients`` callers, the requests it sends in turn
    (``per_client`` of them; a run uses as many as its window admits).

    Each caller cycles through the same ``PERIOD`` stratified sizes,
    freshly shuffled every period, so any run's first requests cover the
    distributions alike on every seed."""
    rng = np.random.Generator(np.random.PCG64(seed))
    order = np.random.Generator(np.random.PCG64(
        traffic.get("order_seed", seed)))
    ident = np.random.Generator(np.random.PCG64(0))
    pool_p = lengths(traffic["prompt"], PERIOD, ident)
    pool_o = lengths(traffic["output"], PERIOD, ident)
    streams: List[List[Req]] = []
    idx = 0
    for _ in range(traffic["clients"]):
        reqs: List[Req] = []
        while len(reqs) < per_client:
            p = pool_p[order.permutation(PERIOD)]
            o = pool_o[order.permutation(PERIOD)]
            for pi, oi in zip(_prompts(traffic, p, rng), o):
                reqs.append(Req(idx, 0.0, pi, int(oi)))
                idx += 1
        streams.append(reqs[:per_client])
    return streams


def max_prompt(traffic: dict) -> int:
    d = traffic["prompt"]
    return int(d.get("max", d.get("value")))


def per_client_bound(traffic: dict, seconds: float) -> int:
    """Requests one closed-loop caller could send in ``seconds`` if each
    of its ``min`` output tokens took at least 2 ms: an upper bound, so
    the stream never runs dry."""
    d = traffic["output"]
    per_req = 0.002 * int(d.get("min", d.get("value")))
    return max(4, int(math.ceil(seconds / per_req)))
