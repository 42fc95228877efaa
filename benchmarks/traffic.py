"""The one general traffic generator: an open-loop request schedule
from a traffic file's parameters and ``--seed``.

The lengths and the inter-arrival gaps are the quantiles of the file's
distributions at ``(i + 0.5) / n``, put in an order drawn from the
file's own ``schedule_seed``; ``--seed`` draws the token ids (and, in
the builder, the weights).  So every seed offers the same requests at
the same instants.  Measured on the chip (PERF.md, PR 24): with the
order drawn from ``--seed`` too, two seeds' p90 time to first token
differed by up to 10% where two runs of one seed differ by 1-2% -- the
order is part of the mix, not noise to average over.  Another order is
another traffic file.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Arrival:
    """One request of the schedule; ``due_s`` is relative to the
    opening of the measured window (negative: lead-in)."""

    rid: str
    due_s: float
    prompt: List[int]
    max_new_tokens: int


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` integer lengths at the distribution's evenly spaced
    quantiles, clipped to [min, max]."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)


def _gaps(rate_per_s: float, n: int, arrivals: str) -> np.ndarray:
    """``n`` inter-arrival gaps with mean exactly 1 / rate: the
    exponential's evenly spaced quantiles (a Poisson process's gaps)."""
    if arrivals != "poisson":
        raise ValueError(f"unknown arrival process {arrivals!r}")
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    return g / g.mean() / rate_per_s


def _span(params: dict, order, ids, n: int, start_s: float, span_s: float,
          vocab: int, prefix: str) -> List[Arrival]:
    """``n`` arrivals over [start_s, start_s + span_s): the gaps sum to
    the span, so the last arrival falls one (random) phase short of
    its end."""
    if n <= 0:
        return []
    prompts = order.permutation(_quantiles(params["prompt_tokens"], n))
    outputs = order.permutation(_quantiles(params["output_tokens"], n))
    gaps = order.permutation(_gaps(n / span_s, n, params["arrivals"]))
    due = start_s + np.cumsum(gaps) - gaps[0] * order.uniform(0.0, 1.0)
    total = params["max_total_tokens"]
    out = []
    for i in range(n):
        p = int(min(prompts[i], total - params["output_tokens"]["min"]))
        o = int(min(outputs[i], total - p))
        out.append(Arrival(
            rid=f"{prefix}{i:04d}", due_s=float(max(due[i], start_s)),
            prompt=[int(t) for t in ids.integers(0, vocab, p)],
            max_new_tokens=o))
    return sorted(out, key=lambda a: a.due_s)


def open_loop_schedule(params: dict, seed: int, seconds: float,
                       vocab: int) -> List[Arrival]:
    """The lead-in (``lead_in_s`` seconds before the window, rids
    ``lead...``) and the window's own requests (rids ``req...``), in
    order of their due instants."""
    order = np.random.default_rng([int(params["schedule_seed"]), 0x7AFF1C])
    ids = np.random.default_rng([int(seed), 0x70CE25])
    rate = float(params["rate_per_s"])
    lead_s = float(params.get("lead_in_s", 0.0))
    lead = _span(params, order, ids, int(round(rate * lead_s)), -lead_s,
                 lead_s, vocab, "lead")
    window = _span(params, order, ids, max(1, int(round(rate * seconds))),
                   0.0, seconds, vocab, "req")
    return lead + window
