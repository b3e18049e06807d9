"""The one traffic generator: every mix is a data file under bench/traffic/.

A mix names its loop and its distributions; this module turns it, and a
seed, into requests. Lengths and budgets are a fixed set, the mid-quantiles
of the stated distributions, sent in a fixed low-discrepancy order (every
stretch of consecutive requests holds short and long ones, and every
budget, in the stated shares); the seed draws the prompt tokens and the
order of an open loop's phases and gaps. So every seed asks for the same
work, and a closed loop's waves are the same from seed to seed.

Mix keys:
  loop         "closed" (clients that each wait for their reply) or "open"
               (arrivals on a schedule, whatever the server does)
  clients      closed loop: number of clients, each sending its next request
               when the last token of its previous one reached the host
  pool         closed loop: requests per pass through the mix's sizes; the
               clients take them in order and start over at the end
  rate         open loop: base arrival rate, requests/s (outside bursts)
  burst        open loop: {"factor", "seconds", "gap_mean_s"}: bursts at
               factor x rate lasting `seconds`, separated by exponential
               gaps of mean `gap_mean_s` (a Markov-modulated Poisson process)
  horizon_s    open loop: seconds of arrivals scheduled (longer than a window)
  prompt_len   {"kind": "fixed", "value": n}
  output_len   {"kind": "lognormal", "median", "sigma", "min", "max"}
               or {"kind": "fixed", "value": n}
  budgets      {"bits": [...], "shares": [...]}: power budget of a request
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
import numpy as np


@dataclasses.dataclass
class Item:
    """One request as the generator makes it, before the engine sees it."""
    uid: int
    prompt: np.ndarray          # (prompt_len,) int32
    max_new_tokens: int
    budget_bits: int


def load(bench_dir: str, name: str) -> dict:
    with open(os.path.join(bench_dir, "traffic", name + ".json")) as f:
        return json.load(f)


def _quantile_draws(dist: dict, n: int) -> np.ndarray:
    """n values at the mid-quantiles (i + 0.5) / n of ``dist``, ascending."""
    kind = dist["kind"]
    if kind == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    if kind == "lognormal":
        nd = NormalDist()
        mu = math.log(float(dist["median"]))
        vals = [math.exp(mu + float(dist["sigma"]) * nd.inv_cdf((i + 0.5) / n))
                for i in range(n)]
        out = np.clip(np.round(vals), int(dist["min"]), int(dist["max"]))
        return out.astype(np.int64)
    raise ValueError(f"unknown length distribution {kind!r}")


def _budget_draws(budgets: dict, n: int) -> np.ndarray:
    """A fixed multiset of n budgets in the stated shares."""
    bits = [int(b) for b in budgets["bits"]]
    shares = np.asarray(budgets["shares"], np.float64)
    cum = np.cumsum(shares / shares.sum())
    idx = np.searchsorted(cum, (np.arange(n) + 0.5) / n)
    return np.asarray([bits[min(i, len(bits) - 1)] for i in idx], np.int64)


def _exp_quantiles(mean: float, n: int) -> np.ndarray:
    return np.asarray([-mean * math.log(1.0 - (i + 0.5) / n)
                       for i in range(n)])


def rng_for(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed))


def _spread(values: np.ndarray, step: float) -> np.ndarray:
    """``values`` in the order of the low-discrepancy sequence i * step
    mod 1: the k-th smallest value goes where that sequence has its k-th
    smallest point."""
    where = np.argsort((np.arange(len(values)) * step) % 1.0, kind="stable")
    out = np.empty_like(values)
    out[where] = np.sort(values)
    return out


GOLDEN = 0.6180339887498949       # lengths
SILVER = 0.41421356237309515      # budgets: a step unrelated to GOLDEN


def make_items(mix: dict, seed: int, vocab: int, n: int) -> list[Item]:
    """n requests: prompts drawn from the seed; lengths and budgets from
    fixed quantile sets in a fixed order."""
    rng = rng_for(seed)
    plen = _quantile_draws(mix["prompt_len"], n)
    olen = _spread(_quantile_draws(mix["output_len"], n), GOLDEN)
    bud = _spread(_budget_draws(mix["budgets"], n), SILVER)
    prompts = rng.integers(0, vocab, size=(n, int(plen.max())), dtype=np.int64)
    return [Item(uid=i, prompt=prompts[i, :plen[i]].astype(np.int32),
                 max_new_tokens=int(olen[i]), budget_bits=int(bud[i]))
            for i in range(n)]


def open_schedule(mix: dict, seed: int) -> np.ndarray:
    """Arrival times (s after window start) of a Markov-modulated Poisson
    process: base phases at `rate`, each followed by a burst of
    burst.seconds at burst.factor x rate. One cycle of `horizon_s` holds
    round(horizon_s / (gap_mean_s + seconds)) such pairs, whose base phase
    lengths are exponential quantiles (mean gap_mean_s) scaled to fill the
    cycle exactly; gaps inside a phase are exponential quantiles. The seed
    permutes phases and gaps, so every cycle of every seed holds the same
    arrivals. The cycle repeats three times."""
    rng = rng_for(seed + 1)
    rate = float(mix["rate"])
    b = mix["burst"]
    horizon = float(mix["horizon_s"])
    burst_s, factor = float(b["seconds"]), float(b["factor"])
    n_phase = max(1, int(round(horizon / (float(b["gap_mean_s"]) + burst_s))))
    base = _exp_quantiles(1.0, n_phase)
    base = rng.permutation(base * ((horizon - n_phase * burst_s) / base.sum()))
    cycle = []
    t = 0.0
    for base_len in base:
        for length, r in ((base_len, rate), (burst_s, rate * factor)):
            n_arr = max(1, int(round(length * r)))
            gaps = rng.permutation(_exp_quantiles(1.0 / r, n_arr))
            # scale the gaps so the phase holds its arrivals exactly
            gaps = gaps * (length / gaps.sum())
            cycle.extend(t + np.cumsum(gaps))
            t += length
    cycle = np.sort(np.asarray(cycle))
    return np.concatenate([cycle + k * horizon for k in range(3)])


def mean_rate(mix: dict) -> float:
    """Long-run offered rate of an open mix, requests/s."""
    b = mix["burst"]
    base, burst = float(b["gap_mean_s"]), float(b["seconds"])
    rate = float(mix["rate"])
    return rate * (base + float(b["factor"]) * burst) / (base + burst)
