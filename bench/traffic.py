"""Traffic from a mix file: one general generator for every mix.

A mix (``bench/traffic/<name>.json``) holds only parameters:

* ``online``: open-loop arrivals with gamma inter-arrival gaps of a given
  coefficient of variation, at ``rate_share_of_knee`` times the
  configuration's ``knee_req_per_s``, with lognormal prompt and output
  lengths (median, sigma, clipped to [min, max]).
* ``offline`` (optional): a backlog that never drains -- lognormal prompt
  and output lengths, ``waiting`` requests kept queued at all times, and
  ``preroll_s`` seconds of offline work before the window opens.
* ``warm_max_seqs``: the most sequences one iteration of this mix is
  warmed up for (``program.warm_up``); a plan with more compiles inside
  the window, and the run's ``compiles_in_window`` shows it.

Every seed gets the same set of gaps and of lengths, in another order: the
sets come from quantiles of their distributions, the seed only permutes
them and draws the token ids.  So runs with different seeds carry the same
work.  Each part of the mix draws from its own stream of the seed, so one
seed gives the same online arrivals in every mix that has the same
``online`` parameters.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import List

import numpy as np
from scipy import special, stats

HERE = os.path.dirname(os.path.abspath(__file__))
STREAMS = {"online": 0, "offline": 1}


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_set(spec: dict, n: int) -> np.ndarray:
    """n lengths at the quantiles of the clipped lognormal, ascending."""
    z = special.ndtri(_quantiles(n))
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def gamma_gaps(rate: float, cv: float, n: int) -> np.ndarray:
    """n inter-arrival gaps at the quantiles of a gamma distribution of
    mean 1/rate and the given coefficient of variation, rescaled so that
    they sum to exactly n/rate."""
    shape = 1.0 / (cv * cv)
    g = stats.gamma.ppf(_quantiles(n), shape, scale=1.0 / (rate * shape))
    return g * (n / rate) / g.sum()


@dataclass
class OnlineRequest:
    due: float  # seconds after the window opens
    prompt: np.ndarray
    max_new: int
    sent: float = None  # seconds after the window opens
    handle: object = None
    error: str = None


@dataclass
class Traffic:
    rate: float
    online: List[OnlineRequest]
    offline: List[tuple] = field(default_factory=list)  # (prompt, max_new)
    waiting: int = 0
    preroll_s: float = 0.0


def online_rate(mix: dict, cfg: dict) -> float:
    return float(mix["online"]["rate_share_of_knee"]) * float(cfg["knee_req_per_s"])


def generate(mix: dict, cfg: dict, vocab: int, seed: int, seconds: float,
             rate: float = None) -> Traffic:
    """The requests of one run: online arrivals due inside a window of
    ``seconds`` and the offline backlog's queue, from ``seed``."""
    rate = online_rate(mix, cfg) if rate is None else rate
    on = mix["online"]
    rng = np.random.default_rng([seed, STREAMS["online"]])
    n = max(1, int(round(rate * seconds)))
    gaps = rng.permutation(gamma_gaps(rate, on["cv"], n))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    plen = rng.permutation(lognormal_set(on["prompt"], n))
    olen = rng.permutation(lognormal_set(on["output"], n))
    online = [
        OnlineRequest(
            float(t), rng.integers(0, vocab, int(p)).astype(np.int32), int(o)
        )
        for t, p, o in zip(due, plen, olen)
    ]
    tr = Traffic(rate=rate, online=online)
    off = mix.get("offline")
    if off:
        rng = np.random.default_rng([seed, STREAMS["offline"]])
        m = off["pool"]
        plen = rng.permutation(lognormal_set(off["prompt"], m))
        olen = rng.permutation(lognormal_set(off["output"], m))
        tr.offline = [
            (rng.integers(0, vocab, int(p)).astype(np.int32), int(o))
            for p, o in zip(plen, olen)
        ]
        tr.waiting = off["waiting"]
        tr.preroll_s = off["preroll_s"]
    return tr
