"""The one traffic generator: reads a traffic file's parameters and draws a
cell's inputs from ``--seed``.

Every seed gets the same multiset of sizes and inter-arrival gaps, in its
own order: lengths and gaps sit at evenly spaced quantiles of their
distributions and the seed permutes them, and draws the token ids. So seeds
change which request is long and when, never how much work a run holds.

Traffic kinds:

* ``open_loop``: requests due at ``rate`` per second over the window,
  exponential gaps, prompt and output lengths from ``prompt`` / ``output``
  (``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
  ``{"dist": "uniform", "min", "max"}``).
* ``closed_loop``: fixed batches of ``batch`` inputs, each sent as soon as
  the one before it is done.
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass
class Req:
    rid: int
    due: float                     # seconds after the window opens
    prompt: np.ndarray             # (P,) int32
    gen: int


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the evenly spaced quantiles (i + 1/2) / n."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in q])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + (spec["max"] - spec["min"]) * q
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.round(v), spec["min"], spec["max"]).astype(np.int64)


def open_loop(traffic: dict, seed: int, seconds: float, vocab: int,
              rate: float | None = None):
    """The requests due in a window of ``seconds``, in due order."""
    rate = traffic["rate"] if rate is None else rate
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(seed)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps = rng.permutation(gaps * seconds / gaps.sum())
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    plen = rng.permutation(quantiles(traffic["prompt"], n))
    glen = rng.permutation(quantiles(traffic["output"], n))
    return [Req(rid=i, due=float(due[i]),
                prompt=rng.integers(0, vocab, int(plen[i]), dtype=np.int32),
                gen=int(glen[i])) for i in range(n)]


def lengths_used(traffic: dict, seconds: float,
                 rate: float | None = None) -> list:
    """Every prompt length a window of ``seconds`` holds, for any seed."""
    rate = traffic["rate"] if rate is None else rate
    n = max(1, int(round(rate * seconds)))
    return sorted(set(int(x) for x in quantiles(traffic["prompt"], n)))


def percentile(values, p: float) -> float:
    """The ``p``-th percentile by linear interpolation, as numpy's default."""
    return float(np.percentile(np.asarray(values, np.float64), p))

