"""The stock traffic generator: a traffic mix is a data file of parameters
(``bench/traffic/<name>.json``) and this module turns it and a seed into
requests.

Every seed gets the same work in the same order.  Prompt and output lengths
are stratified quantiles of the mix's distributions and the inter-arrival
gaps stratified quantiles of the exponential, each put in one fixed order
(the same for every seed); the seed draws only the token ids.  Runs with
different seeds then differ by what the model computes, not by the amount
of work or when it arrives.

Parameters:

* ``rate_per_s``: requests due at fixed wall times, ``round(rate *
  seconds)`` of them in the window, open loop;
* ``prompt`` / ``output``: a length distribution, ``{"dist": "lognormal",
  "median", "sigma", "min", "max"}``, ``{"dist": "uniform", "min", "max"}``
  or ``{"dist": "fixed", "value"}``;
* ``generator`` (optional): the name of a module ``bench/traffic/<name>.py``
  that takes this module's place for the mix.  It provides ``requests(mix,
  seed, seconds, vocab)`` and ``warm_up(mix, vocab)``, and may provide
  ``Loop``, the driver class (default ``bench.drive.OpenLoop``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import sys
from statistics import NormalDist
from typing import List

import numpy as np

TRAFFIC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traffic")


@dataclasses.dataclass
class Req:
    """One generated request."""
    prompt: np.ndarray       # int32 token ids
    max_new: int
    due_s: float = 0.0       # offset from the window's start


def for_mix(mix: dict):
    """The generator module of a mix: the one it names, else this one."""
    name = mix.get("generator")
    if name is None:
        return sys.modules[__name__]
    path = os.path.join(TRAFFIC, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench.traffic.{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles (at (i + 0.5) / n) of a length
    distribution, as whole tokens, ascending."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "fixed":
        x = np.full(n, float(dist["value"]))
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] + 1 - dist["min"])
        x = np.floor(x)
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(v)) for v in u])
        x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = dist.get("min", 1), dist.get("max", int(x.max()))
    return np.clip(x, lo, hi).astype(np.int64)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([stream, seed & 0xFFFFFFFF, seed >> 32])


def _fixed_order(values: np.ndarray, stream: int) -> np.ndarray:
    """The values in one order that no seed changes."""
    return np.random.default_rng([stream]).permutation(values)


def _tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, n, dtype=np.int32)


def requests(mix: dict, seed: int, seconds: float, vocab: int) -> List[Req]:
    """``round(rate * seconds)`` requests due within ``(0, seconds]``: the
    gaps are the exponential's stratified quantiles in a fixed order,
    scaled so that the last request is due as the window closes."""
    n = max(1, round(mix["rate_per_s"] * seconds))
    u = (np.arange(n) + 0.5) / n
    gaps = _fixed_order(-np.log1p(-u), 1)
    due = np.cumsum(gaps) * (seconds / gaps.sum())
    plen = _fixed_order(quantiles(mix["prompt"], n), 2)
    olen = _fixed_order(quantiles(mix["output"], n), 3)
    trng = _rng(seed, 2)
    return [Req(_tokens(trng, int(plen[i]), vocab), int(olen[i]),
                float(due[i])) for i in range(n)]


def warm_up(mix: dict, vocab: int) -> List[Req]:
    """Two requests that run every step program the mix uses: two-chunk
    prompts, and (when the mix decodes) a few tokens each, so that prefill
    alone, prefill beside decode, and decode alone all occur."""
    chunk = mix["engine"]["chunk"]
    outs = quantiles(mix["output"], 1)
    new = 1 if int(outs.max()) == 1 else 3
    rng = np.random.default_rng([4])
    return [Req(_tokens(rng, chunk + 1, vocab), new) for _ in range(2)]
