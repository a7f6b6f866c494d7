"""The general traffic generator: one mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) gives the arrival process, the prompt
and output length distributions and the prompt buckets.

An open loop is what users send: arrivals are a Poisson process (gaps drawn
independently from an exponential) and each request's lengths are drawn
independently, all from the seed, so bursts of arrivals and of long prompts
come as they would.

A closed loop keeps a fixed number of requests in flight, so a window holds
the work its requests carry, and every seed serves the same work: the
lengths are the stratified quantiles of their distributions,
``F^-1((i + 0.5) / n)`` for ``i < n``, and the seed only orders them and
draws the token ids.  The order is drawn in rounds of ``STRATA``: each round
takes one value from each of ``STRATA`` equal bands of quantiles, in a
random order.  So any run of rounds holds the same mix of sizes, and two
seeds differ in which request comes when, not in how much work a window
holds.

Mix keys:

* ``arrival``: ``{"process": "closed", "concurrency": "slots" | n}`` keeps
  that many requests in flight, the next one due when one finishes; or
  ``{"process": "poisson", "rate_per_s": r, "ramp_s": s}``, an open loop at
  rate ``r`` whose arrivals start ``s`` seconds before the measured window.
* ``prompt``: ``{"dist": "lognormal", "median", "sigma", "buckets"}``; a
  length is rounded up to the smallest bucket that holds it, and one above
  the largest bucket is cut to it.
* ``output``: ``{"dist": "lognormal", "median", "sigma", "min", "max"}``,
  the number of tokens served per request (the first comes from the
  prompt's prefill), clipped to ``[min, max]``.
* ``first_cohort``: ``"staggered"`` (closed loop) gives the requests in
  flight at the start of the window a uniform share of their drawn output
  (two tokens at least: the prefill's and one decoded), so that they finish
  spread out and not as one wave.
* ``requests``: how many requests a closed loop draws (it takes them in
  order); an open loop draws one for each arrival before the window's end.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np

_N = NormalDist()


@dataclasses.dataclass
class Req:
    rid: int
    prompt: np.ndarray   # int32 token ids
    output: int          # tokens to serve, the prefill's token included
    due: Optional[float]  # seconds from the window's start; None: closed loop


@dataclasses.dataclass
class Schedule:
    closed: bool
    concurrency: int     # closed loop: requests in flight
    requests: List[Req]  # closed: the order clients take them; open: by due
    max_len: int         # slot length the mix needs: longest prompt + output


STRATA = 8


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def stratified_order(values: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """``values`` (sorted) in rounds of one draw from each of ``STRATA``
    bands, the bands shuffled within each round."""
    n = len(values)
    k = min(STRATA, n)
    bands = [rng.permutation(b) for b in np.array_split(np.arange(n), k)]
    order = []
    for r in range(max(len(b) for b in bands)):
        pick = [b[r] for b in bands if r < len(b)]
        order.extend(rng.permutation(pick))
    return values[np.asarray(order, np.int64)]


def _lognormal(spec: Dict, z: np.ndarray) -> np.ndarray:
    """Lengths at standard normal draws (or quantiles) ``z``."""
    if spec.get("dist", "lognormal") != "lognormal":
        raise ValueError(f"unknown length distribution {spec.get('dist')!r}")
    return spec["median"] * np.exp(spec["sigma"] * z)


def _normal_quantiles(n: int) -> np.ndarray:
    return np.array([_N.inv_cdf(float(x)) for x in _quantiles(n)])


def bucket(length: float, buckets: List[int]) -> int:
    for b in sorted(buckets):
        if length <= b:
            return int(b)
    return int(max(buckets))


def prompt_lengths(mix: Dict, z: np.ndarray) -> np.ndarray:
    raw = _lognormal(mix["prompt"], z)
    return np.array([bucket(x, mix["prompt"]["buckets"]) for x in raw], np.int64)


def output_lengths(mix: Dict, z: np.ndarray) -> np.ndarray:
    o = mix["output"]
    raw = _lognormal(o, z)
    return np.clip(np.ceil(raw), o["min"], o["max"]).astype(np.int64)


def max_len(mix: Dict) -> int:
    """Slot length: the longest prompt plus the longest output (the first
    output token needs no slot row of its own, the spec width adds one)."""
    return int(max(mix["prompt"]["buckets"]) + mix["output"]["max"] + 1)


def generate(mix: Dict, seed: int, vocab: int, *, seconds: float, slots: int) -> Schedule:
    rng = np.random.default_rng(int(seed))
    arr = mix["arrival"]
    if arr["process"] == "closed":
        conc = slots if arr.get("concurrency", "slots") == "slots" else int(arr["concurrency"])
        n = max(int(mix["requests"]), conc)
        due = [None] * n
        z = _normal_quantiles(n)
        plen = stratified_order(prompt_lengths(mix, z), rng)
        olen = stratified_order(output_lengths(mix, z), rng)
    elif arr["process"] == "poisson":
        rate, t = float(arr["rate_per_s"]), -float(arr.get("ramp_s", 0.0))
        conc, due = 0, []
        while t < seconds:  # the last arrival, past the window, is not kept
            t += float(rng.exponential(1.0 / rate))
            due.append(t)
        n = len(due)
        plen = prompt_lengths(mix, rng.standard_normal(n))
        olen = output_lengths(mix, rng.standard_normal(n))
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    if conc and mix.get("first_cohort") == "staggered":
        share = stratified_order(_quantiles(conc), rng)
        olen[:conc] = np.maximum(2, np.ceil(share * olen[:conc])).astype(np.int64)
    reqs = []
    for i in range(n):
        if due[i] is not None and due[i] >= seconds:
            break
        prompt = rng.integers(0, vocab, size=int(plen[i]), dtype=np.int32)
        reqs.append(Req(rid=i, prompt=prompt, output=int(olen[i]),
                        due=None if due[i] is None else float(due[i])))
    return Schedule(closed=conc > 0, concurrency=conc, requests=reqs, max_len=max_len(mix))
