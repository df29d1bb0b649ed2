"""Traffic generators, driven by the data files beside this module.

A traffic file (``bench/traffic/<name>.json``) names a generator ``kind``
and its parameters.  Every generator turns ``(traffic, sizes, seed)`` into
a :class:`Plan`: the request specs in the order they are offered, and for
an open loop their due times.

Work is fixed by the traffic file, not by the seed: each attribute
(prompt length, answer length, image bucket, gap between arrivals) is
drawn at ``n`` stratified quantiles of its distribution, so every seed
offers the same multiset of sizes; the seed only shuffles them, each
attribute on its own, and draws the token ids and image features.

Kinds:

* ``open_poisson`` -- independent users at ``rate_rps``; gaps are the
  exponential distribution's quantiles, shuffled.  ``n`` is the number of
  arrivals the window can hold.
* ``backlog`` -- an offline batch: the harness keeps ``depth_slots`` x
  ``n_slots`` requests queued all window; ``n`` is ``max_requests``.
* ``closed`` -- ``clients`` users, each sending its next request after the
  previous one finished and an exponential think time of mean
  ``think_s_mean``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import List, Optional

import numpy as np

KINDS = ("open_poisson", "backlog", "closed")


@dataclass
class RequestSpec:
    """One request as offered: the prompt's text ids after ``n_patches``
    image placeholders, the answer length asked for, and where its image
    features come from (generated lazily from ``feat_seed``)."""

    idx: int
    n_patches: int
    text: np.ndarray
    max_new: int
    feat_seed: int
    temperature: float = 0.0

    @property
    def prompt_len(self) -> int:
        return self.n_patches + len(self.text)


@dataclass
class Plan:
    kind: str
    requests: List[RequestSpec]
    due_s: Optional[np.ndarray] = None      # open loop: offsets from start
    think_s: Optional[np.ndarray] = None    # closed loop: per request
    params: dict = field(default_factory=dict)


def _quantiles(n: int) -> np.ndarray:
    """Midpoints of ``n`` equal-probability strata."""
    return (np.arange(n) + 0.5) / n


def draw_lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths at stratified quantiles of ``dist``, clipped to
    ``[min, max]`` and shuffled by ``rng``.

    ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
    or ``{"dist": "uniform", "min": a, "max": b}`` (both ends included)."""
    u = _quantiles(n)
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif dist["dist"] == "uniform":
        x = lo + u * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    out = np.clip(np.rint(x), lo, hi).astype(np.int64)
    return rng.permutation(out)


def draw_buckets(images: list, n: int, rng: np.random.Generator
                 ) -> np.ndarray:
    """Image bucket index of each request, in exact shares, shuffled.
    ``images`` is ``[{"bucket": i, "share": p}, ...]``."""
    shares = np.array([float(i["share"]) for i in images])
    if abs(shares.sum() - 1.0) > 1e-6:
        raise ValueError(f"image shares sum to {shares.sum()}, not 1")
    counts = np.floor(shares * n).astype(int)
    # hand the remainder to the largest fractional parts, deterministically
    rest = n - counts.sum()
    order = np.argsort(-(shares * n - counts), kind="stable")
    counts[order[:rest]] += 1
    out = np.concatenate([np.full(c, int(i["bucket"]))
                          for c, i in zip(counts, images)])
    return rng.permutation(out)


def exponential_quantiles(mean: float, n: int, rng: np.random.Generator
                          ) -> np.ndarray:
    return rng.permutation(-np.log1p(-_quantiles(n)) * float(mean))


def request_count(traffic: dict, seconds: float) -> int:
    kind = traffic["kind"]
    if kind == "open_poisson":
        return max(1, int(math.ceil(float(traffic["rate_rps"]) * seconds)))
    if kind == "backlog":
        return int(traffic["max_requests"])
    if kind == "closed":
        # a client can send at most one request per think time plus the
        # shortest service; bounded above by the file's cap
        return int(traffic["max_requests"])
    raise ValueError(f"unknown traffic kind {kind!r}; known: {KINDS}")


def generate(traffic: dict, buckets: tuple, vocab_size: int, seed: int,
             seconds: float, stream: int = 0) -> Plan:
    """The request plan of one run.  ``buckets`` are the configuration's
    per-image patch counts (``vision_token_buckets``); a traffic file names
    images by bucket index, so one mix serves any vision model.  ``stream``
    separates independent draws from one seed (0: the window, 1: warm-up).
    """
    kind = traffic["kind"]
    n = request_count(traffic, seconds)
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, stream])
    bucket_idx = draw_buckets(traffic["images"], n, rng)
    text_len = draw_lengths(traffic["text_tokens"], n, rng)
    max_new = draw_lengths(traffic["output_tokens"], n, rng)
    temp = float(traffic.get("temperature", 0.0))
    reqs = []
    for i in range(n):
        text = rng.integers(3, vocab_size, int(text_len[i]), dtype=np.int64)
        reqs.append(RequestSpec(
            idx=i, n_patches=int(buckets[int(bucket_idx[i])]),
            text=text.astype(np.int32), max_new=int(max_new[i]),
            feat_seed=int(rng.integers(0, 2**62)), temperature=temp))
    plan = Plan(kind=kind, requests=reqs, params=dict(traffic))
    if kind == "open_poisson":
        gaps = exponential_quantiles(1.0 / float(traffic["rate_rps"]), n,
                                     rng)
        plan.due_s = np.cumsum(gaps) - gaps[0]
    elif kind == "closed":
        plan.think_s = exponential_quantiles(
            float(traffic["think_s_mean"]), n, rng)
    return plan


def vision_features(spec: RequestSpec, feat_dim: int) -> np.ndarray:
    """(1, n_patches, feat_dim) float32 stub image features of one request,
    unit variance, from the request's own seed."""
    rng = np.random.default_rng(spec.feat_seed)
    return rng.standard_normal((1, spec.n_patches, feat_dim),
                               dtype=np.float32)
