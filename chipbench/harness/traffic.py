"""One general generator for every traffic mix; a mix is a data file.

Every seed gets the SAME sizes and arrival gaps (the quantiles of the mix's
distributions at evenly spaced points) in the SAME order (shuffled once, by
the mix's own `shape_seed`), and its own token ids and documents. At four
fifths of capacity the order of arrivals alone moves a p90 first-token time
by tens of percent, so two seeds offer the same work at the same times, and
what differs between runs is the system and not the draw.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


def quantiles(dist: dict, n: int) -> np.ndarray:
    """n values of `dist` at the quantiles (i + 0.5) / n, clipped to its
    min/max, as integers unless the distribution says `"float": true`."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        v = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "exponential":
        v = -np.log1p(-u) * dist["mean"]
    elif kind == "fixed":
        v = np.full(n, dist["value"], float)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist:
        v = np.maximum(v, dist["min"])
    if "max" in dist:
        v = np.minimum(v, dist["max"])
    return v if dist.get("float") else np.rint(v).astype(np.int64)


def zipf_counts(count: int, s: float, n: int) -> np.ndarray:
    """How many of n draws go to each of `count` items under Zipf(s), by
    largest remainders, so that the counts sum to n exactly."""
    w = 1.0 / np.arange(1, count + 1) ** s
    exact = w / w.sum() * n
    base = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - base))[: n - base.sum()]:
        base[i] += 1
    return base


@dataclasses.dataclass
class PlannedRequest:
    index: int
    prompt: np.ndarray
    max_new_tokens: int
    due_s: float | None = None      # open loop: offset from the plan's start
    document: int | None = None


def _documents(traffic: dict, vocab: int, rng) -> list[np.ndarray]:
    spec = traffic.get("documents")
    if not spec:
        return []
    lens = quantiles(spec["len"], spec["count"])
    return [rng.integers(0, vocab, (int(n),)).astype(np.int32) for n in lens]


def serve_plan(traffic: dict, vocab: int, seed: int, n: int):
    """n requests of a serving mix and its documents. Sizes, arrival gaps
    and the choice of document are the mix's quantiles in the mix's own
    order; token ids and the documents' contents are the seed's."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(traffic.get("shape_seed", 0))
    docs = _documents(traffic, vocab, rng)
    prompt_lens = order.permutation(quantiles(traffic["prompt_len"], n))
    out_lens = order.permutation(quantiles(traffic["output_len"], n))
    which = None
    if docs:
        counts = zipf_counts(len(docs), traffic["documents"]["zipf_s"], n)
        which = order.permutation(np.repeat(np.arange(len(docs)), counts))
    due = None
    if traffic["kind"] == "open_loop":
        gaps = quantiles({"dist": "exponential", "float": True,
                          "mean": 1.0 / traffic["rate_per_s"]}, n)
        due = np.cumsum(order.permutation(gaps))
    plan = []
    for i in range(n):
        unique = rng.integers(0, vocab, (int(prompt_lens[i]),)).astype(
            np.int32)
        doc = int(which[i]) if which is not None else None
        prompt = unique if doc is None else np.concatenate([docs[doc], unique])
        plan.append(PlannedRequest(
            index=i, prompt=prompt, max_new_tokens=int(out_lens[i]),
            due_s=float(due[i]) if due is not None else None, document=doc))
    return plan, docs


def open_loop_count(traffic: dict, seconds: float) -> int:
    """Requests to plan so that arrivals cover fill + window."""
    horizon = traffic["fill_seconds"] + seconds
    return int(math.ceil(traffic["rate_per_s"] * horizon * 1.05)) + 8


def train_corpus(traffic: dict, vocab: int, seed: int) -> np.ndarray:
    """[corpus_batches * batch, seq_len + 1] int32 token rows that all
    differ: ids drawn Zipf(s) over a seeded permutation of the vocabulary
    (text is Zipfian; uniform ids would leave the model nothing to learn)."""
    rng = np.random.default_rng(seed)
    rows = traffic["corpus_batches"] * traffic["batch"]
    shape = (rows, traffic["seq_len"] + 1)
    s = traffic["token_zipf_s"]
    w = 1.0 / np.arange(1, vocab + 1) ** s
    cdf = np.cumsum(w / w.sum())
    ranks = np.searchsorted(cdf, rng.random(shape), side="left")
    ranks = np.minimum(ranks, vocab - 1)
    return rng.permutation(vocab).astype(np.int32)[ranks]
