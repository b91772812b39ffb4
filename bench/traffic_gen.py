"""One general generator for every traffic mix, driven by the mix's file.

Serving (``"driver": "serve"``): an open-loop request schedule.  A topic
mixture in the manner of ``repro.sched.workloads`` (disjoint pools of token
ids, a fixed Zipf ranking inside each pool, von-Mises mixture weights over
the topic ring) with realistic sizes, and with one change: each request
draws all its tokens from one topic, so expert popularity is correlated
within a request as it is in a document.

Every seed gets the same work: in each segment of the run (pre-roll,
window, tail) the arrivals are the same, and the multiset of prompt
lengths, output lengths and topics is fixed by the mix's proportions
(largest remainder); the seed shuffles the sizes and draws the token ids.
So two seeds differ in which request comes when, not in how much is asked
or when.

Training (``"driver": "train"``): the synthetic LM stream of
``repro.data.pipeline.SyntheticLM`` (Zipf unigrams with a fixed successor
map), step-indexed so that every step's rows differ.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), stream])


def apportion(n: int, probs) -> np.ndarray:
    """Counts summing to ``n`` in the given proportions (largest
    remainder, ties to the lower index)."""
    p = np.asarray(probs, np.float64)
    p = p / p.sum()
    quota = n * p
    base = np.floor(quota).astype(np.int64)
    rest = n - int(base.sum())
    order = np.lexsort((np.arange(p.size), -(quota - base)))
    base[order[:rest]] += 1
    return base


def topic_weights(n_topics: int, kappa: float) -> np.ndarray:
    k = np.arange(n_topics)
    w = np.exp(kappa * np.cos(2.0 * np.pi * k / n_topics))
    return w / w.sum()


@dataclass
class Request:
    due_s: float            # offset from the start of the schedule
    tokens: np.ndarray      # [prompt] int32
    max_new_tokens: int
    topic: int
    segment: int = 0        # index of the run segment it belongs to


def serve_schedule(mix: dict, vocab: int, seed: int, segments):
    """Requests for consecutive segments of the run, sorted by due time.

    ``segments`` lists each segment's length in seconds (pre-roll,
    window, tail).  Each segment gets round(rate x length) requests with
    exactly the mix's proportions of prompt lengths, output lengths and
    topics, in an order the seed shuffles; its gaps are the quantiles of
    the exponential distribution in one fixed order (a stream that does
    not depend on the seed), scaled to fill the segment.  So every seed
    gets the same arrivals and the same sizes, in another order."""
    rng = _rng(seed, 1)
    fixed = _rng(0, 4)
    draw = topic_sampler(mix, vocab, seed)
    w = topic_weights(mix["topics"], mix["kappa"])
    out, t0 = [], 0.0
    for seg, length in enumerate(segments):
        n = max(1, round(float(mix["rate_rps"]) * length))
        lens = np.repeat(mix["prompt_lens"], apportion(n, mix["prompt_probs"]))
        outs = np.repeat(mix["output_lens"], apportion(n, mix["output_probs"]))
        topics = np.repeat(np.arange(mix["topics"]), apportion(n, w))
        for a in (lens, outs, topics):
            rng.shuffle(a)
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
        fixed.shuffle(gaps)
        due = t0 + length * (np.cumsum(gaps) - gaps[0]) / gaps.sum()
        out += [Request(float(due[i]), draw(rng, int(topics[i]), int(lens[i])),
                        int(outs[i]), int(topics[i]), seg) for i in range(n)]
        t0 += length
    return out


def topic_sampler(mix: dict, vocab: int, seed: int):
    """draw(rng, topic, length) -> token ids of one topic's pool, Zipf
    ranked; the pools are a fixed partition of the vocabulary per seed."""
    pool = int(mix["pool"])
    if pool * mix["topics"] > vocab:
        raise ValueError("topic pools do not fit the vocabulary")
    perm = _rng(seed, 0).permutation(vocab)[:pool * mix["topics"]].reshape(
        mix["topics"], pool)
    ranks = np.arange(1, pool + 1, dtype=np.float64) ** -float(mix["zipf_a"])
    cdf = np.cumsum(ranks / ranks.sum())

    def draw(rng, topic: int, length: int) -> np.ndarray:
        ids = np.minimum(np.searchsorted(cdf, rng.random(length)), pool - 1)
        return perm[topic, ids].astype(np.int32)
    return draw


def profile_batches(mix: dict, vocab: int, seed: int, n_batches: int,
                    batch: int, seq: int) -> list:
    """Batches of the mix's topics (in the mixture's proportions) for the
    server's expert-path profiling, from their own stream of the seed."""
    rng = _rng(seed, 3)
    draw = topic_sampler(mix, vocab, seed)
    w = topic_weights(mix["topics"], mix["kappa"])
    topics = np.repeat(np.arange(mix["topics"]),
                       apportion(n_batches * batch, w))
    rng.shuffle(topics)
    out = []
    for b in range(n_batches):
        toks = np.stack([draw(rng, int(t), seq + 1)
                         for t in topics[b * batch:(b + 1) * batch]])
        out.append({"tokens": toks[:, :-1], "labels": toks[:, 1:]})
    return out


class LMStream:
    """Step-indexed synthetic LM batches (copy of ``SyntheticLM``): Zipf
    unigrams over the vocabulary; with probability ``markov_p`` the next
    token is the previous token's fixed successor."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix = mix
        self.vocab = vocab
        self.seed = int(seed)
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        p = ranks ** -float(mix["zipf_a"])
        self.cdf = np.cumsum(p / p.sum())
        self.successor = _rng(seed, 2).integers(0, vocab, size=vocab)

    def batch(self, step: int) -> dict:
        b, s = int(self.mix["batch"]), int(self.mix["seq"])
        rng = _rng(self.seed, 1000 + int(step))
        toks = np.minimum(np.searchsorted(self.cdf, rng.random((b, s + 1))),
                          self.vocab - 1)
        follow = rng.random((b, s)) < float(self.mix["markov_p"])
        nxt = toks[:, 1:]
        nxt[follow] = self.successor[toks[:, :-1][follow]]
        return {"tokens": toks[:, :-1].astype(np.int32),
                "labels": toks[:, 1:].astype(np.int32)}
