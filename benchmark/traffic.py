"""The one general traffic generator: a mix is a data file of
parameters, and what a run sends or trains on is made from it and
``--seed`` alone. Every key read here is in the mix's file: none has a
default in code.

Serving mixes (``kind: serve_open_loop``). The number of requests is
``rate x seconds``; their prompt lengths, output lengths and
inter-arrival gaps are the evenly spaced quantiles of the mix's
distributions (``arrivals.process: exponential_quantiles``: the gaps a
Poisson process of that rate draws from, stratified, so their sum is
fixed), so every run sends the *same multiset* of sizes and gaps. This
is a stratified trace, not a Poisson draw. Their order comes from the
mix's ``schedule_seed`` where that is a number (a replayed schedule:
every seed meets the same long prompt in the same burst, and ``--seed``
decides every token id, and the weights), and where it is null from
``--seed`` (seeds then differ in which long prompt meets which burst,
never in how much work the window holds).

Training mixes (``kind: train_stream``): a first-order Markov stream at
the configuration's vocabulary (each token has ``branching`` plausible
successors), fixed global batch, no packing.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, Iterator, List

import numpy as np


def _rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([0x6B6678, int(seed), *tags]))


def _normal_quantiles(n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    return np.array([statistics.NormalDist().inv_cdf(x) for x in u])


def quantile_lengths(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of a length distribution, clipped
    and rounded. Kinds: lognormal (median, sigma), uniform (min, max)."""
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * np.exp(dist["sigma"] * _normal_quantiles(n))
    elif kind == "uniform":
        x = dist["min"] + (dist["max"] - dist["min"]) * (
            (np.arange(n) + 0.5) / n)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(int)


def quantile_gaps(arrivals: Dict[str, Any], n: int, rate: float
                  ) -> np.ndarray:
    """``n`` inter-arrival gaps with mean 1/rate: evenly spaced
    quantiles of the exponential distribution."""
    if arrivals["process"] != "exponential_quantiles":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    return g / g.mean() / rate


def serve_requests(mix: Dict[str, Any], vocab: int, rate: float,
                   seconds: float, seed: int) -> List[Dict[str, Any]]:
    """The window's requests: [{due_s, prompt, max_new_tokens,
    temperature}], due times from 0, sorted."""
    n = max(1, int(round(rate * seconds)))
    schedule = mix["schedule_seed"]
    order = _rng(seed if schedule is None else schedule, 1)
    prompt_len = order.permutation(quantile_lengths(mix["prompt_tokens"], n))
    out_len = order.permutation(quantile_lengths(mix["output_tokens"], n))
    gaps = order.permutation(
        quantile_gaps(mix["arrivals"], max(1, n - 1), rate))
    due = np.concatenate([[0.0], np.cumsum(gaps)])[:n]
    ids = _rng(seed, 2)
    prefixes: List[List[int]] = []
    shared = mix["shared_prefix"]
    if shared:
        prefixes = [ids.integers(0, vocab, size=shared["tokens"]).tolist()
                    for _ in range(shared["count"])]
        ranks = np.arange(1, shared["count"] + 1) ** -float(shared["zipf"])
        pick = order.choice(shared["count"], size=n, p=ranks / ranks.sum())
    reqs = []
    for i in range(n):
        prompt = ids.integers(0, vocab, size=int(prompt_len[i])).tolist()
        if shared:
            prompt = prefixes[pick[i]] + prompt
        reqs.append({"due_s": float(due[i]), "prompt": prompt,
                     "max_new_tokens": int(out_len[i]),
                     "temperature": float(mix["temperature"])})
    return reqs


def setup_requests(mix: Dict[str, Any], vocab: int, seed: int
                   ) -> List[Dict[str, Any]]:
    """Requests a mix asks to be sent once in set-up: its shared
    prefixes (so the prefix cache is warm when the window opens)."""
    shared = mix["shared_prefix"]
    if not shared or not shared["send_in_setup"]:
        return []
    ids = _rng(seed, 2)
    return [{"prompt": ids.integers(0, vocab, size=shared["tokens"]).tolist(),
             "max_new_tokens": 1, "temperature": 0.0}
            for _ in range(shared["count"])]


def describe_lengths(reqs: List[Dict[str, Any]]) -> Dict[str, Any]:
    """The drawn distribution, for a run's log."""
    def q(xs):
        xs = sorted(xs)
        pick = lambda p: xs[min(len(xs) - 1, int(p * len(xs)))]
        return {"min": xs[0], "p50": pick(0.5), "p95": pick(0.95),
                "max": xs[-1], "sum": sum(xs)}
    return {"n": len(reqs),
            "prompt_tokens": q([len(r["prompt"]) for r in reqs]),
            "output_tokens": q([r["max_new_tokens"] for r in reqs]),
            "span_s": reqs[-1]["due_s"]}


def warm_prompt_lengths(mix: Dict[str, Any], buckets_from: int = 8
                        ) -> List[int]:
    """One prompt length for every power-of-two bucket the mix's prompt
    lengths fall into (what a bucketing server must have compiled)."""
    d = mix["prompt_tokens"]
    lo, hi = d["min"], d["max"]
    shared = mix["shared_prefix"]
    if shared:
        lo, hi = lo + shared["tokens"], hi + shared["tokens"]
    out, b = [], buckets_from
    while b < lo:
        b *= 2
    while True:
        out.append(min(b, hi))
        if b >= hi:
            return out
        b *= 2


# -- training ----------------------------------------------------------------

def markov_batches(mix: Dict[str, Any], vocab: int, seed: int
                   ) -> Iterator[np.ndarray]:
    """Endless [global_batch, sequence_tokens + 1] int32 batches of a
    fixed random first-order Markov chain; every row of every batch
    differs."""
    B, S = mix["global_batch_sequences"], mix["sequence_tokens"]
    branching = mix["branching"]
    chain = _rng(seed, 3)
    succ = chain.integers(0, vocab, size=(vocab, branching))
    probs = np.sort(chain.dirichlet(np.ones(branching) * 2.0))[::-1]
    step = 0
    while True:
        rng = _rng(seed, 4, step)
        toks = np.empty((B, S + 1), np.int32)
        toks[:, 0] = rng.integers(0, vocab, size=B)
        choices = rng.choice(branching, p=probs, size=(B, S))
        for t in range(S):
            toks[:, t + 1] = succ[toks[:, t], choices[:, t]]
        yield toks
        step += 1
