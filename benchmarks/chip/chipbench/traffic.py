"""The general traffic generator: every mix is a data file it reads.

Sizes, arrival times and their order come from the mix's own
``shape_seed``, so every ``--seed`` gets the same sequence of them; the
run's seed draws the token ids (and, elsewhere, the weights). Runs with
different seeds then do the same work on other data.
"""
from __future__ import annotations

import numpy as np


def lengths(spec: dict, n: int, rng) -> np.ndarray:
    """``n`` whole lengths from a {dist, median, sigma, min, max} spec:
    lognormal around the median, or uniform over [min, max]."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    elif spec["dist"] == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def phases(tf: dict, seconds: float) -> list:
    """(name, start_s, end_s) of an open loop's arrival phases."""
    w = float(tf["warm_s"])
    return [("warm", 0.0, w), ("window", w, w + seconds),
            ("drain", w + seconds, w + seconds + float(tf["drain_max_s"]))]


def open_schedule(tf: dict, seconds: float, seed: int, vocab: int) -> list:
    """Open-loop Poisson arrivals at ``rate_rps``: the arrival times of
    each phase are a Poisson count of sorted uniforms, and the
    (prompt, max_new) sizes a fixed sequence; the seed draws unique
    prompt tokens."""
    shape = np.random.default_rng(int(tf["shape_seed"]))
    run = np.random.default_rng(int(seed))
    rate = float(tf["rate_rps"])
    out = []
    for name, lo, hi in phases(tf, seconds):
        n = int(shape.poisson(rate * (hi - lo)))
        times = np.sort(shape.uniform(lo, hi, n))
        plen = lengths(tf["prompt"], n, shape)
        gen = lengths(tf["max_new"], n, shape)
        for j, t in enumerate(times):
            prompt = run.integers(0, vocab, int(plen[j]), dtype=np.int64)
            out.append({"phase": name, "sched": float(t),
                        "prompt": prompt.astype(np.int32),
                        "max_new": int(gen[j])})
    return out


def zipf_counts(n_items: int, s: float, block: int) -> np.ndarray:
    """Whole counts per item of a block, proportional to 1 / rank**s."""
    w = 1.0 / np.arange(1, n_items + 1) ** s
    c = np.floor(w / w.sum() * block).astype(np.int64)
    c[: block - c.sum()] += 1
    return c


def docqa(tf: dict, seed: int, vocab: int, n_blocks: int) -> dict:
    """Documents and a closed loop's request stream: ``documents`` token
    arrays, and ``n_blocks`` blocks of requests, each block the same
    sequence of (document by Zipf, question length, max_new), in an order
    drawn from ``shape_seed``, every question unique."""
    shape = np.random.default_rng(int(tf["shape_seed"]))
    run = np.random.default_rng(int(seed))
    dl = lengths(tf["doc_len"], int(tf["documents"]), shape)
    docs = [run.integers(0, vocab, int(n), dtype=np.int64).astype(np.int32)
            for n in dl]
    block = int(tf["block"])
    which = np.repeat(np.arange(len(docs)),
                      zipf_counts(len(docs), float(tf["zipf_s"]), block))
    qlen = lengths(tf["question"], block, shape)
    gen = lengths(tf["max_new"], block, shape)
    order = shape.permutation(block)
    reqs = []
    for _ in range(n_blocks):
        for j in order:
            q = run.integers(0, vocab, int(qlen[j]),
                             dtype=np.int64).astype(np.int32)
            reqs.append({"doc": int(which[j]),
                         "prompt": np.concatenate([docs[which[j]], q]),
                         "max_new": int(gen[j])})
    return {"documents": docs, "requests": reqs}
