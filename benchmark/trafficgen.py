"""The one general traffic generator.  A mix is a data file
(``benchmark/traffic/<name>.json``); this turns its parameters and a seed
into requests or batches.  Imports numpy, never JAX (the load generator's
process uses it).

Every seed gets the SAME sizes and inter-arrival gaps, drawn once from the
mix's ``base_seed``: a run is the same amount of work whatever its seed.  An
open loop replays them in the same ORDER too — its metrics are tails, and
which long prompt lands in which burst moved a 95th percentile by a fifth
from seed to seed (PERF.md, PR 24).  The seed draws the token ids (and the
weights), never the shape of the work.
"""

import numpy as np


def _draw(rng, dist, n):
    kind = dist["dist"]
    if kind == "lognormal":
        x = rng.lognormal(np.log(dist["median"]), dist["sigma"], n)
    elif kind == "uniform":
        x = rng.uniform(dist["min"], dist["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.floor(x), dist["min"], dist["max"]).astype(int)


def sizes(traffic, n):
    """``n`` (prompt_len, output_len) pairs from the mix's ``base_seed``."""
    base = np.random.default_rng(traffic["base_seed"])
    prompts = _draw(base, traffic["prompt_len"], n)
    outputs = _draw(base, traffic["output_len"], n)
    return [(int(p), int(o)) for p, o in zip(prompts, outputs)]


def arrivals(traffic, seconds):
    """Due times (seconds from the window's start) of an open loop at the
    mix's fixed rate: ``round(rate * seconds)`` exponential gaps from
    ``base_seed``, scaled to end at ``seconds``."""
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {traffic['arrivals']!r}")
    gaps = np.random.default_rng([traffic["base_seed"], 2]).exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    return np.cumsum(gaps) - gaps[0] * 0.5, n


def prompt_tokens(vocab, length, seed, index):
    """Token ids of request ``index``: uniform over the vocabulary, so no
    two prompts share a prefix (this generator has no sharing parameter
    yet; a mix that wants sharing adds one)."""
    rng = np.random.default_rng([seed, 4, index])
    return rng.integers(0, vocab, length).astype(np.int32)


def open_loop_schedule(traffic, vocab, seconds, seed):
    """The whole open-loop schedule as plain lists, ready for JSON."""
    due, n = arrivals(traffic, seconds)
    return [{"index": i, "due_s": float(due[i]),
             "input_ids": prompt_tokens(vocab, p, seed, i).tolist(),
             "max_new_tokens": o}
            for i, (p, o) in enumerate(sizes(traffic, n))]


def closed_loop_requests(traffic, vocab, seed):
    """An endless stream of requests for a closed loop: the mix's ``cycle``
    sizes, over and over, with fresh tokens."""
    n, cycle = traffic["cycle"], 0
    while True:
        for i, (p, o) in enumerate(sizes(traffic, n)):
            idx = cycle * n + i
            yield idx, prompt_tokens(vocab, p, seed, idx), o
        cycle += 1


def train_batches(traffic, vocab, rows, seed):
    """An endless stream of packed ``[rows, seq_len]`` token batches from a
    ``support``-symbol subset of the vocabulary: a few optimizer steps
    visibly lower the loss on them, towards ln(support); uniform tokens
    over 50k symbols only memorize.  (The scheme is chip_smoke.py's
    ``structured_tokens``, see PERF.md Open questions.)"""
    rng = np.random.default_rng([seed, 5])
    symbols = rng.choice(vocab, size=traffic["support"], replace=False)
    while True:
        yield symbols[rng.integers(0, traffic["support"],
                                   (rows, traffic["seq_len"]))].astype(np.int32)
