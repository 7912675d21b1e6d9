"""Seeded traffic generators, read from a traffic mix's parameters.

``token_batches`` is a copy of ``repro.data.synthetic.token_batches``
and ``serve_requests`` follows ``repro.serve.paged.poisson_trace`` with
log-normal prompt lengths.  Both take any whole seed (several above
2**32 included) and give the same inputs for the same seed.
"""
from __future__ import annotations

import numpy as np


def token_batches(step: int, *, batch: int, seq: int, vocab: int,
                  seed: int = 0, noise: float = 0.05) -> dict:
    """Deterministic synthetic LM batch: each row follows t_{i+1} = (t_i +
    stride) mod vocab for a per-row stride from a small set, with a
    ``noise`` share of corrupted positions."""
    rng = np.random.default_rng(seed + step)
    start = rng.integers(0, vocab, size=(batch, 1))
    stride = rng.choice([1, 2, 3, 5, 7], size=(batch, 1))
    toks = (start + stride * np.arange(seq)[None, :]) % vocab
    corrupt = rng.random((batch, seq)) < noise
    toks = np.where(corrupt, rng.integers(0, vocab, size=(batch, seq)),
                    toks).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)
    labels[:, -1] = -100
    return {"tokens": toks, "labels": labels}


def node_batch(step: int, mix: dict, vocab: int, seed: int) -> dict:
    """Step ``step``'s batch for every node: ``(nodes, rows, seq)``."""
    n, b, t = mix["nodes"], mix["rows_per_node"], mix["seq"]
    raw = token_batches(step, batch=n * b, seq=t, vocab=vocab, seed=seed,
                        noise=mix["noise"])
    return {k: v.reshape(n, b, t) for k, v in raw.items()}


def serve_requests(mix: dict, seed: int, vocab: int) -> list[tuple]:
    """``[(rid, prompt_tokens, arrival_step), ...]`` sorted by arrival.

    The prompt lengths and the gaps between arrivals, in their order, are
    drawn from the mix's own ``shape_seed``, so every run seed serves the
    same work; the run seed draws the token ids.  The first request
    arrives at step 0."""
    p = mix["prompt"]
    n = mix["requests"]
    shape_rng = np.random.default_rng(mix["shape_seed"])
    lens = np.exp(np.log(p["median"])
                  + p["sigma"] * shape_rng.standard_normal(n))
    lens = np.clip(np.rint(lens), p["min"], p["max"]).astype(np.int64)
    gaps = shape_rng.exponential(1.0 / mix["rate_per_step"], size=n - 1)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)])
    rng = np.random.default_rng(seed)
    return [(rid, tuple(int(x) for x in rng.integers(0, vocab,
                                                      size=int(lens[rid]))),
             float(arrivals[rid])) for rid in range(n)]
