"""The one traffic generator of the training cells: next-token batches drawn
from seeded order-1 Markov corpora, as a traffic file's `markov` block
states them.

Each of `n_corpora` corpora is a successor table (every token has
`successors` likely next tokens); row b of every batch follows corpus
b mod n_corpora, so the c workers' rows come from different corpora and
their losses differ. At each position a row jumps to a uniformly drawn
token with probability `noise`. All rows of a group of batches advance
together (vectorised over rows), so the host never sets the pace.
"""
from __future__ import annotations

from typing import Iterator

import numpy as np

#: batches drawn together
GROUP = 8


def markov_batches(vocab: int, seq_len: int, batch: int, seed: int, *,
                   n_corpora: int, noise: float, successors: int) -> Iterator[dict]:
    """Endless {"tokens", "labels"} batches of (batch, seq_len) int32, with
    labels the next tokens. The same seed gives the same stream."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0]))
    tables = rng.integers(0, vocab, (n_corpora, vocab, successors), dtype=np.int32)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    rows = GROUP * batch
    corpus = np.arange(rows) % batch % n_corpora
    while True:
        toks = np.empty((rows, seq_len + 1), np.int32)
        t = rng.integers(0, vocab, rows, dtype=np.int32)
        for s in range(seq_len + 1):
            toks[:, s] = t
            jump = rng.random(rows) < noise
            fresh = rng.integers(0, vocab, rows, dtype=np.int32)
            pick = rng.integers(0, successors, rows)
            t = np.where(jump, fresh, tables[corpus, t, pick])
        for g in range(GROUP):
            block = toks[g * batch:(g + 1) * batch]
            yield {"tokens": block[:, :-1], "labels": block[:, 1:]}


def batches_for(cfg: dict, traffic: dict, seed: int) -> Iterator[dict]:
    """The stream a cell's traffic file describes."""
    mk = traffic["markov"]
    return markov_batches(int(cfg["vocab_size"]), int(traffic["seq_len"]),
                          int(traffic["global_batch"]), seed,
                          n_corpora=int(mk["n_corpora"]), noise=float(mk["noise"]),
                          successors=int(mk["successors"]))
