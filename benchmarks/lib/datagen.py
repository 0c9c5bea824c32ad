"""The benchmark's training data: a packed token stream made from a seed.

Token ids are Zipf-distributed (p(rank r) ~ r^-a) over a seeded permutation
of the vocabulary, documents have lognormal lengths and are joined by one EOS
id, and the stream is cut into sequences with no padding.  Uniform random
tokens cannot be learned; on this stream the loss must fall from about ln V
as the model learns the unigram skew.  NumPy only: the program under test
receives the generated arrays and nothing else.

The same (seed, vocab, parameters) gives byte-identical batches.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


class PackedStream:
    """An endless stream of token ids; `next_batch` cuts [B, S+1] windows
    from it and returns tokens [B, S] and next-token targets [B, S]."""

    def __init__(self, seed: int, vocab_size: int, stream: Dict[str, Any]):
        self._rng = np.random.default_rng([int(seed), 0x5EED])
        self._eos = int(stream["eos_id"])
        if not 0 <= self._eos < vocab_size:
            raise ValueError(f"eos_id {self._eos} outside vocabulary of {vocab_size}")
        doc = stream["doc_len"]
        if doc["distribution"] != "lognormal":
            raise ValueError(f"unknown doc_len distribution {doc['distribution']!r}")
        self._doc_mu = float(np.log(doc["median_tokens"]))
        self._doc_sigma = float(doc["sigma"])
        self._doc_min = int(doc["min_tokens"])
        # Rank r (1 = most frequent) -> a token id other than EOS, by a
        # permutation drawn from the seed, so frequent ids are scattered over
        # the table as a tokenizer's are.
        ids = np.delete(np.arange(vocab_size, dtype=np.int32), self._eos)
        self._rank_to_id = self._rng.permutation(ids)
        weights = np.arange(1, ids.size + 1, dtype=np.float64) ** -float(stream["zipf_exponent"])
        self._cdf = np.cumsum(weights / weights.sum())
        self._cdf[-1] = 1.0
        self._left_in_doc = self._draw_doc_len()

    def _draw_doc_len(self) -> int:
        return max(self._doc_min, int(self._rng.lognormal(self._doc_mu, self._doc_sigma)))

    def _take(self, n: int) -> np.ndarray:
        """The next n tokens of the stream."""
        ranks = np.searchsorted(self._cdf, self._rng.random(n), side="right")
        out = self._rank_to_id[np.minimum(ranks, self._rank_to_id.size - 1)]
        # Lay document boundaries over the draw: one EOS ends each document.
        pos = self._left_in_doc
        while pos < n:
            out[pos] = self._eos
            pos += 1 + self._draw_doc_len()
        self._left_in_doc = pos - n
        return out

    def next_batch(self, batch: int, seq_len: int) -> Dict[str, np.ndarray]:
        toks = self._take(batch * (seq_len + 1)).reshape(batch, seq_len + 1)
        return {
            "tokens": np.ascontiguousarray(toks[:, :-1]),
            "targets": np.ascontiguousarray(toks[:, 1:]),
        }


def unigram_entropy_nats(vocab_size: int, zipf_exponent: float) -> float:
    """Entropy of the token distribution (EOS aside): where a model that has
    learned only the skew can bring the loss."""
    w = np.arange(1, vocab_size, dtype=np.float64) ** -float(zipf_exponent)
    p = w / w.sum()
    return float(-(p * np.log(p)).sum())
