"""datagen.py: equal seeds give byte-identical batches, different seeds do
not, and the stream has the skew and the document boundaries it states."""

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib.datagen import PackedStream, unigram_entropy_nats  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "traffic", "seq1k.json")) as f:
    STREAM = json.load(f)["stream"]


def _batches(seed, n=3, vocab=32768):
    s = PackedStream(seed, vocab, STREAM)
    return [s.next_batch(4, 1024) for _ in range(n)]


def test_equal_seeds_are_byte_identical_and_different_seeds_differ():
    a, b, c = _batches(7), _batches(7), _batches(8)
    for x, y in zip(a, b):
        assert x["tokens"].tobytes() == y["tokens"].tobytes()
        assert x["targets"].tobytes() == y["targets"].tobytes()
    assert a[0]["tokens"].tobytes() != c[0]["tokens"].tobytes()
    assert a[0]["tokens"].tobytes() != a[1]["tokens"].tobytes()  # a new batch every step


def test_shapes_dtypes_and_next_token_targets():
    b = _batches(1, n=1)[0]
    assert b["tokens"].shape == b["targets"].shape == (4, 1024)
    assert b["tokens"].dtype == b["targets"].dtype == np.int32
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["targets"][:, :-1])
    assert 0 <= b["tokens"].min() and b["tokens"].max() < 32768


def test_stream_is_skewed_and_documents_end_in_eos():
    s = PackedStream(3, 32768, STREAM)
    toks = np.concatenate([s.next_batch(16, 1024)["tokens"].ravel() for _ in range(8)])
    counts = np.bincount(toks, minlength=32768)
    eos = STREAM["eos_id"]
    # lognormal documents of median 600 tokens: an EOS every few hundred tokens
    assert 50 < counts[eos] < 1000
    counts[eos] = 0
    top = np.sort(counts)[::-1]
    # Zipf 1.1: the most frequent id takes ~1/H of the mass (H ~ 6.5 here)
    assert 0.10 < top[0] / toks.size < 0.22
    assert top[:100].sum() / toks.size > 0.5
    # the learnable gap: ln V against the unigram entropy
    assert np.log(32768) - unigram_entropy_nats(32768, STREAM["zipf_exponent"]) > 3.5
