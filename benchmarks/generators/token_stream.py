"""Traffic of ``kind: token_stream``: a token stream whose parameters come from a
traffic file (``seq_len``, ``zipf_exponent``).

Sequences of ``seq_len + 1`` tokens cut from one concatenated stream of i.i.d. draws
from a Zipf law over the vocabulary (``p(rank r) ~ r ** -zipf_exponent``). Every seed
draws from the same law, so every seed gives a step the same amount of work; only the
tokens differ. The stream is endless: the harness ends it by setting
:attr:`Dataset.stop`.

The harness finds a generator by the traffic file's ``kind``
(``benchmarks/generators/<kind>.py``) and asks it for three things: ``Dataset`` (the
recipe's ``dataset._target_``: ``vocab_size``, ``seed`` and the file's parameters; a
``stop`` event ends it), ``batch`` (what the recipe's loader makes of the stream at one
optimizer step, for the reference) and ``loss_floor`` (the loss no model of the stream
can go below).
"""

from __future__ import annotations

import functools
import threading

import numpy as np


def zipf_law(vocab_size: int, exponent: float) -> np.ndarray:
    p = np.arange(1, vocab_size + 1, dtype=np.float64) ** -float(exponent)
    return p / p.sum()


def unigram_entropy(vocab_size: int, exponent: float) -> float:
    """Entropy in nats of the law: no model of an i.i.d. stream has a lower loss."""
    p = zipf_law(vocab_size, exponent)
    return float(-(p * np.log(p)).sum())


def sequence(vocab_size: int, seq_len: int, exponent: float, seed: int, index: int) -> np.ndarray:
    """Sequence ``index`` of the stream of ``seed``: ``seq_len + 1`` token ids."""
    cdf = _cdf(vocab_size, exponent)
    rng = np.random.default_rng([int(seed), int(index)])
    ids = np.searchsorted(cdf, rng.random(seq_len + 1), side="right")
    return np.minimum(ids, vocab_size - 1).astype(np.int32)


@functools.lru_cache(maxsize=8)
def _cdf(vocab_size: int, exponent: float) -> np.ndarray:
    return np.cumsum(zipf_law(vocab_size, exponent))


def batch(params: dict, vocab_size: int, seed: int, step: int,
          rows: int) -> tuple[np.ndarray, np.ndarray]:
    """(input_ids, labels), each ``(rows, seq_len)``, of optimizer step ``step`` (1-based)
    as the recipe's loader forms it: consecutive sequences of the stream, labels
    shifted by one."""
    seqs = np.stack([sequence(vocab_size, params["seq_len"], params["zipf_exponent"], seed,
                              (step - 1) * rows + r) for r in range(rows)])
    return seqs[:, :-1], seqs[:, 1:]


def loss_floor(params: dict, vocab_size: int) -> float:
    return unigram_entropy(vocab_size, params["zipf_exponent"])


class Dataset:
    """Iterable, unsized dataset (``dataset._target_`` of the cell's recipe)."""

    def __init__(self, vocab_size: int, seed: int, seq_len: int, zipf_exponent: float):
        self.vocab_size, self.seq_len = int(vocab_size), int(seq_len)
        self.exponent, self.seed = float(zipf_exponent), int(seed)
        self.stop = threading.Event()

    def __iter__(self):
        index = 0
        while not self.stop.is_set():
            ids = sequence(self.vocab_size, self.seq_len, self.exponent, self.seed, index)
            index += 1
            yield {"input_ids": ids, "prompt_len": 0}
