"""The one traffic generator: language-model batches from a seed.

A traffic file (``bench/traffic/<name>.json``) gives its parameters:

- ``generator``: ``"planted_bigram"``, the only kind so far;
- ``batch``, ``seq_len``: rows per step and tokens per row;
- ``bigram_rank``, ``choices``, ``follow_prob``: the planted structure. Token
  t+1 is, with probability ``follow_prob``, one of ``choices`` successors of
  (token t mod ``bigram_rank``), else uniform over the vocabulary;
- ``pool``: distinct batches the measured window cycles through.

The stream is a copy of the program's ``data.synthetic.token_lm_batches``
with its constants as parameters, kept here so that a change to the program
cannot move the yardstick. Ids are drawn from the configuration's
``vocab_size``: a sliced vocabulary gives ids from the slice.

Every seed gives the same shapes and the same amount of work; only the
tokens differ.
"""

from __future__ import annotations

import numpy as np

#: batches that set-up runs through the step and the reference follows
CHECK_STEPS = 3


def planted_bigram(traffic: dict, vocab: int, seed: int):
    """Endless stream of ``{"tokens", "labels"}`` int32 (batch, seq_len)."""
    batch, seq = int(traffic["batch"]), int(traffic["seq_len"])
    rank, choices = int(traffic["bigram_rank"]), int(traffic["choices"])
    follow_prob = float(traffic["follow_prob"])
    rng = np.random.default_rng(seed)
    table = rng.integers(0, vocab, size=(rank, choices))
    while True:
        toks = np.empty((batch, seq + 1), np.int32)
        toks[:, 0] = rng.integers(0, vocab, size=batch)
        noise = rng.random((batch, seq))
        choice = rng.integers(0, choices, size=(batch, seq))
        rand_tok = rng.integers(0, vocab, size=(batch, seq))
        for t in range(seq):
            follow = table[toks[:, t] % rank, choice[:, t]]
            toks[:, t + 1] = np.where(noise[:, t] < follow_prob, follow,
                                      rand_tok[:, t])
        yield {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def make_batches(traffic: dict, vocab: int, seed: int) -> tuple:
    """(check batches, window pool): ``CHECK_STEPS`` batches for the steps
    that set-up takes and the reference follows, then ``pool`` more that
    the window cycles through. All are host arrays."""
    if traffic["generator"] != "planted_bigram":
        raise ValueError(f"unknown traffic generator {traffic['generator']!r}")
    stream = planted_bigram(traffic, vocab, seed)
    check = [next(stream) for _ in range(CHECK_STEPS)]
    pool = [next(stream) for _ in range(int(traffic["pool"]))]
    return check, pool
