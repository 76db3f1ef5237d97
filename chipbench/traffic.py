"""The one generator of inputs that every traffic file feeds.

A traffic file (``traffic/<mix>.json``) names its driver kind and the
parameters of the mix.  Token ids are drawn uniformly over the vocabulary
from ``(seed, stream, index)`` alone, so a seed gives the same inputs
whatever else the run does, and a batch is the same whichever run asks
for it.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

# independent streams of one seed
WARMUP, WINDOW, TRAIN = 1, 2, 3


def load(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def tokens(seed: int, stream: int, index: int, shape, vocab: int) -> np.ndarray:
    rng = np.random.default_rng([seed, stream, index])
    return rng.integers(0, vocab, shape, dtype=np.int32)


def prompts(tr: dict, vocab: int, seed: int, stream: int,
            n_batches: int) -> list:
    """``n_batches`` static batches of ``tr["batch"]`` prompts of
    ``tr["prompt_len"]`` tokens, as one list of rows."""
    rows = []
    for i in range(n_batches):
        rows += list(tokens(seed, stream, i, (tr["batch"], tr["prompt_len"]),
                            vocab))
    return rows


def train_batch(tr: dict, vocab: int, seed: int, index: int) -> dict:
    """Batch ``index`` of a training job: ``tr["batch"]`` rows of
    ``tr["seq_len"] + 1`` tokens, as inputs and next-token labels."""
    t = tokens(seed, TRAIN, index, (tr["batch"], tr["seq_len"] + 1), vocab)
    return {"tokens": t[:, :-1], "labels": t[:, 1:]}
