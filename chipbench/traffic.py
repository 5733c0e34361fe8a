"""The one traffic generator. A mix is a data file of parameters; the
seed decides the token ids, never the amount of work: every seed sends
the same sizes."""
from __future__ import annotations

import math

import numpy as np


# Pairing of prompt with output lengths, and the order of a block's
# requests: the same for every mix and every seed.
PAIRING_SEED = 0
ORDER_SEED = 0


def _loguniform_grid(lo: int, hi: int, n: int) -> list:
    """`n` lengths at the mid-quantiles of a log-uniform law on
    [lo, hi]: the same set for every seed."""
    return [int(round(math.exp(math.log(lo) + (i + 0.5) / n
                               * (math.log(hi) - math.log(lo)))))
            for i in range(n)]


def lengths(spec, n: int) -> list:
    """`n` lengths for a field of a mix: a fixed number, or
    {"dist": "loguniform", "lo": a, "hi": b}."""
    if isinstance(spec, int):
        return [spec] * n
    if spec.get("dist") == "loguniform":
        return _loguniform_grid(int(spec["lo"]), int(spec["hi"]), n)
    raise ValueError(f"unknown length law {spec!r}")


def request_blocks(mix: dict, seed: int, vocab: int):
    """An endless stream of requests (prompt ids, output length) for a
    serving mix. Requests come in blocks of `mix["block"]`: each block
    holds every prompt length of the grid once and every output length
    once. Pairing and order are fixed (`PAIRING_SEED`, `ORDER_SEED`):
    every seed sends the same requests at the same places of the
    stream, and draws only their token ids. With the order drawn from
    the seed, which requests end inside a window changed from seed to
    seed and the rate swung by 2% (PERF.md)."""
    n = int(mix["block"])
    prompts = lengths(mix["prompt_len"], n)
    outputs = lengths(mix["output_len"], n)
    pairing = np.random.default_rng(PAIRING_SEED).permutation(n)
    order = np.random.default_rng(ORDER_SEED)
    rng = np.random.default_rng(seed)
    while True:
        for i in order.permutation(n):
            p, o = prompts[i], outputs[pairing[i]]
            yield rng.integers(0, vocab, p).tolist(), o


def mlm_batches(mix: dict, seed: int, vocab: int):
    """An endless stream of masked-LM batches (ids, labels) of
    `rows` x `seq`: ids uniform over the vocabulary, and exactly
    round(share * seq) positions of each row labelled with the row's
    own id there, the others -100. All rows differ."""
    rows, seq = int(mix["rows"]), int(mix["seq"])
    n_lab = int(round(float(mix["labelled_share"]) * seq))
    rng = np.random.default_rng(seed)
    while True:
        ids = rng.integers(0, vocab, (rows, seq), dtype=np.int32)
        labels = np.full((rows, seq), -100, np.int32)
        cols = np.argsort(rng.random((rows, seq)), axis=1)[:, :n_lab]
        r = np.arange(rows)[:, None]
        labels[r, cols] = ids[r, cols]
        yield ids, labels
