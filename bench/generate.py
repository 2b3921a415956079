"""The general traffic generator: reads a mix's data file and makes its
inputs from the seed. The same seed gives the same inputs.

Training rows (`"generator": "markov_lm"`): a learnable next-token task.
Token t is drawn from a seeded table indexed by tokens t-1 and t-2, and
replaced by a uniform random token with probability `noise` (as the
program's own `data.synthetic.lm_batches`, copied here so that the
benchmark does not depend on it). Each batch has `seq + 1` tokens per row,
split into tokens and next-token labels; every row of every batch is drawn
anew.
"""
from __future__ import annotations

import numpy as np


def markov_lm(mix: dict, vocab: int, seed: int, n_batches: int) -> list:
    """`n_batches` batches of {"tokens", "labels"} int32 [batch, seq]."""
    data = mix["data"]
    b, s = mix["batch"], mix["seq"] + 1
    rng = np.random.default_rng([seed, 0])
    width = min(vocab, data["table_width"])
    table = rng.integers(0, vocab, (vocab, width)).astype(np.int32)
    rows = b * n_batches
    rng = np.random.default_rng([seed, 1])
    x = np.empty((rows, s), np.int32)
    x[:, :2] = rng.integers(0, vocab, (rows, 2))
    noise = rng.random((rows, s)) < data["noise"]
    rand = rng.integers(0, vocab, (rows, s), dtype=np.int32)
    for t in range(2, s):
        nxt = table[x[:, t - 1], x[:, t - 2] % width]
        x[:, t] = np.where(noise[:, t], rand[:, t], nxt)
    x = x.reshape(n_batches, b, s)
    return [{"tokens": x[i, :, :-1].copy(), "labels": x[i, :, 1:].copy()}
            for i in range(n_batches)]


def summary(batches: list) -> dict:
    """Length and value quantiles of the generated rows, for the log."""
    tok = np.stack([bt["tokens"] for bt in batches])
    q = np.quantile(tok, [0.0, 0.5, 1.0]).tolist()
    return {"batches": len(batches), "rows_per_batch": int(tok.shape[1]),
            "row_tokens": int(tok.shape[2]), "token_id_min_median_max": q,
            "distinct_rows": int(len({r.tobytes() for r in
                                      tok.reshape(-1, tok.shape[2])}))}
