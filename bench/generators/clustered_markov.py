"""Training batches for a federated language-model cell: per-cluster
Markov token streams whose successor tables conflict.

Follows the repository's synthetic clustered corpus, re-implemented here so
that a change to the program cannot change the benchmark's inputs.  Reads
from the traffic file ``federation.clients`` and ``federation.clusters``,
and under ``params`` ``seq_len``, ``batch`` (sequences per client per local
iteration), ``pool`` (sequences held per client) and ``noise``; the
vocabulary comes from the configuration.

The source is a callable ``k -> batch`` over protocol iterations
``k = 1, 2, ...`` with leaves ``(C, batch, seq_len)``: the contract of the
round scheduler's ``batch_source``.  Iteration ``k`` of client ``c`` takes
rows ``(k - 1) * batch ...`` of that client's pool, so the first
``pool // batch`` iterations see rows that all differ.
"""
from __future__ import annotations

import numpy as np


def make(seed: int, cfg: dict, traffic: dict, window_s: float | None = None):
    """Each cluster draws a permutation of the whole vocabulary as its
    successor table; client ``i`` belongs to cluster ``i * D // C`` and
    follows its cluster's table, with probability ``noise`` of a uniform
    token instead.  Returns ``(source, pool_tokens)``, the pool being
    ``(C, pool, seq_len + 1)`` int32."""
    fed, p = traffic["federation"], traffic["params"]
    clients, clusters, vocab = fed["clients"], fed["clusters"], cfg["vocab_size"]
    seq_len, batch, pool = p["seq_len"], p["batch"], p["pool"]
    if clients % clusters:
        raise ValueError(f"{clients} clients do not divide into {clusters} clusters")
    rng = np.random.default_rng(seed)
    succ = np.stack([rng.permutation(vocab) for _ in range(clusters)]).astype(np.int32)
    cluster = (np.arange(clients) * clusters // clients)[:, None]
    tokens = np.empty((clients, pool, seq_len + 1), np.int32)
    state = rng.integers(0, vocab, size=(clients, pool), dtype=np.int32)
    for t in range(seq_len + 1):
        tokens[:, :, t] = state
        rand = rng.integers(0, vocab, size=(clients, pool), dtype=np.int32)
        state = np.where(rng.random((clients, pool)) < p["noise"], rand, succ[cluster, state])

    def source(k: int) -> dict:
        rows = (np.arange(batch) + (k - 1) * batch) % pool
        chunk = tokens[:, rows]
        return {"tokens": chunk[:, :, :-1], "labels": chunk[:, :, 1:]}

    return source, tokens
