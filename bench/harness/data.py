"""The corpus and the queries, made from the run's seed.

Tokens are drawn from 1..vocab-1 (``vocab`` the smaller of the two
towers' tables), so token 0 never appears and an all-zero row is always
padding. Each query is a corpus document (its "planted" neighbour) with
the first half of its tokens replaced, so every query has a near
neighbour in the corpus. Source documents are drawn without
replacement: no two queries share one, and warm-up queries are drawn apart
from the window's.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Data:
    corpus: np.ndarray  # (n_docs, doc_len) int32
    queries: np.ndarray  # (n_queries, query_len) int32: warm-up rows first
    planted: np.ndarray  # (n_queries,) source document of each query
    n_warmup: int


def make(seed: int, cfg: dict, n_warmup: int, n_window: int,
         vocab: int) -> Data:
    n_docs, doc_len = cfg["n_docs"], cfg["doc_len"]
    if cfg["query_len"] != doc_len:
        raise ValueError("query rows must be as long as document rows")
    rng = np.random.default_rng([seed, 0])
    corpus = rng.integers(1, vocab, (n_docs, doc_len), dtype=np.int32)
    n_q = n_warmup + n_window
    if n_q > n_docs:
        raise ValueError(f"{n_q} unique queries need more than {n_docs} docs")
    qrng = np.random.default_rng([seed, 1])
    planted = qrng.choice(n_docs, n_q, replace=False)
    queries = corpus[planted].copy()
    half = doc_len // 2
    queries[:, :half] = qrng.integers(1, vocab, (n_q, half), dtype=np.int32)
    return Data(corpus, queries, planted.astype(np.int64), n_warmup)
