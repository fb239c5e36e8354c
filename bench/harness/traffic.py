"""The one traffic generator: a seeded, topic-skewed token corpus split
over workers by a Dirichlet draw, optional audio frames, and per-step
worker batches.

Everything a mix varies is a number in its ``bench/traffic/<name>.json``:

  workers, batch, seq       n workers, rows per worker, tokens per row
  alpha, topics             Dirichlet heterogeneity over topics
  corpus_rows               rows in the corpus (pool per worker = rows / n)
  frames, frame_pool        audio cells: encoder frames per row and the
                            number of distinct seeded frame blocks

The corpus and the Dirichlet split are copies of the program's
``repro.data.synthetic.make_lm_corpus`` (vectorised: the same distribution,
not the same draws) and ``repro.data.dirichlet.partition_by_class``; the
per-step batches go through the program's own
``repro.data.pipeline.worker_batches``.
"""
from __future__ import annotations

from typing import Iterator

import numpy as np


def lm_corpus(rows: int, vocab: int, topics: int, seq_len: int,
              rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """(rows, seq_len) int32 tokens and a topic per row.  A row of topic t
    draws each token from t's slice of the vocabulary with probability 0.9
    and uniformly from the whole vocabulary otherwise."""
    topic = rng.integers(0, topics, size=rows)
    span = vocab // topics
    in_slice = rng.random((rows, seq_len)) < 0.9
    local = topic[:, None] * span + rng.integers(0, span, (rows, seq_len))
    anywhere = rng.integers(0, vocab, (rows, seq_len))
    seqs = np.where(in_slice, local, anywhere)
    return seqs.astype(np.int32), topic.astype(np.int32)


def partition_by_class(labels: np.ndarray, n_workers: int, alpha: float,
                       rng: np.random.Generator) -> list[np.ndarray]:
    """Index lists per worker, sampled by per-worker Dirichlet class mixes;
    every worker gets len // n_workers rows."""
    n_classes = int(labels.max()) + 1
    props = rng.dirichlet([alpha] * n_classes, size=n_workers)
    by_class = [list(rng.permutation(np.where(labels == c)[0]))
                for c in range(n_classes)]
    per_worker = len(labels) // n_workers
    out = []
    for w in range(n_workers):
        want = rng.multinomial(per_worker, props[w])
        idx: list[int] = []
        for c, k in enumerate(want):
            take = min(k, len(by_class[c]))
            idx.extend(by_class[c][:take])
            by_class[c] = by_class[c][take:]
        while len(idx) < per_worker:          # backfill from what is left
            for c in np.argsort([-len(b) for b in by_class]):
                if by_class[c]:
                    idx.append(by_class[c].pop())
                    if len(idx) == per_worker:
                        break
        out.append(np.asarray(idx[:per_worker]))
    return out


def positions_per_row(traffic: dict) -> int:
    """Input positions of one row: decoder tokens plus encoder frames."""
    return int(traffic["seq"]) + int(traffic.get("frames", 0))


def tokens_per_step(traffic: dict) -> int:
    """Input positions of all n workers in one step."""
    return (int(traffic["workers"]) * int(traffic["batch"])
            * positions_per_row(traffic))


def worker_feed(traffic: dict, vocab: int, d_model: int, seed: int
                ) -> Iterator[dict]:
    """Infinite per-step batches in the train step's layout:
    tokens/labels (n, B, seq) int32, and for audio mixes frames
    (n, B, frames, d_model) float32."""
    from repro.data.pipeline import WorkerDataset, worker_batches

    rng = np.random.default_rng(seed)
    seq = int(traffic["seq"])
    tokens, topic = lm_corpus(int(traffic["corpus_rows"]), vocab,
                              int(traffic["topics"]), seq + 1, rng)
    arrays = {"seq": tokens, "y": topic}
    frames = int(traffic.get("frames", 0))
    pool = None
    if frames:
        pool = rng.standard_normal(
            (int(traffic["frame_pool"]), frames, d_model), dtype=np.float32)
        arrays["frame"] = rng.integers(0, len(pool), size=len(tokens))
    idx = partition_by_class(topic, int(traffic["workers"]),
                             float(traffic["alpha"]), rng)
    ds = WorkerDataset(arrays, idx)
    feed_seed = int(rng.integers(0, 2**63 - 1))
    for b in worker_batches(ds, int(traffic["batch"]), seed=feed_seed):
        out = {"tokens": b["seq"][..., :-1], "labels": b["seq"][..., 1:]}
        if pool is not None:
            out["frames"] = pool[b["frame"]]
        yield out
