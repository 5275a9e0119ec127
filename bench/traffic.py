"""Training traffic: fixed-length rows of Zipf-distributed token ids.

A traffic file (``bench/traffic/<name>.json``) gives ``global_batch``,
``seq_len``, ``zipf_a`` and ``doc_len``; the vocabulary comes from the
configuration.  The rows of step ``i`` are a pure function of
``(seed, i)``, so every run with one seed feeds the same tokens, every step
feeds rows of its own, and the reference re-reads the steps it follows.

The arithmetic is that of the program's ``SyntheticLM.batch_at``
(``src/repro/data/pipeline.py``), kept here so that the benchmark's inputs
do not change when the program's generator does.
"""
from __future__ import annotations

from typing import Dict

import numpy as np
import jax


class ZipfRows:
    """``batch_at(step)`` -> ``{"tokens", "labels"}``, int32 ``[B, S]``.

    Passed to the trainer as its ``data``.  Each call is marked on the
    profiler's timeline as ``bench.batch``."""

    def __init__(self, traffic: Dict, vocab: int, seed: int):
        self.batch = int(traffic["global_batch"])
        self.seq_len = int(traffic["seq_len"])
        self.zipf_a = float(traffic["zipf_a"])
        self.doc_len = int(traffic["doc_len"])
        self.vocab = int(vocab)
        self.seed = int(seed)

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        with jax.profiler.TraceAnnotation("bench.batch"):
            rng = np.random.default_rng(np.random.SeedSequence([self.seed, step]))
            B, S = self.batch, self.seq_len
            u = rng.random((B, S + 1))
            ids = np.minimum((u ** (-1.0 / self.zipf_a) - 1.0).astype(np.int64),
                             self.vocab - 1).astype(np.int32)
            # a document boundary (token 0) every doc_len tokens
            pos = np.arange(S + 1)[None, :]
            offs = rng.integers(0, self.doc_len, (B, 1))
            ids = np.where((pos + offs) % self.doc_len == 0, 0, ids)
            return {"tokens": ids[:, :S], "labels": ids[:, 1:]}
