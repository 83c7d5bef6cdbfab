"""The benchmark's span and row counts around each tower call.

:class:`CountedTower` is the program's ``EmbedTower`` with two wraps:

* ``embed`` runs inside a ``jax.profiler.TraceAnnotation`` named
  ``<label>.embed``, counts its calls and the rows the engine asked for,
  and, while ``recording`` is set, keeps each row's output by its tokens
  (the correctness check compares the cheap tower's query embeddings);
* the tower's jitted program (``EmbedTower._embed``) counts, per call, the
  rows it computed, the rows among them that held tokens (not padding to
  the batch, not an all-zero empty slot), and the rows that are documents
  of the corpus (a stage-2 drain), whatever the caller and its padding.
"""
from __future__ import annotations

import dataclasses
import threading

import jax
import numpy as np

from repro.serve import EmbedTower


@dataclasses.dataclass
class RowCounts:
    calls: int = 0  # embed calls
    asked: int = 0  # rows passed to embed
    useful: int = 0  # rows computed that held a token
    computed: int = 0  # rows the jitted program ran, padding included
    doc_rows: int = 0  # rows computed that are documents of the corpus


class CountedTower(EmbedTower):
    """An ``EmbedTower`` that records a span and row counts per call."""

    def attach(self, label: str, docs: set) -> CountedTower:
        """Start counting; ``docs`` holds the corpus rows' token bytes."""
        self.label = label
        self.counts = RowCounts()
        self.recording = False
        self.outputs: dict = {}  # token bytes -> embedding, while recording
        self._lock = threading.Lock()
        program = self._embed

        def counted(params, toks):
            rows = np.asarray(toks)
            useful = int(np.count_nonzero(rows.any(axis=1)))
            n_docs = sum(r.tobytes() in docs for r in rows)
            with self._lock:
                self.counts.computed += rows.shape[0]
                self.counts.useful += useful
                self.counts.doc_rows += n_docs
            return program(params, toks)

        self._embed = counted
        return self

    def embed(self, tokens: np.ndarray, batch: int = 64) -> np.ndarray:
        with jax.profiler.TraceAnnotation(f"{self.label}.embed"):
            out = super().embed(tokens, batch)
        with self._lock:
            self.counts.calls += 1
            self.counts.asked += tokens.shape[0]
            if self.recording:
                for row, e in zip(tokens, out):
                    if row.any():
                        self.outputs[row.tobytes()] = e
        return out

    def snapshot(self) -> RowCounts:
        with self._lock:
            return dataclasses.replace(self.counts)


def diff(a: RowCounts, b: RowCounts) -> RowCounts:
    """Counts of ``b`` less ``a`` (what happened between two snapshots)."""
    return RowCounts(**{f.name: getattr(b, f.name) - getattr(a, f.name)
                        for f in dataclasses.fields(RowCounts)})
