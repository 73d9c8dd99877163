"""Per-client batching pipeline (deterministic, seed-keyed)."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, Tuple

import numpy as np


@dataclass
class ClientDataset:
    x: np.ndarray
    y: np.ndarray

    def __len__(self):
        return len(self.y)


def epoch_batch_rows(n: int, batch_size: int, epoch_seed: int
                     ) -> np.ndarray:
    """Sample positions of one local epoch's batches, (n_batches,
    batch): a seeded shuffle of ``n`` samples cut into full batches
    (drops the ragged tail like FedLab; fewer samples than one batch
    give one short batch)."""
    idx = np.random.default_rng(epoch_seed).permutation(n)
    n_full = max(n // batch_size, 1)
    return idx[:n_full * batch_size].reshape(n_full, -1)


def client_batches(ds: ClientDataset, batch_size: int, epoch_seed: int
                   ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """One local epoch of shuffled batches (``epoch_batch_rows``)."""
    for sl in epoch_batch_rows(len(ds), batch_size, epoch_seed):
        if len(sl) == 0:
            break
        yield ds.x[sl], ds.y[sl]


def lm_batches(tokens: np.ndarray, batch: int, seq: int, seed: int
               ) -> Iterator[np.ndarray]:
    rng = np.random.default_rng(seed)
    n = len(tokens) - seq - 1
    while True:
        starts = rng.integers(0, n, batch)
        yield np.stack([tokens[s:s + seq] for s in starts])
